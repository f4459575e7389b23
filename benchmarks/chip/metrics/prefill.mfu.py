"""Model FLOPs of the prompt tokens admitted in the traced stretch, over
the device time of the prefill executables times the peak of the
configuration's GEMM precision, in %."""

import spec
import tracereduce as tr
import workcount as wc

PREFILL = r"prefill"


def read(ctx):
    rows = [n for call in ctx.work.prefills for n in call]
    ns, n = tr.time_matching(ctx.modules, PREFILL)
    if not rows or not n:
        return None
    fam = spec.family(ctx.arch["family"])
    flops = sum(fam.prefill_flops(ctx.arch, r) for r in rows)
    peak = wc.peak_rate(ctx.peaks, ctx.config["gemm_precision"])
    return 100.0 * flops / (ns / 1e9 * peak)
