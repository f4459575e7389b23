"""Model FLOPs of the tokens decoded in the traced stretch (active slots
only, attention over each slot's live positions), over the decode-step
device time times the peak of the configuration's GEMM precision, in %."""

import spec
import tracereduce as tr
import workcount as wc

STEP = r"^jit_step\b"


def read(ctx):
    ns, n = tr.time_matching(ctx.modules, STEP)
    if not n or not ctx.work.steps:
        return None
    fam = spec.family(ctx.arch["family"])
    flops = sum(fam.decode_flops(ctx.arch, pos) for pos in ctx.work.steps)
    peak = wc.peak_rate(ctx.peaks, ctx.config["gemm_precision"])
    return 100.0 * flops / (ns / 1e9 * peak)
