"""Least time of every AXQ GEMM the traced stretch needed (decode steps at
their active rows with the unembedding, prefill calls at their real prompt
tokens), over the summed device time of the ``axqmm`` kernels, in %."""

import spec
import tracereduce as tr

KERNEL = r"^axqmm$"


def read(ctx):
    ns, n = tr.time_matching(ctx.kernels, KERNEL)
    if not n:
        return None
    fam = spec.family(ctx.arch["family"])
    least = sum(fam.axq_call_least_s(ctx.arch, len(pos), ctx.peaks, True)
                for pos in ctx.work.steps)
    least += sum(fam.axq_call_least_s(ctx.arch, sum(call), ctx.peaks, False)
                 for call in ctx.work.prefills)
    return 100.0 * least / (ns / 1e9)
