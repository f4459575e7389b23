"""Least time of the decode attention the traced steps needed (every layer,
active slots, live positions at the cache dtype), over the summed device
time of the ``flash_decode`` kernel, in %."""

import tracereduce as tr
import workcount as wc

KERNEL = r"^flash_decode$"


def read(ctx):
    ns, n = tr.time_matching(ctx.kernels, KERNEL)
    if not n or not ctx.work.steps:
        return None
    least = ctx.arch["n_layers"] * sum(
        wc.decode_attn_least_s(ctx.arch, pos, ctx.peaks,
                               ctx.config["kv_cache_dtype"])
        for pos in ctx.work.steps)
    return 100.0 * least / (ns / 1e9)
