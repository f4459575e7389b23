"""Device-idle time inside the benchmark's ``bench.tick`` spans, per engine
tick, in ms: the host's own share of each tick (admission bookkeeping,
dispatch, harvesting), which the device waits through."""

import tracereduce as tr


def read(ctx):
    ticks = tr.in_spans(ctx.spans, "bench.tick", ctx.lo, ctx.hi)
    if not ticks:
        return None
    idle = tr.idle_inside(ctx.ops, ctx.spans, "bench.tick", ctx.lo, ctx.hi)
    return idle / ticks / 1e6
