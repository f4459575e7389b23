"""Device-idle time inside the engine's own ``engine.decode_tick`` spans
(the key split, feed and mask transfers and the fused step's dispatch, then
the wait for its tokens), per engine tick, in ms."""

import enginespans


def read(ctx):
    return enginespans.idle_ms_per_tick(ctx, "engine.decode_tick")
