"""Device-idle time inside the engine's own ``engine.admit`` spans (queue
policy, slot fill, padded prompt arrays, their transfers and the prefill
dispatch), per engine tick, in ms."""

import enginespans


def read(ctx):
    return enginespans.idle_ms_per_tick(ctx, "engine.admit")
