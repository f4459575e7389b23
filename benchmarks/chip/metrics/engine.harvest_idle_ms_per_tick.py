"""Device-idle time inside the engine's own ``engine.harvest`` spans (route
and slot counters, per-slot harvest, the emitter push and completions), per
engine tick, in ms."""

import enginespans


def read(ctx):
    return enginespans.idle_ms_per_tick(ctx, "engine.harvest")
