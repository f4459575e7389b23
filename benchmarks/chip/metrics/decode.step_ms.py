"""Device time per fused decode step (the engine's ``step`` executable),
in ms."""

import tracereduce as tr

STEP = r"^jit_step\b"


def read(ctx):
    ns, n = tr.time_matching(ctx.modules, STEP)
    return ns / n / 1e6 if n else None
