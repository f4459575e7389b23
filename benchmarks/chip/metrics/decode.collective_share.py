"""Device time of the collective ops (all-reduce, all-gather,
reduce-scatter, by op name, with their async start and done halves) that
chip 0 ran inside the fused decode step's executables (``jit_step``), over
those executables' device time, in %: the share of a tensor-parallel step
spent exchanging partial results between chips."""

import bisect
import re

STEP = re.compile(r"^jit_step\b")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter)\b")


def read(ctx):
    steps = sorted((s, e) for n, s, e in ctx.modules if STEP.match(n))
    step_ns = sum(e - s for s, e in steps)
    coll_ns = 0
    for n, s, e in ctx.ops:
        if COLLECTIVE.match(n):
            i = bisect.bisect_right(steps, (s, float("inf"))) - 1
            if i >= 0 and e <= steps[i][1]:
                coll_ns += e - s
    if not step_ns or not coll_ns:
        return None
    return 100.0 * coll_ns / step_ns
