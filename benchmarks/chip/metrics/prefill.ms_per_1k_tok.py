"""Device time of the prefill executables per 1,000 prompt tokens admitted
in the traced stretch, in ms (real prompt tokens, not bucket padding)."""

import tracereduce as tr

PREFILL = r"prefill"


def read(ctx):
    tokens = sum(sum(call) for call in ctx.work.prefills)
    ns, n = tr.time_matching(ctx.modules, PREFILL)
    if not tokens or not n:
        return None
    return ns / 1e6 / (tokens / 1e3)
