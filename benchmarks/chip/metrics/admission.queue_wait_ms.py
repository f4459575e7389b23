"""Mean time a request waited in the queue before the admission call that
took it in, over the engine's ``engine.prefill`` spans in the traced
stretch (their ``waited_ms`` summed over their ``requests``), in ms."""

import enginespans


def read(ctx):
    sums = enginespans.prefill_sums(ctx)
    if not sums["requests"]:
        return None
    return sums["waited_ms"] / sums["requests"]
