"""Share of the prompt positions the prefill executables computed that were
padding (bucket length times packed rows, against the real prompt tokens),
over the engine's ``engine.prefill`` spans in the traced stretch, in %."""

import enginespans


def read(ctx):
    sums = enginespans.prefill_sums(ctx)
    if not sums["padded"]:
        return None
    return 100.0 * (1.0 - sums["tokens"] / sums["padded"])
