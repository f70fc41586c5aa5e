"""Share of the traced window in which the device ran nothing (arena cells)."""

from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx)
