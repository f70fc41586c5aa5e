"""Device milliseconds per scenario-round under ``fl.aggregate``: eq. (4)
and the tier update sums (arena cells)."""

from bench.xscope import ms_per_unit


def read(ctx):
    return ms_per_unit(ctx, "fl.aggregate")
