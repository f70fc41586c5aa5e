"""Device milliseconds per scenario-round under ``fl.eval``: the in-scan
and the final test-set evaluation (arena cells)."""

from bench.xscope import ms_per_unit


def read(ctx):
    return ms_per_unit(ctx, "fl.eval")
