"""Device milliseconds per scenario-round under ``fl.gather``: the bank
take and dequantization of the selected clients (arena cells)."""

from bench.xscope import ms_per_unit


def read(ctx):
    return ms_per_unit(ctx, "fl.gather")
