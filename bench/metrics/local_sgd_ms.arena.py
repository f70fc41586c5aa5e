"""Device milliseconds per scenario-round under ``fl.local_sgd``: the
vmapped local SGD over every tier (arena cells)."""

from bench.xscope import ms_per_unit


def read(ctx):
    return ms_per_unit(ctx, "fl.local_sgd")
