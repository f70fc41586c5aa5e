"""Device milliseconds per scenario-round under the control plane's
scopes, ``fl.decide`` + ``fl.select`` + ``fl.queues`` (arena cells)."""

from bench.xscope import ms_per_unit


def read(ctx):
    return ms_per_unit(ctx, "fl.decide", "fl.select", "fl.queues")
