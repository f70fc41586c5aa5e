"""Device milliseconds of ``fl.local_sgd`` per sequential local-SGD step
the window dispatched (``sgd_steps`` of the ``arena.dispatch`` spans;
arena cells)."""

from bench.xscope import ms_per_sgd_step


def read(ctx):
    return ms_per_sgd_step(ctx, "fl.local_sgd")
