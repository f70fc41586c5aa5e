"""Device milliseconds of ``fl.sgd.grad`` (the model's forward and
backward) per sequential local-SGD step; its gap to ``sgd_step_ms`` is
the step's plumbing: shuffle, batch takes, update, masks (arena cells)."""

from bench.xscope import ms_per_sgd_step


def read(ctx):
    return ms_per_sgd_step(ctx, "fl.sgd.grad")
