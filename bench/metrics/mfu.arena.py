"""Useful model FLOPs over the window x chips x bf16 peak (arena cells):
3 x forward per true example trained, 1 x forward per evaluated row."""

from bench.readers import mfu


def read(ctx):
    return mfu(ctx)
