"""Percent of the rows that local SGD trained in the window
(``rows_trained`` of the ``arena.dispatch`` spans, padded slots and
tiers included) that are true rows of the selected clients (arena
cells)."""

from bench.xscope import useful_rows_share


def read(ctx):
    return useful_rows_share(ctx)
