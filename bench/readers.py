"""What the per-layer readers in ``bench/metrics/`` share.

Each takes a ``run.Context`` (the reduced trace and its window, the loop
that ran, the configuration, the chip's peaks) and returns a number, or
None where the trace holds nothing to read.
"""

from __future__ import annotations


def idle_share(ctx) -> float | None:
    """Percent of the window in which no operation ran on the device."""
    if ctx.window_s <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s(ctx.lo, ctx.hi) / ctx.window_s)


def mfu(ctx) -> float | None:
    """Useful model FLOPs of the window over its seconds x chips x peak."""
    run = ctx.run
    flops = run.useful_flops(run.selected_rounds, run.eval_rows)
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])


def idle_ms_inside(ctx, span: str) -> float | None:
    """Device idle milliseconds inside each ``span``, on average."""
    idle, count = ctx.trace.idle_inside(span, ctx.lo, ctx.hi)
    if not count:
        return None
    return idle * 1e3 / count
