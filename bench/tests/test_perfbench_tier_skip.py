"""``tier_skip_share.arena``: the tier-rounds the arena skipped, read from
the counts its ``arena.reduce`` spans carry into the profiler trace.  On
the CPU: spans made by hand, and one rollout of the tiny arena cell."""

import glob
import tempfile

import numpy as np
import pytest

from bench import run as bench_run
from bench import xtrace

METRIC = "tier_skip_share.arena"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _traced(body):
    """Run ``body()`` under the profiler with the program's spans bridged
    into it, inside a ``bench.window``; returns the metric's reading."""
    import jax
    from repro.obs import trace as obs_trace
    with tempfile.TemporaryDirectory() as d:
        with obs_trace.installed(obs_trace.MemorySink()):
            obs_trace.profiler_bridge(True)
            try:
                jax.profiler.start_trace(d)
                body(outside=True)
                with jax.profiler.TraceAnnotation("bench.window"):
                    body(outside=False)
                jax.profiler.stop_trace()
            finally:
                obs_trace.profiler_bridge(False)
        path = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0]
        ctx = bench_run.Context(xtrace.Trace.load(path), None, None, PEAKS,
                                1)
        ctx.trace_path = path
        return bench_run.read_metric(METRIC, ctx)


def test_share_sums_the_window_spans_counts():
    from repro.obs import trace as obs_trace

    def body(outside):
        if outside:
            with obs_trace.span("arena.reduce") as sp:
                sp.set(tier_rounds_run=100, tier_rounds_skipped=0)
            return
        for run, skipped in ((6, 2), (3, 5)):
            with obs_trace.span("arena.reduce", chunk=0) as sp:
                sp.set(tier_rounds_run=run, tier_rounds_skipped=skipped)
        with obs_trace.span("arena.reduce", chunk=1):
            pass

    assert _traced(body) == pytest.approx(100.0 * 7 / 16)


def test_no_counts_read_nothing():
    from repro.obs import trace as obs_trace

    def body(outside):
        with obs_trace.span("arena.reduce", chunk=0):
            pass

    assert _traced(body) is None


def test_tiny_arena_share_matches_its_selections():
    """One traced rollout of the tiny arena cell: the share equals the
    tier-rounds that no lane drew, counted from the rollout's
    selections."""
    from bench.loops import ArenaRun
    from bench.tests.tiny import tiny_cell
    c = tiny_cell("cifar10_cnn.arena_zoo")
    run = ArenaRun(c["cfg"], c["cell"], 2**33 + 15)
    run.setup()

    def body(outside):
        if not outside:
            run.window(1e9, max_units=1)

    got = _traced(body)
    (sel,) = run.selected_rounds
    tier_of = np.asarray(run.bank.tier_of)
    tiers = run.bank.num_tiers
    drawn = np.stack([(tier_of[sel] == t).any(axis=(0, 2))
                      for t in range(tiers)], axis=-1)
    assert drawn.shape == (run.rounds, tiers)
    assert got == pytest.approx(100.0 * (~drawn).sum() / drawn.size)
