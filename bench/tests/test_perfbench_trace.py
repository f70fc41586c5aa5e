"""The reduction from a profiler trace to busy time, kernel events and
idle gaps, on hand-made intervals and on a small trace recorded on a
TPU v5e (``record_trace.py``).  Reading the file needs no TPU."""

from pathlib import Path

import pytest

from bench import xtrace

TRACE = Path(__file__).resolve().parent / "data" / "tiny_tpu.xplane.pb"


def test_union_merges_overlaps_and_drops_empty():
    got = xtrace.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9), (8, 8)])
    assert got == [(0, 4), (5, 9)]
    assert xtrace.length(got) == 8


def test_gaps_and_clip():
    busy = [(0, 4), (5, 9), (12, 20)]
    assert xtrace.gaps(busy, 2, 14) == [(4, 5), (9, 12)]
    assert xtrace.gaps([], 0, 3) == [(0, 3)]
    assert xtrace.clip(busy, 2, 14) == [(2, 4), (5, 9), (12, 14)]


def test_label_takes_innermost_open_span():
    tr = xtrace.Trace({"/device:TPU:0": [(0, 10, "%a = x"),
                                         (30, 40, "%b = y")]},
                      [(0, 100, "bench.window"), (5, 35, "trainer.round"),
                       (12, 20, "arena.plan")])
    assert tr.label(15) == "arena.plan"
    assert tr.label(25) == "trainer.round"
    assert tr.label(60) == "bench.window"
    assert tr.window() == (0, 100)
    assert tr.busy_s(0, 100) == pytest.approx(20e-9)
    gaps = tr.idle_gaps(0, 100)
    assert gaps[0] == ("bench.window", pytest.approx(60e-9))
    idle, count = tr.idle_inside("trainer.round", 0, 100)
    assert count == 1 and idle == pytest.approx(20e-9)
    assert tr.top_ops(0, 100) == [("a", pytest.approx(1e-8)),
                                  ("b", pytest.approx(1e-8))]


@pytest.fixture(scope="module")
def recorded():
    if not TRACE.exists():
        pytest.skip("no recorded trace")
    return xtrace.Trace.load(str(TRACE))


def test_recorded_trace_has_device_ops_and_host_spans(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    ops = recorded.devices["/device:TPU:0"]
    assert len(ops) > 10
    rounds = [s for s in recorded.spans if s[2] == "trainer.round"]
    assert len(rounds) == 3


def test_recorded_busy_union_and_idle(recorded):
    lo = min(s for s, _, _ in recorded.spans)
    hi = max(e for _, e, _ in recorded.spans)
    ops = recorded.devices["/device:TPU:0"]
    busy = recorded.busy_s(lo, hi)
    # the union is no longer than the summed op time nor the window,
    # and no shorter than the longest op
    assert max(e - s for s, e, _ in ops) * 1e-9 <= busy
    assert busy <= min(sum(e - s for s, e, _ in ops) * 1e-9,
                       (hi - lo) * 1e-9)
    idle, count = recorded.idle_inside("trainer.round", lo, hi)
    assert count == 3
    inside = sum(e - s for s, e, n in recorded.spans
                 if n == "trainer.round") * 1e-9
    assert 0 < idle < inside
    gaps = recorded.idle_gaps(lo, hi)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_recorded_kernel_events(recorded):
    lo, hi = 0, float("inf")
    events = recorded.events_matching(xtrace.KERNEL_PATTERN, lo, hi)
    assert len(events) == 3          # one eq.-(4) call per traced round
    by_hand = sum(e - s for s, e, n in recorded.devices["/device:TPU:0"]
                  if n.startswith("%fl_aggregate") and "custom-call" in n)
    assert sum(e - s for s, e, _ in events) == by_hand > 0
