"""Flight recorder contract: span nesting/ordering and sink round-trips,
Chrome-trace export validity, the metrics registry reproducing
``RolloutReport.meta``'s dispatch accounting exactly, the strict
watchdog raising on a forced post-warmup retrace (and staying silent on
the warmed path), the zero-overhead no-sink fast path, ``take``'s
deep-copied meta, and the chunk store's schema/provenance gate."""

import importlib.util
import json
import os
import time
from pathlib import Path

import numpy as np
import pytest

from test_arena import N, _mixed_grid, _mixed_k_grid, _setup

from repro.obs import trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.watchdog import RetraceError, Watchdog
from repro.sim import Arena, NpzChunkStore, SweepService


def _obs_report():
    """``tools/obs_report.py`` as a module (``tools`` is no package)."""
    path = Path(__file__).resolve().parents[1] / "tools" / "obs_report.py"
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- tracer core -----------------------------------------------------------


def test_span_nesting_order_and_parents():
    """Children emit before parents (Chrome-trace style); ids/parents/
    depths describe the live stack; attrs round-trip, including
    mid-span .set()."""
    with trace.installed(trace.MemorySink()) as sink:
        with trace.span("outer", a=1) as outer:
            with trace.span("middle") as mid:
                with trace.span("inner", chunk=3):
                    pass
            outer.set(found=2)
        trace.event("tick", k=8)
    names = [r["name"] for r in sink.records]
    assert names == ["inner", "middle", "outer", "tick"]
    inner, middle, outer, tick = sink.records
    assert inner["parent"] == middle["id"]
    assert middle["parent"] == outer["id"]
    assert outer["parent"] is None
    assert (inner["depth"], middle["depth"], outer["depth"]) == (2, 1, 0)
    assert outer["attrs"] == {"a": 1, "found": 2}
    assert inner["attrs"] == {"chunk": 3}
    assert tick["dur"] == 0.0 and tick["attrs"] == {"k": 8}
    # spans time their bodies: parent interval contains the child's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9


def test_jsonl_sink_round_trip(tmp_path):
    """JsonlSink records read back identically through load_jsonl —
    numpy attr values coerced to plain JSON scalars."""
    path = str(tmp_path / "flight.jsonl")
    with trace.installed(trace.JsonlSink(path, flush_every=1)):
        with trace.span("arena.dispatch", chunk=np.int64(2),
                        k_pad=np.float32(4.0), lanes=[1, 2]):
            pass
    records = trace.load_jsonl(path)
    assert len(records) == 1
    r = records[0]
    assert r["name"] == "arena.dispatch"
    assert r["attrs"] == {"chunk": 2, "k_pad": 4.0, "lanes": [1, 2]}
    assert r["dur"] >= 0.0
    # every line is one complete JSON object (append-only, line-atomic)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_chrome_trace_export_valid(tmp_path):
    """The exported Chrome-trace file is loadable JSON in the
    traceEvents format: complete "X" events with µs ts/dur, instant
    records as "i" events, plus the process-name metadata event."""
    with trace.installed(trace.MemorySink()) as sink:
        with trace.span("arena.run", lanes=4):
            with trace.span("arena.dispatch", chunk=0):
                pass
        trace.event("watchdog.retrace", retraces=1)
    out = str(tmp_path / "chrome.json")
    trace.export_chrome_trace(list(sink.records), out)
    with open(out) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in complete} == {"arena.run",
                                             "arena.dispatch"}
    assert [e["name"] for e in instants] == ["watchdog.retrace"]
    for e in complete:
        assert e["dur"] >= 0.0 and "ts" in e and e["pid"] == 0
    disp = next(e for e in complete if e["name"] == "arena.dispatch")
    run = next(e for e in complete if e["name"] == "arena.run")
    assert run["ts"] <= disp["ts"] <= disp["ts"] + disp["dur"] \
        <= run["ts"] + run["dur"] + 1e-3


def test_no_sink_is_noop_singleton_and_cheap():
    """The zero-overhead contract: without a sink, span() returns the
    shared no-op singleton (no allocation, no clock read) and event()
    does nothing — cheap enough to live on hot paths permanently."""
    assert not trace._SINKS
    s1 = trace.span("arena.dispatch", chunk=1, k_pad=8)
    s2 = trace.span("anything.else")
    assert s1 is trace._NOOP and s2 is trace._NOOP
    with s1:
        pass
    assert s1.set(x=1) is s1
    t0 = time.perf_counter()
    for i in range(100_000):
        with trace.span("hot.path", i=i):
            pass
    elapsed = time.perf_counter() - t0
    # ~100ns/call on any modern host; 2s bound = pure-smoke margin
    assert elapsed < 2.0, f"no-sink span path too slow: {elapsed:.3f}s"


# -- metrics registry ------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("arena.dispatches")
    c.inc()
    c.inc(3)
    assert c.value == 4 and reg.get("arena.dispatches") == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("service.queue_depth").set(7)
    h = reg.histogram("pool.quant.abs_err")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["arena.dispatches"] == 4
    assert snap["service.queue_depth"] == 7.0
    assert snap["pool.quant.abs_err"]["count"] == 4
    assert snap["pool.quant.abs_err"]["p50"] == pytest.approx(0.2)
    assert snap["pool.quant.abs_err"]["sum"] == pytest.approx(1.0)
    assert reg.get("absent", default=None) is None
    assert "arena.dispatches" in reg.names()


def test_registry_reproduces_meta_accounting_mixed_k_auto():
    """On a fresh arena, one auto-planned mixed-K run's cumulative
    registry counters equal the report meta exactly (the registry-as-
    view contract); a second run accumulates additively while meta
    stays per-run; dispatch_accounting still cross-checks."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    arena = Arena(eng, k_mode="auto")
    T = 3
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    m = arena.metrics
    assert m.get("arena.runs") == 1
    assert m.get("arena.dispatches") == rep.meta["dispatches"]
    assert m.get("arena.executables_built") == \
        rep.meta["executables_built"]
    assert m.get("arena.executables_cached") == \
        rep.meta["executables_cached"]
    assert rep.dispatch_accounting()["dispatches"] == \
        rep.meta["dispatches"]
    rep2 = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert m.get("arena.runs") == 2
    assert m.get("arena.dispatches") == \
        rep.meta["dispatches"] + rep2.meta["dispatches"]
    assert m.get("arena.executables_built") == \
        rep.meta["executables_built"] + rep2.meta["executables_built"]
    # the public attributes remain views over the same registry
    assert arena.traces == m.get("arena.traces")
    assert arena.input_cache_hits == m.get("arena.input_cache.hits")
    assert arena.input_cache_misses == m.get("arena.input_cache.misses")


# -- watchdog --------------------------------------------------------------


def test_watchdog_strict_raises_on_forced_retrace():
    """Warm at T rounds, run at T+1: the round-count change retraces the
    warmed executable — a strict watchdog turns that silent latency
    multiplication into RetraceError; the violation record carries the
    retrace count and survives on the watchdog."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_grid(s=4)
    arena = Arena(eng)
    dog = Watchdog(strict=True).attach(arena)
    T = 3
    arena.warmup(params0, sp, bank, grid, T)
    assert dog.armed
    h_all = arena.sample_channels(grid, T, N)
    lr = np.full(T, 0.1, np.float32)
    # warmed same-shape run: no violation
    arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert dog.violations == []
    # forced retrace: different round count = new scan shape
    lr2 = np.full(T + 1, 0.1, np.float32)
    with pytest.raises(RetraceError, match="post-warmup retrace"):
        arena.run(params0, sp, bank, grid, T + 1, lr2)
    assert len(dog.violations) == 1
    assert dog.violations[0]["retraces"] >= 1


def test_watchdog_nonstrict_warns_once_and_advances_baseline():
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_grid(s=4)
    arena = Arena(eng)
    dog = Watchdog(strict=False).attach(arena)
    T = 3
    arena.warmup(params0, sp, bank, grid, T)
    lr2 = np.full(T + 1, 0.1, np.float32)
    with pytest.warns(RuntimeWarning, match="post-warmup retrace"):
        arena.run(params0, sp, bank, grid, T + 1, lr2)
    # the baseline advanced: repeating the (now-cached) shape is clean
    h2 = arena.sample_channels(grid, T + 1, N)
    arena.run(params0, sp, bank, grid, T + 1, lr2, h_all=h2)
    assert len(dog.violations) == 1


# -- the streaming acceptance path -----------------------------------------


def test_streaming_service_jsonl_covers_every_chunk(tmp_path):
    """One warmed streaming SweepService run with a JSONL sink yields a
    Chrome-trace-loadable span file covering plan -> compile ->
    dispatch -> reduce, with one arena.dispatch and one arena.reduce
    span per chunk; the strict watchdog sees zero post-warmup retraces;
    the service/store counters land in the shared registry; the health
    summary reads the stall ratios from the spans."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_grid(s=4)
    T, chunk = 6, 2
    arena = Arena(eng, chunk_size=chunk)
    svc = SweepService(arena, params0, sp, bank,
                       checkpoint_dir=str(tmp_path / "ckpt"))
    Watchdog(strict=True).attach(arena)
    log = str(tmp_path / "sweep.jsonl")
    with trace.installed(trace.JsonlSink(log, flush_every=1)):
        svc.warmup(grid, T)
        t = svc.submit(grid, T)
        done = svc.run_pending()             # strict: raises on retrace
    assert done == [t]
    records = trace.load_jsonl(log)
    names = [r["name"] for r in records]
    for phase in ("arena.warmup", "service.batch", "arena.run",
                  "arena.plan", "arena.compile", "arena.dispatch",
                  "arena.reduce", "store.save", "service.reduce"):
        assert phase in names, f"missing span {phase}"
    n_chunks = -(-T // chunk)
    run_spans = [r for r in records if r["name"] == "arena.run"]
    dispatch = [r for r in records if r["name"] == "arena.dispatch"
                and r["ts"] > run_spans[0]["ts"]]
    reduce_ = [r for r in records if r["name"] == "arena.reduce"
               and r["ts"] > run_spans[0]["ts"]]
    assert len(dispatch) == n_chunks
    assert len(reduce_) == n_chunks
    assert sorted(r["attrs"]["chunk"] for r in dispatch) == \
        list(range(n_chunks))
    # chrome-trace loadable
    out = str(tmp_path / "sweep_trace.json")
    trace.export_chrome_trace(records, out)
    with open(out) as f:
        doc = json.load(f)
    assert any(e.get("name") == "arena.dispatch"
               for e in doc["traceEvents"])
    # shared registry: service + store + arena in one namespace
    m = arena.metrics
    assert svc.stats["batches"] == 1 and svc.stats["scenarios"] == 4
    assert svc.stats["coalesced_lanes"] == [4]
    assert m.get("store.saves") == svc.store.saves > 0
    # the stall view: p99 / p50 of the dispatch and reduce spans
    health = _obs_report().health_summary(records)
    for key in ("dispatch_stall_ratio", "reduce_stall_ratio"):
        assert np.isfinite(health[key]) and health[key] >= 1.0, health


# -- report meta deep copy -------------------------------------------------


def test_take_deep_copies_meta():
    """Mutating a split report's nested per-bucket counters (or plan)
    must not leak into the parent — and a full-coverage take keeps the
    accounting valid while a true slice clears buckets."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    arena = Arena(eng, k_mode="auto")
    T = 3
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    parent_buckets = json.loads(json.dumps(rep.meta["buckets"]))
    full = rep.take(np.arange(len(grid)))
    assert full.meta["split_from"] == len(grid)
    assert full.dispatch_accounting()["dispatches"] == \
        rep.meta["dispatches"]
    full.meta["buckets"][0]["lanes"].append(999)
    full.meta["buckets"][0]["dispatches"] = 12345
    full.meta["plan"] = None
    assert rep.meta["buckets"] == parent_buckets
    assert rep.meta["plan"] is not None
    rep.dispatch_accounting()                 # parent still consistent
    sub = rep.take(np.array([1, 0]))
    assert sub.meta["buckets"] == []
    sub2 = rep.take(np.array([0, 1]))         # partial, in order: slice
    assert sub2.meta["buckets"] == []


# -- chunk store schema / provenance ---------------------------------------


def _fake_store(tmp_path, **kw):
    def carry_like(s):
        return {"params": {"w": np.zeros((s, 2), np.float32)},
                "queues": np.zeros((s, 3), np.float32),
                "rng": np.zeros((s, 2), np.uint32)}
    return NpzChunkStore(str(tmp_path), carry_like, **kw)


def test_store_manifest_records_schema_and_provenance(tmp_path):
    import repro.sim.service as service_mod
    store = _fake_store(tmp_path)
    carry = {"params": {"w": np.ones((2, 2), np.float32)},
             "queues": np.ones((2, 3), np.float32),
             "rng": np.zeros((2, 2), np.uint32)}
    store.save("tag1", 4, carry, {"loss": np.zeros((2, 4), np.float32)})
    assert store.saves == 1
    with open(tmp_path / "tag1_carry.json") as f:
        md = json.load(f)["metadata"]
    assert md["schema_version"] == \
        service_mod.CHUNK_STORE_SCHEMA_VERSION
    assert md["t"] == 4 and md["s"] == 2
    assert md["host"] and md["jax_version"] and md["grid_digest"] == \
        "tag1"
    assert md["saved_at"].endswith("Z")
    t, restored, metrics = store.load("tag1")
    assert t == 4 and store.loads == 1
    np.testing.assert_array_equal(np.asarray(restored["queues"]),
                                  carry["queues"])


def test_store_refuses_resume_on_schema_mismatch(tmp_path):
    store = _fake_store(tmp_path)
    carry = {"params": {"w": np.ones((2, 2), np.float32)},
             "queues": np.ones((2, 3), np.float32),
             "rng": np.zeros((2, 2), np.uint32)}
    store.save("tag1", 4, carry, {"loss": np.zeros((2, 4), np.float32)})
    # simulate a checkpoint written by an older incompatible build
    mpath = tmp_path / "tag1_carry.json"
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["metadata"]["schema_version"] = 0
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="schema_version 0"):
        store.load("tag1")
    # a manifest with NO version field (pre-provenance file) is refused
    # the same way — missing counts as version 0
    del manifest["metadata"]["schema_version"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="refuses to resume"):
        store.load("tag1")
    assert store.loads == 0


def test_store_counters_share_service_registry(tmp_path):
    """A store built by the service writes store.* into the arena's
    registry; a standalone store gets its own."""
    task, eng, bank, sp, params0 = _setup()
    arena = Arena(eng)
    svc = SweepService(arena, params0, sp, bank,
                       checkpoint_dir=str(tmp_path))
    assert svc.store.metrics is arena.metrics
    assert svc.metrics is arena.metrics
    standalone = _fake_store(tmp_path / "solo")
    assert standalone.metrics is not arena.metrics
