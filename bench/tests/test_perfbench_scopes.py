"""The scope reduction (``bench/xscope.py``): scope paths, innermost-op
attribution and the ``tf_op`` reader on hand-made data and on traces
recorded on a TPU v5e; the program's scopes in its compiled
executables; the ``arena.dispatch`` counts against the tiny cell's
shapes; and the readers that were there before, pinned where they read
the same traces.  Nothing here needs a TPU."""

import glob
import json
import tempfile
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import readers, xscope, xtrace
from bench import run as bench_run

DATA = Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny_tpu.xplane.pb"
SCOPED = DATA / "tiny_tpu_scoped.xplane.pb"
CELL = "cifar10_cnn.arena_zoo"
NEW_READERS = ("control_ms.arena", "gather_ms.arena", "local_sgd_ms.arena",
               "sgd_step_ms.arena", "sgd_grad_step_ms.arena",
               "aggregate_ms.arena", "eval_ms.arena", "useful_rows.arena")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_scope_names_are_the_programs():
    from repro.obs import scopes
    assert xscope.LAYERS == scopes.LAYERS
    assert xscope.SCOPES == scopes.SCOPES
    assert xscope.TIER_PREFIX == scopes.TIER_PREFIX
    assert scopes.tier(2048) == "fl.tier.2048"


@pytest.mark.parametrize("path,want", [
    ("jit(step)/vmap(fl.local_sgd)/while/body/closed_call/fl.sgd.grad/"
     "transpose(jvp(fl.sgd.grad))/dot_general:",
     ("fl.local_sgd", "fl.sgd.grad")),
    ("jit(scan_fn)/vmap()/while/body/closed_call/fl.tier.512/fl.local_sgd/"
     "vmap()/while/body/closed_call/fl.sgd.shuffle/jit(_where)/select_n",
     ("fl.tier.512", "fl.local_sgd", "fl.sgd.shuffle")),
    ("transpose(jvp(vmap(fl.sgd.grad)))/conv_general_dilated:fusion",
     ("fl.sgd.grad",)),
    ("jit(scan_fn)/vmap(fl.eval)/jit(_metrics)/reduce_sum", ("fl.eval",)),
    ("jit(step)/dot_general:", ()),
    ("", ()),
    ("jit(f)/fl.tier.x/fl.localsgd/fl.eval2/xfl.gather/fl.sgd", ()),
])
def test_scope_paths_match_plain_and_wrapped_names(path, want):
    assert xscope.scopes_of(path) == want


@pytest.mark.parametrize("events,lo,hi,want", [
    # a loop and its body ops: the loop keeps the instants between them
    ([(0, 100, "loop"), (10, 20, "a"), (30, 60, "b"), (40, 50, "c")],
     0, 100, {"loop": 60, "a": 10, "b": 20, "c": 10}),
    # the same, clipped to a window
    ([(0, 100, "loop"), (10, 20, "a"), (30, 60, "b"), (40, 50, "c")],
     5, 55, {"loop": 15, "a": 10, "b": 15, "c": 10}),
    # two that start together: the shorter is inside
    ([(0, 10, "outer"), (0, 4, "inner")], 0, 10,
     {"inner": 4, "outer": 6}),
    # idle time goes to nobody
    ([(0, 10, "a"), (20, 30, "b")], 0, 40, {"a": 10, "b": 10}),
    # overlapping, not nested: the later start wins while it runs
    ([(0, 10, "a"), (5, 15, "b")], 0, 20, {"a": 5, "b": 10}),
    # empty and out-of-window events
    ([(3, 3, "z"), (50, 60, "late"), (0, 2, "a")], 0, 40, {"a": 2}),
])
def test_innermost_attribution(events, lo, hi, want):
    got = xscope.innermost(events, lo, hi)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(xtrace.length(xtrace.clip(
        xtrace.union([(s, e) for s, e, _ in events]), lo, hi)))


def test_reduce_splits_busy_time_into_layers():
    ops = [(0, 100, "%while.1 = w"),
           (10, 40, "%fusion.1 = f"),
           (40, 70, "%fusion.2 = g"),
           (80, 90, "%copy.1 = c"),
           (120, 130, "%fusion.3 = h")]
    paths = ["jit(f)/vmap()/while",
             "jit(f)/fl.tier.64/vmap(fl.local_sgd)/while/body/fl.sgd.grad/x",
             "jit(f)/fl.tier.64/vmap(fl.local_sgd)/while/body/"
             "fl.sgd.update/y",
             "",
             "jit(f)/fl.decide/z"]
    tr = xtrace.Trace({"/device:TPU:0": ops}, [(0, 200, "bench.window")])
    space = xscope.XSpace(
        {"/device:TPU:0": [(n, p) for (_, _, n), p in zip(ops, paths)]},
        [(5.0, {"sgd_steps": 4, "rows_trained": 64}),
         (50.0, {"sgd_steps": 4, "rows_trained": 64, "cold": 1}),
         (300.0, {"sgd_steps": 99, "rows_trained": 99})])
    r = xscope.reduce(tr, space, 0, 200)
    ns = 1e-9
    assert r.scoped and r.dispatches == 2
    assert (r.sgd_steps, r.rows_trained) == (8, 128)
    assert r.busy_s == pytest.approx(110 * ns)
    assert r.seconds["fl.local_sgd"] == pytest.approx(60 * ns)
    assert r.seconds["fl.tier.64"] == pytest.approx(60 * ns)
    assert r.seconds["fl.sgd.grad"] == pytest.approx(30 * ns)
    assert r.seconds["fl.decide"] == pytest.approx(10 * ns)
    assert r.seconds["unscoped"] == pytest.approx(40 * ns)
    layers = r.layer_s(*xscope.LAYERS) + r.seconds["unscoped"]
    assert layers == pytest.approx(r.busy_s)
    assert [k for k, _ in r.breakdown()][:3] == ["fl.local_sgd", "unscoped",
                                                 "fl.decide"]


def test_join_falls_back_to_text_where_positions_disagree():
    ops = [(0, 1, "%a = x"), (1, 2, "%b = y"), (2, 3, "%c = z")]
    paths = [("%b = y", "p/fl.gather/t"), ("%a = x", "p/fl.eval/t"),
             ("%c = z", "p/fl.decide/t"), ("%c = z", "q/fl.select/t")]
    assert xscope.join(ops, paths) == ["p/fl.eval/t", "p/fl.gather/t", ""]


def test_trace_path_comes_from_the_callers_frame():
    ctx = SimpleNamespace()

    def execute(ctx, path):
        return reader(ctx)

    def reader(ctx):
        return xscope.trace_path(ctx)

    assert execute(ctx, "/t/x.xplane.pb") == "/t/x.xplane.pb"
    assert reader(SimpleNamespace()) is None
    ctx.trace_path = "/u/y.xplane.pb"
    assert execute(ctx, "/t/x.xplane.pb") == "/u/y.xplane.pb"


# -- the recorded traces ------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    return xtrace.Trace.load(str(TINY))


def test_tf_op_reader_on_recorded_trace(tiny):
    space = xscope.read_xspace(str(TINY))
    assert list(space.ops) == ["/device:TPU:0"] and not space.dispatches
    pairs = space.ops["/device:TPU:0"]
    by_op = {xtrace.op_name(text): path for text, path in pairs}
    assert by_op["convolution_tanh_fusion"] == "jit(step)/dot_general:"
    assert by_op["slice.0"] == "jit(fl_aggregate)/slice:"
    assert by_op["fl_aggregate.1"] == "jit(fl_aggregate)/pallas_call:"
    assert by_op["copy-done"] == ""
    ops = tiny.devices["/device:TPU:0"]
    assert [n for _, _, n in ops] == [text for text, _ in pairs]
    lo = min(s for s, _, _ in tiny.spans)
    hi = max(e for _, e, _ in tiny.spans)
    r = xscope.reduce(tiny, space, lo, hi)
    assert not r.scoped and r.sgd_steps == 0
    assert r.seconds == {"unscoped": pytest.approx(r.busy_s)}
    assert r.busy_s == pytest.approx(tiny.busy_s(lo, hi))


def test_existing_readers_read_the_recorded_trace_as_before(tiny):
    lo, hi = 43680870.0, 51577910.0
    assert (min(s for s, _, _ in tiny.spans),
            max(e for _, e, _ in tiny.spans)) == (lo, hi)
    run = SimpleNamespace(selected_rounds=[0, 1, 2], eval_rows=0,
                          useful_flops=lambda sel, rows: 1e9 * len(sel))
    ctx = SimpleNamespace(trace=tiny, lo=lo, hi=hi, run=run, chips=1,
                          window_s=(hi - lo) * 1e-9, peaks=PEAKS)
    assert readers.idle_share(ctx) == pytest.approx(98.07771012936493,
                                                    rel=1e-12)
    assert readers.mfu(ctx) == pytest.approx(0.192837143992421, rel=1e-12)
    assert tiny.top_ops(lo, hi) == [
        ("pad.2", pytest.approx(7.2563e-05)),
        ("fl_aggregate.1", pytest.approx(2.5265e-05)),
        ("convolution_tanh_fusion.2", pytest.approx(1.4074e-05)),
        ("convolution_tanh_fusion", pytest.approx(1.1823e-05)),
        ("pad.0", pytest.approx(1.1383e-05)),
        ("slice.0", pytest.approx(1.1175e-05)),
        ("copy-done", pytest.approx(5.477e-06)),
        ("copy-start", pytest.approx(4.4e-08))]
    gaps = tiny.idle_gaps(lo, hi)
    assert [g[0] for g in gaps] == ["idle"] * 2 + ["trainer.round"] * 8
    assert [g[1] for g in gaps[:6]] == pytest.approx(
        [0.003653397, 0.002934405, 0.000309336, 0.000241696, 0.000227783,
         0.000208651])


@pytest.fixture(scope="module")
def scoped():
    """The scoped trace with a stand-in for the loop that recorded it."""
    from bench.loops import ArenaRun
    side = json.loads(SCOPED.with_suffix(".json").read_text())
    run = ArenaRun(side["cfg"], side["cell"], side["seed"])
    run.units, run.eval_rows = side["units"], side["eval_rows"]
    run.sizes = np.asarray(side["sizes"])
    run.selected_rounds = [np.asarray(s) for s in side["selected_rounds"]]
    ctx = bench_run.Context(xtrace.Trace.load(str(SCOPED)), run,
                            side["cfg"], PEAKS, 1)
    ctx.trace_path = str(SCOPED)
    return ctx


@pytest.mark.parametrize("metric", NEW_READERS)
def test_every_new_reader_reads_the_scoped_trace(scoped, metric):
    v = bench_run.read_metric(metric, scoped)
    assert v is not None and np.isfinite(v) and v >= 0
    if metric == "useful_rows.arena":
        assert 0 < v <= 100


def test_scoped_trace_layers_make_up_the_busy_time(scoped):
    r = xscope.reading(scoped)
    assert set(xscope.LAYERS) <= set(r.seconds)
    assert r.busy_s == pytest.approx(scoped.trace.busy_s(scoped.lo,
                                                         scoped.hi))
    assert r.seconds["unscoped"] < 0.05 * r.busy_s
    assert (r.layer_s(*xscope.LAYERS) + r.seconds["unscoped"]
            == pytest.approx(r.busy_s))
    grad = bench_run.read_metric("sgd_grad_step_ms.arena", scoped)
    assert grad <= bench_run.read_metric("sgd_step_ms.arena", scoped)
    assert r.dispatches >= 1 and r.sgd_steps > 0


def test_existing_readers_on_the_scoped_trace(scoped):
    """``idle_share``, ``mfu``, ``device_ops`` and ``idle_gaps`` of the
    scoped trace, pinned where the code that was there computes them."""
    tr, lo, hi = scoped.trace, scoped.lo, scoped.hi
    assert bench_run.read_metric("idle_share.arena", scoped) == \
        pytest.approx(66.70405704717217, rel=1e-12)
    assert bench_run.read_metric("mfu.arena", scoped) == \
        pytest.approx(0.0036624166425359163, rel=1e-12)
    assert tr.top_ops(lo, hi)[:4] == [
        ("while.769", pytest.approx(0.008450538)),
        ("while.783", pytest.approx(0.004943152)),
        ("while.820", pytest.approx(0.004902118)),
        ("fusion.800", pytest.approx(0.002419593))]
    assert tr.idle_gaps(lo, hi)[:4] == [
        ("arena.reduce", pytest.approx(0.006086062)),
        ("arena.upload", pytest.approx(0.00349054)),
        ("arena.dispatch", pytest.approx(0.003118103)),
        ("arena.eval", pytest.approx(0.002008064))]


# -- the program on the CPU ---------------------------------------------------

@pytest.fixture(scope="module")
def tiny_arena():
    """The tiny arena cell set up on the CPU, the text of every
    executable its warm-up compiled, and one rollout traced with the
    program's spans bridged into the profiler."""
    import jax
    from bench.loops import ArenaRun
    from bench.tests.tiny import tiny_cell
    from repro.obs import trace as obs_trace
    c = tiny_cell(CELL)
    run = ArenaRun(c["cfg"], c["cell"], 2**33 + 9)
    texts, compile_ = [], jax.stages.Lowered.compile

    def spy(self, *a, **k):
        out = compile_(self, *a, **k)
        texts.append(out.as_text())
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.stages.Lowered, "compile", spy)
        run.setup()
    with tempfile.TemporaryDirectory() as d:
        with obs_trace.installed(obs_trace.MemorySink()) as sink:
            obs_trace.profiler_bridge(True)
            try:
                jax.profiler.start_trace(d)
                with jax.profiler.TraceAnnotation("bench.window"):
                    run.window(1e9, max_units=1)
                jax.profiler.stop_trace()
            finally:
                obs_trace.profiler_bridge(False)
        path = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0]
        ctx = bench_run.Context(xtrace.Trace.load(path), run, c["cfg"],
                                PEAKS, 1)
        ctx.trace_path = path
        got = {m: bench_run.read_metric(m, ctx) for m in NEW_READERS}
        space = xscope.read_xspace(path)
    return SimpleNamespace(run=run, texts=texts, sink=sink, got=got,
                           space=space)


def _op_names(text: str) -> set:
    import re
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_every_scope_is_in_the_compiled_arena(tiny_arena):
    found = set()
    for text in tiny_arena.texts:
        for name in _op_names(text):
            found.update(xscope.scopes_of(name))
    assert set(xscope.SCOPES) <= found
    tiers = {s for s in found if s.startswith(xscope.TIER_PREFIX)}
    rows = {b.bucket_examples for b in tiny_arena.run.bank.tiers}
    assert tiers == {f"{xscope.TIER_PREFIX}{n}" for n in rows}


def test_data_plane_scopes_are_in_the_compiled_round_step(tiny_arena):
    import jax
    import jax.numpy as jnp
    run = tiny_arena.run
    bank = run.engine.make_bank(run.bed.clients, tiered="single")
    k = run.cfg["sample_count"]
    fn = run.engine._build_step(bank.steps_per_epoch)
    text = fn.lower(run.params0, *bank.device_args(), *bank.quant_args(),
                    None, jnp.arange(k, dtype=jnp.int32),
                    jnp.full((k,), 1.0 / k, jnp.float32),
                    jnp.float32(0.05),
                    jax.random.split(jax.random.PRNGKey(0), k)
                    ).compile().as_text()
    found = set()
    for name in _op_names(text):
        found.update(xscope.scopes_of(name))
    assert found == {"fl.gather", "fl.local_sgd", "fl.aggregate",
                     "fl.sgd.shuffle", "fl.sgd.batch", "fl.sgd.grad",
                     "fl.sgd.update"}


def test_bridged_dispatch_counts_match_the_shapes(tiny_arena):
    run = tiny_arena.run
    cfg = run.cfg
    lanes, k, bs = len(run.grid), cfg["sample_count"], cfg["batch_size"]
    # every tier runs every round under the lane vmap: E x B_t / bs steps
    steps = cfg["local_epochs"] * sum(b.bucket_examples // bs
                                      for b in run.bank.tiers)
    want_steps = steps * run.rounds
    want_rows = want_steps * bs * k * lanes
    spans = [r["attrs"] for r in tiny_arena.sink.records
             if r["name"] == "arena.dispatch"]
    assert len(spans) == -(-run.rounds // run.cell["chunk_size"])
    assert sum(a["sgd_steps"] for a in spans) == want_steps
    assert sum(a["rows_trained"] for a in spans) == want_rows
    bridged = [stats for _, stats in tiny_arena.space.dispatches]
    assert [s["sgd_steps"] for s in bridged] == [a["sgd_steps"]
                                                 for a in spans]
    assert [s["rows_trained"] for s in bridged] == [a["rows_trained"]
                                                    for a in spans]


def test_new_readers_without_device_scopes(tiny_arena):
    """A trace with no scoped device op (the CPU's, or the parent
    program's): the readers return nothing, and the counts still read."""
    got = tiny_arena.got
    assert {m for m, v in got.items() if v is None} == \
        set(NEW_READERS) - {"useful_rows.arena"}
    assert 0 < got["useful_rows.arena"] <= 100
