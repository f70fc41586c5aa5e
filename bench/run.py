#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); ``bench/cells/<cell>.json``
holds what is the cell's own (rollout shape, the limits of the check).
The run builds the data and weights from ``--seed``, warms every shape
the window uses (compiles go to ``<checkout>/.jax_cache``), drives the
program through the first steps it will check, measures for
``--seconds``, then compares those first steps with the plain reference
(``bench/reference.py``).  With ``--trace 1`` the window runs under
``jax.profiler`` and the cell's per-layer metrics are read from the trace
by the readers in ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), with ``checks`` last: each compared number beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, the
run exits with code 3 and prints no result; a compilation inside the
window exits with code 4.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

EXIT_NO_CHIP, EXIT_COMPILED_IN_WINDOW = 3, 4
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration,
    traffic, cell file and the metrics it reports, found by name."""
    spec = load_json(ROOT / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg, cell = cell_files(name, w["config"], w["traffic"])

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    layer = [m for m in spec["per_layer"] if mine(m)]
    return dict(name=name, chips=w["chips"], cfg=cfg, cell=cell, e2e=e2e,
                per_layer=layer)


def cell_files(name: str, config: str, traffic: str) -> tuple[dict, dict]:
    """(configuration, traffic with the cell's own file over it)."""
    cfg = load_json(BENCH / "configs" / f"{config}.json")
    cell = load_json(BENCH / "traffic" / f"{traffic}.json")
    cell.update(load_json(BENCH / "cells" / f"{name}.json"))
    return cfg, cell


def read_metric(name: str, ctx) -> float | None:
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class Compiles:
    """Counts traces and backend compiles, process-wide."""

    count = 0

    @classmethod
    def listen(cls):
        import jax

        def on_event(event, duration, **_):
            if event in COMPILE_EVENTS:
                cls.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


class Context:
    """What a per-layer reader sees: the reduced trace and its window
    (ns), the loop that ran, the configuration, the chip's peaks."""

    def __init__(self, trace, run, cfg, peaks, chips):
        self.trace, self.run, self.cfg = trace, run, cfg
        self.peaks, self.chips = peaks, chips
        self.lo, self.hi = trace.window()
        self.window_s = (self.hi - self.lo) * 1e-9


def device_info(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def execute(c: dict, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, peaks: dict | None = None,
            log=sys.stderr) -> tuple[int, dict]:
    """One run of cell ``c``; returns (exit code, result).  ``peaks``
    stands in for the table's entry where the device has none (tests on
    the CPU); a device missing from ``bench/peaks.json`` is an error."""
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < c["chips"]):
        print(f"needs {c['chips']} TPU chip(s); jax sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=log)
        return EXIT_NO_CHIP, {}
    import jax.numpy as jnp
    from bench.loops import LOOPS
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    Compiles.listen()
    cell = c["cell"]
    run = LOOPS[cell["kind"]](c["cfg"], cell, seed)
    run.setup()
    setup_s = time.perf_counter() - T_START
    before = Compiles.count
    tdir = None
    if trace:
        from repro.obs import trace as obs_trace
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        sink = obs_trace.install_sink(obs_trace.MemorySink(capacity=1 << 16))
        obs_trace.profiler_bridge(True)
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("bench.window"):
            values = run.window(seconds, max_units=cell["trace_units"])
        jax.profiler.stop_trace()
        obs_trace.profiler_bridge(False)
        obs_trace.remove_sink(sink)
    else:
        values = run.window(seconds)
    compiled = Compiles.count - before
    if compiled:
        print(f"{compiled} trace(s) or compile(s) inside the window",
              file=log)
        return EXIT_COMPILED_IN_WINDOW, {}
    device = device_info(devices, c["chips"])
    failed = run.window_failures()
    attempted = run.units
    values["setup_s"] = setup_s

    breakdown = None
    if trace:
        from bench.xtrace import Trace
        import glob
        path = glob.glob(f"{tdir}/plugins/profile/*/*.xplane.pb")[0]
        tr = Trace.load(path)
        if peaks is None:
            table = load_json(BENCH / "peaks.json")
            if device["kind"] not in table:
                raise KeyError(f"no peaks for device kind {device['kind']!r}"
                               f" in bench/peaks.json")
            peaks = table[device["kind"]]
        ctx = Context(tr, run, c["cfg"], peaks, c["chips"])
        device["busy_s"] = tr.busy_s(ctx.lo, ctx.hi)
        device["window_s"] = ctx.window_s
        breakdown = {"device_ops": [list(x) for x in
                                    tr.top_ops(ctx.lo, ctx.hi)],
                     "idle_gaps": [list(x) for x in
                                   tr.idle_gaps(ctx.lo, ctx.hi)]}
        metrics = {}
        for m in c["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["e2e"]}

    run.free()
    got = run.program()
    want = run.reference(jnp.float32)
    numbers = run.compare(got, want)
    limits = cell["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(failed == 0 and all(
        math.isfinite(v["value"]) and v["value"] <= v["limit"]
        for v in checks.values()))
    for k, v in checks.items():
        print(f"check {k}: {v['value']:.6g} (limit {v['limit']:.6g})",
              file=log)
    print(f"check failed_units: {failed} (limit 0)", file=log)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = load_cell(args.workload)
    code, result = execute(c, args.seed, args.seconds, bool(args.trace))
    if code == 0:
        sys.stdout.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
