"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  * convergence (Figs. 1/2): LROA vs Uni-D/Uni-S/DivFL + % latency saved
  * lambda sweep (Fig. 3), V sweep (Fig. 4), K sweep (Figs. 5/6), and the
    ScenarioArena grid throughput (S-batched vs host-looped rollouts,
    recorded in the ``arena`` section of BENCH_round_engine.json)
  * kernel microbenches + Algorithm-2 solver latency
  * round-engine throughput (sequential vs fused vs scan rounds/sec,
    written to BENCH_round_engine.json)
  * roofline terms per (arch x shape x mesh) from the dry-run dumps

Default scale finishes on CPU in tens of minutes; --paper-scale switches to
the paper's 120-device / 2000-round configuration; --smoke runs every
section at tiny shapes in well under a minute (CI guard for the perf paths).
"""

from __future__ import annotations

import argparse
import sys


def smoke_config():
    from benchmarks.common import BenchConfig
    return BenchConfig(num_devices=6, rounds=3, sample_count=2,
                       local_epochs=1, batch_size=8, num_classes=2,
                       image_shape=(4, 4, 1), examples=240)


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface, importable without jax/numpy — the docs drift
    guard (``tools/check_docs.py``) parses every ``python -m
    benchmarks.run ...`` command quoted in docs/ against this parser."""
    ap = argparse.ArgumentParser(prog="benchmarks.run", description=__doc__)
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes everywhere; exercises every bench path")
    ap.add_argument("--skip", default="",
                    help="comma list: convergence,sweeps,kernels,"
                         "round_engine,roofline")
    ap.add_argument("--obs", metavar="LOG", nargs="?",
                    const="runlogs/bench.jsonl", default=None,
                    help="record a flight-recorder span log (JSONL) for "
                         "the whole bench run; optional path, default "
                         "runlogs/bench.jsonl — render it with "
                         "tools/obs_report.py")
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    skip = set(filter(None, args.skip.split(",")))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks.common import BenchConfig
    if args.smoke:
        cfg = smoke_config()
    elif args.paper_scale:
        cfg = BenchConfig.paper_scale()
    else:
        cfg = BenchConfig()

    sink = None
    if args.obs:
        from repro.obs import trace as obs_trace
        sink = obs_trace.install_sink(obs_trace.JsonlSink(args.obs))
        print(f"# obs: recording spans to {args.obs}", file=sys.stderr)

    print("name,us_per_call,derived")
    if "kernels" not in skip:
        from benchmarks import bench_kernels
        for row in bench_kernels.run(smoke=args.smoke):
            print(row, flush=True)
    if "round_engine" not in skip:
        from benchmarks import bench_round_engine
        for row in bench_round_engine.run(smoke=args.smoke):
            print(row, flush=True)
    if "convergence" not in skip:
        from benchmarks import bench_convergence
        for row in bench_convergence.run(cfg):
            print(row, flush=True)
    if "sweeps" not in skip:
        from benchmarks import bench_sweeps
        sweeps = [
            (bench_sweeps.lambda_sweep, dict(mus=(1.0,))),
            (bench_sweeps.v_sweep, dict(nus=(1e5,), rounds=10)),
            (bench_sweeps.k_sweep, dict(ks=(2,))),
            (bench_sweeps.heterogeneity_sweep,
             dict(spreads=(2.0,), rounds=10)),
            (bench_sweeps.zoo_sweep, dict(rounds=3, seeds=1)),
            (bench_sweeps.arena_sweep,
             dict(s_values=(2, 4), rounds=3, smoke=True)),
        ]
        for fn, smoke_kwargs in sweeps:
            for row in fn(cfg, **(smoke_kwargs if args.smoke else {})):
                print(row, flush=True)
    if "roofline" not in skip:
        from benchmarks import bench_roofline
        for row in bench_roofline.run():
            print(row, flush=True)

    if sink is not None:
        from repro.obs import trace as obs_trace
        obs_trace.remove_sink(sink)
        sink.close()
        print(f"# obs: span log written to {sink.path}", file=sys.stderr)


if __name__ == "__main__":
    main()
