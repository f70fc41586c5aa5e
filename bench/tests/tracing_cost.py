"""What the program's tracing costs when it is on, without a profiler.

Run on a TPU host from the checkout root:

    python bench/tests/tracing_cost.py [--seed N] [--pairs P]

It sets up the arena cell's loop as ``bench/run.py`` does, then times
rollouts back to back, alternating off (no sink, no bridge) and on (a
``MemorySink`` and ``profiler_bridge``, as the traced run installs them,
but no ``jax.profiler`` trace).  One JSON line goes to standard output:
each side's rollout seconds, their medians and the on-cost in percent.
"""

from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
CELL = "cifar10_cnn.arena_zoo"


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2**33 + 7)
    ap.add_argument("--pairs", type=int, default=3)
    args = ap.parse_args()
    import jax
    from bench import run as bench_run
    from bench.loops import LOOPS
    from repro.obs import trace as obs_trace

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".jax_cache"))
    c = bench_run.load_cell(CELL)
    run = LOOPS[c["cell"]["kind"]](c["cfg"], c["cell"], args.seed)
    run.setup()
    times = {"off": [], "on": []}
    for i in range(2 * args.pairs):
        side = ("off", "on")[i % 2]
        if side == "on":
            sink = obs_trace.install_sink(obs_trace.MemorySink(1 << 16))
            obs_trace.profiler_bridge(True)
        t = time.perf_counter()
        run._rollout()
        times[side].append(time.perf_counter() - t)
        if side == "on":
            obs_trace.profiler_bridge(False)
            obs_trace.remove_sink(sink)
    med = {k: statistics.median(v) for k, v in times.items()}
    print(json.dumps({"cell": CELL, "seconds": times, "median": med,
                      "on_cost_pct": 100.0 * (med["on"] / med["off"] - 1),
                      "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
