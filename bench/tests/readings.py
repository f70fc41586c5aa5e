"""The readings that the check's limits are set from, on the chip.

    python bench/tests/readings.py --workload <cell> --seeds 1,2,3 \
        [--control 3] [--faults half_batch,altered --fault-seeds 3]

``--workload`` may also name a cell that has a file under
``bench/cells/`` (``<config>.<traffic>``) but no entry in BENCHMARK.json.
For each seed: the cell's set-up (the program driven through the steps
the check compares) and the numbers compared against the reference.
``--control N``: on the first N seeds also the control, the reference
computed in bfloat16 put in the program's place.  ``--faults``: the named
faults of ``bench/tests/faults.py`` planted under the timed path, each
on ``--fault-seeds`` seeds.  One JSON line per reading; nothing is
timed, and the window is not run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import run as bench_run
    from bench.loops import LOOPS
    from bench.tests.faults import FAULTS
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cells = {w["name"] for w in bench_run.load_json(
        bench_run.ROOT / "BENCHMARK.json")["workloads"]}
    if args.workload in cells:
        c = bench_run.load_cell(args.workload)
    else:
        cfg, cell = bench_run.cell_files(args.workload,
                                         *args.workload.split(".", 1))
        c = dict(cfg=cfg, cell=cell)
    seeds = [int(s) for s in args.seeds.split(",")]

    def reading(mode, seed, numbers, t0):
        print(json.dumps({"mode": mode, "seed": seed, "numbers": numbers,
                          "s": round(time.perf_counter() - t0, 1)}),
              flush=True)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        r = LOOPS[c["cell"]["kind"]](c["cfg"], c["cell"], seed)
        r.setup()
        r.free()
        want = r.reference(jnp.float32)
        got = r.program()
        reading("program", seed, r.compare(got, want), t0)
        if isinstance(got, list):
            for lane, (g, w) in enumerate(zip(got, want)):
                numbers = r.compare([g], [w])
                numbers["finite"] = [bool(np.isfinite(g["loss"]).all()),
                                     bool(np.isfinite(w["loss"]).all())]
                reading(f"lane{lane}:{r.names[lane]}:{r.modes[lane]}", seed,
                        numbers, t0)
        if i < args.control:
            reading("control", seed,
                    r.compare(r.reference(jnp.bfloat16), want), t0)
    for name in filter(None, args.faults.split(",")):
        for seed in seeds[:args.fault_seeds]:
            t0 = time.perf_counter()
            with FAULTS[name]():
                r = LOOPS[c["cell"]["kind"]](c["cfg"], c["cell"], seed)
                r.setup()
            r.free()
            reading(name, seed, r.compare(r.program(),
                                          r.reference(jnp.float32)), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
