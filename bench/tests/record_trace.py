"""Record the small profiler trace that ``test_perfbench_trace.py`` reads.

Run on a TPU host from the checkout root:

    python bench/tests/record_trace.py [--out PATH]

It traces three annotated "rounds" of a small jitted step followed by
the Pallas eq.-(4) kernel, with host gaps between them, and copies the
``.xplane.pb`` to ``bench/tests/data/tiny_tpu.xplane.pb``.  A summary of
the trace's planes, lines and event names goes to standard output.
"""

from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
OUT = Path(__file__).resolve().parent / "data" / "tiny_tpu.xplane.pb"


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=OUT)
    dest = ap.parse_args().out
    import jax
    import jax.numpy as jnp
    from repro.kernels import fl_aggregate

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2

    @jax.jit
    def step(a, b):
        for _ in range(4):
            a = jnp.tanh(a @ b)
        return a

    a = jnp.ones((512, 512), jnp.float32)
    b = jnp.eye(512, dtype=jnp.float32) * 0.5
    n, k = 545_002, 8
    theta = jnp.zeros((n,), jnp.float32)
    deltas = jnp.ones((k, n), jnp.float32)
    coeffs = jnp.full((k,), 0.125, jnp.float32)
    jax.block_until_ready(step(a, b))
    jax.block_until_ready(fl_aggregate(theta, deltas, coeffs))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for t in range(3):
                with jax.profiler.TraceAnnotation("trainer.round"):
                    out = step(a, b)
                    out = fl_aggregate(theta, deltas, coeffs)
                    jax.block_until_ready(out)
                time.sleep(0.002)
        path = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0]
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, dest)
    prof = jax.profiler.ProfileData.from_file(str(dest))
    for plane in prof.planes:
        for line in plane.lines:
            names = Counter(e.name for e in line.events)
            print(plane.name, "|", line.name, "|", len(list(line.events)),
                  "|", names.most_common(40))
    print("bytes", dest.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
