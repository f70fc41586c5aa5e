"""Kernel microbenchmarks.  Off TPU the jnp reference path is timed (the
Pallas bodies are validated in interpret mode by tests); on a TPU the
kernels run natively and `derived` adds the least time the running chip
could take for the same shape (`roofline_us`, from its published peaks —
never modelled for a device other than the one measured)."""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, time_us
from repro.kernels import ref
from repro.launch.mesh import device_peaks


def _roofline(flops: float = 0.0, bytes_moved: float = 0.0) -> str:
    """``;roofline_us=...`` for the running TPU, empty off TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return ""
    peaks = device_peaks(dev.device_kind)
    us = max(flops / peaks["flops_bf16"], bytes_moved / peaks["hbm_bw"]) * 1e6
    return f";roofline_us={us:.1f}"


def bench_flash(b=1, h=8, hkv=2, s=1024, d=64) -> str:
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(rng, 1), (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(rng, 2), (b, hkv, s, d))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (b, hkv, s, d))
    f = jax.jit(lambda q, k, v: ref.mha_reference(q, k, v, causal=True))
    us = time_us(lambda *a: jax.block_until_ready(f(*a)), q, k, v, iters=5)
    flops = 4.0 * b * h * s * s * d
    return csv_row(f"kernels/flash_attention/b{b}h{h}s{s}d{d}", us,
                   f"flops={flops:.2e}" + _roofline(flops=flops))


def bench_ssd(b=2, s=2048, nh=8, hd=64, n=64, chunk=128) -> str:
    rng = jax.random.PRNGKey(1)
    from repro.kernels import ssd_chunk
    x = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(rng, 2),
                                           (b, s, nh)))
    a_log = jnp.log(jnp.linspace(1.0, 8.0, nh))
    b_in = jax.random.normal(jax.random.fold_in(rng, 3), (b, s, n))
    c_in = jax.random.normal(jax.random.fold_in(rng, 4), (b, s, n))
    us = time_us(lambda: jax.block_until_ready(
        ssd_chunk(x, dt, a_log, b_in, c_in, chunk=chunk)), iters=5)
    flops = b * s * chunk * nh * (n + hd) * 2.0
    return csv_row(f"kernels/ssd_chunk/b{b}s{s}nh{nh}", us,
                   f"intra_chunk_flops={flops:.2e}")


def bench_aggregate(n=4_000_000, k=4) -> str:
    rng = jax.random.PRNGKey(2)
    from repro.kernels import fl_aggregate
    theta = jax.random.normal(jax.random.fold_in(rng, 1), (n,))
    deltas = jax.random.normal(jax.random.fold_in(rng, 2), (k, n))
    coeffs = jax.nn.softmax(jax.random.normal(jax.random.fold_in(rng, 3),
                                              (k,)))
    us = time_us(lambda: jax.block_until_ready(
        fl_aggregate(theta, deltas, coeffs)), iters=10)
    bytes_moved = (k + 2.0) * n * 4
    return csv_row(f"kernels/fl_aggregate/n{n}k{k}", us,
                   f"bytes={bytes_moved:.2e}"
                   + _roofline(bytes_moved=bytes_moved))


def bench_aggregate_pytree(hidden=256, k=8) -> str:
    """eq.-(4) on a real model pytree: per-leaf reduce vs ravelled fused."""
    from repro.fl import aggregate_fused, aggregate_stacked
    from repro.models import MLPTask
    task = MLPTask(input_dim=3072, num_classes=10, hidden=hidden)
    params = task.init(jax.random.PRNGKey(0))
    deltas = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(1),
                                    (k,) + p.shape, p.dtype), params)
    coeffs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (k,)))
    stacked = jax.jit(aggregate_stacked)
    fused = jax.jit(aggregate_fused, static_argnames=("impl",))
    us_s = time_us(lambda: jax.block_until_ready(
        stacked(params, deltas, coeffs)), iters=10)
    us_f = time_us(lambda: jax.block_until_ready(
        fused(params, deltas, coeffs)), iters=10)
    return csv_row(f"kernels/fl_aggregate_pytree/h{hidden}k{k}", us_f,
                   f"per_leaf_us={us_s:.1f};fused_us={us_f:.1f}")


def bench_solver(n=120) -> str:
    import numpy as np
    from repro.core import estimate_hyperparams, paper_default_params, solve_p2
    rng = np.random.default_rng(0)
    params = paper_default_params(
        num_devices=n, data_sizes=rng.integers(200, 600, n).astype("float32"))
    hp = estimate_hyperparams(params, 0.1, loss_scale=1.5)
    import jax.numpy as jnp
    h = jnp.asarray(np.clip(rng.exponential(0.1, n), 0.01, 0.5)
                    .astype("float32"))
    queues = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (n,))) * 1e3
    us = time_us(lambda: jax.block_until_ready(
        solve_p2(params, h, queues, hp.V, hp.lam)), iters=10)
    return csv_row(f"core/algorithm2_solve_p2/N{n}", us,
                   "per_round_decision_latency")


def run(smoke: bool = False) -> List[str]:
    if smoke:
        return [bench_flash(b=1, h=2, hkv=2, s=64, d=16),
                bench_ssd(b=1, s=64, nh=2, hd=16, n=8, chunk=16),
                bench_aggregate(n=10_000, k=2),
                bench_aggregate_pytree(hidden=16, k=2),
                bench_solver(n=8)]
    return [bench_flash(), bench_ssd(), bench_aggregate(),
            bench_aggregate_pytree(), bench_solver()]


if __name__ == "__main__":
    for row in run():
        print(row)
