"""Production mesh builders (TPU v5e pods) and per-device peak figures.

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialisation.

Every mesh is built with ``Auto`` axis types: the engine and the arena
place their own inputs (``NamedSharding`` on the bank's client axis,
``shard_map`` over ``data``) and rely on the compiler to propagate
shardings through ``jnp.take`` and friends, which ``Explicit`` axes (the
``jax.make_mesh`` default since jax 0.7) reject.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests (axis names match production)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_fl_mesh(num_shards: int | None = None):
    """1-D ``('data',)`` mesh for FL client-axis OR scenario-axis sharding.

    Two consumers share the axis name contract (the name, not the mesh
    shape, is the contract):

    * the FL round engine ``shard_map``s the K sampled clients (and the
      ClientBank's N axis) over ``data`` — intra-rollout scaling;
    * the ScenarioArena (``repro.sim.Arena(mesh=...)``) ``shard_map``s
      its *scenario* axis over ``data`` — whole rollouts per shard, no
      cross-shard collectives, the strong-scaling axis for Sec.-VII
      sweep grids.  The arena's engine must then be mesh-free (the two
      shardings compose by handing each consumer its own axis of a
      larger mesh, not by nesting shard_maps).

    On a pod, pass the ``data`` axis of :func:`make_production_mesh`
    instead.
    """
    n = len(jax.devices()) if num_shards is None else num_shards
    return _auto_mesh((n,), ("data",))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s
        "ops_int8": 393e12,       # OP/s
        "hbm_bytes": 16e9,        # bytes
        "hbm_bw": 819e9,          # bytes/s
        "ici_bw": 1600e9 / 8,     # bytes/s, all links of one chip
    },
}


def device_peaks(device_kind: str) -> dict:
    """Peak figures of one chip of ``device_kind``; a kind missing from
    :data:`DEVICE_PEAKS` is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)})") from None
