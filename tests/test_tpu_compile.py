"""Compile-only checks of the main path's Pallas kernel for a TPU v5e chip
that is described, not attached: the eq.-(4) aggregation
(``fl_aggregate_tpu``) and the sharded delta-reduce path
(``kernels.fl_delta_reduce``), at the ravelled length of the paper's CNN
client model, for the unsharded K = 8 and the per-chip K = 2 of a 4-chip
mesh, plain and batched under ``jax.vmap`` (how the scenario arena calls
them).  Interpret-mode tests cannot see what the TPU compiler refuses —
this file can, without a chip."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.fl_aggregate import fl_aggregate_tpu

N_CNN = 545_002       # CNNTask(image_shape=(32, 32, 3), num_classes=10)
LANES = 14            # 7 controllers x {iid, markov}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: entries compiled for a described chip cannot be read back
    without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _struct(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _lead(batched: bool) -> tuple:
    return (LANES,) if batched else ()


@pytest.mark.parametrize("batched", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("k", [2, 8])
def test_fl_aggregate_compiles_for_v5e(one_chip, k, batched):
    fn = jax.vmap(fl_aggregate_tpu) if batched else fl_aggregate_tpu
    lead = _lead(batched)
    compiled = jax.jit(fn).lower(
        _struct(lead + (N_CNN,), one_chip),
        _struct(lead + (k, N_CNN), one_chip),
        _struct(lead + (k,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batched", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("k", [2, 8])
def test_fl_delta_reduce_kernel_compiles_for_v5e(one_chip, monkeypatch, k,
                                                 batched):
    # the dispatch asks the default backend (the CPU here); point it at
    # the TPU so the kernel, not its interpret mode, is lowered
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def reduce(deltas, coeffs):
        return ops.fl_delta_reduce(deltas, coeffs)

    fn = jax.vmap(reduce) if batched else reduce
    lead = _lead(batched)
    compiled = jax.jit(fn).lower(
        _struct(lead + (k, N_CNN), one_chip),
        _struct(lead + (k,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
