"""Pallas TPU kernel for the LROA unbiased aggregation (paper eq. (4)).

    theta^{t+1} = theta^t + sum_{k} coeff_k * delta_k,
    coeff_k = w_{n_k} / (K q_{n_k})

This is the FL server's hot path at datacenter scale: K client deltas of d
model parameters each (d up to billions) reduced into the global model. The
fused kernel streams [K, block] delta tiles through VMEM and performs the
weighted reduction in one pass — K+1 reads + 1 write per element instead of
the K round trips of a naive loop over clients.

grid = (num_blocks,); coefficients ride along in SMEM (scalar prefetch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _aggregate_kernel(coeff_ref, theta_ref, delta_ref, out_ref):
    # SMEM holds scalars only: read one coefficient at a time (K is static,
    # so the loop unrolls) instead of loading the [K] ref as a vector,
    # which the TPU compiler refuses.
    k = delta_ref.shape[0]
    upd = coeff_ref[0] * delta_ref[0, :].astype(jnp.float32)
    for i in range(1, k):
        upd = upd + coeff_ref[i] * delta_ref[i, :].astype(jnp.float32)
    out_ref[...] = (theta_ref[...].astype(jnp.float32) +
                    upd).astype(out_ref.dtype)


def fl_aggregate_tpu(theta: Array, deltas: Array, coeffs: Array, *,
                     block: int = 65_536, interpret: bool = False) -> Array:
    """theta: [N]; deltas: [K, N]; coeffs: [K] -> updated theta [N]."""
    (n,) = theta.shape
    k = deltas.shape[0]
    pad = (-n) % block
    if pad:
        theta = jnp.pad(theta, (0, pad))
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
    nb = theta.shape[0] // block

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i, coeff: (i,)),
            pl.BlockSpec((k, block), lambda i, coeff: (0, i)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i, coeff: (i,)),
    )
    out = pl.pallas_call(
        _aggregate_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(theta.shape, theta.dtype),
        interpret=interpret,
    )(coeffs, theta, deltas)
    return out[:n]
