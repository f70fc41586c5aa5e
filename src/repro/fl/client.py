"""Client-side local training: E epochs of mini-batch SGD (Algorithm 1, l.9).

Model-agnostic: a ``Task`` supplies ``init``/``loss_fn``/``metrics`` over
pytree parameters; the client returns the *update* ``theta^{t,E} - theta^t``
(Algorithm 1, l.10) so the server can apply the unbiased aggregation (4).

Two execution modes share the same SGD body (``_local_sgd_body``):

* ``local_update``       — one client per call (the legacy / DivFL path);
* ``batched_local_sgd``  — the round engine's hot path: all K sampled
  clients train in ONE computation via ``jax.vmap`` over a stacked
  ``[K, B, ...]`` client batch, returning stacked deltas ``[K, ...]`` and
  per-client losses ``[K]``; a pure trace the engine fuses into its own
  jit alongside aggregation and the queue update.

Padding / bucketing contract (ClientBank / round engine)
--------------------------------------------------------
``vmap`` requires every client in the batch to share a static data shape, so
the bank pads every client dataset in a stack to one common bucket of ``B``
examples — the GLOBAL bucket for a ``ClientBank`` (one compiled data shape
per task), or that tier's bucket for each rung of a ``TieredClientBank``
(one compiled data shape per tier; the contract below applies per stack
verbatim):

* ``B = bucket_num_batches(max_i ceil(n_i / batch_size)) * batch_size`` —
  the bucket is sized from the *ceil* step count rounded up to the next
  power of two, so ``B >= n_i`` always holds (the tiled stream contains
  every example);
* each client's data is padded by **cyclic tiling** (example ``j`` of the
  padded stream is example ``j mod n_i``), so every padded batch contains
  only real examples and gradients are never polluted by zero rows;
* each local epoch samples **without replacement from the client's true
  ``n_i`` examples only**: when ``num_examples`` is given, padded rows
  (``j >= n_i``) receive a sentinel sort key and land at the end of the
  epoch's ordering, and for ``n_i >= bs`` the applied step count satisfies
  ``steps_i * bs <= n_i`` so they are never consumed — every example has
  equal inclusion probability, exactly the sequential path's statistics
  (cyclic duplicates are never over-weighted).  When ``n_i < bs`` the one
  applied batch must hold ``bs`` rows, so it consumes padded rows
  ``n_i .. bs-1`` (in index order — the tiled duplicates of examples
  ``0 .. bs-n_i-1``): the *same* deterministic duplicate multiset the
  sequential path produces when :func:`local_update` tiles ``n_i`` up to
  one full batch, so the two paths still agree;
* every client only *applies* its own true ``steps_i = max(n_i // bs, 1)``
  optimizer steps per epoch: the scan still runs ``B // batch_size``
  iterations (static shape), and steps beyond ``steps_i`` are masked out of
  the params/momentum/loss (``num_steps`` argument).  Padding therefore
  changes neither which examples a client trains on nor how many SGD steps
  it takes.  When ``n_i == B`` (no padding; ``num_steps``/``num_examples``
  None) this is *exactly* the sequential semantics of :func:`local_update`;
* the epoch ordering is the argsort of iid uniform keys drawn identically
  by the masked and unmasked traces, so a mask covering the full bucket
  (``num_examples == B``, ``num_steps == B // bs``) reproduces the
  unmasked trace bit-for-bit — the bank's always-masked gather path stays
  bit-identical to an unmasked host-stacked round.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Bucketing / cyclic tiling are host-side data-plane ops: they live in the
# numpy-only data layer (shared with ClientBank construction) and are
# re-exported here because they are part of the client-side contract.
from repro.data.pipeline import bucket_num_batches, pad_client_data
from repro.obs import scopes
from repro.optim import SGD, apply_updates

PyTree = Any


class Task(Protocol):
    """Minimal model interface the FL substrate trains against."""

    def init(self, rng: jax.Array) -> PyTree: ...

    def loss_fn(self, params: PyTree, batch: Dict[str, jax.Array]
                ) -> jax.Array: ...

    def metrics(self, params: PyTree, batch: Dict[str, jax.Array]
                ) -> Dict[str, jax.Array]: ...


@dataclasses.dataclass(frozen=True)
class ClientConfig:
    local_epochs: int = 2
    batch_size: int = 32
    momentum: float = 0.9
    max_grad_norm: float = 0.0     # 0 => no clipping


def _num_batches(num_examples: int, batch_size: int) -> int:
    return max(num_examples // batch_size, 1)


def _local_sgd_body(loss_fn, params: PyTree, x: jax.Array, y: jax.Array,
                    lr: jax.Array, rng: jax.Array, cfg: ClientConfig,
                    steps_per_epoch: int,
                    num_steps: Optional[jax.Array] = None,
                    num_examples: Optional[jax.Array] = None
                    ) -> Tuple[PyTree, jax.Array]:
    """E epochs of shuffled mini-batch SGD; pure trace (vmap/jit composable).

    ``num_steps`` (traced scalar, defaults to all ``steps_per_epoch`` steps)
    masks out optimizer steps beyond a client's true per-epoch step count;
    ``num_examples`` (traced scalar) restricts each epoch's sampling to the
    first ``num_examples`` rows — the client's true dataset inside a padded
    bucket — so cyclic-tiling duplicates never skew inclusion probability.
    Both are the bucketing contract for batched execution over padded data.
    """
    opt = SGD(momentum=cfg.momentum)
    opt_state = opt.init(params)
    bs = cfg.batch_size
    n = x.shape[0]

    def epoch(carry, erng):
        params, opt_state = carry
        # Epoch order = argsort of iid uniform keys (a uniform random
        # permutation).  Masked and unmasked traces share the SAME key
        # draw, so a mask covering the full bucket reproduces the
        # unmasked ordering bit-for-bit — the ClientBank path (always
        # masked) stays bit-identical to an unmasked host-stacked round.
        with jax.named_scope(scopes.SGD_SHUFFLE):
            scores = jax.random.uniform(erng, (n,))
            if num_examples is not None:
                # without-replacement sample of the true examples: padded
                # rows get a sentinel key and sort last (stable, so in
                # index order), out of reach of the num_steps applied
                # batches when num_examples >= bs; a tiny client
                # (num_examples < bs) fills its single batch with the
                # first padded rows — the same duplicate multiset the
                # sequential tile-to-one-batch path uses (see module
                # docstring)
                scores = jnp.where(jnp.arange(n) < num_examples, scores,
                                   2.0)
            perm = jnp.argsort(scores)[:steps_per_epoch * bs].reshape(
                steps_per_epoch, bs)

        def step(carry, batch):
            params, opt_state = carry
            si, idx = batch
            # gather each batch inside the step: a whole-epoch permuted
            # copy of the bucket (plus the transpose the scan needs under
            # vmap) would hold two more [lanes, K, B, ...] buffers on the
            # device at once
            with jax.named_scope(scopes.SGD_BATCH):
                bx = jnp.take(x, idx, axis=0)
                by = jnp.take(y, idx, axis=0)
            with jax.named_scope(scopes.SGD_GRAD):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, {"x": bx, "y": by})
                if cfg.max_grad_norm > 0:
                    from repro.optim import clip_by_global_norm
                    grads = clip_by_global_norm(grads, cfg.max_grad_norm)
            with jax.named_scope(scopes.SGD_UPDATE):
                updates, new_opt = opt.update(grads, opt_state, params, lr)
                new_params = apply_updates(params, updates)
                if num_steps is not None:
                    keep = si < num_steps
                    new_params = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(keep, a, b), new_params,
                        params)
                    new_opt = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(keep, a, b), new_opt,
                        opt_state)
                    loss = jnp.where(keep, loss, 0.0)
            return (new_params, new_opt), loss

        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), (jnp.arange(steps_per_epoch), perm))
        if num_steps is None:
            epoch_loss = jnp.mean(losses)
        else:
            epoch_loss = jnp.sum(losses) / num_steps.astype(jnp.float32)
        return (params, opt_state), epoch_loss

    rngs = jax.random.split(rng, cfg.local_epochs)
    (params, _), losses = jax.lax.scan(epoch, (params, opt_state), rngs)
    return params, jnp.mean(losses)


_local_sgd = partial(jax.jit, static_argnames=("loss_fn", "cfg",
                                               "steps_per_epoch"))(
    _local_sgd_body)


def local_update(task: Task, global_params: PyTree, data_x: np.ndarray,
                 data_y: np.ndarray, lr: float, rng: jax.Array,
                 cfg: ClientConfig) -> Tuple[PyTree, float]:
    """Run E local epochs; return (theta^{t,E} - theta^t, mean loss)."""
    steps = _num_batches(data_x.shape[0], cfg.batch_size)
    if data_x.shape[0] < steps * cfg.batch_size:
        # fewer examples than one batch: tile up to a single full batch
        data_x, data_y = pad_client_data(np.asarray(data_x),
                                         np.asarray(data_y),
                                         steps * cfg.batch_size)
    new_params, loss = _local_sgd(task.loss_fn, global_params,
                                  jnp.asarray(data_x), jnp.asarray(data_y),
                                  jnp.asarray(lr, jnp.float32), rng, cfg,
                                  steps)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, new_params,
                                   global_params)
    return delta, float(loss)


def batched_local_sgd(loss_fn, params: PyTree, xs: jax.Array, ys: jax.Array,
                      lr: jax.Array, rngs: jax.Array, cfg: ClientConfig,
                      steps_per_epoch: int,
                      num_steps: Optional[jax.Array] = None,
                      num_examples: Optional[jax.Array] = None
                      ) -> Tuple[PyTree, jax.Array]:
    """vmap of the SGD body over a stacked ``[K, B, ...]`` client batch.

    ``num_steps`` (``[K]`` int array or None) carries each client's true
    per-epoch step count so padded clients don't over-train;
    ``num_examples`` (``[K]`` int array or None) their true dataset sizes
    so epoch sampling never draws a padded duplicate row (see module
    docstring).  Returns stacked deltas (leaves ``[K, ...]``) and
    per-client losses ``[K]``.  Pure trace: callers embed it in their own
    jit (the round engine fuses it with aggregation + queue update).
    """
    if num_steps is None:
        def one(x, y, r):
            return _local_sgd_body(loss_fn, params, x, y, lr, r, cfg,
                                   steps_per_epoch)
        new_params, losses = jax.vmap(one)(xs, ys, rngs)
    elif num_examples is None:
        def one(x, y, r, s):
            return _local_sgd_body(loss_fn, params, x, y, lr, r, cfg,
                                   steps_per_epoch, num_steps=s)
        new_params, losses = jax.vmap(one)(xs, ys, rngs, num_steps)
    else:
        def one(x, y, r, s, m):
            return _local_sgd_body(loss_fn, params, x, y, lr, r, cfg,
                                   steps_per_epoch, num_steps=s,
                                   num_examples=m)
        new_params, losses = jax.vmap(one)(xs, ys, rngs, num_steps,
                                           num_examples)
    deltas = jax.tree_util.tree_map(lambda a, p: a - p, new_params, params)
    return deltas, losses


def flatten_update(delta: PyTree, proj_dim: int = 256,
                   seed: int = 0) -> np.ndarray:
    """Random-project an update pytree to a small vector (DivFL similarity).

    Uses a count-sketch style signed bucket projection — O(d) time,
    deterministic in ``seed`` — so similarity costs O(N^2 proj_dim)
    instead of O(N^2 d).
    """
    leaves = [np.asarray(x, np.float32).ravel()
              for x in jax.tree_util.tree_leaves(delta)]
    flat = np.concatenate(leaves) if leaves else np.zeros((1,), np.float32)
    rng = np.random.default_rng(seed)
    buckets = rng.integers(0, proj_dim, flat.shape[0])
    signs = rng.choice(np.asarray([-1.0, 1.0], np.float32), flat.shape[0])
    out = np.zeros((proj_dim,), np.float32)
    np.add.at(out, buckets, flat * signs)
    return out
