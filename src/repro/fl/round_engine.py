"""Fused device-resident FL round engine over a ClientBank.

PR 1 collapsed a round to one jitted computation but still stacked the K
sampled clients' data on the host every round and re-uploaded it — the
dominant non-compute cost on GPU/TPU once the round itself is fused.  The
engine now consumes a :class:`repro.fl.client_bank.ClientBank`: all N
clients' bucketed data lives on device as ``[N, B, ...]`` stacks, and a
round is

    gather K selected rows inside the jit  (jnp.take over the bank)
      -> vmapped E-epoch local SGD           (client.batched_local_sgd)
      -> fused eq.-(4) aggregation           (server.aggregate_fused;
                                              Pallas fl_aggregate on TPU)
    with the params buffer donated off-CPU — ZERO per-round host->device
    transfers of client data.

One gather core (:meth:`_gathered_round`) serves every path, so the
single-round and multi-round data planes can no longer diverge:

* :meth:`round_step` — one fused round from the bank + a ``selected``
  index vector; the trainer's hot path (controller decisions + sampling
  stay on the host so stateful controllers and callbacks keep working).
* :meth:`run_scan` — benchmark/sweep fast path: an entire multi-round
  Algorithm-1 rollout (decide -> sample -> train -> aggregate -> queue
  update) inside a single ``lax.scan`` over the same bank.  The scan
  body (:meth:`_build_scan`, shared with the ScenarioArena) treats the
  sampling count K as TRACED data over a static slot count ``K_max``:
  per-slot draws are prefix-stable (``fold_in(round_key, slot)``) and
  slots beyond the traced ``k_act`` are inert (row-0 gather, zeroed
  eq.-(4) coefficients and metric contributions), which is what lets a
  mixed-K arena grid fuse into one padded-K executable whose lanes stay
  bitwise-equal to the per-K programs.  It can also evaluate a test set
  on device every ``eval_every`` rounds (``eval_fn`` — see
  ``repro.sim.eval.EvalBank``).
* :meth:`round_step_stacked` — the PR-1 host-stacked round, retained for
  bank-vs-host equivalence tests and transfer-cost benchmarking.

Tier ladder (:class:`repro.fl.client_bank.TieredClientBank`): a skewed
bank holds one ``[N_t, B_t, ...]`` stack per power-of-two size tier, and
a round runs ONE fused gathered round per non-empty tier of the selected
set — each tier ``jnp.take``s its slots (non-members clamped to row 0 and
masked out by zeroed coefficients) through the same :meth:`_gathered_round`
core, and the per-tier eq.-(4) contributions are summed into the params
(:meth:`_tier_loop_round`).  A selection that lands entirely in one tier
(including every round of a one-tier ladder) short-circuits to the
single-bucket executable, bit-identical to :class:`ClientBank` rounds.
``run_scan`` rides the same tier loop with each tier's training behind a
selection-conditioned ``lax.cond`` (the sampled selection is traced, so
the skip is a runtime branch — a round that hits one tier pays one tier),
and the mesh-sharded path rides it too — each tier's round shard_maps its
K-client axis exactly like the single-bucket path.  Executable count
stays one compiled data shape per tier: per-tier single-bucket steps,
plus one tiered executable per distinct hit-tier subset (bounded by the
ladder's ``max_tiers``).

Mesh sharding: pass ``mesh`` (e.g. ``launch.mesh.make_fl_mesh()`` or the
``data`` axis of ``launch.mesh.make_production_mesh()``) and the K-client
axis of every round is ``shard_map``ped over ``mesh_axis``: each shard
trains K/shards clients and reduces its partial eq.-(4) term, the partials
``psum`` across shards (``server.aggregate_fused_psum``), and params stay
replicated.  The bank itself shards its N axis over the same mesh when
divisible.

Bucketing contract: see ``repro.fl.client`` / ``repro.data.pipeline`` —
each bank stack tiles its clients to one power-of-two bucket (the global
bucket for :class:`ClientBank`, one per rung for the tier ladder), so
each task compiles exactly one data shape per tier, and ``num_steps``/
``num_examples`` masks preserve true per-client step counts and sampling
statistics.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import policy as pol
from repro.core import queues as vq
from repro.obs import scopes
from repro.obs import trace as obs_trace
from repro.core import system_model as sm
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.fl.client_bank import ClientBank, TieredClientBank

PyTree = Any
AnyBank = Any   # ClientBank | TieredClientBank

#: the scan's per-round ``[T]`` bool column of the tiers that trained
#: (tier ladders only); the arena counts it and neither it nor
#: :meth:`RoundEngine.run_scan` reports it as a metric
TIERS_RUN = "tiers_run"


def _tier_parts(parts_key: tuple, buffers: tuple) -> list:
    """Zip the static per-tier key ``(tid, steps, masked[, quant])`` with
    the matching device buffers ``(xs, ys, ns, ne[, sc, zp])`` into the
    ``(tid, xs, ys, ns, ne, sc, zp, steps)`` entries ``_tier_loop_round``
    consumes — the ONE place the parts layout is defined, shared by the
    tiered step and the tiered scan.  Pre-quantization callers (3-tuple
    keys, 4-tuple buffers) get ``sc = zp = None`` — the fp32 trace."""
    out = []
    for key, buf in zip(parts_key, buffers):
        tid, steps = key[0], key[1]
        xs, ys, ns, ne = buf[:4]
        sc, zp = (buf[4], buf[5]) if len(buf) >= 6 else (None, None)
        out.append((tid, xs, ys, ns, ne, sc, zp, steps))
    return out


def _bank_quant_args(bank) -> tuple:
    """``bank.quant_args()`` with a fp32 default for banks predating the
    quantized storage mode (duck-typed callers, test doubles)."""
    fn = getattr(bank, "quant_args", None)
    return fn() if fn is not None else (None, None)


def bank_layout_key(bank: AnyBank, tier_subset=None) -> tuple:
    """The ``bank_key`` that :meth:`RoundEngine._scan_plan` would return
    for ``bank`` (optionally restricted to a static ``tier_subset``),
    computed WITHOUT touching device buffers.  The arena's dispatch
    planner uses this to ask "is this bucket's executable already
    compiled?" against the arena cache before paying for a plan — so the
    two layouts must stay in lockstep: ``masked`` here is
    ``not tier.uniform``, exactly when ``device_args`` returns non-None
    step masks, and ``quant`` is the int8-storage flag, exactly when
    ``quant_args`` returns non-None codes (the dequantizing gather is a
    different trace)."""
    if isinstance(bank, TieredClientBank) and bank.num_tiers == 1:
        bank = bank.tiers[0]
    if isinstance(bank, TieredClientBank):
        tiers = (tuple(range(bank.num_tiers)) if tier_subset is None
                 else tuple(tier_subset))
        return tuple((t, bank.tiers[t].steps_per_epoch,
                      not bank.tiers[t].uniform,
                      bank.tiers[t].storage == "int8") for t in tiers)
    return (bank.steps_per_epoch, not bank.uniform,
            getattr(bank, "storage", "fp32") == "int8")


def _default_donate() -> bool:
    # Buffer donation is a no-op (warning) on CPU; enable it only where the
    # runtime honours it.
    return jax.default_backend() != "cpu"


def _default_select(sp, t, h, queues, q, key, slots, kvec, cid):
    """The historical slot fill — the paper's i.i.d. draw; ``cid`` is
    ignored (selection mode baked into the executable)."""
    return pol.sampled_selection(sp, t, h, queues, q, key, slots, kvec)


class RoundEngine:
    """Executes FL rounds as fused, device-resident computations.

    Jitted executables are cached per (steps_per_epoch, masked, quant,
    clusters) for single rounds and (bank layout, K, policy, dropout) for
    scans — with a single-bucket bank that is one step executable per
    trainer; a tier ladder adds one step executable per tier plus one
    tier-loop executable per distinct hit-tier subset (keyed by the
    static (tier, steps, masked, quant) tuple).  Bank buffers are never
    donated; only params (and the scan's queues) are.
    """

    def __init__(self, task: fl_client.Task, client_cfg: fl_client.ClientConfig,
                 impl: str = "auto", donate: Optional[bool] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 mesh_axis: str = "data"):
        self.task = task
        self.cfg = client_cfg
        self.impl = impl
        self.donate = _default_donate() if donate is None else donate
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self._step_fns: Dict[tuple, Any] = {}
        self._stacked_fns: Dict[tuple, Any] = {}
        self._scan_fns: Dict[tuple, Any] = {}
        self._tiered_fns: Dict[tuple, Any] = {}

    def make_bank(self, client_data, tiered: str = "auto",
                  max_tiers: int = 4, storage: str = "fp32",
                  clusters: Optional[int] = None) -> AnyBank:
        """Build the device-resident bank this engine's rounds gather from
        (client axis co-sharded with the engine's mesh).

        ``tiered``: 'auto' builds the bucket-ladder
        :class:`TieredClientBank` only when the partition actually spans
        more than one size tier (a uniform ladder IS the single-bucket
        bank); 'single' forces the one-global-bucket :class:`ClientBank`;
        'tiered' forces the ladder even when it has one rung.

        ``storage``: 'fp32' (default, the historical bitwise path) or
        'int8' per-client-quantized rows dequantized inside the fused
        gather.  ``clusters``: fit k-means cluster routing for
        ``round_step(..., hierarchical=True)`` — single-bucket banks
        only (the tier loop already reduces per tier).
        """
        if tiered not in ("auto", "single", "tiered"):
            raise ValueError(f"unknown bank mode {tiered!r}")
        from repro.data.pipeline import validate_client_data
        validate_client_data(client_data)
        assignment = None
        if tiered == "auto":
            from repro.data.pipeline import assign_tiers
            sizes = [int(np.asarray(x).shape[0]) for x, _ in client_data]
            assignment = assign_tiers(sizes, self.cfg.batch_size, max_tiers)
            # the bank reuses this exact assignment, so the auto decision
            # and the constructed ladder cannot diverge
            tiered = "single" if len(assignment[1]) == 1 else "tiered"
        if tiered == "single":
            return ClientBank(client_data, self.cfg, mesh=self.mesh,
                              mesh_axis=self.mesh_axis, storage=storage,
                              clusters=clusters)
        if clusters is not None:
            raise ValueError("clusters= needs a single-bucket bank "
                             "(tiered='single'), got a tier ladder")
        return TieredClientBank(client_data, self.cfg, mesh=self.mesh,
                                mesh_axis=self.mesh_axis,
                                max_tiers=max_tiers, assignment=assignment,
                                storage=storage)

    # -- shared round core -------------------------------------------------

    def _shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.mesh_axis])

    def _round_core(self, params, xs, ys, coeffs, lr, rngs, num_steps,
                    num_examples, steps: int, cluster_sel=None,
                    num_clusters: int = 0):
        """Train the stacked clients + aggregate — optionally shard_mapped
        over the client axis.  Pure trace shared by every entry point.

        ``cluster_sel`` (``[K]`` traced cluster ids, optional) switches
        the eq.-(4) reduce to the hierarchical cluster-then-global form
        (``server.aggregate_hierarchical``; its psum twin under a mesh).
        ``None`` keeps the flat reduce — the historical trace, untouched.
        """
        loss_fn, cfg, impl = self.task.loss_fn, self.cfg, self.impl
        shards = self._shards()
        if shards <= 1:
            with jax.named_scope(scopes.LOCAL_SGD):
                deltas, losses = fl_client.batched_local_sgd(
                    loss_fn, params, xs, ys, lr, rngs, cfg, steps,
                    num_steps=num_steps, num_examples=num_examples)
            with jax.named_scope(scopes.AGGREGATE):
                if cluster_sel is not None:
                    return fl_server.aggregate_hierarchical(
                        params, deltas, coeffs, cluster_sel,
                        num_clusters), losses
                return fl_server.aggregate_fused(params, deltas, coeffs,
                                                 impl=impl), losses
        k = xs.shape[0]
        if k % shards:
            raise ValueError(
                f"sample_count {k} not divisible by mesh axis "
                f"{self.mesh_axis!r} size {shards}")
        axis = self.mesh_axis

        if cluster_sel is not None:
            def body_h(params, lr, xs, ys, coeffs, rngs, ns, ne, csel):
                with jax.named_scope(scopes.LOCAL_SGD):
                    deltas, losses = fl_client.batched_local_sgd(
                        loss_fn, params, xs, ys, lr, rngs, cfg, steps,
                        num_steps=ns, num_examples=ne)
                with jax.named_scope(scopes.AGGREGATE):
                    new_params = fl_server.aggregate_hierarchical_psum(
                        params, deltas, coeffs, csel, num_clusters, axis)
                return new_params, losses

            sharded = jax.shard_map(
                body_h, mesh=self.mesh,
                in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis),
                          P(axis), P(axis), P(axis)),
                out_specs=(P(), P(axis)), check_vma=False)
            return sharded(params, lr, xs, ys, coeffs, rngs, num_steps,
                           num_examples, cluster_sel)

        def body(params, lr, xs, ys, coeffs, rngs, ns, ne):
            with jax.named_scope(scopes.LOCAL_SGD):
                deltas, losses = fl_client.batched_local_sgd(
                    loss_fn, params, xs, ys, lr, rngs, cfg, steps,
                    num_steps=ns, num_examples=ne)
            with jax.named_scope(scopes.AGGREGATE):
                new_params = fl_server.aggregate_fused_psum(
                    params, deltas, coeffs, axis, impl=impl)
            return new_params, losses

        # P(axis) specs on the None masks apply to zero leaves — one spec
        # tuple covers both the masked and unmasked traces.
        sharded = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis),
                      P(axis), P(axis)),
            out_specs=(P(), P(axis)), check_vma=False)
        return sharded(params, lr, xs, ys, coeffs, rngs, num_steps,
                       num_examples)

    def _gathered_round(self, params, all_x, all_y, all_steps, all_sizes,
                        all_scale, all_zero, selected, coeffs, lr, rngs,
                        steps: int, cluster_of=None, num_clusters: int = 0):
        """THE gather core: select K clients from ``[N, ...]`` bank stacks
        inside the trace (``jnp.take``) and run the round on them.  Both
        ``round_step`` and the scan body go through here, so the two data
        planes share one implementation.

        ``all_scale`` / ``all_zero`` (``[N]`` f32, or None) are an int8
        bank's per-client affine codes: the K selected rows are
        dequantized RIGHT HERE, after the take — fp32 rows only ever
        materialize at ``[K, B, ...]``, never at bank scale.  With None
        codes the trace below is character-for-character the historical
        fp32 gather (the bitwise non-regression contract).  ``cluster_of``
        (``[N]`` int32, optional) routes the round's aggregation through
        the hierarchical reduce (see :meth:`_round_core`).
        """
        with jax.named_scope(scopes.GATHER):
            xs = jnp.take(all_x, selected, axis=0)
            if all_scale is not None:
                shape = selected.shape + (1,) * (xs.ndim - 1)
                xs = (xs.astype(jnp.float32)
                      * jnp.take(all_scale, selected).reshape(shape)
                      + jnp.take(all_zero, selected).reshape(shape))
            ys = jnp.take(all_y, selected, axis=0)
            ns = None if all_steps is None else jnp.take(all_steps,
                                                         selected)
            ne = None if all_sizes is None else jnp.take(all_sizes,
                                                         selected)
            csel = (None if cluster_of is None
                    else jnp.take(cluster_of, selected))
        return self._round_core(params, xs, ys, coeffs, lr, rngs, ns, ne,
                                steps, cluster_sel=csel,
                                num_clusters=num_clusters)

    def _tier_loop_round(self, params, parts, tier_sel, pos_sel, coeffs,
                         lr, rngs, cond_skip: bool = False,
                         lane_axis: Optional[str] = None):
        """THE tier loop: one fused gathered round per tier, contributions
        summed across tiers.

        ``parts``: static sequence of ``(tid, xs, ys, ns, ne, steps)`` —
        one entry per tier to run; ``tier_sel`` / ``pos_sel``: ``[K]``
        per-slot tier id and tier-local row.  Each tier runs ALL K slots
        through :meth:`_gathered_round` on its own stack (one compiled
        data shape per tier): non-member slots gather row 0 and carry a
        zeroed coefficient, so they contribute exactly nothing to that
        tier's eq.-(4) term and their loss is masked out.  The per-tier
        aggregated params are turned back into update terms and summed —
        mathematically eq. (4) over the full selection; the f32 summation
        order differs from a flat single-bucket aggregation (tiers are
        reduced innermost-first), which only matters at the ulp level.
        Shared by the tiered ``round_step`` and the tiered scan body, so
        the two tiered data planes cannot diverge; with a mesh each
        tier's round shard_maps its K axis via :meth:`_round_core`
        exactly like the single-bucket path.

        ``cond_skip``: wrap each tier's training in a selection-
        conditioned ``lax.cond`` so a tier the (traced) selection misses
        costs a predicate instead of a full ``K * B_t`` vmapped SGD — the
        scan body's path, where tier emptiness cannot be routed on the
        host.  A hit tier runs the identical trace as the unconditional
        loop, and a missed tier's contribution was exactly zero anyway
        (zeroed coefficients), so the two modes agree.  Off by default:
        ``round_step`` routes hit tiers on the host, every part it
        passes is non-empty, and a cond would only add overhead.

        ``lane_axis``: the name of an enclosing lane ``vmap`` (the
        ScenarioArena's).  A per-lane predicate is batched there, and a
        cond on a batched predicate lowers to running both branches and
        selecting, so every lane would train every tier.  With the axis
        named, the predicate is decided across the lanes (``psum`` of the
        per-lane hits, which comes back unbatched): the cond stays a real
        branch, a tier that no lane drew is skipped for all of them, and
        a tier that any lane drew runs for all of them, exactly as
        without the skip.

        Returns ``(new_params, losses, ran)``; with ``cond_skip``,
        ``ran`` (``[len(parts)]`` bool) says which tiers trained this
        round, and without it ``ran`` is None.
        """
        upd, losses = None, jnp.zeros(pos_sel.shape, jnp.float32)
        ran = []
        for tid, xs, ys, ns, ne, sc, zp, steps in parts:
            with jax.named_scope(scopes.tier(xs.shape[1])):
                mask = tier_sel == tid
                pos = jnp.where(mask, pos_sel, 0)
                cf = coeffs * mask.astype(coeffs.dtype)

                def run_tier(pos, cf, xs=xs, ys=ys, ns=ns, ne=ne, sc=sc,
                             zp=zp, steps=steps, mask=mask):
                    p_t, l_t = self._gathered_round(params, xs, ys, ns, ne,
                                                    sc, zp, pos, cf, lr,
                                                    rngs, steps)
                    with jax.named_scope(scopes.AGGREGATE):
                        u_t = jax.tree_util.tree_map(lambda a, b: a - b,
                                                     p_t, params)
                    return u_t, l_t.astype(jnp.float32) * mask

                if cond_skip:
                    def skip_tier(pos, cf):
                        return (jax.tree_util.tree_map(jnp.zeros_like,
                                                       params),
                                jnp.zeros(pos_sel.shape, jnp.float32))

                    hit = jnp.any(mask)
                    if lane_axis is not None:
                        hit = jax.lax.psum(hit.astype(jnp.int32),
                                           lane_axis) > 0
                    u_t, l_t = jax.lax.cond(hit, run_tier, skip_tier, pos,
                                            cf)
                    ran.append(hit)
                else:
                    u_t, l_t = run_tier(pos, cf)
            with jax.named_scope(scopes.AGGREGATE):
                upd = (u_t if upd is None else
                       jax.tree_util.tree_map(jnp.add, upd, u_t))
            losses = losses + l_t
        with jax.named_scope(scopes.AGGREGATE):
            new_params = jax.tree_util.tree_map(jnp.add, params, upd)
        return new_params, losses, jnp.stack(ran) if ran else None

    # -- single fused round ------------------------------------------------

    def _build_step(self, steps: int, num_clusters: int = 0):
        def step(params, all_x, all_y, all_steps, all_sizes, all_scale,
                 all_zero, all_clusters, selected, coeffs, lr, rngs):
            return self._gathered_round(params, all_x, all_y, all_steps,
                                        all_sizes, all_scale, all_zero,
                                        selected, coeffs, lr, rngs,
                                        steps, cluster_of=all_clusters,
                                        num_clusters=num_clusters)

        donate = (0,) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def round_step(self, global_params: PyTree, bank: AnyBank,
                   selected: np.ndarray, coeffs: np.ndarray, lr: float,
                   rngs: jax.Array, hierarchical: bool = False
                   ) -> Tuple[PyTree, jax.Array]:
        """One fused round gathered from the device-resident bank.

        ``selected``: [K] client indices (any integer array — the gather
        runs inside the jit, so no client data crosses the host boundary);
        ``coeffs``: [K] per-draw aggregation weights; ``rngs``: [K, 2]
        per-client PRNG keys.  Returns (new global params, per-client
        losses [K]).  The params argument is donated off-CPU — callers
        must use the returned pytree.  Bank buffers are never donated.

        An int8-storage bank rides the same call: its per-client affine
        codes flow through ``quant_args()`` and the gather dequantizes
        the K selected rows in-trace (a distinct cached executable — the
        fp32 trace is untouched).  ``hierarchical=True`` runs eq. (4) as
        the cluster-then-global reduce over the bank's k-means routing
        (requires a bank built with ``clusters=``; single-bucket banks
        and pools only).

        A :class:`TieredClientBank` routes through the tier loop: one
        fused gathered round per tier the selection actually hits, with a
        single-tier selection short-circuiting to the single-bucket
        executable (bit-identical to a :class:`ClientBank` round).
        """
        selected = np.asarray(selected)
        if selected.size and not (0 <= int(selected.min()) and
                                  int(selected.max()) < bank.num_clients):
            # jnp.take clips out-of-range indices inside the jit, which
            # would silently train the wrong client — keep the host
            # path's IndexError semantics.
            raise IndexError(
                f"selected indices {selected} out of range for bank of "
                f"{bank.num_clients} clients")
        if isinstance(bank, TieredClientBank):
            if hierarchical:
                raise ValueError(
                    "hierarchical aggregation is single-bucket only — "
                    "the tier loop already reduces per tier")
            return self._round_step_tiered(global_params, bank, selected,
                                           coeffs, lr, rngs)
        steps = bank.steps_per_epoch
        all_x, all_y, all_steps, all_sizes = bank.device_args()
        all_scale, all_zero = _bank_quant_args(bank)
        if hierarchical:
            all_clusters = getattr(bank, "cluster_of_device", None)
            if all_clusters is None:
                raise ValueError(
                    "hierarchical=True needs a bank built with "
                    "clusters=... (no cluster routing on this bank)")
            num_clusters = int(bank.num_clusters)
        else:
            all_clusters, num_clusters = None, 0
        key = (steps, all_steps is not None, all_scale is not None,
               num_clusters)
        fn = self._step_fns.get(key)
        cold = fn is None
        if cold:
            fn = self._step_fns[key] = self._build_step(steps,
                                                        num_clusters)
        with obs_trace.span("engine.round", k=int(selected.size),
                            cold=cold):
            return fn(global_params, all_x, all_y, all_steps, all_sizes,
                      all_scale, all_zero, all_clusters,
                      jnp.asarray(selected, jnp.int32),
                      jnp.asarray(coeffs, jnp.float32),
                      jnp.asarray(lr, jnp.float32), rngs)

    # -- tiered rounds -----------------------------------------------------

    def _build_tiered_step(self, parts_key: tuple):
        """One jit per distinct hit-tier subset: the whole tier loop
        (every hit tier's gathered round + the cross-tier sum) fuses into
        a single dispatch.  ``parts_key``: static ``(tid, steps, masked,
        quant)`` per hit tier — buffer pytrees arrive as a matching
        tuple."""
        def step(params, buffers, tier_sel, pos_sel, coeffs, rngs, lr):
            new_params, losses, _ = self._tier_loop_round(
                params, _tier_parts(parts_key, buffers), tier_sel, pos_sel,
                coeffs, lr, rngs)
            return new_params, losses

        donate = (0,) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _round_step_tiered(self, global_params: PyTree,
                           bank: TieredClientBank, selected: np.ndarray,
                           coeffs: np.ndarray, lr: float, rngs: jax.Array
                           ) -> Tuple[PyTree, jax.Array]:
        """Tier-aware round: host-side routing (selection indices are host
        data anyway), device-side training.  Only the tiers the selection
        hits run — an empty tier costs nothing."""
        tier_sel = bank.tier_of[selected]
        pos_sel = bank.pos_in_tier[selected]
        hit = np.unique(tier_sel)
        if hit.size <= 1:
            # whole selection in one tier (or empty, matching the
            # ClientBank no-op semantics): the tier IS a single-bucket
            # bank — reuse the classic executable, bit-identical to a
            # ClientBank round (and to the pre-ladder engine).
            tier = bank.tiers[int(hit[0]) if hit.size else 0]
            return self.round_step(global_params, tier, pos_sel, coeffs,
                                   lr, rngs)
        parts_key, buffers = [], []
        for t in hit:
            tier = bank.tiers[int(t)]
            xs, ys, ns, ne = tier.device_args()
            sc, zp = tier.quant_args()
            parts_key.append((int(t), tier.steps_per_epoch,
                              ns is not None, sc is not None))
            buffers.append((xs, ys, ns, ne, sc, zp))
        parts_key = tuple(parts_key)
        fn = self._tiered_fns.get(parts_key)
        if fn is None:
            fn = self._tiered_fns[parts_key] = \
                self._build_tiered_step(parts_key)
        return fn(global_params, tuple(buffers),
                  jnp.asarray(tier_sel, jnp.int32),
                  jnp.asarray(pos_sel, jnp.int32),
                  jnp.asarray(coeffs, jnp.float32), rngs,
                  jnp.asarray(lr, jnp.float32))

    # -- PR-1 host-stacked round (equivalence / transfer benchmarking) -----

    def _build_stacked(self, steps: int):
        def step(params, xs, ys, coeffs, lr, rngs, num_steps, num_examples):
            return self._round_core(params, xs, ys, coeffs, lr, rngs,
                                    num_steps, num_examples, steps)

        donate = (0,) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def round_step_stacked(self, global_params: PyTree, xs: np.ndarray,
                           ys: np.ndarray, coeffs: np.ndarray, lr: float,
                           rngs: jax.Array,
                           num_steps: Optional[np.ndarray] = None,
                           num_examples: Optional[np.ndarray] = None
                           ) -> Tuple[PyTree, jax.Array]:
        """The PR-1 data plane: host-stacked ``[K, B, ...]`` batches
        uploaded per round (``bank.gather_host`` produces them).  Same
        round core as :meth:`round_step` — kept so equivalence tests and
        benchmarks can pin the bank path against it, byte for byte.
        """
        steps = xs.shape[1] // self.cfg.batch_size
        key = (steps, num_steps is not None)
        fn = self._stacked_fns.get(key)
        if fn is None:
            fn = self._stacked_fns[key] = self._build_stacked(steps)
        if num_steps is not None:
            num_steps = jnp.asarray(num_steps, jnp.int32)
        if num_examples is not None:
            num_examples = jnp.asarray(num_examples, jnp.int32)
        return fn(global_params, jnp.asarray(xs), jnp.asarray(ys),
                  jnp.asarray(coeffs, jnp.float32),
                  jnp.asarray(lr, jnp.float32), rngs, num_steps,
                  num_examples)

    # -- multi-round scan fast path ----------------------------------------

    def _scan_plan(self, bank: AnyBank, tier_subset=None,
                   lane_axis: Optional[str] = None):
        """(round_fn, data, bank_key) — the data-plane half of a rollout
        over ``bank``: ``round_fn(params, data, selected, coeffs, lr,
        rngs) -> (params, losses, tiers_run)`` is the single-bucket
        gathered round (``tiers_run`` None) or the tier loop (``[T]``
        bool, the tiers that trained), and ``data`` the opaque
        device-buffer pytree it consumes.  Shared by :meth:`run_scan` and
        the ScenarioArena (``repro.sim``), so the host-looped and
        scenario-batched rollouts ride ONE data plane.  A one-tier ladder
        collapses to its single bucket here (bitwise the
        :class:`ClientBank` plan); a multi-tier ladder's round runs each
        tier under a selection-conditioned ``lax.cond`` (``cond_skip`` —
        rounds whose draw lands in few tiers stop paying
        ``K * sum_t B_t`` work).  ``lane_axis`` names the caller's lane
        ``vmap`` (the arena's; None elsewhere): the skip is then decided
        across the lanes, so the cond stays a real branch and a tier is
        skipped in a round where no lane drew it (see
        :meth:`_tier_loop_round`).

        ``tier_subset`` (sorted tier-id tuple, tiered banks only) builds
        the round against a STATIC subset of the ladder: tiers outside
        the subset simply do not exist in the trace.  This is the arena
        dispatch planner's scan-skip lever — a bucket of lanes that can
        never draw tier ``t`` over the whole rollout compiles a body
        without it, where the runtime skip would still pay each round's
        predicate and skips only rounds in which no lane of the bucket
        drew the tier.  Callers OWN the safety argument: selections that
        land outside the subset would gather garbage positions; the
        planner only emits subsets covering each lane's replayed
        footprint.  The returned ``bank_key`` keeps the per-tier layout
        triples, so distinct subsets cache distinct executables."""
        if isinstance(bank, TieredClientBank) and bank.num_tiers == 1:
            bank = bank.tiers[0]            # the ladder IS one bucket
        if not isinstance(bank, TieredClientBank):
            if tier_subset is not None and tuple(tier_subset) != (0,):
                raise ValueError(
                    f"tier_subset={tier_subset!r} on a single-bucket "
                    f"bank — only None or (0,) make sense there")
            tier_subset = None
        if isinstance(bank, TieredClientBank):
            if tier_subset is None:
                tier_subset = tuple(range(bank.num_tiers))
            else:
                tier_subset = tuple(tier_subset)
                if tier_subset != tuple(sorted(set(tier_subset))):
                    raise ValueError(f"tier_subset must be sorted and "
                                     f"unique, got {tier_subset!r}")
                if not tier_subset or not set(tier_subset) <= set(
                        range(bank.num_tiers)):
                    raise ValueError(
                        f"tier_subset {tier_subset!r} outside the "
                        f"ladder's {bank.num_tiers} tiers")
            parts_key, buffers = [], []
            for t in tier_subset:
                tier = bank.tiers[t]
                xs, ys, ns, ne = tier.device_args()
                sc, zp = tier.quant_args()
                parts_key.append((t, tier.steps_per_epoch, ns is not None,
                                  sc is not None))
                buffers.append((xs, ys, ns, ne, sc, zp))
            parts_key = tuple(parts_key)

            def round_fn(params, data, selected, coeffs, lr, rngs):
                bufs, tier_of, pos = data
                with jax.named_scope(scopes.GATHER):
                    tier_sel = jnp.take(tier_of, selected)
                    pos_sel = jnp.take(pos, selected)
                return self._tier_loop_round(
                    params, _tier_parts(parts_key, bufs), tier_sel,
                    pos_sel, coeffs, lr, rngs, cond_skip=True,
                    lane_axis=lane_axis)

            data = (tuple(buffers), bank.tier_of_device, bank.pos_device)
            return round_fn, data, parts_key
        all_x, all_y, all_steps, all_sizes = bank.device_args()
        all_scale, all_zero = _bank_quant_args(bank)
        steps, masked = bank.steps_per_epoch, all_steps is not None

        def round_fn(params, data, selected, coeffs, lr, rngs):
            return (*self._gathered_round(params, *data, selected, coeffs,
                                          lr, rngs, steps), None)

        return (round_fn,
                (all_x, all_y, all_steps, all_sizes, all_scale, all_zero),
                (steps, masked, all_scale is not None))

    def round_sgd_steps(self, bank_key: tuple) -> int:
        """Sequential local-SGD steps of one scan round over the data
        plane ``bank_key`` (from :meth:`_scan_plan`): local epochs x the
        ``steps`` each tier passes to ``batched_local_sgd``, summed over
        the key's tiers.  That is the work dispatched, an upper bound on
        the work done: a tier that no lane drew in a round is skipped
        (the ``tiers_run`` scan column says which ran)."""
        if isinstance(bank_key[0], tuple):      # a tier ladder
            steps = sum(part[1] for part in bank_key)
        else:
            steps = bank_key[0]
        return self.cfg.local_epochs * steps

    def _build_scan(self, k: int, decide_fn, round_fn, select_fn=None,
                    eval_fn=None, eval_every: int = 0,
                    use_dropout: bool = False):
        """Full-rollout scan body; UN-jitted (``run_scan`` jits it, the
        ScenarioArena vmaps it over a scenario axis first).

        ``decide_fn(sp, h, queues, V, lam, cid, kvec) -> ControlDecision``
        supplies the control plane — a fixed ``repro.core.policy`` rule
        (``cid`` ignored) or the traced ``lax.switch`` dispatch
        (controller-as-data); ``round_fn`` the data plane from
        :meth:`_scan_plan`.  ``eb`` is the rollout's energy budget
        ``[N]`` as a traced input (the scenario axis sweeps it), applied
        over ``sp`` before anything reads it.

        ``select_fn(sp, t, h, queues, q, key, slots, kvec, cid) ->
        drawn [K_max] int32`` fills the client slots from the decision —
        ``None`` uses the paper's i.i.d. draw
        (``policy.sampled_selection``, byte-identical to the historical
        inline code), a fixed rule comes from
        :meth:`_fixed_policy_select`, and the arena passes the traced
        ``policy.select_by_id`` dispatch so deterministic controllers
        (round-robin's cyclic schedule, DivFL's facility-location greedy)
        ride the same scan.  Every mode must be prefix-stable in the slot
        index (the padded-K invariant below).

        ``use_dropout`` (STATIC) threads a per-round alive mask
        ``drop_seq`` ([T, N] float, 1.0 = alive) through the scan:
        dropped clients reuse the inert-slot masking — their eq.-(4)
        coefficient, loss contribution, and wall-time/energy terms are
        zeroed, exactly like padded slots — but they stay in the
        ``selected`` output (the dispatch footprint is selection-, not
        survival-, dependent) and the expected-energy queue drift is
        untouched (the controller plans on expectations; realized
        dropouts are a data-plane event).  ``False`` builds the exact
        historical trace — the dropout axis cannot perturb existing
        rollouts (``drop_seq`` is passed as ``None`` and never read).

        Padded-K contract: ``k`` is the STATIC slot count ``K_max`` and
        the traced ``k_act`` (scalar int) / ``kvec`` (``[N]`` float, the
        same K broadcast — see the materialization note below) carry the
        rollout's TRUE sampling count.  Every per-slot quantity is
        prefix-stable in the slot index — slot ``i`` draws its selection
        and its client PRNG key from ``fold_in(key, i)``, independent of
        ``K_max`` — and slots ``i >= k_act`` are inert: their draw clamps
        to row 0, their eq.-(4) coefficient, loss contribution, and
        wall-time/energy terms are zeroed (the exact non-member masking
        ``_tier_loop_round`` uses), and their ``selected`` output is -1.
        A padded rollout (``k_act < K_max``) is therefore bit-identical
        on the model trajectory to the same rollout built with
        ``K_max == k_act`` — zero coefficients contribute exactly 0.0 to
        the vmap-stable eq.-(4) sum, and masked additions of 0.0 are
        exact — which is what lets a mixed-K ScenarioArena grid run as
        ONE executable (see ``repro.sim.arena``).

        Bitwise contract with the ScenarioArena: ``V``, ``lam`` — and the
        per-rollout K, ``kvec`` — must arrive MATERIALIZED as ``[N]``
        vector arguments, not rank-0 scalars.  A scalar lets XLA's
        algebraic simplifier reassociate scalar-multiply chains inside
        the solver in the unbatched trace but not in a vmapped one (it is
        a per-lane vector there), drifting arena lanes from this scan at
        the last ulp; an array argument's producer is opaque to XLA, so
        both traces compute the identical elementwise graph.

        ``eval_fn(params, eval_data) -> {name: scalar}`` (optional) adds
        an on-device test-set evaluation every ``eval_every`` rounds: the
        scan carry holds the last evaluation (the "stacked carry" — under
        the arena's vmap it is the whole ``[S, ...]`` lane stack), the
        round index drives an UNBATCHED ``lax.cond`` (the predicate
        depends only on the shared round counter, so vmap keeps it a real
        branch — off-rounds pay a predicate, not an evaluation), and each
        round emits ``test_<name>`` columns holding the most recent
        evaluation.  Round 0's carry is an evaluation of the initial
        params.  Evaluation only reads ``params``; the model trajectory
        is unchanged.

        Chunked-rollout contract (the arena's streaming path): ``t0`` is
        the TRACED global index of this segment's first round — the scan
        runs rounds ``t0 .. t0 + len(h_seq)`` of the logical rollout, so
        the ``eval_every`` predicate keeps firing on global round
        boundaries across segments — and ``last_ev`` optionally seeds the
        eval carry from a previous segment (``None`` evaluates the
        incoming params, the monolithic behaviour).  The returned
        ``extras`` tuple is the remaining scan carry — ``(rng,)`` or
        ``(rng, last_ev)`` — exactly what the next segment must receive
        for the chunked trajectory to be bitwise-identical to the
        one-shot scan: the per-round ``jax.random.split`` chain continues
        from the carried key, and every other carry leaf is threaded
        unchanged.  Because ``t0`` is traced, equal-length segments share
        one executable.
        """
        if select_fn is None:
            select_fn = _default_select

        def scan_fn(params, queues, sp, eb, data, h_seq, drop_seq, lr_seq,
                    rng, V, lam, cid, kvec, k_act, eval_data, t0, last_ev):
            sp_run = dataclasses.replace(sp, energy_budget=eb)
            n = sp_run.num_devices
            w = sp_run.data_weights
            slots = jnp.arange(k)
            active = slots < k_act
            af = active.astype(jnp.float32)
            k_f = k_act.astype(jnp.float32)

            def body(carry, inp):
                if eval_fn is not None:
                    params, queues, rng, last_ev = carry
                else:
                    params, queues, rng = carry
                if use_dropout:
                    t_idx, h, alive, lr = inp
                else:
                    t_idx, h, lr = inp
                with jax.named_scope(scopes.DECIDE):
                    dec = decide_fn(sp_run, h, queues, V, lam, cid, kvec)
                with jax.named_scope(scopes.SELECT):
                    rng, k_sel, k_cli = jax.random.split(rng, 3)
                    # slot fill from the decision — every mode's slot i
                    # depends only on (round inputs, i), never on K_max:
                    # the padded-K invariant above
                    drawn = select_fn(sp_run, t_idx, h, queues, dec.q,
                                      k_sel, slots, kvec, cid)
                    selected = jnp.where(active, drawn, 0)
                    rngs = jax.vmap(
                        lambda i: jax.random.fold_in(k_cli, i))(slots)
                    if use_dropout:
                        # realized dropouts zero the slot exactly like a
                        # padded slot; `act` replaces `af` everywhere a
                        # surviving upload is what counts
                        act = af * jnp.take(alive, selected)
                    else:
                        act = af
                    coeffs = (jnp.take(w, selected) /
                              (jnp.take(kvec, selected) *
                               jnp.take(dec.q, selected)) * act)
                params, losses, tiers_run = round_fn(
                    params, data, selected, coeffs, lr, rngs)
                with jax.named_scope(scopes.QUEUES):
                    queues = vq.update_queues(
                        queues,
                        vq.energy_increment(sp_run, h, dec.p, dec.f, dec.q,
                                            k=kvec))
                    t = sm.round_time(sp_run, h, dec.p, dec.f, k=kvec)
                    e = sm.round_energy(sp_run, h, dec.p, dec.f, k=kvec)
                    if use_dropout:
                        loss = (jnp.sum(losses * act) /
                                jnp.maximum(jnp.sum(act), 1.0))
                        live = active & (act > 0.0)
                        # all slots dropped: no upload finished this round
                        wall = jnp.maximum(jnp.max(jnp.where(
                            live, jnp.take(t, selected), -jnp.inf)), 0.0)
                    else:
                        loss = jnp.sum(losses * af) / k_f
                        live = active
                        wall = jnp.max(jnp.where(
                            live, jnp.take(t, selected), -jnp.inf))
                    # inactive slots scatter to the dropped out-of-range
                    # row n
                    mask = jnp.zeros((n,), jnp.float32).at[
                        jnp.where(live, selected, n)].set(1.0, mode="drop")
                    out = dict(
                        loss=loss,
                        wall_time=wall,
                        energy_mean=(jnp.sum(e * mask) /
                                     jnp.maximum(jnp.sum(mask), 1.0)),
                        queue_mean=jnp.mean(queues),
                        queue_norm=jnp.linalg.norm(queues),
                        q_min=jnp.min(dec.q), q_max=jnp.max(dec.q),
                        selected=jnp.where(active, selected, -1),
                    )
                    if tiers_run is not None:
                        out[TIERS_RUN] = tiers_run
                if eval_fn is not None:
                    with jax.named_scope(scopes.EVAL):
                        last_ev = jax.lax.cond(
                            (t_idx + 1) % eval_every == 0,
                            lambda op: eval_fn(op[0], eval_data),
                            lambda op: op[1],
                            (params, last_ev))
                    out.update({"test_" + name: v
                                for name, v in last_ev.items()})
                    return (params, queues, rng, last_ev), out
                return (params, queues, rng), out

            num_rounds = h_seq.shape[0]
            if use_dropout:
                xs = (t0 + jnp.arange(num_rounds), h_seq, drop_seq,
                      lr_seq)
            else:
                xs = (t0 + jnp.arange(num_rounds), h_seq, lr_seq)
            if eval_fn is not None:
                last_ev0 = (eval_fn(params, eval_data) if last_ev is None
                            else last_ev)
                carry0 = (params, queues, rng, last_ev0)
            else:
                carry0 = (params, queues, rng)
            carry, outs = jax.lax.scan(body, carry0, xs)
            return carry[0], carry[1], tuple(carry[2:]), outs

        return scan_fn

    @staticmethod
    def _fixed_policy_decide(policy: str):
        """A ``decide_fn`` for :meth:`_build_scan` that always runs one
        named ``repro.core.policy`` rule (the traced ``cid`` is ignored —
        the policy is baked into the executable, no switch overhead)."""
        fn = pol.DECIDE_FNS[pol.POLICY_IDS[policy]]

        def decide(sp, h, queues, V, lam, cid, kvec):
            return fn(sp, h, queues, V, lam, k=kvec)

        return decide

    @staticmethod
    def _fixed_policy_select(policy: str):
        """A ``select_fn`` for :meth:`_build_scan` that always runs one
        named policy's selection mode (no switch — and for sampled-mode
        policies the trace is byte-identical to the historical inline
        draw, the bitwise anchor the arena lanes replay against)."""
        fn = pol.SELECT_FNS[pol.SELECTION_MODES[policy]]

        def select(sp, t, h, queues, q, key, slots, kvec, cid):
            return fn(sp, t, h, queues, q, key, slots, kvec)

        return select

    def run_scan(self, global_params: PyTree, sp: sm.SystemParams,
                 bank: AnyBank, h_seq: np.ndarray, lr_seq: np.ndarray,
                 rng: jax.Array, *, queues: Optional[jax.Array] = None,
                 policy: str = "lroa", V: float = 0.0, lam: float = 0.0,
                 drop_seq: Optional[np.ndarray] = None
                 ) -> Tuple[PyTree, jax.Array, Dict[str, np.ndarray]]:
        """Run ``h_seq.shape[0]`` full Algorithm-1 rounds in one jitted scan.

        ``bank``: the device-resident all-client bank (its ``num_steps`` /
        ``num_examples`` masks keep padded clients from over-training or
        over-sampling their duplicated rows relative to Algorithm 1); a
        :class:`TieredClientBank` runs the tier loop inside the scan body
        — each tier's training sits behind a selection-conditioned
        ``lax.cond`` (the selection is traced, so the skip is a runtime
        branch, not host routing), with non-member slots of a hit tier
        masked out by zeroed coefficients; a one-tier ladder delegates to
        the single-bucket scan unchanged.  ``h_seq``: [T, N] channel gains
        (``ChannelProcess.sample_sequence`` or ``sample_jax`` precompute
        them without host loops); ``lr_seq``: [T] learning rates.
        ``policy`` is any rule in the ``repro.core.policy.POLICIES``
        controller zoo — every registered controller (including DivFL's
        in-trace facility-location greedy and the deterministic
        round-robin schedule) runs fused; the policy's decide rule AND
        its selection mode are both baked into the executable.
        ``drop_seq`` ([T, N] float, 1.0 = alive, optional) threads a
        per-round realized-dropout mask through the scan; ``None`` (the
        default) builds the exact historical no-dropout trace.  Returns
        (final params, final queues, per-round metric arrays).  Both the
        params pytree and the ``queues`` array are donated off-CPU —
        callers must use the returned values, not the arguments.  Bank
        buffers are never donated.
        """
        if policy not in pol.POLICY_IDS:
            raise ValueError(f"unknown policy {policy!r} (scan-traceable: "
                             f"{pol.POLICIES})")
        use_dropout = drop_seq is not None
        round_fn, data, bank_key = self._scan_plan(bank)
        key = (bank_key, sp.sample_count, policy, use_dropout)
        fn = self._scan_fns.get(key)
        if fn is None:
            with obs_trace.span("arena.compile", stage="build",
                                layer="engine", policy=policy,
                                k=int(sp.sample_count)):
                scan_fn = self._build_scan(
                    sp.sample_count,
                    self._fixed_policy_decide(policy),
                    round_fn,
                    self._fixed_policy_select(policy),
                    use_dropout=use_dropout)
                donate = (0, 1) if self.donate else ()
                fn = self._scan_fns[key] = jax.jit(scan_fn,
                                                   donate_argnums=donate)
        if queues is None:
            queues = vq.init_queues(sp.num_devices)
        n = sp.num_devices
        # K is passed as DATA even though it is static here — both as the
        # materialized [N] vector the decide rules consume (kvec) and the
        # scalar active-slot count (k_act) — so this trace is the exact
        # graph a padded-K arena lane computes (bitwise contract).
        with obs_trace.span("engine.round", what="run_scan",
                            policy=policy, rounds=int(h_seq.shape[0]),
                            k=int(sp.sample_count)):
            params, queues, _, outs = fn(
                global_params, queues, sp,
                jnp.asarray(sp.energy_budget, jnp.float32), data,
                jnp.asarray(h_seq, jnp.float32),
                (jnp.asarray(drop_seq, jnp.float32) if use_dropout
                 else None),
                jnp.asarray(lr_seq, jnp.float32), rng,
                jnp.full((n,), V, jnp.float32), jnp.full((n,), lam,
                                                         jnp.float32),
                jnp.int32(pol.POLICY_IDS[policy]),
                jnp.full((n,), sp.sample_count, jnp.float32),
                jnp.int32(sp.sample_count), None, jnp.int32(0), None)
            metrics = {name: np.asarray(v) for name, v in outs.items()
                       if name != TIERS_RUN}
        return params, queues, metrics
