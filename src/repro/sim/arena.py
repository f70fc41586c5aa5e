"""ScenarioArena — device-batched multi-rollout sweeps over the fused scan.

The paper's entire evaluation (Sec. VII) is a grid of rollouts: LROA vs
Uni-D / Uni-S across seeds, Lyapunov weights (mu, nu), energy budgets,
and channel statistics.  Host-looping ``RoundEngine.run_scan`` pays one
dispatch chain per grid point; the arena instead stacks the S scenarios
struct-of-arrays (:class:`ScenarioGrid`) and lays the engine's scan body
out over the scenario axis — ``jax.vmap`` lanes by default, or
``lax.map`` lanes (``batch='map'``, the CPU/strong-scaling mode; see
:class:`Arena`) — so ONE jitted program executes every rollout, sharing
the read-only (never-donated) ClientBank across all lanes:

* **Controller-as-data.**  Each lane carries a traced ``controller_id``;
  the scan body dispatches ``repro.core.policy.decide_by_id``
  (``lax.switch``), so a single executable runs a mixed LROA/Uni-D/Uni-S
  grid.  DivFL is host-stateful and rejected at grid construction.
* **Bit-identical model rollouts.**  Lane ``s`` of ``Arena.run``
  reproduces ``engine.run_scan`` on scenario ``s``'s (seed, channels, V,
  lam, budget): the model trajectory — final params, per-round losses,
  selections, realised latency — is bit-for-bit identical on the
  leaf-chunked aggregation path (CPU/GPU), because the scan body, data
  plane, and PRNG stream are shared code and the eq.-(4) reduction is
  written vmap-stably (see ``server.aggregate_stacked``).  The
  control-plane diagnostics (queue/energy scalars from Algorithm 2's
  bisection solver) agree to float32 resolution (~1e-6 relative) rather
  than bitwise: XLA fuses those elementwise chains shape-dependently,
  so the batched and unbatched programs may round a final ulp apart.
  Tiered banks relax the model half to f32 resolution too — each
  tier's body runs batched over the lanes here and unbatched in
  ``run_scan``, and XLA may round the two a final ulp apart.  The
  contract is regression-tested in ``tests/test_arena.py``.
* **Tier skip across lanes.**  The lane ``vmap`` carries the axis name
  :data:`LANE_AXIS`, and the tier loop decides each tier's ``lax.cond``
  across it (see ``RoundEngine._tier_loop_round``): a tier that no lane
  drew in a round is skipped for all lanes, a tier that any lane drew
  runs for all of them.  The arena counts the tier-rounds run and
  skipped (``arena.tier_rounds_run`` / ``_skipped``, and on the
  ``arena.reduce`` span).
* **Channels pregenerated on device.**  Per-scenario (mean, clip) channel
  statistics feed a vmapped ``environment.sample_gains`` — the whole
  ``[S, T, N]`` gain tensor is drawn in one jit from the scenario seeds.
* **Scenario-axis sharding.**  Pass ``mesh=`` (e.g. ``launch.mesh.
  make_fl_mesh()``) and the scenario axis is ``shard_map``ped over the
  ``data`` axis: whole rollouts per shard, zero cross-shard collectives
  (embarrassingly parallel — the strong-scaling axis for sweep grids).
  The engine itself must then be mesh-free: client-axis and
  scenario-axis sharding compose by running the arena on the ``data``
  axis of a larger mesh, not by nesting shard_maps.
* **Padded-K dispatch fusion.**  K is per-scenario DATA, not shape: the
  scan body is built at the grid's static ``K_max`` and each lane's
  true K rides in as traced ``k_act``/``kvec`` (slots beyond ``k_act``
  are inert — row-0 gather, zeroed eq.-(4) coefficients and
  loss/latency/energy contributions — so padded lanes stay bitwise
  equal on the model trajectory to the per-K groups they replace).  A
  mixed-K grid is ONE compiled executable and ONE dispatch
  (``k_mode='pad'``, the default; ``'group'`` keeps the legacy
  one-program-per-K path for comparison).
* **On-device evaluation.**  Pass an :class:`repro.sim.eval.EvalBank`
  and the final ``[S, ...]`` params are evaluated in one vmapped
  ``task.metrics`` dispatch (``RolloutReport.final_metrics``);
  ``eval_every=E`` also evaluates inside the rollout executable every E
  rounds behind an unbatched ``lax.cond`` (``test_*`` per-round
  columns) — no host-side per-lane eval loop.
* **Warmup / executable cache.**  Executables are cached per (bank
  layout, K_max, shards, eval config); :meth:`Arena.warmup` compiles
  them eagerly so same-shape ``run`` calls (the iterate-on-V workflow)
  never retrace — ``Arena.traces`` counts scan-body traces for
  asserting exactly that.
* **Streaming chunked pipeline.**  ``chunk_size=T_c`` splits a T-round
  rollout into ``ceil(T / T_c)`` scan segments over the SAME body:
  chunk 0 runs the monolithic start executable at segment length, later
  chunks a resume executable whose (params, queues, rng, last-eval)
  carry arrives per-lane and is donated between segments.  Host
  reduction of chunk c's metric columns overlaps chunk c+1's device
  execution (async dispatch with a bounded in-flight window — no
  ``block_until_ready`` between chunks), and the chunked trajectory is
  bitwise-identical to the one-shot scan.  A ``chunk_store`` persists
  the carry at chunk boundaries (atomic npz via ``repro.checkpoint``)
  so interrupted runs resume bit-identically —
  ``repro.sim.service.SweepService`` builds the continuous warmed
  sweep-service loop on top.

Outputs land in a :class:`repro.sim.report.RolloutReport` (``[S, T]``
metric arrays + stacked final params/queues + ``meta`` execution-shape
counters) whose reducers emit the paper's latency / accuracy / loss /
energy trade-off curves.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import policy as pol
from repro.core import system_model as sm
from repro.core.controller import estimate_hyperparams_arrays
from repro.fl.environment import (CHANNEL_MODE_IDS, CHANNEL_MODES,
                                  sample_channel_sequence,
                                  sample_dropout_mask)
from repro.fl.round_engine import TIERS_RUN, bank_layout_key
from repro.obs import trace as obs
from repro.obs.metrics import MetricsRegistry
from repro.sim.cost_model import CostModel
from repro.sim.dispatch import DispatchPlan, lane_footprints, plan_dispatch
from repro.sim.report import RolloutReport, concat_chunk_metrics

PyTree = Any

#: the name of the lane ``vmap``: the tier loop decides its skip across it
LANE_AXIS = "lanes"


def _as_f32(value, s: int) -> np.ndarray:
    return np.broadcast_to(np.asarray(value, np.float32), (s,)).copy()


@dataclasses.dataclass(frozen=True)
class ScenarioGrid:
    """Struct-of-arrays stack of S scenarios (all fields shape ``[S]``).

    ``controller`` holds ``repro.core.policy.POLICY_IDS`` ids;
    ``energy_scale`` multiplies the base ``SystemParams.energy_budget``;
    (``mean_gain``, ``min_gain``, ``max_gain``) are the per-scenario
    truncated-exponential channel statistics; ``sample_count`` is K.
    ``chan_mode`` selects the channel process per lane
    (``repro.fl.environment.CHANNEL_MODE_IDS`` — 'iid' or 'markov'),
    with (``bad_gain``, ``p_gb``, ``p_bg``) the Gilbert-Elliott
    bad-state mean and transition probabilities (ignored by 'iid'
    lanes); ``dropout`` is the per-client per-round dropout probability
    (0.0 = the historical always-alive trace).  Build with
    :meth:`create` (broadcasting scalars) or :meth:`product` (cartesian
    sweep axes).
    """

    controller: np.ndarray
    seed: np.ndarray
    V: np.ndarray
    lam: np.ndarray
    energy_scale: np.ndarray
    mean_gain: np.ndarray
    min_gain: np.ndarray
    max_gain: np.ndarray
    sample_count: np.ndarray
    # non-stationary axes; None defaults keep pre-zoo constructions
    # (and their digests' field iteration order) valid
    chan_mode: Optional[np.ndarray] = None
    bad_gain: Optional[np.ndarray] = None
    p_gb: Optional[np.ndarray] = None
    p_bg: Optional[np.ndarray] = None
    dropout: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return int(self.controller.shape[0])

    def __post_init__(self):
        s = len(self)
        defaults = dict(chan_mode=np.zeros((s,), np.int32),
                        bad_gain=np.full((s,), 0.02, np.float32),
                        p_gb=np.zeros((s,), np.float32),
                        p_bg=np.zeros((s,), np.float32),
                        dropout=np.zeros((s,), np.float32))
        for name, default in defaults.items():
            if getattr(self, name) is None:
                object.__setattr__(self, name, default)
        for f in dataclasses.fields(self):
            arr = getattr(self, f.name)
            if arr.shape != (s,):
                raise ValueError(f"ScenarioGrid.{f.name} must have shape "
                                 f"({s},), got {arr.shape}")
        if s == 0:
            raise ValueError("empty ScenarioGrid")
        if np.any((self.chan_mode < 0) |
                  (self.chan_mode >= len(CHANNEL_MODES))):
            raise ValueError(f"chan_mode ids must index {CHANNEL_MODES}")
        for name in ("p_gb", "p_bg"):
            vals = getattr(self, name)
            if np.any((vals < 0.0) | (vals > 1.0)):
                raise ValueError(f"ScenarioGrid.{name} must lie in [0, 1]")
        if np.any((self.dropout < 0.0) | (self.dropout >= 1.0)):
            raise ValueError("ScenarioGrid.dropout must lie in [0, 1)")
        # jax.random.PRNGKey truncates seeds to 32 bits under the default
        # x64-disabled runtime, so seeds differing only above bit 31 would
        # silently run IDENTICAL lanes — reject them instead
        if np.any(self.seed < 0) or np.any(self.seed >= 2 ** 32):
            raise ValueError("ScenarioGrid seeds must fit in uint32 "
                             "(PRNGKey truncates wider seeds, which would "
                             "silently alias scenarios)")
        if np.any(self.sample_count < 1):
            raise ValueError(
                f"ScenarioGrid sample_count values must be >= 1, got "
                f"{self.sample_count.tolist()}")

    @staticmethod
    def _check_sample_counts(sample_count, num_devices) -> None:
        """Reject K > N at construction — the paper's sampling draws K of
        N devices, and an oversized K would otherwise surface only as a
        shape/semantics failure deep inside the rollout trace."""
        if num_devices is None:
            return
        ks = np.atleast_1d(np.asarray(sample_count, np.int64))
        if np.any(ks > int(num_devices)):
            bad = sorted(int(v) for v in np.unique(ks[ks > num_devices]))
            raise ValueError(
                f"sample_count values {bad} exceed num_devices="
                f"{int(num_devices)} (K must satisfy K <= N)")

    @staticmethod
    def _controller_ids(controllers) -> np.ndarray:
        ids = []
        for c in np.atleast_1d(np.asarray(controllers, object)):
            if isinstance(c, (int, np.integer)):
                cid = int(c)
                if not 0 <= cid < len(pol.POLICIES):
                    raise ValueError(f"controller id {cid} out of range "
                                     f"for {pol.POLICIES}")
            else:
                name = str(c)
                if name not in pol.POLICY_IDS:
                    raise ValueError(f"unknown controller {name!r} "
                                     f"(scan-traceable: {pol.POLICIES})")
                cid = pol.POLICY_IDS[name]
            ids.append(cid)
        return np.asarray(ids, np.int32)

    @staticmethod
    def _channel_mode_ids(modes) -> np.ndarray:
        ids = []
        for m in np.atleast_1d(np.asarray(modes, object)):
            if isinstance(m, (int, np.integer)):
                mid = int(m)
                if not 0 <= mid < len(CHANNEL_MODES):
                    raise ValueError(f"channel mode id {mid} out of range "
                                     f"for {CHANNEL_MODES}")
            else:
                name = str(m)
                if name not in CHANNEL_MODE_IDS:
                    raise ValueError(f"unknown channel mode {name!r} "
                                     f"(known: {CHANNEL_MODES})")
                mid = CHANNEL_MODE_IDS[name]
            ids.append(mid)
        return np.asarray(ids, np.int32)

    @classmethod
    def create(cls, controllers, seeds, V, lam, *, energy_scale=1.0,
               mean_gain=0.1, min_gain=0.01, max_gain=0.5,
               sample_count=2, chan_mode="iid", bad_gain=0.02, p_gb=0.0,
               p_bg=0.0, dropout=0.0,
               num_devices=None) -> "ScenarioGrid":
        """Element-wise grid: every argument broadcasts to the common
        scenario count S (controllers by name or id, channel modes by
        name or id).  ``num_devices`` (optional) validates every K
        against N up front."""
        cls._check_sample_counts(sample_count, num_devices)
        ids = cls._controller_ids(controllers)
        modes = cls._channel_mode_ids(chan_mode)
        seeds = np.atleast_1d(np.asarray(seeds, np.int64))
        s = max(ids.shape[0], seeds.shape[0], modes.shape[0],
                *(np.atleast_1d(np.asarray(v)).shape[0]
                  for v in (V, lam, energy_scale, mean_gain, min_gain,
                            max_gain, sample_count, bad_gain, p_gb, p_bg,
                            dropout)))
        return cls(
            controller=np.broadcast_to(ids, (s,)).copy(),
            seed=np.broadcast_to(seeds, (s,)).copy(),
            V=_as_f32(V, s), lam=_as_f32(lam, s),
            energy_scale=_as_f32(energy_scale, s),
            mean_gain=_as_f32(mean_gain, s),
            min_gain=_as_f32(min_gain, s),
            max_gain=_as_f32(max_gain, s),
            sample_count=np.broadcast_to(
                np.asarray(sample_count, np.int32), (s,)).copy(),
            chan_mode=np.broadcast_to(modes, (s,)).copy(),
            bad_gain=_as_f32(bad_gain, s),
            p_gb=_as_f32(p_gb, s), p_bg=_as_f32(p_bg, s),
            dropout=_as_f32(dropout, s),
        )

    @classmethod
    def product(cls, controllers, seeds, V, lam, *, energy_scale=(1.0,),
                mean_gain=(0.1,), min_gain=(0.01,), max_gain=(0.5,),
                sample_count=(2,), chan_mode=("iid",), bad_gain=(0.02,),
                p_gb=(0.0,), p_bg=(0.0,), dropout=(0.0,),
                num_devices=None) -> "ScenarioGrid":
        """Cartesian sweep: one scenario per element of the cross product
        of the given axes (the Sec. VII comparison grid: controllers x
        seeds x hyper-parameters x budgets x channels x K x channel
        modes x dropout).  The Gilbert-Elliott shape axes (``bad_gain``,
        ``p_gb``, ``p_bg``) cross like any other axis — sweep them only
        with a markov ``chan_mode`` in play, or they multiply lanes that
        ignore them.  ``num_devices`` (optional) validates every K
        against N up front — a clear construction-time error instead of
        a failure inside the rollout trace."""
        cls._check_sample_counts(sample_count, num_devices)
        ids = cls._controller_ids(controllers)
        modes = cls._channel_mode_ids(chan_mode)
        axes = [ids.tolist(), np.atleast_1d(seeds).tolist(),
                np.atleast_1d(V).tolist(), np.atleast_1d(lam).tolist(),
                np.atleast_1d(energy_scale).tolist(),
                np.atleast_1d(mean_gain).tolist(),
                np.atleast_1d(min_gain).tolist(),
                np.atleast_1d(max_gain).tolist(),
                np.atleast_1d(sample_count).tolist(),
                modes.tolist(),
                np.atleast_1d(bad_gain).tolist(),
                np.atleast_1d(p_gb).tolist(),
                np.atleast_1d(p_bg).tolist(),
                np.atleast_1d(dropout).tolist()]
        rows = list(itertools.product(*axes))
        cols = list(zip(*rows))
        return cls(
            controller=np.asarray(cols[0], np.int32),
            seed=np.asarray(cols[1], np.int64),
            V=np.asarray(cols[2], np.float32),
            lam=np.asarray(cols[3], np.float32),
            energy_scale=np.asarray(cols[4], np.float32),
            mean_gain=np.asarray(cols[5], np.float32),
            min_gain=np.asarray(cols[6], np.float32),
            max_gain=np.asarray(cols[7], np.float32),
            sample_count=np.asarray(cols[8], np.int32),
            chan_mode=np.asarray(cols[9], np.int32),
            bad_gain=np.asarray(cols[10], np.float32),
            p_gb=np.asarray(cols[11], np.float32),
            p_bg=np.asarray(cols[12], np.float32),
            dropout=np.asarray(cols[13], np.float32),
        )

    def take(self, idx: np.ndarray) -> "ScenarioGrid":
        """Sub-grid of the given scenario indices (grid order kept)."""
        return ScenarioGrid(**{f.name: getattr(self, f.name)[idx]
                               for f in dataclasses.fields(self)})

    @classmethod
    def concat(cls, grids: "List[ScenarioGrid]") -> "ScenarioGrid":
        """Stack several grids into one (lane order = submission order)
        — the sweep service's coalescing primitive: compatible pending
        submissions concatenate into a single batched grid, execute as
        one arena program, and split back per submission with
        ``RolloutReport.take``."""
        if not grids:
            raise ValueError("no grids to concatenate")
        return cls(**{f.name: np.concatenate(
            [getattr(g, f.name) for g in grids])
            for f in dataclasses.fields(grids[0])})

    def controller_names(self) -> list:
        return [pol.POLICIES[c] for c in self.controller]

    def channel_mode_names(self) -> list:
        return [CHANNEL_MODES[m] for m in self.chan_mode]

    def channel_config(self, s: int):
        """Scenario ``s``'s channel statistics as a ``ChannelConfig`` —
        the exact process an individual host replay of lane ``s`` must
        sample from."""
        from repro.fl.environment import ChannelConfig
        return ChannelConfig(
            mean_gain=float(self.mean_gain[s]),
            min_gain=float(self.min_gain[s]),
            max_gain=float(self.max_gain[s]),
            seed=int(self.seed[s]),
            mode=CHANNEL_MODES[int(self.chan_mode[s])],
            bad_gain=float(self.bad_gain[s]),
            p_gb=float(self.p_gb[s]), p_bg=float(self.p_bg[s]),
            dropout=float(self.dropout[s]))

    def scenario_system_params(self, sp: sm.SystemParams, s: int
                               ) -> sm.SystemParams:
        """Scenario ``s``'s SystemParams — the exact parameters an
        individual ``run_scan`` reproduction of lane ``s`` must use."""
        eb = np.asarray(sp.energy_budget, np.float32) * self.energy_scale[s]
        return dataclasses.replace(sp, sample_count=int(
            self.sample_count[s]), energy_budget=jnp.asarray(eb))


# module-level jits: a jit wrapper built inside a method would retrace
# and recompile on every call (jax caches on callable identity)
_sample_channels = jax.jit(
    jax.vmap(sample_channel_sequence,
             in_axes=(0, None, None, 0, 0, 0, 0, 0, 0, 0)),
    static_argnums=(1, 2))

_sample_dropout = jax.jit(
    jax.vmap(sample_dropout_mask, in_axes=(0, None, None, 0)),
    static_argnums=(1, 2))


@jax.jit
def _scenario_keys(seeds: jax.Array) -> Tuple[jax.Array, jax.Array]:
    # vmapped PRNGKey/split are bitwise identical to the per-seed host
    # loop (threefry init and split are elementwise on the key words) —
    # regression-tested — and one fused dispatch instead of S tiny ones.
    roots = jax.vmap(jax.random.PRNGKey)(seeds)
    return jax.vmap(lambda k: tuple(jax.random.split(k)))(roots)


def scenario_keys(grid: ScenarioGrid) -> Tuple[jax.Array, jax.Array]:
    """Per-scenario PRNG streams: ``(channel_keys [S, 2], rollout_keys
    [S, 2])``, both split from ``PRNGKey(seed)``.  This split IS the
    reproducibility contract — an individual ``run_scan`` with
    ``rng=rollout_keys[s]`` over ``h_all[s]`` replays arena lane ``s``.
    """
    return _scenario_keys(jnp.asarray(grid.seed, jnp.uint32))


@jax.jit
def _grid_hyperparams(sp_k, gains, scales, mus, nus, scale_f0):
    def one(gain, escale, m, n, f0):
        sp_s = dataclasses.replace(
            sp_k, energy_budget=sp_k.energy_budget * escale)
        lam_s, v_s, _, _ = estimate_hyperparams_arrays(
            sp_s, gain, loss_scale=f0, mu=m, nu=n)
        return lam_s, v_s
    return jax.vmap(one)(gains, scales, mus, nus, scale_f0)


def derive_hyperparams(sp: sm.SystemParams, grid: ScenarioGrid, mu, nu,
                       loss_scale=1.0) -> ScenarioGrid:
    """Fill the grid's (lam, V) from per-scenario (mu, nu) via the
    Sec. VII-B estimates — computed INSIDE one jit per K group
    (``estimate_hyperparams_arrays`` is pure jax), using each scenario's
    own mean channel gain and scaled energy budget."""
    s = len(grid)
    mu = _as_f32(mu, s)
    nu = _as_f32(nu, s)
    loss_scale = _as_f32(loss_scale, s)
    lam = np.zeros(s, np.float32)
    v = np.zeros(s, np.float32)
    for k in np.unique(grid.sample_count):
        idx = np.flatnonzero(grid.sample_count == k)
        sp_k = dataclasses.replace(sp, sample_count=int(k))
        lam_k, v_k = _grid_hyperparams(
            sp_k, jnp.asarray(grid.mean_gain[idx]),
            jnp.asarray(grid.energy_scale[idx]),
            jnp.asarray(mu[idx]), jnp.asarray(nu[idx]),
            jnp.asarray(loss_scale[idx]))
        lam[idx] = np.asarray(lam_k)
        v[idx] = np.asarray(v_k)
    return dataclasses.replace(grid, lam=lam, V=v)


class Arena:
    """Runs a :class:`ScenarioGrid` as one batched program over one engine.

    ``engine``: a mesh-free :class:`repro.fl.round_engine.RoundEngine`
    (the arena owns the parallel axis — see the module docstring).
    ``mesh``: optional 1-D mesh whose ``mesh_axis`` shards the scenario
    axis, whole rollouts per shard.  ``batch`` picks how lanes are laid
    out inside each (per-shard) program:

    * ``'vmap'`` (default) — lanes batched into wide ops.  The
      accelerator-friendly mode: S tiny rollouts become one set of
      S-wide kernels.  Algorithm 2's ``while_loop``s run in cross-lane
      lockstep (every lane pays the slowest lane's trip count).
    * ``'map'`` — lanes laid out sequentially (``lax.map``), each
      executing the exact unbatched rollout trace with its own solver
      trip counts.  The CPU-friendly mode: combined with scenario
      sharding it strong-scales near-linearly in local devices, with no
      lockstep amplification.

    ``k_mode`` picks how a mixed-K grid is executed:

    * ``'pad'`` (default) — ONE padded-K executable for the whole grid:
      the program is shaped by ``K_max = max(grid.sample_count)`` and
      each lane carries its true K as traced data (``k_act``/``kvec``,
      see ``RoundEngine._build_scan``); padded slots are inert (row-0
      gather, zeroed coefficients), so every lane stays bit-identical on
      the model trajectory to the per-K group it replaces — at one
      compile and one dispatch instead of one per distinct K.
    * ``'group'`` — the legacy path: one jitted program per distinct K,
      lanes scattered back into grid order on the host.  Kept for the
      bench baseline and for grids so K-skewed that padding waste
      (every lane trains ``K_max`` slots) beats compile/dispatch savings.
    * ``'auto'`` — shape-adaptive dispatch: a
      :func:`repro.sim.dispatch.plan_dispatch` cost model buckets the
      lanes by ``(K, tier footprint)`` signature into a small ladder of
      executables under ``max_executables``, with pad and group as
      reachable degenerate plans.  ``run`` plans for a ONE-run horizon
      (cold grids collapse toward the single padded executable — the
      workflow win), :meth:`warmup` for a steady-state horizon (buckets
      split by signature — the throughput win) and compiles every bucket
      in that plan; post-warmup ``run`` calls see the warmed buckets via
      the cache-aware cost model and re-pick them.  Multi-tier banks
      additionally get per-bucket STATIC tier subsets: lane footprints
      are replayed by a control-plane probe (selections depend only on
      the control plane, never on training — the same determinism the
      lane-equivalence tests pin down), so a bucket whose lanes never
      draw tier ``t`` compiles a scan body without it — where the
      padded program's per-round skip (a tier drawn by no lane of the
      program) pays each round's predicate, and runs the tier for every
      lane in a round where any lane drew it.  Results
      are stitched back to grid order (device-side ``concatenate`` +
      ``take`` per params leaf); per-bucket lanes stay bitwise-equal on
      the model trajectory to their pad/group counterparts.

    Compiled executables are cached per (bank layout, K_max, shard
    count, eval config) — :meth:`warmup` populates the cache eagerly so
    repeated same-shape ``run`` calls (the iterate-on-V workflow) never
    trace or compile again; ``self.traces`` counts scan-body traces for
    asserting that.  The bank and the initial params are never donated,
    so one arena serves any number of grids; the per-lane queue carry IS
    donated off-CPU (the arena allocates it per run).

    Memory audit (padded-K vs per-K groups): the executable's live state
    is the per-lane scan carry — params ``[S, ...]`` + queues ``[S, N]``
    (+ the last-eval carry with ``eval_every``) — plus one ``K_max``-wide
    training buffer per lane.  Grouped execution holds the same ``[S]``
    stacked outputs anyway (all groups' results are concatenated on the
    host), so padding adds only the ``(K_max - K_s)`` inert training
    slots per lane, bounded by ``S * (K_max - K_min) * B`` bucket rows —
    and removes the host-side per-lane params re-stack the grouped
    scatter pays.  Queue-carry donation keeps the padded program's peak
    at parity with the per-K programs'.
    """

    def __init__(self, engine, mesh: Optional[jax.sharding.Mesh] = None,
                 mesh_axis: str = "data", batch: str = "vmap",
                 k_mode: str = "pad",
                 cost_model: Optional[CostModel] = None,
                 max_executables: int = 4,
                 chunk_size: Optional[int] = None):
        if engine.mesh is not None:
            raise ValueError(
                "ScenarioArena shards the scenario axis; build the "
                "RoundEngine without a mesh (client-axis shard_map does "
                "not nest under the arena's vmap/shard_map)")
        if batch not in ("vmap", "map"):
            raise ValueError(f"unknown batch mode {batch!r} "
                             "(expected 'vmap' or 'map')")
        if k_mode not in ("pad", "group", "auto"):
            raise ValueError(f"unknown k_mode {k_mode!r} "
                             "(expected 'pad', 'group' or 'auto')")
        if max_executables < 1:
            raise ValueError(f"max_executables must be >= 1, "
                             f"got {max_executables}")
        self.engine = engine
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.batch = batch
        self.k_mode = k_mode
        #: prices for ``k_mode='auto'`` planning (``None`` = the tracked
        #: calibration defaults; see ``repro.sim.cost_model``)
        self.cost_model = (cost_model if cost_model is not None
                           else CostModel())
        #: hard cap on buckets an ``'auto'`` plan may emit
        self.max_executables = max_executables
        #: default rollout chunk length for the streaming path: ``None``
        #: runs the classic monolithic scan; an int T_c splits every
        #: rollout into ``ceil(T / T_c)`` pipelined scan segments whose
        #: carry is donated between chunks (``run``'s ``chunk_size=``
        #: overrides per call)
        self.chunk_size = chunk_size
        #: dispatched-but-unreduced chunk window of the streaming path —
        #: chunk c+1 is dispatched while chunk c's columns convert to
        #: host arrays, and the pipeline never runs more than this many
        #: chunks ahead of the reduction
        self.in_flight = 2
        self._fns: Dict[tuple, Any] = {}
        # (sgd steps, rows per lane) of one round, per executable key
        self._work: Dict[tuple, Tuple[int, int]] = {}
        # control-plane probe executables / replayed footprints, kept
        # OUT of self._fns so executables_cached keeps counting rollout
        # programs only
        self._probe_fns: Dict[tuple, Any] = {}
        self._footprint_cache: Dict[bytes, list] = {}
        #: the flight recorder's metrics registry — ONE namespace for
        #: every runtime tally of this arena and anything built on it
        #: (the sweep service and chunk store share it).  The historical
        #: counter attributes (``traces``, ``input_cache_hits`` /
        #: ``_misses``) are read-only views over it.
        self.metrics = MetricsRegistry()
        #: optional :class:`repro.obs.watchdog.Watchdog` — armed by
        #: :meth:`warmup`, notified after every :meth:`run`
        self.watchdog = None
        # device-input caches (bounded, insertion-order eviction): lane
        # constants keyed by grid content, lr sequences by value, channel
        # tensors by (grid, T, N) — steady-state service submissions of a
        # known grid re-use the device arrays and transfer nothing
        self._input_cache_cap = 16
        self._lane_cache: Dict[bytes, dict] = {}
        self._lr_cache: Dict[bytes, jax.Array] = {}
        self._chan_cache: Dict[bytes, jax.Array] = {}

    # -- registry views (the pre-obs counter attributes) ---------------------

    @property
    def traces(self) -> int:
        """Scan-body trace count — every jit (re)trace of a group
        executable runs the counted wrapper once, so a warmed arena
        must keep this constant across same-shape ``run`` calls.  A
        view over ``metrics['arena.traces']``."""
        return self.metrics.counter("arena.traces").value

    @property
    def input_cache_hits(self) -> int:
        """Device-input cache hits (lane constants + lr + channels) — a
        view over ``metrics['arena.input_cache.hits']``."""
        return self.metrics.counter("arena.input_cache.hits").value

    @property
    def input_cache_misses(self) -> int:
        """Device-input cache misses — a view over
        ``metrics['arena.input_cache.misses']``."""
        return self.metrics.counter("arena.input_cache.misses").value

    def _shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.mesh_axis])

    # -- channel pregeneration / device-input caches -------------------------

    @staticmethod
    def _grid_digest(grid: ScenarioGrid, extra: tuple = ()) -> bytes:
        """Content hash of every grid column (+ ``extra`` context) — the
        key of the device-input caches and the chunk-checkpoint tag, so
        it must be a pure function of values, never of Python object
        identity (checkpoint tags survive process restarts)."""
        hasher = hashlib.sha1()
        for f in dataclasses.fields(grid):
            hasher.update(np.ascontiguousarray(
                getattr(grid, f.name)).tobytes())
        hasher.update(repr(extra).encode())
        return hasher.digest()

    def _cache_put(self, cache: dict, key, value):
        if len(cache) >= self._input_cache_cap:
            cache.pop(next(iter(cache)))
        cache[key] = value
        return value

    def sample_channels(self, grid: ScenarioGrid, num_rounds: int,
                        num_devices: int) -> jax.Array:
        """Every scenario's channel sequence, ``[S, T, N]``, drawn on
        device in one jit from the per-scenario (seed, mode, mean, clip,
        chain) columns (vmapped ``environment.sample_channel_sequence``
        — stationary lanes consume the raw channel key exactly as the
        pre-zoo ``sample_gains`` did, markov lanes the ``fold_in(key,
        1)`` stream).  Cached by (grid content, T, N): the draw is a
        pure function of those, so repeated sweeps of a known grid (the
        service steady state) reuse the device tensor instead of
        re-sampling it."""
        key = self._grid_digest(grid, ("chan", num_rounds, num_devices))
        hit = self._chan_cache.get(key)
        if hit is not None:
            self.metrics.counter("arena.input_cache.hits").inc()
            return hit
        self.metrics.counter("arena.input_cache.misses").inc()
        with obs.span("arena.upload", what="channels", lanes=len(grid),
                      rounds=num_rounds):
            chan_keys, _ = scenario_keys(grid)
            h_all = _sample_channels(chan_keys, num_rounds,
                                     num_devices,
                                     jnp.asarray(grid.chan_mode),
                                     jnp.asarray(grid.mean_gain),
                                     jnp.asarray(grid.bad_gain),
                                     jnp.asarray(grid.min_gain),
                                     jnp.asarray(grid.max_gain),
                                     jnp.asarray(grid.p_gb),
                                     jnp.asarray(grid.p_bg))
        return self._cache_put(self._chan_cache, key, h_all)

    def sample_dropout(self, grid: ScenarioGrid, num_rounds: int,
                       num_devices: int) -> jax.Array:
        """Every scenario's alive mask, ``[S, T, N]`` float32 (1.0 =
        alive), from the DEDICATED ``fold_in(chan_key, 2)`` stream of
        the same per-scenario channel keys — so enabling the axis never
        perturbs the gains (the stream-separation regression contract).
        Cached like :meth:`sample_channels`."""
        key = self._grid_digest(grid, ("drop", num_rounds, num_devices))
        hit = self._chan_cache.get(key)
        if hit is not None:
            self.metrics.counter("arena.input_cache.hits").inc()
            return hit
        self.metrics.counter("arena.input_cache.misses").inc()
        with obs.span("arena.upload", what="dropout", lanes=len(grid),
                      rounds=num_rounds):
            chan_keys, _ = scenario_keys(grid)
            drop_all = _sample_dropout(chan_keys, num_rounds, num_devices,
                                       jnp.asarray(grid.dropout))
        return self._cache_put(self._chan_cache, key, drop_all)

    def _lane_inputs(self, grid: ScenarioGrid, sp: sm.SystemParams) -> dict:
        """The per-lane device constants a group executable consumes —
        energy budgets, V/lam/kvec materialized ``[S, N]``, controller
        ids, active-slot counts, rollout keys — cached by grid content
        so steady-state re-runs upload nothing.  Entries are read-only:
        none of these ever flow into a donated argnum (queues and the
        chunk carry are allocated or produced per run)."""
        key = self._grid_digest(
            grid, ("lane", sp.num_devices,
                   np.asarray(sp.energy_budget, np.float32).tobytes()))
        hit = self._lane_cache.get(key)
        if hit is not None:
            self.metrics.counter("arena.input_cache.hits").inc()
            return hit
        self.metrics.counter("arena.input_cache.misses").inc()
        upload = obs.span("arena.upload", what="lane_constants",
                          lanes=len(grid))
        upload.__enter__()
        s, n = len(grid), sp.num_devices
        _, roll_keys = scenario_keys(grid)
        eb = (np.asarray(sp.energy_budget, np.float32)[None, :] *
              grid.energy_scale[:, None])
        vals = dict(
            eb=jnp.asarray(eb),
            V=jnp.asarray(np.broadcast_to(grid.V[:, None], (s, n))),
            lam=jnp.asarray(np.broadcast_to(grid.lam[:, None], (s, n))),
            cid=jnp.asarray(grid.controller),
            kvec=jnp.asarray(np.broadcast_to(
                grid.sample_count[:, None].astype(np.float32), (s, n))),
            k_act=jnp.asarray(grid.sample_count, jnp.int32),
            roll_keys=roll_keys)
        upload.__exit__(None, None, None)
        return self._cache_put(self._lane_cache, key, vals)

    def _lr_device(self, lr_seq) -> jax.Array:
        """Device copy of the ``[T]`` learning-rate sequence, cached by
        value — one upload per distinct schedule."""
        lr_np = np.asarray(lr_seq, np.float32)
        key = lr_np.tobytes()
        hit = self._lr_cache.get(key)
        if hit is not None:
            self.metrics.counter("arena.input_cache.hits").inc()
            return hit
        self.metrics.counter("arena.input_cache.misses").inc()
        with obs.span("arena.upload", what="lr", rounds=int(lr_np.shape[0])):
            lr_dev = jnp.asarray(lr_np)
        return self._cache_put(self._lr_cache, key, lr_dev)

    # -- the batched rollout ------------------------------------------------

    def _eval_key(self, eval_bank, eval_every):
        if eval_bank is None or not eval_every:
            return None
        return (id(eval_bank.task), int(eval_every))

    def _build_group_fn(self, key: tuple, k: int, round_fn, eval_bank,
                        eval_every, resume: bool = False,
                        use_dropout: bool = False):
        """jit( [shard_map(] vmap(scan body) [)] ) for one K group,
        stored in ``self._fns`` under the caller's ``key`` — (bank
        layout, K_max, shard count, eval config), built ONCE in
        ``_run_group`` so lookup and insertion cannot drift apart.
        ``round_fn`` closes over only static layout captured in the
        bank-layout key component (the device buffers arrive via the
        ``data`` argument) and the eval data arrives traced too, so the
        cache key is sound — same contract as the engine's
        ``_scan_fns``.

        ``resume=False`` builds the rollout-START executable: params
        broadcast across lanes (``in_axes=None``), rng/last-eval derived
        inside (last-eval ``None`` — the initial evaluation runs
        UNBATCHED under vmap, exactly the monolithic program, which is
        why chunk 0 of a chunked rollout reuses this very executable).
        ``resume=True`` builds the chunk-CONTINUATION executable: the
        (params, queues, rng, last-eval) carry arrives per-lane
        (``in_axes=0``) and every carry leaf is donated — chunk c's
        output buffers become chunk c+1's carry in place.  Because
        rounds >= 1 of the monolithic vmapped scan already compute on a
        batched params carry, continuing with batched params is the
        identical per-round graph — the chunked == monolithic bitwise
        contract."""
        def decide(sp, h, queues, V, lam, cid, kvec):
            return pol.decide_by_id(cid, sp, h, queues, V, lam, k=kvec)

        def select(sp, t, h, queues, q, skey, slots, kvec, cid):
            return pol.select_by_id(cid, sp, t, h, queues, q, skey,
                                    slots, kvec)

        ek = self._eval_key(eval_bank, eval_every)
        # make_eval_fn closes over the TASK, not the bank: the cached
        # executable lives for the arena's lifetime, and capturing a
        # bank-bound callable would pin the test-set device buffers with
        # it (the data itself arrives as traced arguments)
        eval_fn = (None if ek is None
                   else eval_bank.make_eval_fn(eval_bank.task))
        inner = self.engine._build_scan(k, decide, round_fn,
                                        select_fn=select,
                                        eval_fn=eval_fn,
                                        eval_every=eval_every or 0,
                                        use_dropout=use_dropout)

        def scan_fn(*args):
            # runs at TRACE time only (the executable replays without
            # re-entering Python) — the zero-retrace warmup assertion
            self.metrics.counter("arena.traces").inc()
            return inner(*args)

        # the carry trio (params, rng-continuation via the rng argument,
        # last-eval) is per-lane on the resume executable, broadcast /
        # absent on the start executable; t0 (the global round offset) is
        # always a shared traced scalar so equal-length chunks share one
        # executable
        p_ax = 0 if resume else None
        ev_ax = 0 if resume else None
        d_ax = 0 if use_dropout else None
        if self.batch == "vmap":
            batched = jax.vmap(scan_fn,
                               in_axes=(p_ax, 0, None, 0, None, 0, d_ax,
                                        None, 0, 0, 0, 0, 0, 0, None,
                                        None, ev_ax),
                               axis_name=LANE_AXIS)
        else:
            def batched(params, queues, sp, eb, data, h_seq, drop_seq,
                        lr_seq, rng, V, lam, cid, kvec, k_act, eval_data,
                        t0, last_ev):
                if resume:
                    def one(lane):
                        (p_s, q0, eb_s, h_s, d_s, rng_s, V_s, lam_s,
                         cid_s, kv_s, ka_s, ev_s) = lane
                        return scan_fn(p_s, q0, sp, eb_s, data, h_s,
                                       d_s, lr_seq, rng_s, V_s, lam_s,
                                       cid_s, kv_s, ka_s, eval_data, t0,
                                       ev_s)
                    return jax.lax.map(one, (params, queues, eb, h_seq,
                                             drop_seq, rng, V, lam, cid,
                                             kvec, k_act, last_ev))

                def one(lane):
                    (q0, eb_s, h_s, d_s, rng_s, V_s, lam_s, cid_s, kv_s,
                     ka_s) = lane
                    return scan_fn(params, q0, sp, eb_s, data, h_s, d_s,
                                   lr_seq, rng_s, V_s, lam_s, cid_s,
                                   kv_s, ka_s, eval_data, t0, last_ev)
                return jax.lax.map(one, (queues, eb, h_seq, drop_seq,
                                         rng, V, lam, cid, kvec, k_act))
        if self.mesh is not None:
            ax = self.mesh_axis
            p_spec = P(ax) if resume else P()
            d_spec = P(ax) if use_dropout else P()
            batched = jax.shard_map(
                batched, mesh=self.mesh,
                in_specs=(p_spec, P(ax), P(), P(ax), P(), P(ax), d_spec,
                          P(), P(ax), P(ax), P(ax), P(ax), P(ax), P(ax),
                          P(), P(), p_spec),
                out_specs=P(ax), check_vma=False)
        # the queue carry (argnum 1) is donated off-CPU: the arena
        # allocates it per run, so the padded program reuses that buffer
        # for the [S, N] carry instead of holding both — part of the
        # padded-vs-grouped peak-memory parity audit (class docstring).
        # On the start executable params (argnum 0) are shared across
        # lanes and never donated; the resume executable's whole carry —
        # params (0), queues (1), rng (8), last-eval (16) — is arena-
        # owned chunk output and donates between segments.
        if resume:
            donate = (0, 1, 8, 16) if self.engine.donate else ()
        else:
            donate = (1,) if self.engine.donate else ()
        fn = jax.jit(batched, donate_argnums=donate)
        self._fns[key] = fn
        # one round's local-SGD work, for the dispatch spans: sequential
        # steps of one lane program and the rows one lane trains, padded
        # slots and tiers included
        steps = self.engine.round_sgd_steps(key[0])
        self._work[key] = (steps, steps * self.engine.cfg.batch_size * k)
        return fn

    @staticmethod
    def _carry_tree(carry: tuple) -> dict:
        """(params, queues, extras) chunk carry as a named flat-ish dict
        — the checkpoint wire format (stable names, so a restored file's
        structure is reconstructable from the service's own config)."""
        params, queues, extras = carry
        tree = {"params": params, "queues": queues, "rng": extras[0]}
        if len(extras) > 1:
            tree["last_ev"] = extras[1]
        return tree

    @staticmethod
    def _carry_from_tree(tree: dict) -> tuple:
        extras = ((tree["rng"], tree["last_ev"]) if "last_ev" in tree
                  else (tree["rng"],))
        return tree["params"], tree["queues"], extras

    def _chunk_tag(self, grid: ScenarioGrid, sp, k_max, tier_subset,
                   eval_every, num_rounds, chunk, h_digest) -> str:
        """Filename-safe content tag of one group's chunked execution —
        a pure function of everything that shapes the trajectory, so a
        restarted process resuming the same submission recomputes the
        same tag (and a different grid/chunking can never collide)."""
        hasher = hashlib.sha1()
        hasher.update(self._grid_digest(grid, (
            "chunk", k_max, tier_subset, int(eval_every or 0),
            num_rounds, chunk, self.batch, self._shards(),
            np.asarray(sp.energy_budget, np.float32).tobytes(),
            h_digest)))
        return "chunk_" + hasher.hexdigest()[:20]

    def _run_group(self, global_params: PyTree, sp: sm.SystemParams,
                   bank, grid: ScenarioGrid, h_all, lr_seq,
                   k_max: Optional[int] = None, eval_bank=None,
                   eval_every=None, tier_subset=None,
                   warm_aot: bool = False,
                   chunk_size: Optional[int] = None, chunk_store=None,
                   h_digest=None, drop_all=None):
        """One K group (uniform K, or a padded mixed-K grid when
        ``k_max`` is given) as one jitted program — or, with
        ``chunk_size``, as a pipeline of carry-donated scan segments.
        Returns ``(params, queues, metrics, executables_built,
        dispatches)`` with metrics as HOST arrays in the group's grid
        order.  ``tier_subset`` builds (and caches) the executable
        against a static subset of a tiered bank's ladder — the dispatch
        planner's scan-skip lever; the caller guarantees the group's
        lanes never select outside it.  ``warm_aot=True`` AOT-lowers and
        compiles the executable(s) instead of running (results come back
        None); the compile lands in the jit call cache, so the next real
        call neither traces nor compiles.

        The chunked pipeline: chunk 0 runs the START executable (the
        monolithic program at segment length — the initial in-scan eval
        stays unbatched, see ``_build_group_fn``), later chunks run the
        RESUME executable with the previous segment's (params, queues,
        rng, last-eval) carry donated in and the global round offset
        ``t0`` traced.  Host reduction of chunk c's metric columns
        overlaps chunk c+1's device execution: jax dispatch is async, so
        the only blocking point is the ``np.asarray`` on a chunk that
        has had a full segment of device time to finish — bounded by the
        ``self.in_flight`` dispatched-but-unreduced window, never a
        ``block_until_ready`` between chunks.  ``chunk_store`` (the
        sweep service's checkpoint protocol: ``.load(tag)``,
        ``.save(tag, t_next, carry, metrics)``, ``.finish(tag)``,
        ``.every``) persists the carry + reduced columns at chunk
        boundaries and resumes a half-finished group bit-identically."""
        if k_max is None:
            k_max = int(grid.sample_count[0])
        sp_k = dataclasses.replace(sp, sample_count=k_max)
        use_dropout = drop_all is not None
        if use_dropout:
            drop_all = jnp.asarray(drop_all, jnp.float32)
        round_fn, data, bank_key = self.engine._scan_plan(
            bank, tier_subset,
            lane_axis=LANE_AXIS if self.batch == "vmap" else None)
        ek = self._eval_key(eval_bank, eval_every)
        key = (bank_key, k_max, self._shards(), ek, use_dropout)
        built = 0
        fn = self._fns.get(key)
        if fn is None:
            with obs.span("arena.compile", stage="build", resume=False,
                          k_max=int(k_max), key=repr(key)):
                fn = self._build_group_fn(key, k_max, round_fn,
                                          eval_bank, eval_every,
                                          use_dropout=use_dropout)
            built += 1
        s = len(grid)
        if s % self._shards():
            raise ValueError(
                f"scenario count {s} not divisible by mesh axis "
                f"{self.mesh_axis!r} size {self._shards()} (per-K group "
                f"sizes must split evenly across shards)")
        lane = self._lane_inputs(grid, sp)
        n = sp.num_devices
        # one dispatched round's work; lanes run one after another under
        # 'map'
        round_steps, lane_rows = self._work[key]
        if self.batch != "vmap":
            round_steps *= s // self._shards()
        round_rows = lane_rows * s
        eval_data = None if ek is None else eval_bank.device_args()
        h_all = jnp.asarray(h_all, jnp.float32)
        lr_dev = self._lr_device(lr_seq)
        num_rounds = int(h_all.shape[1])

        def start_args(h_seg, d_seg, lr_seg, q0):
            # V/lam — and each lane's true K — are the materialized
            # [S, N] cached device constants (_build_scan's bitwise
            # contract); the queue carry is donated, so it is allocated
            # per run and no cached buffer ever flows into argnum 1
            return (global_params, q0, sp_k, lane["eb"], data, h_seg,
                    d_seg, lr_seg, lane["roll_keys"], lane["V"],
                    lane["lam"], lane["cid"], lane["kvec"],
                    lane["k_act"], eval_data, jnp.int32(0), None)

        if chunk_size is None and chunk_store is None:
            # classic monolithic scan: one executable, one dispatch
            args = start_args(h_all, drop_all, lr_dev,
                              jnp.zeros((s, n), jnp.float32))
            if warm_aot:
                with obs.span("arena.compile", stage="aot",
                              k_max=int(k_max), lanes=s,
                              rounds=num_rounds):
                    fn.lower(*args).compile()
                return None, None, None, built, 0
            with obs.span("arena.dispatch", k_max=int(k_max), lanes=s,
                          rounds=num_rounds, cold=bool(built),
                          sgd_steps=round_steps * num_rounds,
                          rows_trained=round_rows * num_rounds):
                params, queues, _, outs = fn(*args)
            with obs.span("arena.reduce", k_max=int(k_max), lanes=s,
                          rounds=num_rounds) as red:
                metrics = self._reduce_outs(outs, red)
            return params, queues, metrics, built, 1

        chunk = (num_rounds if chunk_size is None
                 else max(1, int(chunk_size)))
        resume_key = key + ("resume",)
        tag, t_start, carry, reduced = None, 0, None, []
        if chunk_store is not None:
            tag = self._chunk_tag(grid, sp, k_max, tier_subset,
                                  eval_every, num_rounds, chunk,
                                  h_digest)
            hit = chunk_store.load(tag)
            if hit is not None:
                t_start, carry_np, prefix = hit
                carry = self._carry_from_tree(jax.tree_util.tree_map(
                    jnp.asarray, carry_np))
                reduced.append(dict(prefix))
        segments = [(t0, min(chunk, num_rounds - t0))
                    for t0 in range(t_start, num_rounds, chunk)]
        rfn = self._fns.get(resume_key)
        need_resume = len(segments) > (1 if carry is None else 0)
        if need_resume and rfn is None:
            with obs.span("arena.compile", stage="build", resume=True,
                          k_max=int(k_max), key=repr(resume_key)):
                rfn = self._build_group_fn(resume_key, k_max, round_fn,
                                           eval_bank, eval_every,
                                           resume=True,
                                           use_dropout=use_dropout)
            built += 1

        def drop_seg(t0, ln):
            return (None if drop_all is None
                    else drop_all[:, t0:t0 + ln])

        def resume_args(c, h_seg, d_seg, lr_seg, t0):
            c_params, c_queues, c_extras = c
            c_ev = c_extras[1] if len(c_extras) > 1 else None
            return (c_params, c_queues, sp_k, lane["eb"], data, h_seg,
                    d_seg, lr_seg, c_extras[0], lane["V"], lane["lam"],
                    lane["cid"], lane["kvec"], lane["k_act"], eval_data,
                    jnp.int32(t0), c_ev)

        if warm_aot:
            # compile every segment shape the chunked run will hit: the
            # start executable at the first segment length, the resume
            # executable at each distinct later length (the ragged tail
            # is a second shape) — carry shapes come from structs, no
            # execution
            seen = set()
            p_struct = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    (s,) + tuple(np.shape(a)), np.asarray(a).dtype
                    if not hasattr(a, "dtype") else a.dtype),
                global_params)
            q_struct = jax.ShapeDtypeStruct((s, n), jnp.float32)
            rng_struct = jax.ShapeDtypeStruct((s, 2), jnp.uint32)
            extras_struct = ((rng_struct,) if ek is None else
                             (rng_struct, eval_bank.carry_struct(
                                 global_params, s)))
            for i, (t0, ln) in enumerate(segments):
                h_seg, lr_seg = h_all[:, t0:t0 + ln], lr_dev[t0:t0 + ln]
                first = i == 0 and carry is None and t_start == 0
                which = ("start" if first else "resume", ln)
                if which in seen:
                    continue
                seen.add(which)
                if first:
                    with obs.span("arena.compile", stage="aot",
                                  which="start", k_max=int(k_max),
                                  lanes=s, rounds=int(ln)):
                        fn.lower(*start_args(
                            h_seg, drop_seg(t0, ln), lr_seg,
                            q_struct)).compile()
                else:
                    with obs.span("arena.compile", stage="aot",
                                  which="resume", k_max=int(k_max),
                                  lanes=s, rounds=int(ln)):
                        rfn.lower(*resume_args(
                            (p_struct, q_struct, extras_struct), h_seg,
                            drop_seg(t0, ln), lr_seg, t0)).compile()
            return None, None, None, built, 0

        # -- the pipeline: dispatch ahead, reduce behind -------------------
        # (device outs, segment length, chunk index)
        pending: List[Tuple[Any, int, int]] = []

        def reduce_oldest():
            outs_d, ln_r, idx = pending.pop(0)
            # np.asarray blocks only on THIS chunk's output buffers —
            # later chunks keep executing asynchronously (the span
            # therefore measures the honest stall: how long the host
            # waited for device work to catch up)
            with obs.span("arena.reduce", chunk=idx, rounds=int(ln_r),
                          k_max=int(k_max), lanes=s) as red:
                reduced.append(self._reduce_outs(outs_d, red))

        dispatches = 0
        for i, (t0, ln) in enumerate(segments):
            while len(pending) >= self.in_flight:
                reduce_oldest()
            h_seg, lr_seg = h_all[:, t0:t0 + ln], lr_dev[t0:t0 + ln]
            with obs.span("arena.dispatch", chunk=i, t0=int(t0),
                          rounds=int(ln), k_max=int(k_max), lanes=s,
                          sgd_steps=round_steps * ln,
                          rows_trained=round_rows * ln):
                if carry is None and i == 0 and t_start == 0:
                    q0 = jnp.zeros((s, n), jnp.float32)
                    params, queues, extras, outs = fn(
                        *start_args(h_seg, drop_seg(t0, ln), lr_seg,
                                    q0))
                else:
                    params, queues, extras, outs = rfn(
                        *resume_args(carry, h_seg, drop_seg(t0, ln),
                                     lr_seg, t0))
            dispatches += 1
            carry = (params, queues, extras)
            pending.append((outs, ln, i))
            last = i == len(segments) - 1
            if (chunk_store is not None and not last and
                    (i + 1) % max(1, getattr(chunk_store, "every", 1))
                    == 0):
                # checkpoint: drain the pipeline (metrics must cover
                # exactly [0, t0+ln)), snapshot the carry to host, and
                # hand both to the store BEFORE the next dispatch can
                # donate the carry buffers away
                while pending:
                    reduce_oldest()
                carry_np = jax.tree_util.tree_map(np.asarray, carry)
                chunk_store.save(
                    tag, t0 + ln, self._carry_tree(carry_np),
                    concat_chunk_metrics(reduced))
        while pending:
            reduce_oldest()
        metrics = concat_chunk_metrics(reduced)
        if chunk_store is not None:
            chunk_store.finish(tag)
        params, queues, _ = carry
        return params, queues, metrics, built, dispatches

    def _reduce_outs(self, outs: dict, red) -> dict:
        """One dispatch's metric columns as host arrays, less the tier
        loop's ``tiers_run`` flags, which are counted instead: tier-rounds
        run and skipped, one per tier and round of each program that
        decides its skip on its own (a shard's lane ``vmap``, or one lane
        under ``'map'``).  The counts go on the ``arena.reduce`` span
        ``red`` and the ``arena.tier_rounds_run`` / ``_skipped``
        counters."""
        host = {name: np.asarray(v) for name, v in outs.items()}
        ran = host.pop(TIERS_RUN, None)
        if ran is not None:
            if self.batch == "vmap":
                # every lane of a shard's vmap holds its shard's flags
                ran = ran[::ran.shape[0] // self._shards()]
            run = int(np.count_nonzero(ran))
            skipped = int(ran.size) - run
            red.set(tier_rounds_run=run, tier_rounds_skipped=skipped)
            self.metrics.counter("arena.tier_rounds_run").inc(run)
            self.metrics.counter("arena.tier_rounds_skipped").inc(skipped)
        return host

    # -- shape-adaptive dispatch planning -----------------------------------

    def _tier_work(self, bank) -> Dict[int, float]:
        """``{tier id: bucket rows per padded slot per round}`` — the
        cost model's work weights: local epochs x steps/epoch x batch
        rows, per tier of the ladder (a single bank is tier 0)."""
        banks = (bank.tiers if hasattr(bank, "tiers") else [bank])
        epochs = float(self.engine.cfg.local_epochs)
        return {t: epochs * b.steps_per_epoch * b.batch_size
                for t, b in enumerate(banks)}

    def _probe_footprints(self, sp, bank, grid: ScenarioGrid, h_all,
                          num_rounds: int) -> list:
        """Per-lane tier footprints, replayed WITHOUT training: the
        scan's selections depend only on the control plane — queues
        evolve from the decide outputs, the rng carry evolves by
        ``split`` alone, slot draws are prefix-stable ``fold_in`` —
        never on the model, so a probe scan whose round_fn is a no-op
        reproduces every lane's exact selection trace at control-plane
        cost (the same determinism the lane-equivalence tests pin
        down).  Probe executables are cached per (K_max, batch mode);
        probe RESULTS are cached by content hash of the inputs that
        shape selections, so steady-state re-runs of one grid replan
        from memory."""
        s, n = len(grid), sp.num_devices
        k_max = int(grid.sample_count.max())
        eb_base = np.asarray(sp.energy_budget, np.float32)
        h_np = np.asarray(h_all, np.float32)
        hasher = hashlib.sha1()
        for part in (h_np, grid.controller, grid.seed, grid.V, grid.lam,
                     grid.energy_scale, grid.sample_count, eb_base):
            hasher.update(np.ascontiguousarray(part).tobytes())
        hasher.update(str((k_max, n, num_rounds)).encode())
        cache_key = hasher.digest()
        hit = self._footprint_cache.get(cache_key)
        if hit is not None:
            return hit

        pk = (k_max, self.batch)
        fn = self._probe_fns.get(pk)
        if fn is None:
            def decide(sp_run, h, queues, V, lam, cid, kvec):
                return pol.decide_by_id(cid, sp_run, h, queues, V, lam,
                                        k=kvec)

            def select(sp_run, t, h, queues, q, skey, slots, kvec, cid):
                return pol.select_by_id(cid, sp_run, t, h, queues, q,
                                        skey, slots, kvec)

            def noop_round(params, data, selected, coeffs, lr, rngs):
                return params, jnp.zeros(selected.shape, jnp.float32), None

            inner = self.engine._build_scan(k_max, decide, noop_round,
                                            select_fn=select)
            if self.batch == "vmap":
                batched = jax.vmap(inner,
                                   in_axes=(None, 0, None, 0, None, 0,
                                            None, None, 0, 0, 0, 0, 0,
                                            0, None, None, None))
            else:
                def batched(params, queues, sp_run, eb, data, h_seq,
                            drop_seq, lr_seq, rng, V, lam, cid, kvec,
                            k_act, eval_data, t0, last_ev):
                    def one(lane):
                        (q0, eb_s, h_s, rng_s, V_s, lam_s, cid_s, kv_s,
                         ka_s) = lane
                        return inner(params, q0, sp_run, eb_s, data,
                                     h_s, drop_seq, lr_seq, rng_s, V_s,
                                     lam_s, cid_s, kv_s, ka_s,
                                     eval_data, t0, last_ev)
                    return jax.lax.map(one, (queues, eb, h_seq, rng, V,
                                             lam, cid, kvec, k_act))
            fn = self._probe_fns[pk] = jax.jit(batched)
        _, roll_keys = scenario_keys(grid)
        eb = eb_base[None, :] * grid.energy_scale[:, None]
        sp_k = dataclasses.replace(sp, sample_count=k_max)
        probe_span = obs.span("arena.probe", lanes=s, k_max=k_max,
                              rounds=num_rounds)
        probe_span.__enter__()
        _, _, _, outs = fn(
            jnp.zeros((1,)), jnp.zeros((s, n), jnp.float32), sp_k,
            jnp.asarray(eb), None, jnp.asarray(h_np), None,
            jnp.zeros((num_rounds,), jnp.float32), roll_keys,
            jnp.asarray(np.broadcast_to(grid.V[:, None], (s, n))),
            jnp.asarray(np.broadcast_to(grid.lam[:, None], (s, n))),
            jnp.asarray(grid.controller),
            jnp.asarray(np.broadcast_to(
                grid.sample_count[:, None].astype(np.float32), (s, n))),
            jnp.asarray(grid.sample_count, jnp.int32), None,
            jnp.int32(0), None)
        fps = lane_footprints(np.asarray(outs["selected"]),
                              np.asarray(bank.tier_of))
        probe_span.__exit__(None, None, None)
        self._footprint_cache[cache_key] = fps
        return fps

    def _plan(self, sp, bank, grid: ScenarioGrid, num_rounds: int,
              h_all, *, runs: float, eval_key,
              use_dropout: bool = False) -> DispatchPlan:
        """The ``k_mode='auto'`` plan for this grid at the given reuse
        horizon (``runs=1`` for a cold :meth:`run`, ``math.inf`` for
        :meth:`warmup`'s steady state).  The cost model sees the arena's
        executable cache through ``is_cached``, so a warmed arena's
        plans snap to the already-compiled buckets."""
        multi_tier = hasattr(bank, "tiers") and bank.num_tiers > 1
        footprints = (self._probe_footprints(sp, bank, grid, h_all,
                                             num_rounds)
                      if multi_tier else None)

        def is_cached(bucket) -> bool:
            bk = bank_layout_key(bank, bucket.tiers)
            return (bk, bucket.k_pad, self._shards(),
                    eval_key, use_dropout) in self._fns

        return plan_dispatch(
            grid.sample_count, rounds=num_rounds,
            tier_work=self._tier_work(bank), footprints=footprints,
            cost_model=self.cost_model,
            max_executables=self.max_executables, is_cached=is_cached,
            runs=runs)

    def _run_plan(self, global_params: PyTree, sp, bank,
                  grid: ScenarioGrid, h_all, lr_seq,
                  plan: DispatchPlan, eval_bank=None, eval_every=None,
                  warm_aot: bool = False,
                  chunk_size: Optional[int] = None, chunk_store=None,
                  h_digest=None, drop_all=None):
        """Execute (or, with ``warm_aot``, AOT-compile) every bucket of
        ``plan`` and stitch the lanes back to grid order.  Params are
        stitched on DEVICE — one ``concatenate`` + one ``take`` per
        leaf — instead of the legacy grouped path's per-lane slice/
        re-stack (O(S x leaves) dispatches); metrics/queues are host
        arrays and concatenate there.  ``chunk_size``/``chunk_store``
        run each bucket through the chunked pipeline (each bucket
        checkpoints under its own content tag, so multi-bucket plans
        resume per bucket).  Returns ``(params, queues, metrics,
        built_total, bucket_meta)`` with everything but ``bucket_meta``
        None under ``warm_aot``."""
        k_max = int(grid.sample_count.max())
        chunks = []
        built_total = 0
        bucket_meta = []
        for b in plan.buckets:
            idx = np.asarray(b.lanes, np.int64)
            params_g, queues_g, outs_g, built, nd = self._run_group(
                global_params, sp, bank, grid.take(idx),
                h_all[jnp.asarray(idx)], lr_seq, k_max=b.k_pad,
                eval_bank=eval_bank, eval_every=eval_every,
                tier_subset=b.tiers, warm_aot=warm_aot,
                chunk_size=chunk_size, chunk_store=chunk_store,
                h_digest=h_digest,
                drop_all=(None if drop_all is None
                          else drop_all[jnp.asarray(idx)]))
            built_total += int(built)
            bucket_meta.append(dict(
                lanes=[int(i) for i in b.lanes], k_pad=int(b.k_pad),
                tiers=None if b.tiers is None else list(b.tiers),
                dispatches=int(nd),
                executables_built=int(built)))
            chunks.append((params_g, queues_g, outs_g))
        if warm_aot:
            return None, None, None, built_total, bucket_meta
        if plan.num_buckets == 1:
            # single bucket = the padded fast path: lanes already in
            # grid order, no permutation or concatenation needed
            params_g, queues_g, outs_g = chunks[0]
            return (params_g, np.asarray(queues_g), dict(outs_g),
                    built_total, bucket_meta)
        inv = plan.inverse_permutation()
        inv_dev = jnp.asarray(inv)
        params = jax.tree_util.tree_map(
            lambda *ls: jnp.take(jnp.concatenate(ls, axis=0), inv_dev,
                                 axis=0), *[c[0] for c in chunks])
        queues = np.concatenate([np.asarray(c[1]) for c in chunks],
                                axis=0)[inv]
        metrics: Dict[str, np.ndarray] = {}
        for name in chunks[0][2]:
            parts = []
            for _, _, outs_g in chunks:
                v = np.asarray(outs_g[name])
                if name == "selected" and v.shape[-1] < k_max:
                    pad = np.full(v.shape[:-1] + (k_max - v.shape[-1],),
                                  -1, v.dtype)
                    v = np.concatenate([v, pad], axis=-1)
                parts.append(v)
            metrics[name] = np.concatenate(parts, axis=0)[inv]
        return params, queues, metrics, built_total, bucket_meta

    def run(self, global_params: PyTree, sp: sm.SystemParams, bank,
            grid: ScenarioGrid, num_rounds: int, lr_seq,
            *, h_all: Optional[jax.Array] = None,
            drop_all: Optional[jax.Array] = None, eval_bank=None,
            eval_every: Optional[int] = None,
            chunk_size: Optional[int] = None,
            chunk_store=None) -> RolloutReport:
        """Instrumented entry point — see :meth:`_run_impl` for the
        full execution contract.  Opens the top-level ``arena.run``
        span, folds the run's meta into the shared metrics registry
        (``arena.runs`` / ``arena.dispatches`` /
        ``arena.executables_built`` cumulative counters — the per-run
        deltas stay in ``RolloutReport.meta``), and reports to the
        attached :class:`~repro.obs.watchdog.Watchdog` (which, post-
        warmup, turns any new trace or executable into a violation)."""
        run_span = obs.span("arena.run", k_mode=self.k_mode,
                            lanes=len(grid), rounds=int(num_rounds))
        with run_span:
            report = self._run_impl(
                global_params, sp, bank, grid, num_rounds, lr_seq,
                h_all=h_all, drop_all=drop_all, eval_bank=eval_bank,
                eval_every=eval_every, chunk_size=chunk_size,
                chunk_store=chunk_store)
            run_span.set(
                dispatches=int(report.meta.get("dispatches", 0)),
                executables_built=int(
                    report.meta.get("executables_built", 0)))
        self._record_run_meta(report.meta)
        if self.watchdog is not None:
            self.watchdog.observe_run(self, report.meta)
        return report

    def _record_run_meta(self, meta: dict) -> None:
        """Fold one run's meta deltas into the cumulative registry (the
        additive per-bucket contract itself stays cross-checked by
        ``RolloutReport.dispatch_accounting``)."""
        m = self.metrics
        m.counter("arena.runs").inc()
        m.counter("arena.dispatches").inc(int(meta.get("dispatches", 0)))
        m.counter("arena.executables_built").inc(
            int(meta.get("executables_built", 0)))
        m.gauge("arena.executables_cached").set(len(self._fns))

    def _run_impl(self, global_params: PyTree, sp: sm.SystemParams, bank,
            grid: ScenarioGrid, num_rounds: int, lr_seq,
            *, h_all: Optional[jax.Array] = None,
            drop_all: Optional[jax.Array] = None, eval_bank=None,
            eval_every: Optional[int] = None,
            chunk_size: Optional[int] = None,
            chunk_store=None) -> RolloutReport:
        """(The uninstrumented body of :meth:`run`.)  Execute every scenario of ``grid`` for ``num_rounds`` rounds.

        ``global_params``: the shared initial model (broadcast to every
        lane, never donated).  ``sp``: base SystemParams — each lane
        overrides ``energy_budget`` (scaled) and ``sample_count`` from
        the grid.  ``bank``: the shared read-only ClientBank (single or
        tiered).  ``lr_seq``: ``[T]`` learning rates shared across
        scenarios.  ``h_all``: optional precomputed ``[S, T, N]`` channel
        tensor (defaults to :meth:`sample_channels` from the grid's
        seeds/statistics — stationary or Gilbert-Elliott per the grid's
        ``chan_mode`` column).  ``drop_all``: optional precomputed
        ``[S, T, N]`` alive mask (defaults to :meth:`sample_dropout`
        when any lane has ``dropout > 0``; an all-zero dropout column
        builds the exact historical no-dropout executable).

        ``eval_bank``: optional :class:`repro.sim.eval.EvalBank` — the
        final ``[S, ...]`` params are evaluated in ONE vmapped dispatch
        and land as ``test_*`` columns in ``RolloutReport.final_metrics``
        (closing the accuracy half of the Sec.-VII trade-off on device).
        ``eval_every``: additionally evaluate INSIDE the rollout
        executable every that many rounds (``test_*`` per-round columns
        in ``metrics`` — a step curve holding the latest evaluation; the
        model trajectory is unchanged).

        ``chunk_size`` (defaulting to the arena's ``chunk_size``)
        switches every group onto the streaming pipeline: the T-round
        scan becomes ``ceil(T / chunk_size)`` segments whose (params,
        queues, rng, last-eval) carry is donated between chunks, with
        host reduction of each chunk's metric columns overlapped with
        the next chunk's device execution (a bounded ``in_flight``
        dispatch-ahead window — never a ``block_until_ready`` between
        chunks).  The chunked trajectory is bitwise-identical to the
        monolithic scan in every ``k_mode`` (the carry — including the
        per-round PRNG split chain and the EvalBank last-eval — threads
        across boundaries unchanged, and the traced global round offset
        keeps ``eval_every`` firing on the same rounds).  ``chunk_store``
        (see ``repro.sim.service``) additionally persists the carry +
        reduced columns at chunk boundaries so an interrupted run
        resumes bit-identically.

        A mixed-K grid runs as ONE padded-``K_max`` executable by
        default (``k_mode='pad'``; ``'group'`` restores one program per
        distinct K).  ``RolloutReport.meta`` records the execution shape
        — ``k_groups``, per-run ``dispatches``, ``executables_built``
        (compiles triggered by this call) and ``executables_cached`` —
        so callers can assert "one executable" instead of inferring it
        from timing.  Returns a :class:`RolloutReport`; lane ``s``
        reproduces — bit-identically for the model trajectory
        (params/loss/selected/wall_time, leaf-chunked aggregation path),
        to f32 resolution for the queue/energy diagnostics —::

            engine.run_scan(global_params,
                            grid.scenario_system_params(sp, s), bank,
                            h_all[s], lr_seq, rng=scenario_keys(grid)[1][s],
                            policy=grid.controller_names()[s],
                            V=grid.V[s], lam=grid.lam[s],
                            drop_seq=drop_all[s])  # when dropout is on
        """
        s = len(grid)
        # same invariant (and message) as construction-time validation —
        # one source of truth for K <= N
        ScenarioGrid._check_sample_counts(grid.sample_count,
                                          sp.num_devices)
        if eval_every is not None and eval_bank is None:
            raise ValueError("eval_every requires an eval_bank")
        lr_seq = np.asarray(lr_seq, np.float32)
        if lr_seq.shape != (num_rounds,):
            raise ValueError(f"lr_seq must have shape ({num_rounds},), "
                             f"got {lr_seq.shape}")
        h_derived = h_all is None
        if h_derived:
            h_all = self.sample_channels(grid, num_rounds, sp.num_devices)
        h_all = jnp.asarray(h_all)
        if h_all.shape != (s, num_rounds, sp.num_devices):
            raise ValueError(
                f"h_all must have shape {(s, num_rounds, sp.num_devices)},"
                f" got {h_all.shape}")
        if chunk_size is None:
            chunk_size = self.chunk_size
        h_digest = None
        if chunk_store is not None:
            # checkpoint tags must identify the trajectory across
            # processes: an arena-derived channel tensor is a pure
            # function of (grid, T, N) already in the tag; a caller-
            # provided one is hashed by content (one host transfer, paid
            # only when checkpointing)
            h_digest = ("auto" if h_derived else hashlib.sha1(
                np.ascontiguousarray(np.asarray(h_all, np.float32))
                .tobytes()).hexdigest())

        if drop_all is None and np.any(np.asarray(grid.dropout) > 0.0):
            drop_all = self.sample_dropout(grid, num_rounds,
                                           sp.num_devices)
        if drop_all is not None:
            drop_all = jnp.asarray(drop_all, jnp.float32)
            if drop_all.shape != (s, num_rounds, sp.num_devices):
                raise ValueError(
                    "drop_all must have shape "
                    f"{(s, num_rounds, sp.num_devices)}, "
                    f"got {drop_all.shape}")

        ks = np.unique(grid.sample_count)
        k_max = int(ks.max())
        bank_nbytes = getattr(bank, "nbytes", None)
        meta = dict(k_mode=self.k_mode, k_groups=[int(k) for k in ks],
                    k_max=k_max, batch=self.batch, shards=self._shards(),
                    chunk_size=(None if chunk_size is None
                                else int(chunk_size)),
                    in_flight=self.in_flight,
                    # scale-plane accounting: the memory claim as a
                    # tracked number on every report (None for duck-typed
                    # banks predating it)
                    bank_storage=getattr(bank, "storage", "fp32"),
                    bank_nbytes=(None if bank_nbytes is None
                                 else int(bank_nbytes)),
                    bank_bytes_per_client=getattr(bank, "bytes_per_client",
                                                  None))
        if self.k_mode == "auto":
            # shape-adaptive dispatch: plan at the ONE-run horizon — a
            # cold arena collapses toward the padded single bucket, a
            # warmed arena's cached steady buckets win through is_cached
            with obs.span("arena.plan", k_mode="auto", lanes=s,
                          k_max=k_max):
                plan = self._plan(sp, bank, grid, num_rounds, h_all,
                                  runs=1.0,
                                  eval_key=self._eval_key(eval_bank,
                                                          eval_every),
                                  use_dropout=drop_all is not None)
            params, queues, metrics, built, bucket_meta = self._run_plan(
                global_params, sp, bank, grid, h_all, lr_seq, plan,
                eval_bank=eval_bank, eval_every=eval_every,
                chunk_size=chunk_size, chunk_store=chunk_store,
                h_digest=h_digest, drop_all=drop_all)
            meta.update(dispatches=sum(b["dispatches"]
                                       for b in bucket_meta),
                        executables_built=built,
                        executables_cached=len(self._fns),
                        plan=plan.describe(), buckets=bucket_meta)
            return RolloutReport(
                grid=grid, num_rounds=num_rounds, params=params,
                queues=queues, metrics=metrics, meta=meta,
                final_metrics=self._final_eval(eval_bank, params))
        if self.k_mode == "pad" or ks.size == 1:
            # padded-K fusion: the whole grid — mixed K included — is ONE
            # executable (K_max slots per lane, each lane's true K traced
            # as data) and one dispatch per rollout chunk
            with obs.span("arena.plan", k_mode="pad", lanes=s,
                          k_max=k_max):
                plan = DispatchPlan.padded(grid.sample_count)
            params, queues, metrics, built, nd = self._run_group(
                global_params, sp, bank, grid, h_all, lr_seq,
                k_max=k_max, eval_bank=eval_bank, eval_every=eval_every,
                chunk_size=chunk_size, chunk_store=chunk_store,
                h_digest=h_digest, drop_all=drop_all)
            meta.update(dispatches=int(nd), executables_built=int(built),
                        executables_cached=len(self._fns),
                        plan=plan.describe(),
                        buckets=[dict(lanes=list(range(s)), k_pad=k_max,
                                      tiers=None, dispatches=int(nd),
                                      executables_built=int(built))])
            return RolloutReport(
                grid=grid, num_rounds=num_rounds, params=params,
                queues=np.asarray(queues), metrics=metrics, meta=meta,
                final_metrics=self._final_eval(eval_bank, params))
        # Legacy mixed-K grouping: K shapes the per-round selection, so
        # each distinct K runs as its own jitted group and the lanes are
        # scattered back into grid order ("selected" right-pads to max K).
        with obs.span("arena.plan", k_mode="group", lanes=s,
                      k_max=k_max):
            plan = DispatchPlan.grouped(grid.sample_count)
        lane_params = [None] * s
        queues_all = np.zeros((s, sp.num_devices), np.float32)
        metrics: Dict[str, np.ndarray] = {}
        built_total = 0
        nd_total = 0
        bucket_meta = []
        for k in ks:
            idx = np.flatnonzero(grid.sample_count == k)
            sub = grid.take(idx)
            params_g, queues_g, outs_g, built, nd = self._run_group(
                global_params, sp, bank, sub, h_all[jnp.asarray(idx)],
                lr_seq, eval_bank=eval_bank, eval_every=eval_every,
                chunk_size=chunk_size, chunk_store=chunk_store,
                h_digest=h_digest,
                drop_all=(None if drop_all is None
                          else drop_all[jnp.asarray(idx)]))
            built_total += int(built)
            nd_total += int(nd)
            bucket_meta.append(dict(
                lanes=[int(i) for i in idx], k_pad=int(k), tiers=None,
                dispatches=int(nd), executables_built=int(built)))
            queues_all[idx] = np.asarray(queues_g)
            for j, lane in enumerate(idx):
                lane_params[lane] = jax.tree_util.tree_map(
                    lambda a, j=j: a[j], params_g)
            for name, v in outs_g.items():
                v = np.asarray(v)
                if name == "selected" and v.shape[-1] < k_max:
                    pad = np.full(v.shape[:-1] + (k_max - v.shape[-1],),
                                  -1, v.dtype)
                    v = np.concatenate([v, pad], axis=-1)
                if name not in metrics:
                    metrics[name] = np.zeros((s,) + v.shape[1:], v.dtype)
                metrics[name][idx] = v
        params = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                        *lane_params)
        meta.update(dispatches=nd_total,
                    executables_built=built_total,
                    executables_cached=len(self._fns),
                    plan=plan.describe(),
                    buckets=bucket_meta)
        return RolloutReport(grid=grid, num_rounds=num_rounds,
                             params=params, queues=queues_all,
                             metrics=metrics, meta=meta,
                             final_metrics=self._final_eval(eval_bank,
                                                            params))

    def _final_eval(self, eval_bank, params_stacked) -> Dict[str, Any]:
        """One vmapped ``task.metrics`` dispatch over the final ``[S,
        ...]`` params — the batched replacement for the host-side
        per-lane evaluation loop."""
        if eval_bank is None:
            return {}
        with obs.span("arena.eval", what="final"):
            return {"test_" + name: v for name, v in
                    eval_bank.evaluate_stacked(params_stacked).items()}

    def warmup(self, global_params: PyTree, sp: sm.SystemParams, bank,
               grid: ScenarioGrid, num_rounds: int,
               lr_seq=None, *, h_all: Optional[jax.Array] = None,
               eval_bank=None, eval_every: Optional[int] = None,
               chunk_size: Optional[int] = None) -> dict:
        """Compile EVERY executable a same-shape :meth:`run` will hit,
        so iterating on grid VALUES (the V/lam/seed/channel sweep
        workflow — shapes fixed, data varying) never traces or compiles
        again.  The warmed set is a full :class:`DispatchPlan` per the
        arena's ``k_mode`` — the padded single bucket, every per-K
        group, or (``'auto'``) the STEADY-STATE plan (``runs=inf``
        horizon: the signature-split buckets a warmed arena's runs
        snap to via the cache-aware cost model), each bucket warmed
        individually.

        Every bucket is compiled ahead of time
        (``jit(...).lower(...).compile()`` from shapes — nothing runs on
        the device, and the compile fills the jit call cache), so a
        compile failure surfaces here, before any real run.  Nothing
        observable changes — the arena holds no rollout state, the bank
        is read-only, params are never donated.  Returns
        ``{'executables_built', 'executables_cached', 'traces',
        'plan'}`` for the zero-retrace assertion; subsequent same-shape
        runs keep ``self.traces`` constant.

        ``chunk_size`` (defaulting to the arena's) additionally warms
        the streaming pipeline's executables: the start program at the
        first segment length plus the resume program at every distinct
        later segment length (a ragged tail is a second shape) — so a
        warmed chunked ``run`` keeps ``self.traces`` constant too.

        Warmup is also the :class:`~repro.obs.watchdog.Watchdog` arming
        point: an attached watchdog snapshots the trace counter and the
        executable-cache keys when warmup finishes, and every later
        :meth:`run` is checked against that baseline.
        """
        warm_span = obs.span("arena.warmup", k_mode=self.k_mode,
                             lanes=len(grid), rounds=int(num_rounds))
        warm_span.__enter__()
        before = self.traces
        if lr_seq is None:
            lr_seq = np.zeros(num_rounds, np.float32)
        if h_all is None:
            h_all = self.sample_channels(grid, num_rounds,
                                         sp.num_devices)
        h_all = jnp.asarray(h_all)
        drop_all = None
        if np.any(np.asarray(grid.dropout) > 0.0):
            drop_all = self.sample_dropout(grid, num_rounds,
                                           sp.num_devices)
        if chunk_size is None:
            chunk_size = self.chunk_size
        ek = self._eval_key(eval_bank, eval_every)
        if self.k_mode == "auto":
            plan = self._plan(sp, bank, grid, num_rounds, h_all,
                              runs=math.inf, eval_key=ek,
                              use_dropout=drop_all is not None)
        elif self.k_mode == "group":
            plan = DispatchPlan.grouped(grid.sample_count)
        else:
            plan = DispatchPlan.padded(grid.sample_count)
        _, _, _, built, _ = self._run_plan(
            global_params, sp, bank, grid, h_all, lr_seq, plan,
            eval_bank=eval_bank, eval_every=eval_every,
            warm_aot=True, chunk_size=chunk_size, drop_all=drop_all)
        if eval_bank is not None:
            eval_bank.aot_warm(len(grid), global_params)
        result = {"executables_built": built,
                  "executables_cached": len(self._fns),
                  "traces": self.traces - before,
                  "plan": plan.describe()}
        warm_span.set(executables_built=int(built))
        warm_span.__exit__(None, None, None)
        if self.watchdog is not None:
            self.watchdog.arm(self)
        return result
