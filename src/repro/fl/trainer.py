"""The full FL loop (Algorithm 1) with LROA (or baseline) control, wall-clock
latency and energy accounting, and periodic evaluation.

How a round executes (dataflow)
-------------------------------
All N clients' bucketed data is stacked into a device-resident bank ONCE
at trainer construction — a single-bucket
:class:`~repro.fl.client_bank.ClientBank` for (near-)uniform partitions,
or the bucket-ladder :class:`~repro.fl.client_bank.TieredClientBank` when
the partition spans multiple size tiers (``bank_mode='auto'``; skewed
non-iid splits would otherwise inflate the single global bucket to
``O(N * max_i n_i)`` device rows).  Per round t:
  1. observe channel gains h^t (ChannelProcess)                      [host]
  2. controller decides (f^t, p^t, q^t) — Algorithm 2 for LROA       [jit]
  3. sample K^t draws with replacement by q^t (DivFL selects
     deterministically via facility-location greedy on the shared
     channel-feature similarity)                                     [host]
  4. + 5. the fused fast path (``RoundEngine.round_step``): the K
     selected clients are gathered from the bank *inside* a SINGLE
     jitted computation (``jnp.take`` over the ``[N, B, ...]`` stacks)
     that runs all K local trainings (vmapped E-epoch SGD) and the
     unbiased aggregation (4) (Pallas ``fl_aggregate`` on TPU) — zero
     per-round host->device transfers of client data, one dispatch +
     one loss sync per round.  A tiered bank runs one such fused round
     per tier the selection hits (single-tier selections short-circuit
     to the single-bucket executable).  With a mesh, the client axis is
     shard_mapped over the ``data`` axis (per-shard training + partial
     reduce, cross-shard psum).
  6. queues update; latency += max_{n in K^t} T_n^t (eq. 10), energy
     accrues                                                         [host]

Every controller — DivFL included — rides the fused fast path: DivFL's
selection is a pure function of the round's channel gains (the same
facility-location greedy the arena traces), so no per-client host
round-trip is needed.  ``use_engine=False`` forces the sequential slow
path (one ``local_update`` per client, reading each client's true
examples as a bank slice via ``ClientBank.client_view``) — there DivFL
additionally observes each update vector between trainings, which
enriches its similarity metric from round 1 on (the reference
semantics).  The equivalence tests pin the two paths against each other.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import system_model as sm
from repro.core.baselines import DivFLController
from repro.core.controller import realized_round_time
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.fl.client_bank import ClientBank, TieredClientBank
from repro.fl.environment import ChannelProcess
from repro.fl.round_engine import RoundEngine
from repro.obs import trace as obs_trace

PyTree = Any


@dataclasses.dataclass
class RoundRecord:
    round: int
    wall_time: float          # realised latency of this round (eq. 10)
    cum_time: float
    mean_loss: float
    selected: List[int]
    q_min: float
    q_max: float
    queue_mean: float
    energy_mean: float        # realised mean energy this round
    test_accuracy: Optional[float] = None


@dataclasses.dataclass
class FLRunResult:
    records: List[RoundRecord]
    params: PyTree
    controller_name: str

    @property
    def total_time(self) -> float:
        return self.records[-1].cum_time if self.records else 0.0

    def accuracy_curve(self) -> List[tuple]:
        return [(r.round, r.cum_time, r.test_accuracy)
                for r in self.records if r.test_accuracy is not None]


class FederatedTrainer:
    """Controller-agnostic synchronous FL driver."""

    def __init__(self, task: fl_client.Task, params: sm.SystemParams,
                 controller, channel: ChannelProcess,
                 client_data: Sequence[tuple],
                 client_cfg: fl_client.ClientConfig,
                 lr_schedule: Callable[[jnp.ndarray], jnp.ndarray],
                 test_data: Optional[tuple] = None,
                 eval_every: int = 10, seed: int = 0,
                 use_engine: bool = True,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 bank_mode: str = "auto", bank_storage: str = "fp32"):
        assert len(client_data) == params.num_devices
        self.task = task
        self.params = params
        self.controller = controller
        self.channel = channel
        self.client_cfg = client_cfg
        self.lr_schedule = lr_schedule
        # Pre-convert the test set to device arrays once — evaluate() used to
        # re-upload the full test set on every call.
        self.test_data = (None if test_data is None else
                          (jnp.asarray(test_data[0]),
                           jnp.asarray(test_data[1])))
        self.eval_every = eval_every
        self.use_engine = use_engine
        self.engine = RoundEngine(task, client_cfg, mesh=mesh)
        # The ONE device upload of client data: every round (fused or
        # sequential) reads the bank from here on.  bank_mode 'auto'
        # builds the bucket-ladder TieredClientBank only when the
        # partition spans multiple size tiers; bank_storage 'int8' keeps
        # the rows quantized on device (dequantized inside the fused
        # gather — ~4x clients-per-byte; 'fp32' is the bitwise default).
        self.bank = self.engine.make_bank(client_data, tiered=bank_mode,
                                          storage=bank_storage)
        self._np_rng = np.random.default_rng(seed)
        self._jax_rng = jax.random.PRNGKey(seed)
        self.global_params = task.init(jax.random.PRNGKey(seed + 1))
        self.w = np.asarray(params.data_weights)
        # run_round must work standalone (not only via run()).
        self._records: List[RoundRecord] = []

    @property
    def _fused(self) -> bool:
        """True when rounds run on the fused engine fast path (the single
        eligibility rule shared by run_round, warmup, and run).  Every
        controller is eligible — DivFL's selection is a pure function of
        the round's channel gains, so nothing needs the per-client
        host loop any more."""
        return self.use_engine

    # -- warmup -----------------------------------------------------------

    def warmup(self) -> None:
        """Compile every local-training executable a full run can hit,
        without mutating any trainer state — benchmarks call this so
        steady-state timings exclude jit compilation.

        Fused path, single-bucket bank: ONE executable covers every
        selection (`round_step`'s trace depends only on the bank-wide
        masked/unmasked mode), so one call on a *copy* of the params
        compiles it (donation never touches the live model).  Tiered
        bank: one call per tier compiles each tier's single-bucket
        executable, plus one mixed selection cycling through the tiers
        compiles the tier-loop executable for that hit set; other
        hit-tier subsets (rounds hitting a strict subset of >= 2 tiers)
        still jit on first occurrence — the per-round compile universe is
        bounded by the ladder's rung count, not by the selection.
        Sequential path: one ``local_update`` per distinct post-padding
        data shape (``local_update``'s jit specializes on the array
        shape, not just the step count).  All outputs are discarded.
        """
        rng = jax.random.PRNGKey(0)
        sizes = [int(s) for s in self.bank.sizes]
        bs = self.client_cfg.batch_size
        if self._fused:
            k = self.params.sample_count
            p = jax.tree_util.tree_map(jnp.copy, self.global_params)
            if (isinstance(self.bank, TieredClientBank)
                    and self.bank.num_tiers > 1):
                reps = [int(m[0]) for m in self.bank.tier_members]
                sels = [np.full(k, r, np.int64) for r in reps]
                sels.append(np.asarray([reps[i % len(reps)]
                                        for i in range(k)], np.int64))
            else:
                sels = [np.zeros(k, np.int64)]
            for sel in sels:
                # zero lr/coeffs keep the chained params numerically
                # inert; chaining respects donation off-CPU
                p, _ = self.engine.round_step(
                    p, self.bank, sel, np.zeros(k, np.float32), 0.0,
                    jax.random.split(rng, k))
            jax.block_until_ready(jax.tree_util.tree_leaves(p))
        else:
            seen = set()
            for i, n in enumerate(sizes):
                eff = max(n, bs)   # local_update tiles n < bs up to bs
                if eff in seen:
                    continue
                seen.add(eff)
                x, y = self.bank.client_view(i)
                delta, _ = fl_client.local_update(
                    self.task, self.global_params, x, y, 0.0, rng,
                    self.client_cfg)
                jax.block_until_ready(jax.tree_util.tree_leaves(delta))
        # decide() is pure for every controller and evaluate() only reads
        # state, so warming their executables mutates nothing either
        self.controller.decide(jnp.ones((self.params.num_devices,),
                                        jnp.float32))
        if self.test_data is not None:
            self.evaluate()

    # -- evaluation -------------------------------------------------------

    def evaluate(self) -> float:
        if self.test_data is None:
            return float("nan")
        x, y = self.test_data
        m = self.task.metrics(self.global_params, {"x": x, "y": y})
        return float(m["accuracy"])

    # -- one round --------------------------------------------------------

    def _client_rngs(self, count: int) -> jax.Array:
        """Split the trainer key ``count`` times (same draws as the
        sequential per-client loop, so both paths see identical client
        randomness)."""
        subs = []
        for _ in range(count):
            self._jax_rng, sub = jax.random.split(self._jax_rng)
            subs.append(sub)
        return jnp.stack(subs)

    def _train_fused(self, selected: np.ndarray, coeffs: np.ndarray,
                     lr: float) -> List[float]:
        """Fast path: one fused jit gathers the selected clients from the
        device-resident bank, trains all K, and applies eq. (4)."""
        rngs = self._client_rngs(len(selected))
        self.global_params, losses = self.engine.round_step(
            self.global_params, self.bank, selected, coeffs, lr, rngs)
        return [float(l) for l in np.asarray(losses)]

    def _train_sequential(self, selected: np.ndarray, coeffs: np.ndarray,
                          lr: float) -> List[float]:
        """Slow path: per-client dispatch (DivFL / reference semantics),
        reading each client's true examples as a bank slice."""
        deltas, losses = [], []
        for idx in selected:
            x, y = self.bank.client_view(int(idx))
            self._jax_rng, sub = jax.random.split(self._jax_rng)
            delta, loss = fl_client.local_update(
                self.task, self.global_params, x, y, lr, sub, self.client_cfg)
            deltas.append(delta)
            losses.append(loss)
            if isinstance(self.controller, DivFLController):
                self.controller.observe_updates(
                    np.asarray([idx]),
                    fl_client.flatten_update(delta)[None, :])
        if isinstance(self.controller, DivFLController):
            # DivFL approximates the full update from the diverse subset:
            # plain data-weighted averaging over the chosen clients.
            self.global_params = fl_server.fedavg_reference(
                self.global_params, deltas, self.w[np.asarray(selected)])
        else:
            self.global_params = fl_server.aggregate(
                self.global_params, deltas, coeffs)
        return losses

    def run_round(self, t: int) -> RoundRecord:
        with obs_trace.span("trainer.round", t=int(t)):
            return self._run_round_impl(t)

    def _run_round_impl(self, t: int) -> RoundRecord:
        h = jnp.asarray(self.channel.sample())
        decision = self.controller.decide(h)
        q = np.asarray(decision.q)

        if isinstance(self.controller, DivFLController):
            selected = self.controller.select(h)
        else:
            selected = fl_server.sample_clients(self._np_rng, q,
                                                self.params.sample_count)

        lr = float(self.lr_schedule(jnp.asarray(t)))
        coeffs = fl_server.aggregation_weights(
            selected, q, self.w, self.params.sample_count)
        if self._fused:
            losses = self._train_fused(selected, coeffs, lr)
        else:
            losses = self._train_sequential(selected, coeffs, lr)

        wall = realized_round_time(self.params, h, decision,
                                   np.asarray(selected))
        e_round = np.asarray(sm.round_energy(self.params, h, decision.p,
                                             decision.f))
        self.controller.step_queues(h, decision)

        cum = (self._records[-1].cum_time if self._records else 0.0) + wall
        rec = RoundRecord(
            round=t, wall_time=wall, cum_time=cum,
            mean_loss=float(np.mean(losses)) if losses else float("nan"),
            selected=[int(i) for i in selected],
            q_min=float(q.min()), q_max=float(q.max()),
            queue_mean=float(np.asarray(self.controller.queues).mean()),
            energy_mean=float(e_round[np.unique(selected)].mean()),
        )
        if self.test_data is not None and (t % self.eval_every == 0):
            rec.test_accuracy = self.evaluate()
        self._records.append(rec)
        return rec

    # -- full run ---------------------------------------------------------

    def run(self, num_rounds: int, verbose: bool = False) -> FLRunResult:
        self._records = []
        for t in range(num_rounds):
            rec = self.run_round(t)
            if verbose and (t % max(num_rounds // 10, 1) == 0):
                print(f"[{getattr(self.controller, 'name', '?')}] round {t} "
                      f"loss {rec.mean_loss:.4f} wall {rec.wall_time:.1f}s "
                      f"cum {rec.cum_time:.0f}s acc {rec.test_accuracy}")
        if self.test_data is not None and self._records:
            self._records[-1].test_accuracy = self.evaluate()
        # With buffer donation on (GPU/TPU), any later fused round donates
        # the live global_params buffers, which would invalidate a
        # previously returned result's params — snapshot them so results
        # stay readable.  The sequential path never donates, so it skips
        # the copy.
        params = (jax.tree_util.tree_map(jnp.copy, self.global_params)
                  if self.engine.donate and self._fused
                  else self.global_params)
        return FLRunResult(records=self._records, params=params,
                           controller_name=getattr(self.controller, "name",
                                                   "unknown"))
