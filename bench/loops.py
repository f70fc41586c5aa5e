"""The two closed loops the cells drive, and the check of what they made.

* :class:`ArenaRun` — a policy sweeper: ``repro.sim.Arena.run`` over the
  seven controllers x {iid, markov} channels, one rollout after another.
* :class:`TrainerRun` — a fleet trainer:
  ``repro.fl.FederatedTrainer.run_round`` under the LROA controller, one
  round after another.

Each builds the program's objects once in :meth:`setup`, warms every
shape the window uses, and drives the same objects through the first
steps that :meth:`check` later compares with the plain reference
(``bench.reference``).  :meth:`window` then drives them again for the
measured seconds.
"""

from __future__ import annotations

import sys
import time
from itertools import combinations
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import data as bdata
from bench import models
from bench import reference as ref
from bench.flops import train_flops, forward_flops


def _log(what: str, t0: float) -> float:
    """One line of set-up timing on standard error; returns the time."""
    t = time.perf_counter()
    print(f"setup {what}: {t - t0:.3f} s", file=sys.stderr, flush=True)
    return t


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _leaves(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float64) for k, v in tree.items()}


def norm_gap(got: dict, want: dict, base: dict) -> float:
    """Worst leaf of ``| ||got - base|| - ||want - base|| |`` over
    ``max(||want_leaf - base_leaf||, median leaf's)``.  Leaves that the
    reference moves by under a thousandth of the median leaf's change
    are left out (they move by round-off alone)."""
    want, base = _leaves(want), _leaves(base)
    moved = {k: np.linalg.norm(want[k] - base[k]) for k in want}
    med = float(np.median(list(moved.values())))
    worst = 0.0
    for k, m in moved.items():
        if m < 1e-3 * med:
            continue
        g = np.linalg.norm(np.asarray(got[k], np.float64) - base[k])
        worst = max(worst, abs(g - m) / max(m, med))
    return float(worst)


def rel(a, b) -> float:
    """Largest ``|a - b| / |b|`` over the entries (floor 1e-30)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def system_params(cfg: dict, sizes: np.ndarray):
    """The program's ``SystemParams`` for this configuration."""
    from repro.core.system_model import SystemParams
    s, n = cfg["system"], cfg["num_devices"]
    ones = np.ones((n,), np.float32)
    return SystemParams(
        num_devices=n, sample_count=cfg["sample_count"],
        local_epochs=cfg["local_epochs"], bandwidth_hz=s["bandwidth_hz"],
        noise_power=s["noise_power"],
        model_bits=float(s["param_bits"]) * float(s["model_params"]),
        download_rate=s["download_rate"],
        cycles_per_sample=s["cycles_per_sample"] * ones,
        data_sizes=np.asarray(sizes, np.float32),
        capacitance=s["capacitance"] * ones,
        energy_budget=s["energy_budget"] * ones,
        f_min=s["f_min"] * ones, f_max=s["f_max"] * ones,
        p_min=s["p_min"] * ones, p_max=s["p_max"] * ones)


class _Run:
    """What both loops share: the testbed, the model, the weights."""

    def __init__(self, cfg: dict, cell: dict, seed: int):
        self.cfg, self.cell, self.seed = cfg, cell, seed
        self.units = 0          # scenario-rounds or rounds completed
        self.failures = 0       # of those, the ones with a non-finite loss
        self.round_s: List[float] = []

    def _build_common(self):
        from repro.fl import ClientConfig
        cfg = self.cfg
        net = models.of(cfg)
        self.bed = bdata.make_testbed(cfg, self.seed)
        self.sizes = self.bed.sizes
        self.sp = system_params(cfg, self.sizes)
        self.task = net.Task.of(cfg)
        self.ccfg = ClientConfig(local_epochs=cfg["local_epochs"],
                                 batch_size=cfg["batch_size"],
                                 momentum=cfg["momentum"])
        self.params0 = net.init_params(
            jax.random.PRNGKey(bdata.derived_seed(self.seed, 0)), cfg)

    def window_failures(self) -> int:
        return self.failures

    def useful_flops(self, selected_rounds, eval_rows: int) -> float:
        """3 x forward FLOPs per true example trained (E x floor(n/B) x B
        per selected client) plus 1 x forward per evaluated row."""
        cfg, bs, e = self.cfg, self.cfg["batch_size"], self.cfg["local_epochs"]
        rows = 0
        for sel in selected_rounds:
            for c in np.asarray(sel).ravel():
                if c >= 0:
                    rows += e * max(int(self.sizes[c]) // bs, 1) * bs
        fwd = forward_flops(cfg)
        return float(train_flops(cfg) * rows + fwd * eval_rows)


# -- the reference's round ----------------------------------------------------

def reference_round(model, sysm, cfg, name, h, queues, params, sel, lr,
                    keys):
    """One round of decision rule ``name`` by the reference, trained on
    the program's selection ``sel``: (params, mean loss, queues, q)."""
    hyper, k = cfg["hyper"], cfg["sample_count"]
    f, p, q = ref.decide(name, sysm, h, queues, hyper["V"], hyper["lam"],
                         cfg["solver"])
    coeffs = sysm.w[sel] / (k * q[sel])
    params, loss = model.round(params, sel, coeffs, lr, keys)
    return params, loss, ref.queue_step(sysm, queues, h, f, p, q), q


# -- the arena ----------------------------------------------------------------

#: distinct grids a sweeper's rollouts cycle through: more than the
#: arena's input caches hold (16), so every rollout uploads its own
GRID_POOL = 32


class ArenaRun(_Run):
    unit = "scenario-rounds"

    def setup(self):
        from repro.fl import RoundEngine
        from repro.obs.watchdog import Watchdog
        from repro.sim import Arena, EvalBank
        cfg, cell = self.cfg, self.cell
        t = time.perf_counter()
        self._build_common()
        t = _log("testbed and weights", t)
        self.rounds = cfg["rounds"]
        self.engine = RoundEngine(self.task, self.ccfg)
        self.bank = self.engine.make_bank(self.bed.clients, tiered="auto")
        t = _log("bank", t)
        self.grids = [self._grid(r) for r in range(GRID_POOL)]
        self.grid, self.h_all = self.grids[0]
        self.names = self.grid.controller_names()
        self.modes = [cell["channel_modes"][int(m)]
                      for m in self.grid.chan_mode]
        self.lr_seq = np.full((self.rounds,), cfg["lr"], np.float32)
        n_eval = cfg["eval_examples"]
        self.eval_bank = EvalBank(self.task, self.bed.test_x[:n_eval],
                                  self.bed.test_y[:n_eval])
        self.arena = Arena(self.engine, k_mode=cell["k_mode"],
                           chunk_size=cell["chunk_size"])
        self.watchdog = Watchdog(strict=True).attach(self.arena)
        self.kw = dict(eval_bank=self.eval_bank,
                       eval_every=cell["eval_every"])
        self.arena.warmup(self.params0, self.sp, self.bank, self.grid,
                          self.rounds, self.lr_seq, h_all=self.h_all,
                          **self.kw)
        t = _log("arena warmup", t)
        self.next = 0
        self.first = self._rollout()
        t = _log("first rollout", t)
        self.first_metrics = {k: np.asarray(v)
                              for k, v in self.first.metrics.items()}
        self.first_params = [self.first.scenario_params(s)
                             for s in range(len(self.grid))]

    def _grid(self, r: int):
        """Rollout ``r``'s grid and gains: the seven controllers x the
        channel modes, with lane seeds and gains drawn from the layout
        seed and ``r``, the same sequence for every ``--seed``."""
        from repro.core import POLICIES
        from repro.sim import ScenarioGrid
        cfg, cell = self.cfg, self.cell
        ch, base = cfg["channel"], cfg["layout_seed"]
        per = cell["seeds_per_lane"]
        lane_seeds = tuple(bdata.derived_seed(base, 1000 + r * per + i)
                           for i in range(per))
        grid = ScenarioGrid.product(
            controllers=tuple(POLICIES), seeds=lane_seeds,
            V=(cfg["hyper"]["V"],), lam=(cfg["hyper"]["lam"],),
            sample_count=(cfg["sample_count"],),
            chan_mode=tuple(cell["channel_modes"]), p_gb=(ch["p_gb"],),
            p_bg=(ch["p_bg"],), num_devices=cfg["num_devices"])
        modes = [cell["channel_modes"][int(m)] for m in grid.chan_mode]
        h_all = bdata.channel_gains(cfg, modes, cfg["rounds"],
                                    bdata.derived_seed(base, 5000 + r))
        return grid, h_all

    def _rollout(self):
        grid, h_all = self.grids[self.next % GRID_POOL]
        self.next += 1
        rep = self.arena.run(self.params0, self.sp, self.bank, grid,
                             self.rounds, self.lr_seq, h_all=h_all,
                             **self.kw)
        jax.block_until_ready(rep.params)
        return rep

    def window(self, seconds: float, max_units=None):
        t0 = time.perf_counter()
        per = len(self.grid) * self.rounds
        self.selected_rounds = []
        evals = 0
        stamps = [t0]
        while time.perf_counter() - t0 < seconds:
            rep = self._rollout()
            stamps.append(time.perf_counter())
            self.units += per
            self.selected_rounds.append(np.asarray(rep.metrics["selected"]))
            self.failures += int(np.sum(~np.isfinite(
                np.asarray(rep.metrics["loss"]))))
            evals += len(self.grid) * (1 + self.rounds
                                       // self.cell["eval_every"])
            if max_units is not None and self.units >= max_units:
                break
        self.window_s = time.perf_counter() - t0
        self.eval_rows = evals * self.cfg["eval_examples"]
        took = np.diff(stamps) * 1e3
        print(f"window: {len(took)} rollouts in {self.window_s:.3f} s, "
              f"each {took.min():.1f} / {np.median(took):.1f} / "
              f"{took.max():.1f} ms (min / median / max)",
              file=sys.stderr)
        return {"arena_scenario_rounds_per_s": self.units / self.window_s}

    def free(self):
        del self.arena, self.bank, self.engine, self.first, self.eval_bank

    def reference(self, dtype=jnp.float32) -> List[dict]:
        """Each lane of the set-up rollout, recomputed by the reference
        from the same inputs, with the program's selections checked and
        then trained on: per round the loss and the evaluation column,
        the final params, and the selection gap."""
        cfg, cell = self.cfg, self.cell
        model = ref.ModelRef(cfg, self.bed.clients, dtype)
        k, n = cfg["sample_count"], cfg["num_devices"]
        n_eval, every = cfg["eval_examples"], cell["eval_every"]
        tx, ty = self.bed.test_x[:n_eval], self.bed.test_y[:n_eval]
        ev0 = model.test_loss(self.params0, tx, ty)
        lanes = []
        for s in range(len(self.grid)):
            name = self.names[s]
            sysm = ref.System(cfg, self.sizes)
            queues, params, ev = np.zeros(n), self.params0, ev0
            _, rng = jax.random.split(
                jax.random.PRNGKey(int(self.grid.seed[s])))
            lane = dict(loss=[], test_loss=[], sel_gap=0.0,
                        greedy=name == "divfl")
            for t in range(self.rounds):
                h = np.asarray(self.h_all[s, t], np.float64)
                rng, k_sel, k_cli = jax.random.split(rng, 3)
                sel = np.asarray(self.first_metrics["selected"][s, t],
                                 np.int64)
                keys = [jax.random.fold_in(k_cli, i) for i in range(k)]
                params, loss, queues_next, q = reference_round(
                    model, sysm, cfg, name, h, queues, params, sel,
                    float(self.lr_seq[t]), keys)
                lane["sel_gap"] = max(lane["sel_gap"], _selection_gap(
                    name, sysm, h, q, sel, k_sel, t, k, n))
                queues = queues_next
                if (t + 1) % every == 0:
                    ev = model.test_loss(params, tx, ty)
                lane["loss"].append(loss)
                lane["test_loss"].append(ev)
            lane["params"] = params
            lanes.append(lane)
        return lanes

    def program(self) -> List[dict]:
        """The same quantities as the set-up rollout produced them."""
        m = self.first_metrics
        return [dict(loss=m["loss"][s], test_loss=m["test_loss"][s],
                     params=self.first_params[s])
                for s in range(len(self.grid))]

    def compare(self, got: List[dict], want: List[dict]) -> Dict[str, float]:
        out = dict(sel_gap=0.0, greedy_gap=0.0, loss_rel=0.0, chg_gap=0.0,
                   eval_rel=0.0)
        for g, w in zip(got, want):
            gap = "greedy_gap" if w["greedy"] else "sel_gap"
            out[gap] = max(out[gap], w["sel_gap"])
            out["loss_rel"] = max(out["loss_rel"], rel(g["loss"], w["loss"]))
            out["chg_gap"] = max(out["chg_gap"], norm_gap(
                g["params"], w["params"], self.params0))
            out["eval_rel"] = max(out["eval_rel"],
                                  rel(g["test_loss"], w["test_loss"]))
        return out


def _selection_gap(name, sysm, h, q, sel, k_sel, t, k, n) -> float:
    if name == "round_robin":
        want = (t * k + np.arange(k)) % n
        return 0.0 if np.array_equal(sel, want) else 1.0
    if name == "divfl":
        return ref.divfl_gaps(sysm, h, sel)
    gap = 0.0
    for i, c in enumerate(sel):
        u = 1.0 - float(jax.random.uniform(jax.random.fold_in(k_sel, i), ()))
        gap = max(gap, ref.cdf_gap(q, u, int(c), "left"))
    return gap


# -- the trainer --------------------------------------------------------------

class _Channel:
    """The trainer's channel: the benchmark's gains, one round per call."""

    def __init__(self, cfg, seed):
        self.cfg, self.seed, self.t = cfg, seed, 0
        self.block = np.zeros((0, cfg["num_devices"]), np.float32)
        self.seen: List[np.ndarray] = []

    def sample(self):
        if self.t >= len(self.block):
            more = bdata.channel_gains(self.cfg, ["iid"], 256,
                                       self.seed + 7919 * (self.t + 1))[0]
            self.block = np.concatenate([self.block, more])
        h = self.block[self.t]
        self.t += 1
        if len(self.seen) < 8:
            self.seen.append(h)
        return h


class TrainerRun(_Run):
    unit = "rounds"

    def setup(self):
        from repro.core import LROAController
        from repro.core.controller import LROAHyperParams
        from repro.fl import FederatedTrainer
        cfg, cell = self.cfg, self.cell
        t = time.perf_counter()
        self._build_common()
        t = _log("testbed and weights", t)
        hyper = cfg["hyper"]
        hp = LROAHyperParams(lam=hyper["lam"], V=hyper["V"], lam0=0.0,
                             V0=0.0, mu=0.0, nu=0.0)
        # the gains and the sampler's stream come from the layout, so every
        # seed runs the same selections over the same client sizes: the
        # same work, on its own data and weights
        self.tseed = bdata.derived_seed(cfg["layout_seed"], 1)
        self.channel = _Channel(cfg, cfg["layout_seed"])
        n_eval = cell["eval_examples"]
        lr = cfg["lr"]
        self.trainer = FederatedTrainer(
            self.task, self.sp, LROAController(self.sp, hp), self.channel,
            self.bed.clients, self.ccfg, lambda t: lr,
            test_data=(self.bed.test_x[:n_eval], self.bed.test_y[:n_eval]),
            eval_every=cell["eval_every"], seed=self.tseed)
        self.trainer.global_params = _copy(self.params0)
        t = _log("trainer and bank", t)
        self._warm_tier_subsets()
        self.trainer.warmup()
        self._warm_round_time()
        t = _log("warmup", t)
        jax.block_until_ready(self.trainer.global_params)
        self.records = []
        self.snap = {}
        for i in range(cell["checked_rounds"]):
            self.records.append(self._round())
            if i in (0, cell["checked_rounds"] - 1):
                self.snap[i] = _copy(self.trainer.global_params)
        jax.block_until_ready(self.snap)
        _log("checked rounds", t)

    def _warm_tier_subsets(self):
        """Compile the round executable of every set of tiers a selection
        can hit (the trainer's own warmup covers single tiers and all
        tiers at once), on a copy of the params with zero weights."""
        from repro.fl.client_bank import TieredClientBank
        bank, engine = self.trainer.bank, self.trainer.engine
        if not isinstance(bank, TieredClientBank) or bank.num_tiers < 3:
            return
        k = self.cfg["sample_count"]
        reps = [int(m[0]) for m in bank.tier_members]
        p = _copy(self.trainer.global_params)
        rng = jax.random.split(jax.random.PRNGKey(0), k)
        for size in range(2, bank.num_tiers):
            for subset in combinations(reps, size):
                sel = np.asarray([subset[i % size] for i in range(k)])
                p, _ = engine.round_step(p, bank, sel,
                                         np.zeros(k, np.float32), 0.0, rng)
        jax.block_until_ready(p)

    def _warm_round_time(self):
        """The round's realised latency indexes the round times by the
        unique selected clients, a shape that changes with how many
        distinct clients a round draws: compile each of 1..K."""
        from repro.core.controller import realized_round_time
        n = self.cfg["num_devices"]
        h = jnp.full((n,), self.cfg["channel"]["mean_gain"], jnp.float32)
        dec = self.trainer.controller.decide(h)
        for u in range(1, self.cfg["sample_count"] + 1):
            realized_round_time(self.sp, h, dec, np.arange(u))

    def _round(self):
        t = len(self.round_s)
        t0 = time.perf_counter()
        rec = self.trainer.run_round(t)
        jax.block_until_ready(self.trainer.global_params)
        self.round_s.append(time.perf_counter() - t0)
        return rec

    def window(self, seconds: float, max_units=None):
        first = len(self.round_s)
        t0 = time.perf_counter()
        self.selected_rounds = []
        evals = 0
        while time.perf_counter() - t0 < seconds:
            rec = self._round()
            self.units += 1
            self.selected_rounds.append(np.asarray(rec.selected))
            self.failures += int(not np.isfinite(rec.mean_loss))
            if rec.test_accuracy is not None:
                evals += 1
            if max_units is not None and self.units >= max_units:
                break
        self.window_s = time.perf_counter() - t0
        self.eval_rows = evals * self.cell["eval_examples"]
        times = np.asarray(self.round_s[first:]) * 1e3
        self.window_round_ms = times
        slow = np.argsort(times)[-3:][::-1]
        print(f"window: {len(times)} rounds in {self.window_s:.3f} s, "
              f"{evals} with evaluation; round ms min {times.min():.1f}, "
              f"median {np.median(times):.1f}, slowest "
              + ", ".join(f"#{i} {times[i]:.1f}" for i in slow),
              file=sys.stderr)
        return {"trainer_rounds_per_s": self.units / self.window_s,
                "trainer_round_p90_ms": float(np.percentile(
                    times, 90, method="inverted_cdf"))}

    def free(self):
        del self.trainer

    def reference(self, dtype=jnp.float32) -> dict:
        """The first ``checked_rounds`` rounds recomputed by the
        reference from the same gains, draws and keys, with the
        program's selections checked and then trained on."""
        cfg = self.cfg
        model = ref.ModelRef(cfg, self.bed.clients, dtype)
        k, n = cfg["sample_count"], cfg["num_devices"]
        sysm = ref.System(cfg, self.sizes)
        queues, params = np.zeros(n), self.params0
        draws = np.random.default_rng(self.tseed)
        key = jax.random.PRNGKey(self.tseed)
        out = dict(loss=[], sel_gap=0.0)
        for t, rec in enumerate(self.records):
            h = np.asarray(self.channel.seen[t], np.float64)
            sel = np.asarray(rec.selected, np.int64)
            u = draws.random(k)
            keys = []
            for _ in range(k):
                key, sub = jax.random.split(key)
                keys.append(sub)
            params, loss, queues, q = reference_round(
                model, sysm, cfg, "lroa", h, queues, params, sel,
                cfg["lr"], keys)
            for i, c in enumerate(sel):
                out["sel_gap"] = max(out["sel_gap"],
                                     ref.cdf_gap(q, u[i], int(c), "right"))
            out["loss"].append(loss)
            if t == 0:
                out["params_first"] = params
        out["params_last"] = params
        return out

    def program(self) -> dict:
        last = len(self.records) - 1
        return dict(loss=[r.mean_loss for r in self.records],
                    params_first=self.snap[0], params_last=self.snap[last])

    def compare(self, got: dict, want: dict) -> Dict[str, float]:
        return dict(
            sel_gap=want["sel_gap"],
            loss_rel=rel(got["loss"], want["loss"]),
            upd_gap=norm_gap(got["params_first"], want["params_first"],
                             self.params0),
            chg_gap=norm_gap(got["params_last"], want["params_last"],
                             self.params0))


LOOPS = {"arena": ArenaRun, "trainer": TrainerRun}
