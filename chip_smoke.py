#!/usr/bin/env python3
"""Bring-up check: drive the trainer, round engine and scenario arena once
on a TPU at the paper's own scale.

The deployment is the paper's Sec.-VII testbed at full width,
``BenchConfig.paper_scale()`` with K = 8 draws per round: 120 clients, a
Dirichlet-0.5 non-iid split of a synthetic 32x32x3 / 10-class image set,
the CNN client model (545,002 parameters), E = 2 local epochs of batch 16.
Data and weights are made from seeds.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # the two sharded paths, 4 chips

One chip:
  a. build the testbed and the device-resident bank (``make_bank``,
     tiered 'auto');
  b. ``FederatedTrainer``: ``warmup()`` then 3 fused rounds; the compiled
     round must hold the Pallas eq.-(4) kernel (``tpu_custom_call``), the
     kernel must match a float64 sum, and round 1 must match a plain
     reference (per-client ``client.local_update`` + the eq.-(4) sum at
     highest matmul precision);
  c. ``RoundEngine.run_scan`` for 10 rounds (the scenario of arena lane 0);
  d. ``Arena.run`` (``k_mode='auto'``, ``chunk_size=5``, in-scan
     ``EvalBank``) over the 7 controllers x {iid, markov} for 10 rounds,
     after ``Arena.warmup`` under a strict ``Watchdog``: zero retraces,
     finite losses, lane 0 equal to phase c.

Four chips: the client-axis shard_map with its psum (``RoundEngine(mesh=)``
over the N-sharded bank) and the scenario-axis shard_map
(``Arena(mesh=)``), each against the same computation on one chip.

Every phase prints one line with its wall seconds and the XLA compile
seconds inside it.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU the script exits with
code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

K = 8                   # draws per round
ROUNDS = 10             # phases c and d
CHUNK = 5               # arena chunk length: start + resume executables
EVAL_EVERY = 5
EVAL_EXAMPLES = 1024    # test rows the arena evaluates per lane

# Tolerances, fixed before any chip run (reasons in CHANGES.md):
# the Pallas kernel against a float64 sum of the same f32 inputs;
KERNEL_TOL = dict(rtol=1e-5, atol=1e-6)
# round 1 of the trainer (default TPU matmul precision) against the plain
# reference at "highest": ||p - p_ref|| / ||p_ref - p_0||;
ROUND_REL_TOL = 5e-2
# one program against another of the same math (arena lane vs run_scan,
# sharded vs one chip): the same relative model error, per-round losses,
# and the control-plane columns at f32 resolution.
PATH_REL_TOL = 1e-2
LOSS_TOL = dict(rtol=1e-3, atol=1e-5)
CONTROL_TOL = dict(rtol=1e-5, atol=1e-4)


class Phase:
    """Wall seconds of a block and the XLA compile seconds inside it."""

    compile_s = 0.0
    compiles = 0

    @classmethod
    def listen(cls):
        import jax

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += duration
                cls.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name: str):
        self.name = name
        self.info: dict = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.n0 = Phase.compile_s, Phase.compiles
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            line = {"phase": self.name, "wall_s": round(wall, 3),
                    "compile_s": round(Phase.compile_s - self.c0, 3),
                    "compiles": Phase.compiles - self.n0, **self.info}
            print(json.dumps(line), flush=True)
        return False


class Recorder:
    """Wraps a jitted function and keeps the abstract arguments of its
    last call, so the compiled program that ran can be read back."""

    latest = None

    def __init__(self, fn):
        self.fn = fn
        self.args = None

    def __call__(self, *args):
        import jax

        def abstract(a):
            # a one-device array is left for jit to place, as in the call
            if not isinstance(a, jax.Array):
                return a
            spread = len(a.sharding.device_set) > 1
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if spread else None)

        self.args = jax.tree_util.tree_map(abstract, args)
        Recorder.latest = self
        return self.fn(*args)

    def hlo(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()

    @staticmethod
    def install(engine, names=("_build_step", "_build_tiered_step")):
        for name in names:
            build = getattr(engine, name)
            setattr(engine, name,
                    lambda *a, _build=build, **kw: Recorder(_build(*a,
                                                                   **kw)))


def copy_tree(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, tree)


def flat(tree):
    import jax
    import numpy as np
    return np.concatenate([np.asarray(l, np.float64).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def rel_err(got, want, base) -> float:
    """``||got - want|| / ||want - base||``: the model error relative to
    how far training moved the model from ``base``."""
    import numpy as np
    want = flat(want)
    return float(np.linalg.norm(flat(got) - want)
                 / np.linalg.norm(want - flat(base)))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def paper_config():
    from benchmarks.common import BenchConfig
    return dataclasses.replace(BenchConfig.paper_scale(), sample_count=K)


def lr_sequence(cfg, rounds: int):
    import numpy as np
    from repro.optim import paper_step_decay
    sched = paper_step_decay(cfg.lr, cfg.rounds)
    return np.asarray([float(sched(t)) for t in range(rounds)], np.float32)


def zoo_grid(cfg, hp, seeds=(0,)):
    """All 7 controllers x {iid, markov} (x seeds) at the testbed's K."""
    from repro.core import POLICIES
    from repro.sim import ScenarioGrid
    return ScenarioGrid.product(
        controllers=tuple(POLICIES), seeds=tuple(seeds), V=(hp.V,),
        lam=(hp.lam,), sample_count=(cfg.sample_count,),
        chan_mode=("iid", "markov"), p_gb=(0.15,), p_bg=(0.4,),
        num_devices=cfg.num_devices)


def compare_paths(got_params, want_params, base, got_m, want_m,
                  what: str) -> dict:
    """Two programs of the same math: model within ``PATH_REL_TOL``,
    selections equal, losses and control-plane columns within their
    tolerances."""
    import numpy as np
    err = rel_err(got_params, want_params, base)
    check(err <= PATH_REL_TOL, f"{what}: model rel err {err:.3e} > "
                               f"{PATH_REL_TOL:.0e}")
    np.testing.assert_array_equal(got_m["selected"], want_m["selected"],
                                  err_msg=f"{what}: selections")
    np.testing.assert_allclose(got_m["loss"], want_m["loss"], **LOSS_TOL,
                               err_msg=f"{what}: losses")
    for name in ("wall_time", "energy_mean", "queue_mean", "q_min",
                 "q_max"):
        np.testing.assert_allclose(got_m[name], want_m[name],
                                   **CONTROL_TOL, err_msg=f"{what}: {name}")
    return {"model_rel_err": err}


# -- one chip -----------------------------------------------------------------

def phase_bank(cfg):
    """a. testbed + device-resident bank through ``RoundEngine.make_bank``."""
    import jax
    from benchmarks.common import build_testbed
    from repro.fl import ClientConfig, RoundEngine

    with Phase("a_bank") as ph:
        sp, task, client_data, test = build_testbed(cfg)
        engine = RoundEngine(task, ClientConfig(
            local_epochs=cfg.local_epochs, batch_size=cfg.batch_size))
        bank = engine.make_bank(client_data, tiered="auto")
        jax.block_until_ready(bank.tier_of_device
                              if hasattr(bank, "tiers") else bank.xs)
        tiers = ([t.bucket_examples for t in bank.tiers]
                 if hasattr(bank, "tiers") else [bank.bucket_examples])
        ph.info.update(clients=int(bank.num_clients), tier_buckets=tiers,
                       true_examples=int(bank.true_examples),
                       padded_examples=int(bank.padded_examples),
                       bank_nbytes=int(bank.nbytes),
                       bytes_per_client=float(bank.bytes_per_client))
    return sp, task, client_data, test, engine, bank


def phase_trainer(cfg, sp, task, client_data, test, kernel_expected: bool):
    """b. the trainer's fused rounds, the kernel inside them, and round 1
    against the plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import LROAController, estimate_hyperparams
    from repro.fl import (ChannelConfig, ChannelProcess, ClientConfig,
                          FederatedTrainer)
    from repro.fl import client as fl_client
    from repro.kernels import fl_aggregate
    from repro.optim import paper_step_decay

    with Phase("b_trainer") as ph:
        hp = estimate_hyperparams(sp, 0.1, loss_scale=1.5, mu=cfg.mu,
                                  nu=cfg.nu)
        ccfg = ClientConfig(local_epochs=cfg.local_epochs,
                            batch_size=cfg.batch_size)
        trainer = FederatedTrainer(
            task, sp, LROAController(sp, hp),
            ChannelProcess(cfg.num_devices, ChannelConfig(seed=cfg.seed)),
            client_data, ccfg, paper_step_decay(cfg.lr, cfg.rounds),
            test_data=test, eval_every=cfg.rounds, seed=cfg.seed)
        engine = trainer.engine
        Recorder.install(engine)
        calls = []
        round_step = engine.round_step

        def recording_round_step(params, bank, selected, coeffs, lr, rngs,
                                 **kw):
            calls.append((np.asarray(selected), np.asarray(coeffs),
                          float(lr), rngs))
            return round_step(params, bank, selected, coeffs, lr, rngs,
                              **kw)

        engine.round_step = recording_round_step

        t0 = time.perf_counter()
        c0 = Phase.compile_s
        trainer.warmup()
        ph.info["warmup_s"] = round(time.perf_counter() - t0, 3)
        ph.info["warmup_compile_s"] = round(Phase.compile_s - c0, 3)
        calls.clear()
        p0 = copy_tree(trainer.global_params)    # round 1 donates these
        recs = []
        t0 = time.perf_counter()
        c0 = Phase.compile_s
        for t in range(3):
            recs.append(trainer.run_round(t))
            if t == 0:
                p1 = copy_tree(trainer.global_params)
                round1 = Recorder.latest
                selected, coeffs, lr, rngs = calls[0]
        jax.block_until_ready(trainer.global_params)
        ph.info["rounds_s"] = round(time.perf_counter() - t0, 3)
        ph.info["rounds_compile_s"] = round(Phase.compile_s - c0, 3)
        losses = [r.mean_loss for r in recs]
        check(all(np.isfinite(losses)), f"non-finite losses {losses}")
        ph.info["losses"] = losses
        ph.info["selected_round1"] = selected.tolist()

        hlo = round1.hlo()
        ph.info["kernel_in_round"] = "tpu_custom_call" in hlo
        check(ph.info["kernel_in_round"] == kernel_expected,
              f"compiled round holds the Pallas kernel: "
              f"{ph.info['kernel_in_round']}, expected {kernel_expected}")

        # the kernel alone, at the model's ravelled length, vs float64
        n = int(flat(p0).size)
        rng = np.random.default_rng(0)
        theta = rng.standard_normal(n).astype(np.float32)
        deltas = rng.standard_normal((K, n)).astype(np.float32)
        cf = rng.uniform(0.0, 1.0, K).astype(np.float32)
        got = np.asarray(fl_aggregate(jnp.asarray(theta),
                                      jnp.asarray(deltas), jnp.asarray(cf)))
        want = theta.astype(np.float64) + cf.astype(np.float64) @ deltas
        np.testing.assert_allclose(got, want, **KERNEL_TOL,
                                   err_msg="fl_aggregate vs float64")
        ph.info["kernel_max_abs_err"] = float(np.max(np.abs(got - want)))

        # round 1 against per-client local SGD + the eq.-(4) sum, on the
        # host CPU: an independent backend, and one small compile per
        # client step count instead of a chip program each
        cpu = jax.devices("cpu")[0]
        p0_cpu = jax.device_put(p0, cpu)
        rngs_cpu = jax.device_put(rngs, cpu)
        with jax.default_device(cpu), \
                jax.default_matmul_precision("highest"):
            deltas = []
            for i, client in enumerate(selected):
                x, y = trainer.bank.client_view(int(client))
                d, _ = fl_client.local_update(task, p0_cpu, x, y, lr,
                                              rngs_cpu[i], ccfg)
                deltas.append(flat(d))
        ref = flat(p0) + np.asarray(coeffs, np.float64) @ np.stack(deltas)
        err = rel_err(p1, ref, p0)
        ph.info["round1_rel_err"] = err
        check(err <= ROUND_REL_TOL, f"round 1 vs reference: rel err "
                                    f"{err:.3e} > {ROUND_REL_TOL:.0e}")
    return hp


def phase_scan(cfg, sp, task, engine, bank, hp, grid, h_all):
    """c. a 10-round ``run_scan`` rollout: the scenario of arena lane 0."""
    import jax
    import numpy as np
    from repro.sim import scenario_keys

    with Phase("c_run_scan") as ph:
        params0 = task.init(jax.random.PRNGKey(cfg.seed + 1))
        _, roll_keys = scenario_keys(grid)
        lr_seq = lr_sequence(cfg, ROUNDS)
        policy = grid.controller_names()[0]
        params, queues, metrics = engine.run_scan(
            copy_tree(params0), grid.scenario_system_params(sp, 0), bank,
            np.asarray(h_all[0]), lr_seq, roll_keys[0], policy=policy,
            V=float(grid.V[0]), lam=float(grid.lam[0]))
        jax.block_until_ready(params)
        check(np.all(np.isfinite(metrics["loss"])),
              f"non-finite run_scan losses {metrics['loss']}")
        check(np.all(np.isfinite(flat(params))), "non-finite params")
        ph.info.update(policy=policy, rounds=ROUNDS,
                       losses=metrics["loss"].tolist())
    return params0, params, metrics


def phase_arena(cfg, sp, task, test, engine, bank, grid, h_all, params0,
                lane0):
    """d. the zoo grid through ``Arena.run`` after a strict-watchdog
    warmup; lane 0 against phase c."""
    import jax
    import numpy as np
    from repro.obs.watchdog import Watchdog
    from repro.sim import Arena, EvalBank

    with Phase("d_arena") as ph:
        arena = Arena(engine, k_mode="auto", chunk_size=CHUNK)
        watchdog = Watchdog(strict=True).attach(arena)
        eval_bank = EvalBank(task, test[0][:EVAL_EXAMPLES],
                             test[1][:EVAL_EXAMPLES])
        lr_seq = lr_sequence(cfg, ROUNDS)
        t0 = time.perf_counter()
        c0 = Phase.compile_s
        warm = arena.warmup(params0, sp, bank, grid, ROUNDS, lr_seq,
                            h_all=h_all, eval_bank=eval_bank,
                            eval_every=EVAL_EVERY)
        ph.info["warmup_s"] = round(time.perf_counter() - t0, 3)
        ph.info["warmup_compile_s"] = round(Phase.compile_s - c0, 3)
        traces = arena.traces
        t0 = time.perf_counter()
        c0 = Phase.compile_s
        rep = arena.run(params0, sp, bank, grid, ROUNDS, lr_seq,
                        h_all=h_all, eval_bank=eval_bank,
                        eval_every=EVAL_EVERY)
        jax.block_until_ready(rep.params)
        ph.info["run_s"] = round(time.perf_counter() - t0, 3)
        ph.info["run_compile_s"] = round(Phase.compile_s - c0, 3)
        ph.info.update(lanes=len(grid), rounds=ROUNDS, chunk_size=CHUNK,
                       executables_warmed=warm["executables_built"],
                       buckets=len(rep.meta["plan"]),
                       dispatches=rep.meta["dispatches"],
                       retraces=arena.traces - traces,
                       watchdog_violations=len(watchdog.violations))
        check(arena.traces == traces and not watchdog.violations,
              "post-warmup retrace")
        check(rep.meta["executables_built"] == 0,
              "arena compiled after warmup")
        m = rep.metrics
        bad = ~np.isfinite(m["loss"])
        check(not bad.any(), "non-finite arena losses: " + "; ".join(
            f"lane {s} ({grid.controller_names()[s]}, chan_mode "
            f"{int(grid.chan_mode[s])}) from round {int(np.argmax(row))}"
            f", finite q/wall_time: "
            f"{[bool(np.isfinite(m[c][s]).all()) for c in ('q_min', 'q_max', 'wall_time')]}"
            for s, row in enumerate(bad) if row.any()))
        acc = rep.final_accuracy()
        check(np.all(np.isfinite(acc)), "non-finite arena accuracy")
        ph.info["final_accuracy"] = [round(float(a), 4) for a in acc]
        lane_params, lane_m = lane0
        ph.info["lane0"] = compare_paths(
            rep.scenario_params(0), lane_params, params0,
            {name: v[0] for name, v in rep.metrics.items()}, lane_m,
            "arena lane 0 vs run_scan")


def run_one_chip(cfg) -> None:
    import jax.numpy as jnp
    from repro.sim import Arena
    sp, task, client_data, test, engine, bank = phase_bank(cfg)
    hp = phase_trainer(cfg, sp, task, client_data, test,
                       kernel_expected=_on_tpu())
    grid = zoo_grid(cfg, hp)
    h_all = jnp.asarray(Arena(engine).sample_channels(
        grid, ROUNDS, cfg.num_devices))
    params0, params, metrics = phase_scan(cfg, sp, task, engine, bank, hp,
                                          grid, h_all)
    phase_arena(cfg, sp, task, test, engine, bank, grid, h_all, params0,
                (params, metrics))


# -- four chips ---------------------------------------------------------------

def shard_devices(array) -> dict:
    """``{device id: rows}`` of an array's addressable shards."""
    return {int(s.device.id): int(s.data.shape[0])
            for s in array.addressable_shards}


def phase_sharded_engine(cfg, mesh, kernel_expected: bool) -> None:
    """The client axis shard_mapped over ``data`` (per-chip local SGD +
    partial eq.-(4) reduce, cross-chip psum) over the N-sharded bank,
    against the same rounds on one chip."""
    import jax
    import numpy as np
    from benchmarks.common import build_testbed
    from repro.fl import ClientConfig, RoundEngine

    with Phase("sharded_engine") as ph:
        sp, task, client_data, _ = build_testbed(cfg)
        ccfg = ClientConfig(local_epochs=cfg.local_epochs,
                            batch_size=cfg.batch_size)
        one = RoundEngine(task, ccfg)
        shd = RoundEngine(task, ccfg, mesh=mesh)
        Recorder.install(shd, names=("_build_step",))
        bank1 = one.make_bank(client_data, tiered="single")
        bankn = shd.make_bank(client_data, tiered="single")
        shards = shard_devices(bankn.xs)
        ph.info["bank_rows_per_device"] = shards
        check(len(shards) == mesh.size and
              set(shards.values()) == {cfg.num_devices // mesh.size},
              f"bank not split over the mesh: {shards}")
        params0 = task.init(jax.random.PRNGKey(cfg.seed + 1))
        rng = np.random.default_rng(cfg.seed)
        p1, pn = copy_tree(params0), copy_tree(params0)
        lr = lr_sequence(cfg, 2)
        for t in range(2):
            selected = rng.choice(cfg.num_devices, K, replace=True)
            coeffs = np.asarray(sp.data_weights)[selected] * (
                cfg.num_devices / K)
            rngs = jax.random.split(jax.random.PRNGKey(100 + t), K)
            p1, l1 = one.round_step(p1, bank1, selected, coeffs, lr[t],
                                    rngs)
            pn, ln = shd.round_step(pn, bankn, selected, coeffs, lr[t],
                                    rngs)
            np.testing.assert_allclose(np.asarray(ln), np.asarray(l1),
                                       **LOSS_TOL,
                                       err_msg=f"round {t} client losses")
        err = rel_err(pn, p1, params0)
        ph.info["model_rel_err"] = err
        check(err <= PATH_REL_TOL, f"sharded rounds vs one chip: rel err "
                                   f"{err:.3e} > {PATH_REL_TOL:.0e}")
        ph.info["loss_devices"] = shard_devices(ln)
        hlo = Recorder.latest.hlo()
        ph.info["kernel_in_round"] = "tpu_custom_call" in hlo
        ph.info["all_reduce_in_round"] = "all-reduce" in hlo
        check(ph.info["kernel_in_round"] == kernel_expected,
              "sharded round holds the Pallas kernel: "
              f"{ph.info['kernel_in_round']}, expected {kernel_expected}")
        check(ph.info["all_reduce_in_round"], "no cross-chip psum")


def phase_sharded_arena(cfg, mesh) -> None:
    """The scenario axis shard_mapped over ``data`` (whole rollouts per
    chip), against the same per-chip lane blocks run on one chip."""
    import jax
    import numpy as np
    from benchmarks.common import build_testbed
    from repro.core import estimate_hyperparams
    from repro.fl import ClientConfig, RoundEngine
    from repro.sim import Arena

    rounds = 4
    with Phase("sharded_arena") as ph:
        sp, task, client_data, _ = build_testbed(cfg)
        hp = estimate_hyperparams(sp, 0.1, loss_scale=1.5, mu=cfg.mu,
                                  nu=cfg.nu)
        engine = RoundEngine(task, ClientConfig(
            local_epochs=cfg.local_epochs, batch_size=cfg.batch_size))
        bank = engine.make_bank(client_data, tiered="auto")
        grid = zoo_grid(cfg, hp, seeds=(0, 1))
        s, per = len(grid), len(grid) // mesh.size
        lr_seq = lr_sequence(cfg, rounds)
        params0 = task.init(jax.random.PRNGKey(cfg.seed + 1))
        sharded = Arena(engine, mesh=mesh)
        h_all = sharded.sample_channels(grid, rounds, cfg.num_devices)
        rep = sharded.run(params0, sp, bank, grid, rounds, lr_seq,
                          h_all=h_all)
        leaf = jax.tree_util.tree_leaves(rep.params)[0]
        ph.info["lanes_per_device"] = shard_devices(leaf)
        check(len(ph.info["lanes_per_device"]) == mesh.size and
              set(ph.info["lanes_per_device"].values()) == {per},
              f"lanes not split over the mesh: "
              f"{ph.info['lanes_per_device']}")
        single = Arena(engine)
        errs = []
        for b in range(mesh.size):
            lanes = np.arange(b * per, (b + 1) * per)
            sub = single.run(params0, sp, bank, grid.take(lanes), rounds,
                             lr_seq, h_all=h_all[lanes])
            for i, lane in enumerate(lanes):
                errs.append(compare_paths(
                    rep.scenario_params(int(lane)), sub.scenario_params(i),
                    params0,
                    {name: v[lane] for name, v in rep.metrics.items()},
                    {name: v[i] for name, v in sub.metrics.items()},
                    f"lane {lane}")["model_rel_err"])
        ph.info.update(lanes=s, rounds=rounds,
                       max_model_rel_err=max(errs))


def run_four_chips(cfg, mesh) -> None:
    phase_sharded_engine(cfg, mesh, kernel_expected=_on_tpu())
    phase_sharded_arena(cfg, mesh)


# -- entry point --------------------------------------------------------------

def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the two sharded paths, on 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if not _on_tpu():
        print(f"no TPU found: jax sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU chips, jax "
              f"sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_fl_mesh
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    Phase.listen()
    cfg = paper_config()
    if args.chips == 4:
        run_four_chips(cfg, make_fl_mesh(4))
    else:
        run_one_chip(cfg)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": args.chips}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
