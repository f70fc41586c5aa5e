"""Paper Figs. 3-6 — hyper-parameter sweeps, plus the ScenarioArena
sweep-engine section.

* lambda sweep (Fig. 3): total time and final accuracy vs mu.
* V sweep (Fig. 4): time-averaged energy (constraint satisfaction) and
  time-averaged objective vs nu — the Theorem-4 O(C/V) trade-off.
* K sweep (Figs. 5/6): LROA vs Uni-D across sampling counts.
* zoo sweep (Sec. VII trade-off table): every registered controller x
  {stationary, Markov} channel modes as ONE batched ``Arena.run``,
  seed-aggregated trade-off points + single-planned-dispatch guard.
* arena (Sec. VII grid execution): S-batched ``Arena.run`` vs S
  host-looped ``run_scan`` calls on a mixed-controller grid at the
  round-engine operating point (K=8, N=120), recorded in the ``arena``
  section of ``BENCH_round_engine.json``; the ``arena.mixed_k``
  sub-section additionally pits the padded-K single program against the
  per-K-group execution of a mixed-K grid — and against the
  shape-adaptive ``k_mode='auto'`` dispatch planner (cold collapses to
  the padded workflow win, warmed recovers the grouped steady
  throughput) — plus the on-device batched EvalBank evaluation against
  the host-side per-lane eval loop.  The ``arena.skewed`` sub-section
  shows the auto planner's static per-bucket tier subsets recovering
  the tiered bank's scan-skip under vmap batching, and
  ``planner_guard`` asserts the planner's split/no-split contract in
  the parent process (CI's smoke guard).  The ``arena.streaming``
  sub-section measures the streaming chunked pipeline: chunked
  ``Arena.run`` vs the monolithic one-shot scan (bitwise guard on the
  model trajectory + overhead at chunk in {1, T/4, T}) and the
  ``SweepService``'s sustained scenarios/sec over repeated warmed
  submissions vs the one-shot batched floor.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import List, Optional

import numpy as np

from benchmarks.common import BenchConfig, csv_row, run_controller
from repro.core import (LROAController, estimate_hyperparams,
                        paper_default_params)
from repro.core import system_model as sm
from repro.fl import ChannelConfig, ChannelProcess
import jax.numpy as jnp


def lambda_sweep(cfg: BenchConfig, mus=(0.3, 1.0, 10.0, 50.0)) -> List[str]:
    rows = []
    for mu in mus:
        res = run_controller("lroa", cfg, mu=mu)
        acc = res.accuracy_curve()[-1][2]
        rows.append(csv_row(f"lambda_sweep/mu={mu}", 0.0,
                            f"total_time_s={res.total_time:.0f};"
                            f"final_acc={acc:.3f}"))
    return rows


def v_sweep(cfg: BenchConfig, nus=(1e3, 1e4, 1e5, 1e6),
            rounds: int = 600) -> List[str]:
    """Control-only rollout (no model training needed): tracks the
    time-averaged energy vs budget and the time-averaged objective."""
    rows = []
    n = cfg.num_devices
    rng = np.random.default_rng(cfg.seed)
    sizes = rng.integers(200, 600, n).astype(np.float32)
    params = paper_default_params(num_devices=n, data_sizes=sizes,
                                  sample_count=cfg.sample_count)
    for nu in nus:
        hp = estimate_hyperparams(params, 0.1, loss_scale=1.5, mu=cfg.mu,
                                  nu=nu)
        ctrl = LROAController(params, hp)
        chan = ChannelProcess(n, ChannelConfig(seed=cfg.seed))
        tot_e = np.zeros(n)
        tot_obj = 0.0
        for _ in range(rounds):
            h = jnp.asarray(chan.sample())
            dec = ctrl.decide(h)
            tot_e += np.asarray(sm.expected_energy(params, h, dec.p, dec.f,
                                                   dec.q))
            t = sm.round_time(params, h, dec.p, dec.f)
            w = params.data_weights
            tot_obj += float(jnp.sum(dec.q * t +
                                     hp.lam * jnp.square(w) / dec.q))
            ctrl.step_queues(h, dec)
        rows.append(csv_row(
            f"v_sweep/nu={nu:.0e}", 0.0,
            f"avg_energy_J={tot_e.mean() / rounds:.2f};"
            f"budget_J={float(np.asarray(params.energy_budget).mean()):.1f};"
            f"avg_objective={tot_obj / rounds:.1f};"
            f"queue_mean={float(np.asarray(ctrl.queues).mean()):.0f}"))
    return rows


def k_sweep(cfg: BenchConfig, ks=(2, 4, 6)) -> List[str]:
    rows = []
    for k in ks:
        for name in ("lroa", "uni_d"):
            res = run_controller(name, cfg, sample_count=k)
            acc = res.accuracy_curve()[-1][2]
            rows.append(csv_row(f"k_sweep/K={k}/{name}", 0.0,
                                f"total_time_s={res.total_time:.0f};"
                                f"final_acc={acc:.3f}"))
    return rows


def heterogeneity_sweep(cfg: BenchConfig, spreads=(1.0, 2.0, 4.0),
                        rounds: int = 150) -> List[str]:
    """System-heterogeneity ablation (the paper's core motivation): as the
    CPU-speed spread grows, adaptive sampling should increasingly out-run
    uniform sampling because stragglers are demoted. Control-only rollout —
    realised round latency = max over the sampled set (eq. 10)."""
    import dataclasses as dc

    from repro.core import (LROAController, UniformStaticController,
                            estimate_hyperparams, paper_default_params)
    from repro.core.controller import realized_round_time
    from repro.fl import ChannelConfig, ChannelProcess, HeterogeneityConfig
    from repro.fl import heterogeneous_params, sample_clients

    rows = []
    n = cfg.num_devices
    rng0 = np.random.default_rng(cfg.seed)
    sizes = rng0.integers(200, 600, n).astype(np.float32)
    for spread in spreads:
        base = paper_default_params(num_devices=n, data_sizes=sizes,
                                    sample_count=cfg.sample_count)
        params = heterogeneous_params(
            base, HeterogeneityConfig(cpu_speed_spread=spread,
                                      cycles_spread=spread, seed=7))
        hp = estimate_hyperparams(params, 0.1, loss_scale=1.5, mu=cfg.mu,
                                  nu=cfg.nu)
        totals = {}
        for name, ctrl_cls in (("lroa", LROAController),
                               ("uni_s", UniformStaticController)):
            ctrl = ctrl_cls(params, hp)
            chan = ChannelProcess(n, ChannelConfig(seed=cfg.seed))
            rng = np.random.default_rng(cfg.seed + 1)
            total = 0.0
            for _ in range(rounds):
                h = jnp.asarray(chan.sample())
                dec = ctrl.decide(h)
                sel = sample_clients(rng, np.asarray(dec.q),
                                     params.sample_count)
                total += realized_round_time(params, h, dec, sel)
                ctrl.step_queues(h, dec)
            totals[name] = total
        save = 100.0 * (1 - totals["lroa"] / totals["uni_s"])
        rows.append(csv_row(
            f"heterogeneity_sweep/spread={spread}", 0.0,
            f"lroa_s={totals['lroa']:.0f};uni_s_s={totals['uni_s']:.0f};"
            f"latency_saving_pct={save:.1f}"))
    return rows


def zoo_sweep(cfg: BenchConfig, rounds: int = 20, seeds: int = 2
              ) -> List[str]:
    """Sec.-VII-style trade-off table: the FULL controller zoo (all
    registered decide rules, in-trace DivFL included) crossed with
    {stationary, Markov/Gilbert-Elliott} channel modes, executed as ONE
    batched ``Arena.run`` under ``k_mode='auto'`` — the headline grid of
    the controller-zoo milestone.  Emits one row per
    (controller, channel-mode) trade-off point (seed-aggregated latency /
    loss / energy) plus a dispatch row asserting the whole mixed grid ran
    as a single planned bucket."""
    import jax

    from benchmarks.common import build_testbed
    from repro.core import POLICIES, estimate_hyperparams
    from repro.fl import ClientConfig, RoundEngine
    from repro.optim import paper_step_decay
    from repro.sim import Arena, ScenarioGrid

    params, task, client_data, _ = build_testbed(cfg)
    hp = estimate_hyperparams(params, 0.1, loss_scale=1.5, mu=cfg.mu,
                              nu=cfg.nu)
    engine = RoundEngine(task, ClientConfig(local_epochs=cfg.local_epochs,
                                            batch_size=cfg.batch_size))
    bank = engine.make_bank(client_data)
    grid = ScenarioGrid.product(
        controllers=tuple(POLICIES), seeds=tuple(range(seeds)),
        V=(hp.V,), lam=(hp.lam,), sample_count=(cfg.sample_count,),
        chan_mode=("iid", "markov"), p_gb=(0.15,), p_bg=(0.4,),
        num_devices=cfg.num_devices)
    arena = Arena(engine, k_mode="auto")
    sched = paper_step_decay(cfg.lr, cfg.rounds)
    lr_seq = np.asarray([float(sched(t)) for t in range(rounds)],
                        np.float32)
    t0 = time.perf_counter()
    report = arena.run(task.init(jax.random.PRNGKey(cfg.seed + 1)),
                       params, bank, grid, rounds, lr_seq)
    wall = time.perf_counter() - t0
    rows = []
    for pt in report.tradeoff_table():
        rows.append(csv_row(
            f"zoo_sweep/{pt['controller']}/{pt['chan_mode']}", 0.0,
            f"total_time_s={pt['total_latency']:.0f};"
            f"final_loss={pt['final_loss']:.3f};"
            f"mean_energy_J={pt['mean_energy']:.2f};"
            f"seeds={pt['num_seeds']}"))
    acct = report.dispatch_accounting()
    lanes = len(grid)
    rows.append(csv_row(
        f"zoo_sweep/dispatch/S{lanes}", 1e6 * wall / max(lanes, 1),
        f"buckets={acct['buckets']};dispatches={acct['dispatches']};"
        f"executables_built={acct['executables_built']};"
        f"controllers={len(POLICIES)}"))
    return rows


_ARENA_SHARDS = 2        # forced host devices for the sharded row
_ARENA_SENTINEL = "ARENA-SWEEP-JSON:"


def _arena_measure(s_values, rounds: int, smoke: bool) -> dict:
    """Runs over ``jax.devices()`` (forced host devices in the CPU
    subprocess, the chips in process on an accelerator): for each S,
    time the full grid-execution WORKFLOW of a mixed-controller grid
    three ways — S host-looped iterations (per-rollout channel
    generation + ``run_scan``, the pre-arena workflow), the vmapped
    single-device ``Arena.run``, and the scenario-sharded
    ``Arena(mesh=..., batch='map')`` run (whole rollouts per local
    device, per-lane solver trip counts, the arena's strong-scaling
    mode).  Channel generation is counted on both sides: the host loop
    draws each rollout's sequence separately (``ChannelProcess.
    sample_jax`` semantics) while the arena pregenerates the whole
    ``[S, T, N]`` tensor in one vmapped jit.  Best-of-3 timings."""
    import jax
    from benchmarks.bench_round_engine import (EngineBenchConfig,
                                               _build_trainer)
    from repro.core.policy import POLICIES
    from repro.fl.environment import sample_gains
    from repro.launch.mesh import make_fl_mesh
    from repro.sim import Arena, ScenarioGrid, scenario_keys

    ecfg = EngineBenchConfig.smoke() if smoke else EngineBenchConfig()
    trainer = _build_trainer(ecfg, use_engine=True)
    eng, bank, sp = trainer.engine, trainer.bank, trainer.params
    # the host loop replays run_scan from the SAME params0 every rollout;
    # with donation on (GPU/TPU) the first call would delete its buffer —
    # disable donation before any scan executable is built (the arena
    # never donates, so this changes nothing on that side)
    eng.donate = False
    hp = trainer.controller.hp
    params0 = trainer.task.init(jax.random.PRNGKey(0))
    lr_seq = np.full(rounds, ecfg.lr, np.float32)
    n = ecfg.num_devices
    shards = len(jax.devices())
    stats = {"rounds": rounds, "K": ecfg.sample_count, "N": n,
             "shards": shards, "controllers": list(POLICIES),
             "sharded_batch_mode": "map"}
    arena = Arena(eng)
    arena_sharded = Arena(eng, mesh=make_fl_mesh(), batch="map")
    gen_one = jax.jit(sample_gains, static_argnums=(1, 2))
    for s_count in s_values:
        grid = ScenarioGrid.create(
            controllers=[POLICIES[i % len(POLICIES)]
                         for i in range(s_count)],
            seeds=np.arange(s_count), V=hp.V, lam=hp.lam,
            sample_count=ecfg.sample_count)
        chan_keys, roll_keys = scenario_keys(grid)
        names = grid.controller_names()

        def host_looped():
            for s in range(s_count):
                h_s = gen_one(chan_keys[s], rounds, n,
                              float(grid.mean_gain[s]),
                              float(grid.min_gain[s]),
                              float(grid.max_gain[s]))
                # run_scan syncs per rollout (metrics come back as numpy)
                eng.run_scan(params0, grid.scenario_system_params(sp, s),
                             bank, h_s, lr_seq, roll_keys[s],
                             policy=names[s], V=float(grid.V[s]),
                             lam=float(grid.lam[s]))

        def batched(a):
            rep = a.run(params0, sp, bank, grid, rounds, lr_seq)
            jax.block_until_ready(jax.tree_util.tree_leaves(rep.params))

        def timed(fn):
            fn()                                       # compile / warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return s_count * rounds / best

        host_rps = timed(host_looped)
        vmap_rps = timed(lambda: batched(arena))
        shard_rps = timed(lambda: batched(arena_sharded))
        stats[f"S{s_count}"] = {
            "host_looped_rounds_per_sec": host_rps,
            "batched_rounds_per_sec": vmap_rps,
            "batched_sharded_rounds_per_sec": shard_rps,
            "speedup_batched_vs_host_looped": vmap_rps / host_rps,
            "speedup_sharded_vs_host_looped": shard_rps / host_rps,
        }
    stats["mixed_k"] = _mixed_k_measure(trainer, rounds, smoke)
    stats["skewed"] = _skewed_arena_measure(trainer, rounds, smoke)
    stats["streaming"] = _streaming_measure(trainer, smoke)
    return stats


def _streaming_measure(trainer, smoke: bool) -> dict:
    """Streaming chunked pipeline vs the one-shot batched scan (runs
    INSIDE the arena subprocess), at the round-engine operating point
    (S=16, K=8, N=120 full scale).  Three measurements:

    * the one-shot floor — warmed monolithic ``Arena.run`` best-of-3
      scenarios/sec, params blocked per run (the pre-streaming
      workflow);
    * chunked-vs-monolithic overhead — steady chunked throughput at
      chunk in {1, ceil(T/4), T} as a ratio of the one-shot time, with
      the model trajectory (params + loss/selected/wall_time) asserted
      BITWISE equal to the monolithic run at every chunking first (an
      assertion failure fails the bench — and CI's smoke guard);
    * sustained service throughput — a warmed ``SweepService`` fed
      repeated same-shape submissions, drained in one
      ``run_pending`` (per-batch host reduction overlaps the next
      batch's device chunks; only the LAST batch's params block), at the
      half-rollout and whole-rollout chunkings; the headline
      ``streamed_scenarios_per_sec`` is the better of the two and must
      not fall below the one-shot floor."""
    import jax
    from benchmarks.bench_round_engine import EngineBenchConfig
    from repro.core.policy import POLICIES
    from repro.sim import Arena, ScenarioGrid, SweepService

    ecfg = EngineBenchConfig.smoke() if smoke else EngineBenchConfig()
    eng, bank, sp = trainer.engine, trainer.bank, trainer.params
    hp = trainer.controller.hp
    params0 = trainer.task.init(jax.random.PRNGKey(0))
    s_count = 4 if smoke else 16
    rounds = 4 if smoke else 8
    n = ecfg.num_devices
    lr_seq = np.full(rounds, ecfg.lr, np.float32)
    grid = ScenarioGrid.create(
        controllers=[POLICIES[i % len(POLICIES)] for i in range(s_count)],
        seeds=np.arange(s_count), V=hp.V, lam=hp.lam,
        sample_count=ecfg.sample_count)
    st = {"S": s_count, "K": ecfg.sample_count, "N": n, "rounds": rounds}
    arena = Arena(eng)
    # prime the channel cache so every run (mono, chunked, service) reads
    # the identical [S, T, N] device tensor and transfers nothing
    jax.block_until_ready(arena.sample_channels(grid, rounds, n))

    def mono_run(**kw):
        rep = arena.run(params0, sp, bank, grid, rounds, lr_seq, **kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(rep.params))
        return rep

    def best_seconds(fn, reps=3):
        fn()                                   # compile / warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    mono_s = best_seconds(mono_run)
    st["oneshot_scenarios_per_sec"] = s_count / mono_s

    rep_mono = mono_run()
    chunks = sorted({1, max(1, -(-rounds // 4)), rounds})
    st["chunk_overhead_vs_oneshot"] = {}
    for chunk in chunks:
        rep_c = mono_run(chunk_size=chunk)     # compile + bitwise guard
        # Segments of length >= 2 keep the scan's fused While body and are
        # bitwise-identical to the one-shot program; a length-1 segment
        # (chunk_size=1, or a trailing remainder of 1) gets its
        # trip-count-1 loop unrolled by XLA, which may re-fuse large-shape
        # reductions — hold those chunkings to f32 resolution instead.
        unrolled = chunk == 1 or rounds % chunk == 1
        def _guard(a, b):
            a, b = np.asarray(a), np.asarray(b)
            if unrolled:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)
            else:
                np.testing.assert_array_equal(a, b)
        for name in ("loss", "selected", "wall_time"):
            _guard(rep_mono.metrics[name], rep_c.metrics[name])
        for a, b in zip(jax.tree_util.tree_leaves(rep_mono.params),
                        jax.tree_util.tree_leaves(rep_c.params)):
            _guard(a, b)
        sec = best_seconds(lambda: mono_run(chunk_size=chunk))
        st["chunk_overhead_vs_oneshot"][str(chunk)] = sec / mono_s
    st["chunked_bitwise_equal"] = True

    submissions = 4 if smoke else 8
    st["submissions_per_drain"] = submissions
    st["streamed_by_chunk"] = {}
    for chunk in sorted({max(1, -(-rounds // 2)), rounds}):
        svc = SweepService(arena, params0, sp, bank, chunk_size=chunk,
                           max_lanes=s_count)
        svc.warmup(grid, rounds, lr_seq)

        def stream():
            tickets = [svc.submit(grid, rounds, lr_seq)
                       for _ in range(submissions)]
            svc.run_pending()
            for t in tickets:
                svc.result(t)
        sec = best_seconds(stream)
        st["streamed_by_chunk"][str(chunk)] = (
            submissions * s_count / sec)
    stream_chunk, stream_rps = max(st["streamed_by_chunk"].items(),
                                   key=lambda kv: kv[1])
    st["stream_chunk"] = int(stream_chunk)
    st["streamed_scenarios_per_sec"] = stream_rps
    st["speedup_streamed_vs_oneshot"] = (
        stream_rps / st["oneshot_scenarios_per_sec"])
    return st


def _mixed_k_measure(trainer, rounds: int, smoke: bool) -> dict:
    """Mixed-K grid execution + evaluation (runs INSIDE the arena
    subprocess): a controllers x seeds x K grid executed per-K-group
    (``k_mode='group'``, one compile + one dispatch chain per distinct
    K) vs as ONE padded-K program (``k_mode='pad'``), and the S-lane
    evaluation done as a host loop (jitted per-lane ``task.metrics`` over
    sliced params — the pre-EvalBank workflow) vs one vmapped on-device
    batched pass.

    Records both the steady-state throughput (executables cached) and
    the WORKFLOW throughput of a fresh grid execution including
    compilation — the operating point the fusion exists for: an
    iterate-on-the-grid sweep pays the compile chain on every new shape,
    and the padded program compiles (and dispatches) once instead of
    once per K."""
    import jax
    import jax.numpy as jnp
    from benchmarks.bench_round_engine import EngineBenchConfig
    from repro.data import synthetic_image_classification
    from repro.sim import Arena, EvalBank, ScenarioGrid

    ecfg = EngineBenchConfig.smoke() if smoke else EngineBenchConfig()
    eng, bank, sp = trainer.engine, trainer.bank, trainer.params
    hp = trainer.controller.hp
    params0 = trainer.task.init(jax.random.PRNGKey(0))
    lr_seq = np.full(rounds, ecfg.lr, np.float32)
    n = ecfg.num_devices
    ks = (2, 4) if smoke else (4, 8, 16)
    grid = ScenarioGrid.product(
        controllers=("lroa", "uni_d"), seeds=(0, 1), V=(hp.V,),
        lam=(hp.lam,), sample_count=ks, num_devices=n)
    s_count = len(grid)
    mk = {"K_values": [int(k) for k in ks], "S": s_count,
          "rounds": rounds, "controllers": ["lroa", "uni_d"],
          "num_seeds": 2}
    probe = Arena(eng)
    h_all = probe.sample_channels(grid, rounds, n)
    jax.block_until_ready(h_all)

    def run(a, **kw):
        rep = a.run(params0, sp, bank, grid, rounds, lr_seq, h_all=h_all,
                    **kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(rep.params))
        return rep

    reports = {}
    for mode in ("group", "pad"):
        a = Arena(eng, k_mode=mode)
        t0 = time.perf_counter()
        cold_rep = run(a)                  # cold: compiles + executes
        cold = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):                 # steady: executables cached
            t0 = time.perf_counter()
            rep = run(a)
            best = min(best, time.perf_counter() - t0)
        tag = "grouped" if mode == "group" else "padded"
        # executables the cold run actually compiled for THIS grid (the
        # fusion claim), not the arena-lifetime cache size
        mk[f"{tag}_executables"] = cold_rep.meta["executables_built"]
        mk[f"{tag}_dispatches"] = rep.meta["dispatches"]
        mk[f"{tag}_cold_seconds"] = cold
        mk[f"{tag}_workflow_rounds_per_sec"] = s_count * rounds / cold
        mk[f"{tag}_rounds_per_sec"] = s_count * rounds / best
        reports[mode] = rep
    mk["speedup_padded_vs_grouped_workflow"] = (
        mk["grouped_cold_seconds"] / mk["padded_cold_seconds"])
    mk["speedup_padded_vs_grouped_steady"] = (
        mk["padded_rounds_per_sec"] / mk["grouped_rounds_per_sec"])

    # -- shape-adaptive dispatch (k_mode='auto') ----------------------------
    # cold: a fresh auto arena plans at the one-run horizon, which
    # collapses to the single padded executable — it must keep the padded
    # workflow win; steady: an auto arena warmed through Arena.warmup
    # compiles the runs=inf signature split, and the cache-aware replan
    # snaps every later run to those buckets — it must recover (or beat)
    # the grouped steady throughput.  Both taxes die in one mode.
    a_cold = Arena(eng, k_mode="auto")
    t0 = time.perf_counter()
    cold_rep = run(a_cold)
    cold = time.perf_counter() - t0
    mk["auto_executables"] = cold_rep.meta["executables_built"]
    mk["auto_cold_dispatches"] = cold_rep.meta["dispatches"]
    mk["auto_cold_seconds"] = cold
    mk["auto_workflow_rounds_per_sec"] = s_count * rounds / cold
    a_steady = Arena(eng, k_mode="auto")
    warm = a_steady.warmup(params0, sp, bank, grid, rounds, lr_seq,
                           h_all=h_all)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        rep = run(a_steady)
        best = min(best, time.perf_counter() - t0)
    rep.dispatch_accounting()          # additive per-bucket counters
    # auto lanes are bitwise-identical to the padded program (the
    # prefix-stable padded-K invariant) — assert it where it's measured
    np.testing.assert_array_equal(rep.metrics["loss"],
                                  reports["pad"].metrics["loss"])
    mk["auto_rounds_per_sec"] = s_count * rounds / best
    mk["auto_steady_dispatches"] = rep.meta["dispatches"]
    mk["auto_steady_executables"] = warm["executables_built"]
    mk["auto_steady_plan"] = rep.meta["plan"]
    mk["speedup_auto_vs_grouped_workflow"] = (
        mk["grouped_cold_seconds"] / mk["auto_cold_seconds"])
    mk["speedup_auto_vs_grouped_steady"] = (
        mk["auto_rounds_per_sec"] / mk["grouped_rounds_per_sec"])

    # -- S-lane evaluation: host loop vs on-device batched ------------------
    test_n = 64 if smoke else 1024
    xte, yte = synthetic_image_classification(
        test_n, ecfg.image_shape, ecfg.num_classes, noise=0.3, seed=123)
    ebank = EvalBank(trainer.task, xte, yte)
    rep = reports["pad"]
    xte_d, yte_d = jnp.asarray(xte), jnp.asarray(yte)
    host_metrics = jax.jit(trainer.task.metrics)

    def eval_host_loop():
        for s in range(s_count):
            out = host_metrics(rep.scenario_params(s),
                               {"x": xte_d, "y": yte_d})
            jax.block_until_ready(out["accuracy"])

    def eval_batched():
        ebank.evaluate_stacked(rep.params)

    def best_seconds(fn):
        fn()                               # compile / warm
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    mk["eval_test_examples"] = test_n
    mk["eval_host_loop_seconds"] = best_seconds(eval_host_loop)
    mk["eval_batched_seconds"] = best_seconds(eval_batched)
    mk["speedup_eval_batched_vs_host_loop"] = (
        mk["eval_host_loop_seconds"] / mk["eval_batched_seconds"])
    return mk


def _skewed_arena_measure(trainer, rounds: int, smoke: bool) -> dict:
    """Tier-subset scan-skip recovered under batching (runs INSIDE the
    arena subprocess): on a Dirichlet-skewed tiered bank the per-round
    tier bodies are selection-conditioned ``lax.cond``s, so a SINGLE
    rollout's scan skips the tiers a round misses — but vmapping S lanes
    lowers cond to select and every lane pays every tier body on every
    round (``k_mode='pad'`` ships the full ladder in its one
    executable: the tier-select tax).  ``k_mode='auto'`` probes each
    lane's realised tier footprint on the control plane, buckets lanes
    by it, and compiles each bucket with ONLY its hit tiers — the
    batched-execution form of the skip.  Pad-warmed vs auto-warmed
    steady throughput on the same uniform-K grid."""
    import jax
    from benchmarks.bench_round_engine import (EngineBenchConfig,
                                               _skewed_client_data)
    from repro.core import paper_default_params
    from repro.core.policy import POLICIES
    from repro.sim import Arena, ScenarioGrid

    ecfg = EngineBenchConfig.smoke() if smoke else EngineBenchConfig()
    eng = trainer.engine
    sizes, cd = _skewed_client_data(ecfg)
    bank = eng.make_bank(cd, tiered="tiered")
    sp = paper_default_params(
        num_devices=ecfg.num_devices, sample_count=ecfg.sample_count,
        local_epochs=ecfg.local_epochs,
        data_sizes=sizes.astype(np.float32))
    hp = trainer.controller.hp
    s_count = 4 if smoke else 8
    k = 2 if smoke else 4          # few draws/lane => sparse footprints
    grid = ScenarioGrid.create(
        controllers=[POLICIES[i % len(POLICIES)] for i in range(s_count)],
        seeds=np.arange(s_count), V=hp.V, lam=hp.lam, sample_count=k)
    params0 = trainer.task.init(jax.random.PRNGKey(0))
    lr_seq = np.full(rounds, ecfg.lr, np.float32)
    h_all = Arena(eng).sample_channels(grid, rounds, ecfg.num_devices)
    jax.block_until_ready(h_all)
    stats = {"S": s_count, "K": k, "rounds": rounds,
             "num_tiers": int(bank.num_tiers),
             "tier_buckets": [int(b) for b in bank.tier_buckets]}

    def steady(a):
        a.warmup(params0, sp, bank, grid, rounds, lr_seq, h_all=h_all)
        best, rep = float("inf"), None
        for _ in range(3):
            t0 = time.perf_counter()
            rep = a.run(params0, sp, bank, grid, rounds, lr_seq,
                        h_all=h_all)
            jax.block_until_ready(jax.tree_util.tree_leaves(rep.params))
            best = min(best, time.perf_counter() - t0)
        return s_count * rounds / best, rep

    pad_rps, _ = steady(Arena(eng, k_mode="pad"))
    auto_rps, auto_rep = steady(Arena(eng, k_mode="auto"))
    stats["padded_rounds_per_sec"] = pad_rps
    stats["auto_rounds_per_sec"] = auto_rps
    stats["auto_executables"] = len(auto_rep.meta["plan"])
    stats["auto_plan"] = auto_rep.meta["plan"]
    stats["tiers_per_bucket"] = [
        bank.num_tiers if b["tiers"] is None else len(b["tiers"])
        for b in auto_rep.meta["plan"]]
    stats["speedup_auto_vs_padded_steady"] = auto_rps / pad_rps
    return stats


def planner_guard() -> List[str]:
    """CI guard for the ``k_mode='auto'`` planner (pure host logic, no
    rollouts — runs in the arena_sweep PARENT process): the steady-state
    plan must SPLIT a synthetic K-skewed grid (the padded-slot waste is
    real work), a uniform grid must stay ONE bucket at every horizon (no
    spurious executables), and the cold one-run horizon must collapse
    the skewed grid back to the single padded program (the workflow
    win).  Assertion failures fail the bench — and CI's smoke run."""
    import math

    from repro.sim import plan_dispatch

    work = {0: 128.0}
    skewed_ks = np.array([2] * 10 + [16, 16])
    skew = plan_dispatch(skewed_ks, rounds=5, tier_work=work,
                         runs=math.inf)
    assert skew.num_buckets > 1, (
        f"planner failed to split the K-skewed grid: {skew.describe()}")
    uni = plan_dispatch(np.array([8] * 12), rounds=5, tier_work=work,
                        runs=math.inf)
    assert uni.num_buckets == 1, (
        f"planner split a uniform grid: {uni.describe()}")
    cold = plan_dispatch(skewed_ks, rounds=5, tier_work=work, runs=1.0)
    assert cold.num_buckets == 1, (
        f"cold horizon failed to collapse to padded: {cold.describe()}")
    return [csv_row(
        "arena_sweep/planner_guard", 0.0,
        f"skewed_steady_buckets={skew.num_buckets};"
        f"uniform_steady_buckets={uni.num_buckets};"
        f"skewed_cold_buckets={cold.num_buckets}")]


def _arena_subprocess(s_values, rounds: int, smoke: bool) -> dict:
    """:func:`_arena_measure` in a child process on ``_ARENA_SHARDS``
    forced host devices (CPU only — the child never touches an
    accelerator)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # single-threaded eigen: the sharded program already keeps every core
    # busy with one shard each; per-op multi-threading on top only adds
    # pool contention (it speeds the host loop too — the flag applies to
    # both sides of the comparison)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{_ARENA_SHARDS}"
                        " --xla_cpu_multi_thread_eigen=false")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root)] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    spec = json.dumps({"s_values": list(s_values), "rounds": rounds,
                       "smoke": smoke})
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sweeps",
         "--arena-subprocess", spec],
        env=env, capture_output=True, text=True, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"arena subprocess failed:\n{out.stdout}\n"
                           f"{out.stderr}")
    payload = [line for line in out.stdout.splitlines()
               if line.startswith(_ARENA_SENTINEL)]
    return json.loads(payload[-1][len(_ARENA_SENTINEL):])


def arena_sweep(cfg: BenchConfig, s_values=(4, 16), rounds: int = 5,
                smoke: bool = False, json_path: Optional[str] = None
                ) -> List[str]:
    """ScenarioArena throughput: a mixed LROA/Uni-D/Uni-S grid of S
    rollouts executed as S host-looped ``run_scan`` calls vs ONE batched
    ``Arena.run`` — unsharded (vmap only) and scenario-sharded over
    ``_ARENA_SHARDS`` forced host devices (whole rollouts per device, no
    cross-device collectives; the sharded row is the arena's headline
    number — see the scaling note below for what it can reach per host).

    Pins the round-engine operating point (K=8, N=120 full scale; tiny
    shapes under ``smoke``) at pilot-rollout length (``rounds=5`` — the
    section measures GRID-EXECUTION cost; long-rollout throughput is the
    round_engine scan row's job), and merges an ``arena`` section into
    ``BENCH_round_engine.json`` (the tracked record of
    execution-strategy throughput; ``bench_round_engine`` preserves the
    section when it rewrites the file).  The ``arena.mixed_k``
    sub-section (``_mixed_k_measure``) compares a mixed-K grid run
    per-K-group vs as ONE padded-K program vs the cost-model
    ``k_mode='auto'`` dispatch (cold and ``Arena.warmup``-primed steady
    rows) — workflow (compile included) and steady-state throughput,
    executable/dispatch counts — plus the S-lane evaluation as a host
    loop vs the EvalBank's batched on-device pass; ``arena.skewed``
    (``_skewed_arena_measure``) adds the tiered-bank row where auto's
    per-bucket tier subsets recover the scan-skip under batching;
    ``arena.streaming`` (``_streaming_measure``) adds the streaming
    chunked pipeline — chunked-vs-monolithic bitwise guard + overhead,
    and the ``SweepService``'s sustained scenarios/sec against the
    one-shot batched floor.
    On an accelerator the measurement runs in process over
    ``jax.devices()``: a chip belongs to one process, and this one
    already holds it.  On the CPU it runs in a subprocess pinned to the
    CPU, because the forced host-device count must be set before jax
    initialises; :func:`planner_guard` asserts the planner's
    split/no-split contract host-side.

    Scaling note: the sharded row's ceiling is the local device count.
    On the 2-core recording host the fused per-rollout scan baseline
    already keeps both cores busy, so the S=16 sharded row lands around
    1.5-2x (the tracked record: ~1.99x at S=16); the scenario axis is
    embarrassingly parallel, so clearing 2x with margin needs more local
    devices than the baseline can itself exploit (any accelerator host,
    or a >= 4-core CPU).
    """
    import jax

    if json_path is None:
        json_path = ("BENCH_round_engine.smoke.json" if smoke
                     else "BENCH_round_engine.json")
    if jax.default_backend() == "cpu":
        stats = _arena_subprocess(s_values, rounds, smoke)
    else:
        stats = _arena_measure(s_values, rounds, smoke)
    rows = []
    for s_count in s_values:
        sec = stats[f"S{s_count}"]
        tag = f"S{s_count}K{stats['K']}N{stats['N']}"
        rows += [
            csv_row(f"arena_sweep/host_looped/{tag}",
                    1e6 / sec["host_looped_rounds_per_sec"],
                    f"rounds_per_sec="
                    f"{sec['host_looped_rounds_per_sec']:.2f}"),
            csv_row(f"arena_sweep/batched/{tag}",
                    1e6 / sec["batched_rounds_per_sec"],
                    f"rounds_per_sec={sec['batched_rounds_per_sec']:.2f};"
                    f"speedup_vs_host_looped="
                    f"{sec['speedup_batched_vs_host_looped']:.2f}"),
            csv_row(f"arena_sweep/batched_sharded/{tag}",
                    1e6 / sec["batched_sharded_rounds_per_sec"],
                    f"rounds_per_sec="
                    f"{sec['batched_sharded_rounds_per_sec']:.2f};"
                    f"shards={stats['shards']};"
                    f"speedup_vs_host_looped="
                    f"{sec['speedup_sharded_vs_host_looped']:.2f}"),
        ]
    mk = stats["mixed_k"]
    mtag = (f"S{mk['S']}K" + "+".join(str(k) for k in mk["K_values"]) +
            f"N{stats['N']}")
    rows += [
        csv_row(f"arena_sweep/mixed_k_grouped/{mtag}",
                1e6 / mk["grouped_workflow_rounds_per_sec"],
                f"workflow_rounds_per_sec="
                f"{mk['grouped_workflow_rounds_per_sec']:.2f};"
                f"steady_rounds_per_sec={mk['grouped_rounds_per_sec']:.2f};"
                f"executables={mk['grouped_executables']};"
                f"dispatches={mk['grouped_dispatches']}"),
        csv_row(f"arena_sweep/mixed_k_padded/{mtag}",
                1e6 / mk["padded_workflow_rounds_per_sec"],
                f"workflow_rounds_per_sec="
                f"{mk['padded_workflow_rounds_per_sec']:.2f};"
                f"steady_rounds_per_sec={mk['padded_rounds_per_sec']:.2f};"
                f"executables={mk['padded_executables']};"
                f"dispatches={mk['padded_dispatches']};"
                f"speedup_workflow_vs_grouped="
                f"{mk['speedup_padded_vs_grouped_workflow']:.2f}"),
        csv_row(f"arena_sweep/mixed_k_auto/{mtag}",
                1e6 / mk["auto_workflow_rounds_per_sec"],
                f"workflow_rounds_per_sec="
                f"{mk['auto_workflow_rounds_per_sec']:.2f};"
                f"steady_rounds_per_sec={mk['auto_rounds_per_sec']:.2f};"
                f"cold_executables={mk['auto_executables']};"
                f"steady_dispatches={mk['auto_steady_dispatches']};"
                f"speedup_workflow_vs_grouped="
                f"{mk['speedup_auto_vs_grouped_workflow']:.2f};"
                f"speedup_steady_vs_grouped="
                f"{mk['speedup_auto_vs_grouped_steady']:.2f}"),
        csv_row(f"arena_sweep/mixed_k_eval_host_loop/{mtag}",
                1e6 * mk["eval_host_loop_seconds"],
                f"seconds={mk['eval_host_loop_seconds']:.4f}"),
        csv_row(f"arena_sweep/mixed_k_eval_batched/{mtag}",
                1e6 * mk["eval_batched_seconds"],
                f"seconds={mk['eval_batched_seconds']:.4f};"
                f"speedup_vs_host_loop="
                f"{mk['speedup_eval_batched_vs_host_loop']:.2f}"),
    ]
    sk = stats["skewed"]
    stag = f"S{sk['S']}K{sk['K']}N{stats['N']}tiers{sk['num_tiers']}"
    rows += [
        csv_row(f"arena_sweep/skewed_padded/{stag}",
                1e6 / sk["padded_rounds_per_sec"],
                f"rounds_per_sec={sk['padded_rounds_per_sec']:.2f};"
                f"tiers_compiled={sk['num_tiers']}"),
        csv_row(f"arena_sweep/skewed_auto/{stag}",
                1e6 / sk["auto_rounds_per_sec"],
                f"rounds_per_sec={sk['auto_rounds_per_sec']:.2f};"
                f"executables={sk['auto_executables']};"
                "tiers_per_bucket="
                + "+".join(str(t) for t in sk["tiers_per_bucket"]) + ";"
                f"speedup_vs_padded="
                f"{sk['speedup_auto_vs_padded_steady']:.2f}"),
    ]
    sr = stats["streaming"]
    ttag = f"S{sr['S']}K{sr['K']}N{sr['N']}T{sr['rounds']}"
    rows += [
        csv_row(f"arena_sweep/streaming_oneshot/{ttag}",
                1e6 / sr["oneshot_scenarios_per_sec"],
                f"scenarios_per_sec="
                f"{sr['oneshot_scenarios_per_sec']:.2f}"),
        csv_row(f"arena_sweep/streaming_sustained/{ttag}",
                1e6 / sr["streamed_scenarios_per_sec"],
                f"scenarios_per_sec="
                f"{sr['streamed_scenarios_per_sec']:.2f};"
                f"chunk={sr['stream_chunk']};"
                f"speedup_vs_oneshot="
                f"{sr['speedup_streamed_vs_oneshot']:.2f};"
                f"bitwise_guard={sr['chunked_bitwise_equal']}"),
        csv_row(f"arena_sweep/streaming_chunk_overhead/{ttag}", 0.0,
                "chunked_over_oneshot=" + "+".join(
                    f"{c}:{v:.2f}" for c, v in
                    sr["chunk_overhead_vs_oneshot"].items())),
    ]
    rows += planner_guard()
    try:
        with open(json_path) as f:
            record = json.load(f)
    except (OSError, ValueError):
        record = {}
    record["arena"] = stats
    with open(json_path, "w") as f:
        json.dump(record, f, indent=2)
    return rows


if __name__ == "__main__":
    import sys

    if len(sys.argv) > 2 and sys.argv[1] == "--arena-subprocess":
        # worker mode for arena_sweep: measure under the forced
        # host-device count the parent set in XLA_FLAGS
        spec = json.loads(sys.argv[2])
        print(_ARENA_SENTINEL + json.dumps(_arena_measure(
            spec["s_values"], spec["rounds"], spec["smoke"])))
        sys.exit(0)
    cfg = BenchConfig()
    for row in (lambda_sweep(cfg) + v_sweep(cfg) + k_sweep(cfg)
                + heterogeneity_sweep(cfg) + zoo_sweep(cfg)
                + arena_sweep(cfg)):
        print(row)
