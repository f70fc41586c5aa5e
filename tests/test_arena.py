"""ScenarioArena: the scenario-batched sweep engine reproduces individual
``run_scan`` rollouts lane for lane (model trajectory bitwise, control
diagnostics to f32 resolution), including mixed-controller grids, tiered
banks, mixed sampling counts, and a 2-device CPU scenario-sharded
subprocess case; plus the controller-as-data dispatch, grid construction,
report reducers, tier-skipping cond, and the pure-jax hyper-parameter
estimates the arena derives per scenario."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (POLICIES, POLICY_IDS, decide_by_id,
                        estimate_hyperparams, estimate_hyperparams_arrays,
                        paper_default_params)
from repro.core import policy as pol
from repro.data import synthetic_image_classification
from repro.fl import ClientConfig, RoundEngine
from repro.models import MLPTask
from repro.obs import scopes
from repro.sim import (Arena, CostModel, EvalBank, RolloutReport,
                       ScenarioGrid, derive_hyperparams, scenario_keys)

N = 6
BS = 16
# the model trajectory must match bitwise; the queue/energy diagnostics
# come out of Algorithm 2's bisection solver, whose elementwise chains
# XLA fuses shape-dependently — those agree to f32 resolution instead
BITWISE_METRICS = ("loss", "selected", "wall_time")
TOL = dict(rtol=1e-5, atol=1e-4)


def _client_data(sizes, seed=3):
    total = sum(sizes)
    x, y = synthetic_image_classification(total, (8, 8, 1), num_classes=4,
                                          noise=0.3, seed=seed)
    offs = np.cumsum([0] + list(sizes))
    return [(x[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]])
            for i in range(len(sizes))]


def _setup(sizes=None, bank_mode="single"):
    sizes = [64] * N if sizes is None else sizes
    task = MLPTask(input_dim=64, num_classes=4, hidden=32)
    eng = RoundEngine(task, ClientConfig(local_epochs=2, batch_size=BS))
    bank = eng.make_bank(_client_data(sizes), tiered=bank_mode)
    sp = paper_default_params(num_devices=len(sizes), sample_count=4,
                              data_sizes=np.asarray(sizes, np.float32))
    params0 = task.init(jax.random.PRNGKey(0))
    return task, eng, bank, sp, params0


def _mixed_grid(s=8, k=4):
    """Mixed-controller, mixed-(V, lam, budget, channel) grid of S lanes."""
    ctrl = [POLICIES[i % len(POLICIES)] for i in range(s)]
    return ScenarioGrid.create(
        controllers=ctrl, seeds=np.arange(s),
        V=np.linspace(10.0, 1e4, s).astype(np.float32),
        lam=np.linspace(0.1, 5.0, s).astype(np.float32),
        energy_scale=([1.0, 2.0, 0.5, 1.0] * ((s + 3) // 4))[:s],
        mean_gain=([0.1, 0.2, 0.05, 0.1] * ((s + 3) // 4))[:s],
        sample_count=k)


def _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr, s,
                         model_bitwise=True, drop_all=None):
    """Arena lane ``s`` == the individual run_scan reproduction of it.

    ``model_bitwise=False`` relaxes the model trajectory to tight
    allclose — each tier's body runs batched over the arena's lanes and
    unbatched in run_scan, which XLA may round a final ulp apart, so
    tiered lanes agree to f32 resolution instead of bitwise."""
    _, roll_keys = scenario_keys(grid)
    sp_s = grid.scenario_system_params(sp, s)
    p, q, m = eng.run_scan(params0, sp_s, bank, np.asarray(h_all[s]), lr,
                           roll_keys[s], policy=grid.controller_names()[s],
                           V=float(grid.V[s]), lam=float(grid.lam[s]),
                           drop_seq=(None if drop_all is None
                                     else np.asarray(drop_all[s])))
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(rep.scenario_params(s))):
        if model_bitwise:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
    k = int(grid.sample_count[s])
    for name in BITWISE_METRICS:
        got = rep.metrics[name][s]
        if name == "selected":
            got = got[..., :k]       # strip mixed-K right-padding
        if model_bitwise or name == "selected":
            np.testing.assert_array_equal(m[name], got)
        else:
            np.testing.assert_allclose(m[name], got, **TOL)
    for name in m:
        if name in BITWISE_METRICS:
            continue
        np.testing.assert_allclose(m[name], rep.metrics[name][s], **TOL)
    np.testing.assert_allclose(np.asarray(q), rep.queues[s], **TOL)


# -- tentpole: S-lane arena == S individual run_scan rollouts --------------


def test_arena_mixed_controller_grid_matches_individual_rollouts():
    """An S=8 mixed-controller (lroa/uni_d/uni_s), mixed-hyperparameter
    grid runs as ONE vmapped program whose every lane reproduces the
    fixed-policy run_scan rollout of that scenario."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_grid(s=8)
    arena = Arena(eng)
    T = 4
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, N)
    assert h_all.shape == (8, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert isinstance(rep, RolloutReport)
    assert rep.metrics["loss"].shape == (8, T)
    assert rep.metrics["selected"].shape == (8, T, 4)
    # exactly one executable compiled for the whole mixed grid
    assert len(arena._fns) == 1
    for s in range(8):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)


def test_arena_map_mode_lanes_match_individual_rollouts():
    """batch='map' lays lanes out as lax.map iterations (per-lane solver
    trip counts, no vmap lockstep) — the model trajectory must still be
    bitwise against the individual run_scan reproductions."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_grid(s=4)
    arena = Arena(eng, batch="map")
    T = 4
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)
    with pytest.raises(ValueError, match="batch mode"):
        Arena(eng, batch="bogus")


def test_scenario_keys_vectorised_matches_per_seed_host_loop():
    """The jitted/vmapped key derivation must be bitwise identical to
    building PRNGKey(seed) and splitting per scenario on the host — the
    reproducibility contract individual run_scan replays rely on."""
    grid = ScenarioGrid.create(controllers=["lroa"] * 4,
                               seeds=[0, 1, 7, 123456], V=1.0, lam=1.0)
    chan, roll = scenario_keys(grid)
    for s, seed in enumerate(grid.seed):
        root = jax.random.PRNGKey(int(seed))
        ck, rk = jax.random.split(root)
        np.testing.assert_array_equal(np.asarray(chan[s]), np.asarray(ck))
        np.testing.assert_array_equal(np.asarray(roll[s]), np.asarray(rk))


def test_arena_tiered_bank_lanes_match_individual_tiered_scans():
    """The arena rides the tiered scan plan (per-tier lax.cond inside the
    vmapped body) — every lane must still reproduce the individual
    tiered run_scan."""
    sizes = [64, 10, 33, 64, 100, 17]
    task, eng, bank, sp, params0 = _setup(sizes, bank_mode="tiered")
    assert bank.num_tiers > 1
    grid = ScenarioGrid.create(controllers=["lroa", "uni_d", "uni_s",
                                            "lroa"],
                               seeds=[3, 4, 5, 6], V=200.0, lam=1.0,
                               sample_count=4)
    arena = Arena(eng)
    T = 3
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, len(sizes))
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s, model_bitwise=False)


def _mixed_k_grid():
    return ScenarioGrid.create(controllers=["lroa", "uni_d", "lroa",
                                            "uni_s", "uni_d", "lroa"],
                               seeds=[0, 1, 2, 3, 4, 5], V=100.0, lam=0.5,
                               sample_count=[2, 4, 2, 4, 3, 3])


def test_arena_mixed_sample_counts_group_by_k():
    """The legacy grouped path (k_mode='group'): one jitted program per
    distinct K, lanes scattered back into grid order (selected
    right-padded with -1), and the per-group compile/dispatch counts
    reported in the report metadata."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    arena = Arena(eng, k_mode="group")
    T = 3
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert len(arena._fns) == 3                      # one program per K
    assert rep.meta["k_mode"] == "group"
    assert rep.meta["k_groups"] == [2, 3, 4]
    assert rep.meta["dispatches"] == 3
    assert rep.meta["executables_built"] == 3
    assert rep.metrics["selected"].shape == (6, T, 4)
    assert np.all(rep.metrics["selected"][0, :, 2:] == -1)   # K=2 lanes
    assert np.all(rep.metrics["selected"][1, :, 2:] >= 0)    # K=4 lanes
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)


# -- tentpole: padded-K dispatch fusion ------------------------------------


def test_padded_mixed_k_single_program_bitwise_vs_groups():
    """A mixed-K grid (3 distinct K values) under the default
    k_mode='pad' runs as ONE compiled executable whose padded lanes
    (k_active < K_max) are bitwise-equal — params / loss / selected /
    wall_time on the leaf-chunked path — to the per-K groups they
    replace, and to the individual run_scan reproductions."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    T = 3
    lr = np.full(T, 0.1, np.float32)
    arena = Arena(eng)
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep.meta["k_mode"] == "pad"
    assert rep.meta["dispatches"] == 1
    assert rep.meta["executables_built"] == 1
    assert len(arena._fns) == 1                  # ONE padded executable
    # output layout matches the grouped convention: [S, T, K_max], -1 pad
    assert rep.metrics["selected"].shape == (6, T, 4)
    assert np.all(rep.metrics["selected"][0, :, 2:] == -1)
    # bitwise vs the per-K grouped execution of the SAME grid
    grouped = Arena(eng, k_mode="group")
    rep_g = grouped.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(rep_g.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in BITWISE_METRICS:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_g.metrics[name])
    for name in rep.metrics:
        if name not in BITWISE_METRICS:
            np.testing.assert_allclose(rep.metrics[name],
                                       rep_g.metrics[name], **TOL)
    # ...and vs the individual fixed-policy run_scan rollouts
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)


def test_padded_mixed_k_map_mode_bitwise():
    """batch='map' lanes of a padded mixed-K grid (sequential traces, no
    vmap lockstep) keep the bitwise padded-lane contract."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid().take(np.arange(4))
    T = 3
    lr = np.full(T, 0.1, np.float32)
    arena = Arena(eng, batch="map")
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert len(arena._fns) == 1
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)


def test_padded_mixed_k_tiered_bank_lanes():
    """A tiered-bank mixed-K padded grid still reproduces the individual
    tiered run_scan per lane (f32 resolution — the per-tier lax.cond
    lowers as select under vmap)."""
    sizes = [64, 10, 33, 64, 100, 17]
    task, eng, bank, sp, params0 = _setup(sizes, bank_mode="tiered")
    assert bank.num_tiers > 1
    grid = ScenarioGrid.create(controllers=["lroa", "uni_d", "uni_s",
                                            "lroa"],
                               seeds=[3, 4, 5, 6], V=200.0, lam=1.0,
                               sample_count=[2, 4, 3, 4])
    arena = Arena(eng)
    T = 3
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, len(sizes))
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert len(arena._fns) == 1
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s, model_bitwise=False)


# -- tentpole: controller zoo x non-stationary channels --------------------


def test_zoo_grid_stationary_and_markov_single_run_lane_replay():
    """The headline grid: ALL registered controllers (in-trace DivFL and
    round-robin included) x {stationary, Gilbert-Elliott} channel modes
    runs as ONE ``Arena.run``, every lane bitwise-reproducing its
    fixed-policy ``run_scan`` reference, and the report reduces to the
    Sec.-VII-style trade-off table with one point per
    (controller, channel-mode) configuration."""
    task, eng, bank, sp, params0 = _setup()
    grid = ScenarioGrid.product(
        controllers=tuple(POLICIES), seeds=(0,), V=(100.0,), lam=(0.5,),
        sample_count=(4,), chan_mode=("iid", "markov"), p_gb=(0.2,),
        p_bg=(0.5,), num_devices=N)
    s_total = 2 * len(POLICIES)
    assert len(grid) == s_total and len(POLICIES) >= 6
    arena = Arena(eng)
    T = 3
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, N)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert len(arena._fns) == 1          # one executable, whole zoo
    for s in range(s_total):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all,
                             lr, s)
    table = rep.tradeoff_table()
    assert len(table) == s_total
    assert ({(r["controller"], r["chan_mode"]) for r in table}
            == {(c, m) for c in POLICIES for m in ("iid", "markov")})


def test_zoo_grid_auto_mode_plans_single_dispatch():
    """Satellite guard: the mixed 6+-controller grid under
    ``k_mode='auto'`` executes as ONE planned dispatch bucket."""
    task, eng, bank, sp, params0 = _setup()
    grid = ScenarioGrid.product(
        controllers=tuple(POLICIES), seeds=(0,), V=(100.0,), lam=(0.5,),
        sample_count=(4,), chan_mode=("iid", "markov"), p_gb=(0.2,),
        p_bg=(0.5,), num_devices=N)
    arena = Arena(eng, k_mode="auto")
    T = 3
    rep = arena.run(params0, sp, bank, grid, T,
                    np.full(T, 0.1, np.float32))
    acct = rep.dispatch_accounting()
    assert acct["buckets"] == 1
    assert acct["dispatches"] == 1
    assert acct["lanes_covered"] == len(grid)


def test_dropout_lanes_match_run_scan_and_leave_clean_lanes_bitwise():
    """Per-client dropout lanes replay bitwise against ``run_scan`` with
    the same ``drop_seq``; a zero-dropout lane in the SAME grid stays
    bitwise equal to the historical no-dropout executable's trajectory
    (satellite: adding the dropout axis must not move clean lanes)."""
    task, eng, bank, sp, params0 = _setup()
    T = 4
    lr = np.full(T, 0.1, np.float32)
    grid = ScenarioGrid.create(
        controllers=["lroa", "uni_d", "channel_aware", "divfl"],
        seeds=[0, 1, 2, 3], V=100.0, lam=0.5, sample_count=4,
        dropout=[0.0, 0.4, 0.4, 0.4])
    arena = Arena(eng)
    h_all = arena.sample_channels(grid, T, N)
    drop_all = arena.sample_dropout(grid, T, N)
    # lane 0 has dropout 0.0: its mask column is all-ones
    assert np.all(np.asarray(drop_all[0]) == 1.0)
    assert np.any(np.asarray(drop_all[1:]) == 0.0)
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all,
                             lr, s, drop_all=drop_all)
    # the clean lane vs the historical no-dropout executable (a grid
    # whose dropout column is all zero skips the mask entirely): model
    # trajectory and selections stay bitwise; the loss column crosses
    # two executables whose reduce XLA fuses differently, so it agrees
    # to f32 resolution instead
    clean = grid.take(np.asarray([0]))
    rep0 = Arena(eng).run(params0, sp, bank, clean, T, lr,
                          h_all=h_all[:1])
    for a, b in zip(jax.tree_util.tree_leaves(rep.scenario_params(0)),
                    jax.tree_util.tree_leaves(rep0.scenario_params(0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(rep.metrics["selected"][0],
                                  rep0.metrics["selected"][0])
    np.testing.assert_array_equal(rep.metrics["wall_time"][0],
                                  rep0.metrics["wall_time"][0])
    np.testing.assert_allclose(rep.metrics["loss"][0],
                               rep0.metrics["loss"][0], rtol=1e-6)


# -- controller-as-data dispatch -------------------------------------------


def test_decide_by_id_matches_named_policies():
    sp = paper_default_params(num_devices=N, sample_count=3,
                              data_sizes=np.full(N, 64, np.float32))
    h = jnp.asarray(np.random.default_rng(0).uniform(0.02, 0.4, N)
                    .astype(np.float32))
    queues = jnp.asarray(np.random.default_rng(1).uniform(0, 300, N)
                         .astype(np.float32))
    v = jnp.full((N,), 50.0, jnp.float32)
    lam = jnp.full((N,), 0.7, jnp.float32)
    for name, fn in zip(POLICIES, pol.DECIDE_FNS):
        # jit the direct rule too: the switch is bitwise-faithful to the
        # COMPILED branch (what every arena/run_scan trace executes);
        # eager mode dispatches op-by-op and XLA's fused division chains
        # (cost_effective's q normalisation) drift 1 ulp from that
        direct = jax.jit(fn)(sp, h, queues, v, lam)
        switched = jax.jit(decide_by_id)(jnp.int32(POLICY_IDS[name]), sp,
                                         h, queues, v, lam)
        for a, b in zip(direct, switched):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(fn(sp, h, queues, v, lam), switched):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)


def test_controllers_are_thin_wrappers_over_policy_fns():
    """The stateful classes and the pure rules must make identical
    decisions — the wrapper refactor cannot fork the math."""
    from repro.core import (LROAController, UniformDynamicController,
                            UniformStaticController)
    sp = paper_default_params(num_devices=N, sample_count=3,
                              data_sizes=np.full(N, 64, np.float32))
    hp = estimate_hyperparams(sp, 0.1, loss_scale=1.5, mu=1.0, nu=1e5)
    h = jnp.asarray(np.random.default_rng(2).uniform(0.02, 0.4, N)
                    .astype(np.float32))
    for cls, fn, (v, lam) in [
            (LROAController, pol.decide_lroa, (hp.V, hp.lam)),
            (UniformDynamicController, pol.decide_uni_d, (hp.V, hp.lam)),
            (UniformStaticController, pol.decide_uni_s, (0.0, 0.0))]:
        ctrl = cls(sp, hp)
        ctrl.queues = jnp.asarray(
            np.random.default_rng(3).uniform(0, 300, N).astype(np.float32))
        got = ctrl.decide(h)
        want = fn(sp, h, ctrl.queues, jnp.float32(v), jnp.float32(lam))
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


def test_run_scan_uni_s_policy():
    """uni_s joins the scan-traceable policies (static resources)."""
    task, eng, bank, sp, params0 = _setup()
    T = 3
    h = np.random.default_rng(0).uniform(0.05, 0.4, (T, N)).astype(
        np.float32)
    params, queues, m = eng.run_scan(params0, sp, bank, h,
                                     np.full(T, 0.1, np.float32),
                                     jax.random.PRNGKey(1),
                                     policy="uni_s")
    assert np.all(np.isfinite(m["loss"]))
    np.testing.assert_allclose(m["q_min"], 1.0 / N, rtol=1e-6)
    with pytest.raises(ValueError, match="scan-traceable"):
        eng.run_scan(params0, sp, bank, h, np.full(T, 0.1, np.float32),
                     jax.random.PRNGKey(1), policy="bogus")


def test_run_scan_accepts_every_registered_policy():
    """Every controller in the zoo — in-trace DivFL and round-robin
    included — is a fixed-policy run_scan citizen."""
    task, eng, bank, sp, params0 = _setup()
    T = 3
    h = np.random.default_rng(5).uniform(0.05, 0.4, (T, N)).astype(
        np.float32)
    lr = np.full(T, 0.1, np.float32)
    for policy in POLICIES:
        params, queues, m = eng.run_scan(params0, sp, bank, h, lr,
                                         jax.random.PRNGKey(2),
                                         policy=policy, V=50.0, lam=0.5)
        assert np.all(np.isfinite(m["loss"])), policy
        sel = np.asarray(m["selected"])
        assert sel.shape == (T, sp.sample_count)
        assert np.all((sel >= 0) & (sel < N)), policy


# -- grid construction ------------------------------------------------------


def test_grid_product_and_validation():
    grid = ScenarioGrid.product(controllers=("lroa", "uni_d"),
                                seeds=(0, 1, 2), V=(10.0, 100.0),
                                lam=(0.5,))
    assert len(grid) == 12
    assert set(grid.controller_names()) == {"lroa", "uni_d"}
    sub = grid.take(np.asarray([0, 5]))
    assert len(sub) == 2
    # DivFL is a first-class lane now (in-trace facility-location greedy)
    gd = ScenarioGrid.create(controllers=["divfl"], seeds=[0], V=1.0,
                             lam=1.0)
    assert gd.controller_names() == ["divfl"]
    with pytest.raises(ValueError, match="unknown controller"):
        ScenarioGrid.create(controllers=["bogus"], seeds=[0], V=1.0,
                            lam=1.0)
    with pytest.raises(ValueError, match="out of range"):
        ScenarioGrid.create(controllers=[7], seeds=[0], V=1.0, lam=1.0)
    # PRNGKey truncates to 32 bits: wider seeds would silently alias lanes
    with pytest.raises(ValueError, match="uint32"):
        ScenarioGrid.create(controllers=["lroa"], seeds=[2 ** 32 + 1],
                            V=1.0, lam=1.0)


def test_arena_rejects_meshed_engine():
    task = MLPTask(input_dim=64, num_classes=4, hidden=32)
    eng = RoundEngine(task, ClientConfig(local_epochs=2, batch_size=BS),
                      mesh=jax.make_mesh((1,), ("data",)))
    with pytest.raises(ValueError, match="without a mesh"):
        Arena(eng)


# -- report reducers --------------------------------------------------------


def test_report_reducers_and_tradeoff_table():
    task, eng, bank, sp, params0 = _setup()
    grid = ScenarioGrid.product(controllers=("lroa", "uni_d"),
                                seeds=(0, 1), V=(100.0,), lam=(0.5,),
                                sample_count=(4,))
    arena = Arena(eng)
    T = 3
    lr = np.full(T, 0.1, np.float32)
    rep = arena.run(params0, sp, bank, grid, T, lr)
    s = len(grid)
    assert rep.latency_curve().shape == (s, T)
    assert np.all(np.diff(rep.latency_curve(), axis=1) > 0)
    np.testing.assert_allclose(rep.total_latency(),
                               rep.latency_curve()[:, -1], rtol=1e-6)
    counts = rep.selection_counts(N)
    assert counts.shape == (s, N)
    assert np.all(counts.sum(axis=1) == T * 4)
    table = rep.tradeoff_table()
    # 2 controllers x 1 (V, lam) config, each aggregating 2 seeds
    assert len(table) == 2
    assert all(row["num_seeds"] == 2 for row in table)
    assert {row["controller"] for row in table} == {"lroa", "uni_d"}
    rows = rep.summary()
    assert len(rows) == s and rows[0]["total_latency"] > 0


# -- tier-aware scan skipping ----------------------------------------------


def test_tier_loop_cond_skip_matches_unconditional():
    """The selection-conditioned lax.cond wrapper around each tier's body
    (the scan path's skip) must reproduce the unconditional tier loop."""
    sizes = [64, 10, 33, 64, 100, 17]
    task, eng, bank, sp, params0 = _setup(sizes, bank_mode="tiered")
    round_fn, data, _ = eng._scan_plan(bank)
    sel = np.asarray([1, 4, 0, 5])           # hits several tiers
    assert len(np.unique(bank.tier_of[sel])) > 1
    coeffs = jnp.asarray([.2, .3, .1, .4], jnp.float32)
    rngs = jax.random.split(jax.random.PRNGKey(5), 4)

    from repro.fl.round_engine import _tier_parts
    parts_key = tuple((t, tier.steps_per_epoch,
                       tier.device_args()[2] is not None)
                      for t, tier in enumerate(bank.tiers))
    bufs = tuple(tier.device_args() for tier in bank.tiers)
    tier_sel = jnp.asarray(bank.tier_of[sel], jnp.int32)
    pos_sel = jnp.asarray(bank.pos_in_tier[sel], jnp.int32)

    def run(cond_skip):
        fn = jax.jit(lambda p: eng._tier_loop_round(
            p, _tier_parts(parts_key, bufs), tier_sel, pos_sel, coeffs,
            jnp.float32(0.1), rngs, cond_skip=cond_skip))
        return fn(params0)

    p_cond, l_cond, ran_cond = run(True)
    p_ref, l_ref, ran_ref = run(False)
    # the flags name the tiers the selection drew
    np.testing.assert_array_equal(
        np.asarray(ran_cond),
        np.isin(np.arange(len(bank.tiers)), bank.tier_of[sel]))
    assert ran_ref is None
    for a, b in zip(jax.tree_util.tree_leaves(p_cond),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-7)
    np.testing.assert_allclose(np.asarray(l_cond), np.asarray(l_ref),
                               atol=1e-7)


def _skip_grid():
    """Two lanes at K=2 on the tiered bank of ``_skip_setup``: round 1
    of the rollout draws one tier in both lanes, so the other three
    tiers are drawn by no lane, while round 2 draws three tiers."""
    return ScenarioGrid.create(controllers=["round_robin", "uni_d"],
                               seeds=[0, 1], V=10.0, lam=0.5,
                               sample_count=2)


def _skip_setup():
    return _setup([64, 10, 33, 64, 100, 17], bank_mode="tiered")


def _tier_rounds(arena):
    return (arena.metrics.counter("arena.tier_rounds_run").value,
            arena.metrics.counter("arena.tier_rounds_skipped").value)


def _drawn_tiers(bank, selected):
    """``[lanes, T, tiers]`` bool: the tiers each lane drew each round."""
    tiers = bank.tier_of[np.asarray(selected)]
    return np.stack([(tiers == t).any(axis=-1)
                     for t in range(bank.num_tiers)], axis=-1)


def _run_skip_grid(batch="vmap", lane_axis=None, monkeypatch=None):
    import repro.sim.arena as arena_mod
    if monkeypatch is not None:
        monkeypatch.setattr(arena_mod, "LANE_AXIS", lane_axis)
    task, eng, bank, sp, params0 = _skip_setup()
    grid = _skip_grid()
    arena = Arena(eng, batch=batch)
    T = 4
    lr = np.full(T, 0.1, np.float32)
    h_all = arena.sample_channels(grid, T, len(bank.tier_of))
    rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    return arena, rep, (eng, bank, sp, params0, grid, h_all, lr)


def _assert_lanes_close(rep, ref):
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    np.testing.assert_allclose(rep.metrics["loss"], ref.metrics["loss"],
                               atol=1e-7)
    np.testing.assert_array_equal(rep.metrics["selected"],
                                  ref.metrics["selected"])


def test_arena_tier_skip_matches_every_tier_path(monkeypatch):
    """A tier that no lane drew in a round is skipped for every lane; the
    lanes match the every-tier path (the vmap without a lane axis, where
    each tier's cond lowers to a select) and the selections are equal."""
    arena, rep, (_, bank, *_rest) = _run_skip_grid()
    assert bank.num_tiers > 1
    # the grid does exercise the skip: some round draws a tier in no lane
    drawn = _drawn_tiers(bank, rep.metrics["selected"])
    assert not drawn.any(axis=0).all()
    assert _tier_rounds(arena)[1] > 0
    _, every_tier, _ = _run_skip_grid(lane_axis=None,
                                      monkeypatch=monkeypatch)
    _assert_lanes_close(rep, every_tier)


def _tier_scope_prims(closed):
    """``{fl.tier.<rows>: {primitive names}}`` of the equations whose
    innermost scope is a tier scope, anywhere in ``closed``."""
    import re
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            scope = str(eqn.source_info.name_stack).split("/")[-1]
            if re.fullmatch(r"fl\.tier\.\d+", scope):
                found.setdefault(scope, set()).add(eqn.primitive.name)
            for v in eqn.params.values():
                for x in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(x, ClosedJaxpr):
                        walk(x.jaxpr)
                    elif isinstance(x, Jaxpr):
                        walk(x)

    walk(closed.jaxpr)
    return found


def _arena_jaxpr(arena, rerun):
    """The jaxpr of the arena's one rollout executable, traced with the
    arguments a second ``rerun()`` passes it."""
    (key, fn), = arena._fns.items()
    seen = []

    def record(*args):
        seen.append(args)
        return fn(*args)

    arena._fns[key] = record
    rerun()
    arena._fns[key] = fn
    return jax.make_jaxpr(fn)(*seen[0])


def test_arena_tier_skip_keeps_a_real_cond_under_vmap(monkeypatch):
    """The vmapped arena program holds a ``cond`` around each tier body,
    not the ``select_n`` a per-lane predicate lowers to."""
    for axis in ("lanes", None):
        arena, rep, (eng, bank, sp, params0, grid, h_all, lr) = \
            _run_skip_grid(lane_axis=axis, monkeypatch=monkeypatch)
        prims = _tier_scope_prims(_arena_jaxpr(
            arena, lambda: arena.run(params0, sp, bank, grid, 4, lr,
                                     h_all=h_all)))
        want = {scopes.tier(t.bucket_examples) for t in bank.tiers}
        assert set(prims) == want
        for scope, names in prims.items():
            if axis is None:        # the every-tier path: both branches
                assert "cond" not in names and "select_n" in names, scope
            else:
                assert "cond" in names and "select_n" not in names, scope


def test_arena_tier_skip_counters_match_host_count():
    """``arena.tier_rounds_run`` / ``_skipped`` count, per round and tier,
    whether any lane drew the tier, as the host computes it from the
    selections; the ``arena.reduce`` span carries the same counts."""
    from repro.obs import trace as obs_trace
    with obs_trace.installed(obs_trace.MemorySink()) as sink:
        arena, rep, (_, bank, *_rest) = _run_skip_grid()
    any_lane = _drawn_tiers(bank, rep.metrics["selected"]).any(axis=0)
    run, skipped = _tier_rounds(arena)
    assert (run, skipped) == (int(any_lane.sum()),
                              int((~any_lane).sum()))
    assert skipped > 0
    (red,) = sink.by_name("arena.reduce")
    assert red["attrs"]["tier_rounds_run"] == run
    assert red["attrs"]["tier_rounds_skipped"] == skipped
    assert "tiers_run" not in rep.metrics


def test_arena_tier_skip_map_mode_lanes_unchanged():
    """``batch='map'`` runs each lane's unbatched scan: its tiers skip per
    lane, its lanes reproduce the tiered run_scan bitwise, and they agree
    with the vmapped arena's lanes."""
    arena, rep, (eng, bank, sp, params0, grid, h_all, lr) = \
        _run_skip_grid(batch="map")
    per_lane = _drawn_tiers(bank, rep.metrics["selected"])
    assert _tier_rounds(arena) == (int(per_lane.sum()),
                                   int((~per_lane).sum()))
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)
    _, rep_vmap, _ = _run_skip_grid()
    _assert_lanes_close(rep, rep_vmap)


# -- on-device batched evaluation ------------------------------------------


def _test_set(n=48, seed=11):
    return synthetic_image_classification(n, (8, 8, 1), num_classes=4,
                                          noise=0.3, seed=seed)


def test_on_device_eval_matches_host_metrics_per_lane():
    """EvalBank's batched final evaluation and the in-scan eval columns
    must match per-lane host-side task.metrics to f32 resolution, on a
    padded mixed-K grid, without touching the model trajectory."""
    task, eng, bank, sp, params0 = _setup()
    xte, yte = _test_set()
    eb = EvalBank(task, xte, yte)
    grid = _mixed_k_grid()
    T = 4
    lr = np.full(T, 0.1, np.float32)
    arena = Arena(eng)
    h_all = arena.sample_channels(grid, T, N)
    rep_plain = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    arena_ev = Arena(eng)
    rep = arena_ev.run(params0, sp, bank, grid, T, lr, h_all=h_all,
                       eval_bank=eb, eval_every=2)
    # evaluation only READS params: trajectory identical to the plain run
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(rep_plain.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in rep_plain.metrics:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_plain.metrics[name])
    assert rep.metrics["test_accuracy"].shape == (len(grid), T)
    assert rep.metrics["test_loss"].shape == (len(grid), T)
    xte_d, yte_d = jnp.asarray(xte), jnp.asarray(yte)
    for s in range(len(grid)):
        host = task.metrics(rep.scenario_params(s),
                            {"x": xte_d, "y": yte_d})
        # final batched eval == host per-lane eval
        assert rep.final_metrics["test_accuracy"][s] == pytest.approx(
            float(host["accuracy"]), abs=1e-5)
        assert rep.final_metrics["test_loss"][s] == pytest.approx(
            float(host["loss"]), rel=1e-5)
        # T=4, eval_every=2: the last column evaluated the final params
        assert rep.metrics["test_accuracy"][s, -1] == pytest.approx(
            float(host["accuracy"]), abs=1e-5)
        assert rep.metrics["test_loss"][s, -1] == pytest.approx(
            float(host["loss"]), rel=1e-5)
        # off-rounds hold the previous evaluation (step curve)
        np.testing.assert_array_equal(rep.metrics["test_accuracy"][s, 2],
                                      rep.metrics["test_accuracy"][s, 1])
    # reducers surface the accuracy half of the trade-off
    assert rep.accuracy_curve().shape == (len(grid), T)
    np.testing.assert_array_equal(rep.final_accuracy(),
                                  rep.final_metrics["test_accuracy"])
    table = rep.tradeoff_table()
    assert all("test_accuracy" in row for row in table)
    with pytest.raises(KeyError, match="eval_bank"):
        rep_plain.accuracy_curve()
    with pytest.raises(ValueError, match="eval_bank"):
        arena.run(params0, sp, bank, grid, T, lr, h_all=h_all,
                  eval_every=2)


# -- warmup / executable cache ---------------------------------------------


def test_arena_warmup_then_run_zero_new_traces():
    """Arena.warmup compiles the padded executable; subsequent same-shape
    runs (different V/lam/seeds — the iterate-on-V workflow) must perform
    ZERO new scan-body traces."""
    task, eng, bank, sp, params0 = _setup()
    xte, yte = _test_set()
    eb = EvalBank(task, xte, yte)
    grid = _mixed_k_grid()
    T = 3
    arena = Arena(eng)
    stats = arena.warmup(params0, sp, bank, grid, T, eval_bank=eb,
                         eval_every=2)
    assert stats["executables_built"] == 1
    assert stats["traces"] >= 1
    traces0 = arena.traces
    # same shapes, different values: new V/lam, new seeds, real lr
    import dataclasses as dc
    grid2 = dc.replace(grid, V=grid.V * 3.0, lam=grid.lam + 0.5,
                       seed=grid.seed + 100)
    lr = np.full(T, 0.1, np.float32)
    rep = arena.run(params0, sp, bank, grid2, T, lr, eval_bank=eb,
                    eval_every=2)
    rep2 = arena.run(params0, sp, bank, grid2, T, lr * 0.5, eval_bank=eb,
                     eval_every=2)
    assert arena.traces == traces0          # zero new traces after warmup
    assert rep.meta["executables_built"] == 0
    assert rep2.meta["executables_built"] == 0
    assert np.all(np.isfinite(rep.metrics["loss"]))


# -- shape-adaptive dispatch (k_mode='auto') --------------------------------


# compile amortisation zeroed out: the planner splits by signature even
# on a cold arena, so one run exercises the full multi-bucket path
_SPLIT_CM = CostModel(compile_cost=0.0)


def test_auto_mixed_k_multi_bucket_bitwise_vs_pad_and_group():
    """k_mode='auto' forced into its signature-split plan on a K-skewed
    interleaved grid: three buckets, lanes permuted in and out, every
    lane bitwise-equal (model trajectory, leaf-chunked path) to the
    padded and grouped executions and to its run_scan replay — the cost
    model decides speed, never results."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    T = 3
    lr = np.full(T, 0.1, np.float32)
    auto = Arena(eng, k_mode="auto", cost_model=_SPLIT_CM)
    h_all = auto.sample_channels(grid, T, N)
    rep = auto.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep.meta["k_mode"] == "auto"
    assert rep.meta["dispatches"] == 3          # one bucket per distinct K
    assert rep.meta["executables_built"] == 3
    assert [b["k_pad"] for b in rep.meta["plan"]] == [2, 3, 4]
    # grid-interleaved K: buckets are non-contiguous lane sets
    assert rep.meta["plan"][0]["lanes"] == [0, 2]
    # the grouped execution of the SAME grid is bitwise identical in
    # every output (the buckets ARE the per-K groups here)
    grouped = Arena(eng, k_mode="group")
    rep_g = grouped.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(rep_g.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in rep.metrics:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_g.metrics[name])
    np.testing.assert_array_equal(rep.queues, rep_g.queues)
    # ...and the padded execution matches on the model trajectory
    pad = Arena(eng)
    rep_p = pad.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(rep_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in BITWISE_METRICS:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_p.metrics[name])
    # ...and the individual fixed-policy run_scan replays, lane by lane
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s)


def test_auto_cold_run_collapses_to_the_padded_plan():
    """With real compile prices and nothing cached, a one-shot auto run
    plans exactly the padded single bucket — the cold-workflow
    degenerate case, same executable cache key as k_mode='pad'."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    T = 3
    lr = np.full(T, 0.1, np.float32)
    auto = Arena(eng, k_mode="auto")          # tracked cost calibration
    h_all = auto.sample_channels(grid, T, N)
    rep = auto.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep.meta["dispatches"] == 1
    assert rep.meta["executables_built"] == 1
    assert rep.meta["plan"][0]["k_pad"] == 4
    pad = Arena(eng)
    rep_p = pad.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert set(auto._fns) == set(pad._fns)    # the SAME executable key
    for name in rep.metrics:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_p.metrics[name])


def test_auto_max_executables_one_is_the_pad_degenerate_case():
    """A forced max_executables=1 plan is the padded plan whatever the
    prices say — results and executable cache key identical to
    k_mode='pad'."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    T = 3
    lr = np.full(T, 0.1, np.float32)
    auto = Arena(eng, k_mode="auto", cost_model=_SPLIT_CM,
                 max_executables=1)
    h_all = auto.sample_channels(grid, T, N)
    rep = auto.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep.meta["dispatches"] == 1
    assert len(auto._fns) == 1
    pad = Arena(eng)
    rep_p = pad.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert set(auto._fns) == set(pad._fns)
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(rep_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in rep.metrics:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_p.metrics[name])
    with pytest.raises(ValueError, match="max_executables"):
        Arena(eng, k_mode="auto", max_executables=0)


def test_auto_lane_permutation_round_trip_with_eval_columns():
    """Eval rides the buckets: in-scan test_* columns and the final
    batched evaluation re-stitch to grid order through the lane
    permutation — accuracy_curve() and the report reducers read exactly
    like the padded run's."""
    task, eng, bank, sp, params0 = _setup()
    xte, yte = _test_set()
    eb = EvalBank(task, xte, yte)
    grid = _mixed_k_grid()
    T = 4
    lr = np.full(T, 0.1, np.float32)
    auto = Arena(eng, k_mode="auto", cost_model=_SPLIT_CM)
    h_all = auto.sample_channels(grid, T, N)
    rep = auto.run(params0, sp, bank, grid, T, lr, h_all=h_all,
                   eval_bank=eb, eval_every=2)
    assert rep.meta["dispatches"] == 3
    pad = Arena(eng)
    rep_p = pad.run(params0, sp, bank, grid, T, lr, h_all=h_all,
                    eval_bank=eb, eval_every=2)
    # grid order round-trips: the model-trajectory columns are bitwise,
    # the eval columns (different vmap widths) f32-tight
    for name in BITWISE_METRICS:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep_p.metrics[name])
    np.testing.assert_allclose(rep.accuracy_curve(),
                               rep_p.accuracy_curve(), **TOL)
    np.testing.assert_allclose(rep.final_accuracy(),
                               rep_p.final_accuracy(), **TOL)
    # reducers see grid coordinates in grid order
    rows = rep.summary()
    assert [r["sample_count"] for r in rows] == \
        grid.sample_count.tolist()
    assert [r["controller"] for r in rows] == grid.controller_names()


def test_auto_tiered_bank_static_tier_subsets_match_pad_lanes():
    """Multi-tier bank + K-skewed grid: the control-plane probe's
    footprints bound each bucket to the tiers its lanes actually draw,
    at least one bucket compiles a REDUCED ladder (the recovered
    scan-skip), and every lane still matches the padded full-ladder
    execution and its run_scan replay to f32 resolution."""
    sizes = [64, 10, 33, 64, 100, 17]
    task, eng, bank, sp, params0 = _setup(sizes, bank_mode="tiered")
    assert bank.num_tiers > 1
    grid = ScenarioGrid.create(
        controllers=["lroa", "uni_d", "uni_s", "lroa", "uni_d", "lroa"],
        seeds=[3, 4, 5, 6, 7, 8], V=200.0, lam=1.0,
        sample_count=[2, 4, 2, 4, 3, 3])
    T = 3
    lr = np.full(T, 0.1, np.float32)
    auto = Arena(eng, k_mode="auto", cost_model=_SPLIT_CM,
                 max_executables=6)
    h_all = auto.sample_channels(grid, T, len(sizes))
    rep = auto.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep.meta["dispatches"] > 1
    # every bucket's static tier subset covers exactly the union of its
    # lanes' REALIZED tier draws (probe == execution selections)
    tier_of = np.asarray(bank.tier_of)
    for b in rep.meta["plan"]:
        realized = set()
        for s in b["lanes"]:
            sel = rep.metrics["selected"][s]
            realized |= set(tier_of[sel[sel >= 0]].tolist())
        assert sorted(realized) == b["tiers"]
    # the scan-skip is actually exercised: some bucket dropped a tier
    assert any(len(b["tiers"]) < bank.num_tiers
               for b in rep.meta["plan"])
    # lanes match the padded full-ladder run (dropped tiers contribute
    # exact zeros) and the individual tiered run_scan replays
    pad = Arena(eng)
    rep_p = pad.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    np.testing.assert_array_equal(rep.metrics["selected"],
                                  rep_p.metrics["selected"])
    for a, b in zip(jax.tree_util.tree_leaves(rep.params),
                    jax.tree_util.tree_leaves(rep_p.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    for s in range(len(grid)):
        _assert_lane_matches(rep, eng, bank, sp, params0, grid, h_all, lr,
                             s, model_bitwise=False)


def test_auto_warmup_warms_every_steady_bucket():
    """Arena.warmup under 'auto' plans at the steady-state horizon,
    AOT-compiles EVERY bucket of that plan without running it, and
    subsequent runs re-pick the cached buckets through the cache-aware
    cost model: zero new compiles, zero new traces — and the lanes of a
    cold, unwarmed arena."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    T = 3
    lr = np.full(T, 0.1, np.float32)
    auto = Arena(eng, k_mode="auto")
    h_all = auto.sample_channels(grid, T, N)
    stats = auto.warmup(params0, sp, bank, grid, T, h_all=h_all)
    assert len(stats["plan"]) == 3        # steady split, not the cold pad
    assert stats["executables_built"] == 3
    assert len(auto._fns) == 3
    traces0 = auto.traces
    rep = auto.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep.meta["dispatches"] == 3    # snapped to the warmed buckets
    assert rep.meta["executables_built"] == 0
    assert auto.traces == traces0         # zero new traces after warmup
    acc = rep.dispatch_accounting()
    assert acc["dispatches"] == 3
    assert acc["lanes_covered"] == len(grid)
    # warming changes no result: a cold arena (one padded dispatch)
    # gives the same model trajectory in every lane
    cold = Arena(eng, k_mode="auto")
    rep2 = cold.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    assert rep2.meta["dispatches"] == 1
    for name in BITWISE_METRICS:
        np.testing.assert_array_equal(rep.metrics[name],
                                      rep2.metrics[name])


def test_meta_bucket_accounting_is_additive_in_every_k_mode():
    """Satellite contract: meta['buckets'] counters are per-executable
    and additive — their sums reproduce meta['dispatches'] /
    meta['executables_built'] exactly in pad, group, and auto modes
    (dispatch_accounting raises otherwise)."""
    task, eng, bank, sp, params0 = _setup()
    grid = _mixed_k_grid()
    T = 3
    lr = np.full(T, 0.1, np.float32)
    expected = {"pad": 1, "group": 3, "auto": 3}
    for mode, want in expected.items():
        arena = Arena(eng, k_mode=mode, cost_model=_SPLIT_CM)
        h_all = arena.sample_channels(grid, T, N)
        rep = arena.run(params0, sp, bank, grid, T, lr, h_all=h_all)
        acc = rep.dispatch_accounting()
        assert acc["dispatches"] == rep.meta["dispatches"] == want
        assert acc["executables_built"] == rep.meta["executables_built"]
        assert acc["lanes_covered"] == len(grid)
        assert sum(b["dispatches"] for b in rep.meta["buckets"]) == \
            rep.meta["dispatches"]


# -- K validation -----------------------------------------------------------


def test_grid_validates_sample_count_against_n():
    with pytest.raises(ValueError, match="exceed num_devices"):
        ScenarioGrid.product(controllers=("lroa",), seeds=(0,), V=(1.0,),
                             lam=(1.0,), sample_count=(2, 99),
                             num_devices=N)
    with pytest.raises(ValueError, match="exceed num_devices"):
        ScenarioGrid.create(controllers=["lroa"], seeds=[0], V=1.0,
                            lam=1.0, sample_count=N + 1, num_devices=N)
    with pytest.raises(ValueError, match=">= 1"):
        ScenarioGrid.create(controllers=["lroa"], seeds=[0], V=1.0,
                            lam=1.0, sample_count=0)
    # without num_devices construction passes, but Arena.run still
    # rejects the oversized K before tracing anything
    grid = ScenarioGrid.create(controllers=["lroa"], seeds=[0], V=1.0,
                               lam=1.0, sample_count=N + 2)
    task, eng, bank, sp, params0 = _setup()
    with pytest.raises(ValueError, match="K <= N"):
        Arena(eng).run(params0, sp, bank, grid, 2,
                       np.full(2, 0.1, np.float32))
    with pytest.raises(ValueError, match="k_mode"):
        Arena(eng, k_mode="bogus")


# -- pure-jax hyper-parameter estimates ------------------------------------


def test_estimate_hyperparams_arrays_matches_host_and_jits():
    sp = paper_default_params(num_devices=N, sample_count=3,
                              data_sizes=np.full(N, 64, np.float32))
    hp = estimate_hyperparams(sp, 0.1, loss_scale=1.5, mu=2.0, nu=1e4)
    lam, v, lam0, v0 = jax.jit(estimate_hyperparams_arrays,
                               static_argnums=())(
        sp, jnp.float32(0.1), jnp.float32(1.5), jnp.float32(2.0),
        jnp.float32(1e4))
    assert float(lam) == pytest.approx(hp.lam, rel=1e-6)
    assert float(v) == pytest.approx(hp.V, rel=1e-6)
    assert float(lam0) == pytest.approx(hp.lam0, rel=1e-6)
    assert float(v0) == pytest.approx(hp.V0, rel=1e-6)
    # vmappable over per-scenario (mean_gain, mu, nu) — the arena's
    # setup-jit use case
    lam_b, v_b, _, _ = jax.jit(jax.vmap(
        lambda g, m, n: estimate_hyperparams_arrays(sp, g, 1.5, m, n)))(
        jnp.asarray([0.1, 0.2]), jnp.asarray([2.0, 1.0]),
        jnp.asarray([1e4, 1e5]))
    assert float(lam_b[0]) == pytest.approx(hp.lam, rel=1e-6)
    assert float(v_b[0]) == pytest.approx(hp.V, rel=1e-6)


def test_derive_hyperparams_fills_grid_per_scenario():
    sp = paper_default_params(num_devices=N, sample_count=4,
                              data_sizes=np.full(N, 64, np.float32))
    grid = ScenarioGrid.create(controllers=["lroa", "uni_d"],
                               seeds=[0, 1], V=0.0, lam=0.0,
                               mean_gain=[0.1, 0.2], sample_count=[4, 2])
    out = derive_hyperparams(sp, grid, mu=1.0, nu=1e5, loss_scale=1.5)
    hp0 = estimate_hyperparams(sp, 0.1, loss_scale=1.5, mu=1.0, nu=1e5)
    assert out.lam[0] == pytest.approx(hp0.lam, rel=1e-6)
    assert out.V[0] == pytest.approx(hp0.V, rel=1e-6)
    # lane 1 uses its own K and channel mean
    import dataclasses as dc
    sp1 = dc.replace(sp, sample_count=2)
    hp1 = estimate_hyperparams(sp1, 0.2, loss_scale=1.5, mu=1.0, nu=1e5)
    assert out.lam[1] == pytest.approx(hp1.lam, rel=1e-6)
    assert out.V[1] == pytest.approx(hp1.V, rel=1e-6)


# -- channel pregeneration --------------------------------------------------


def test_sample_channels_per_scenario_statistics():
    task, eng, bank, sp, params0 = _setup()
    grid = ScenarioGrid.create(controllers=["lroa"] * 3, seeds=[0, 1, 2],
                               V=1.0, lam=1.0,
                               mean_gain=[0.05, 0.1, 0.3],
                               min_gain=[0.01, 0.01, 0.05],
                               max_gain=[0.2, 0.5, 0.9])
    arena = Arena(eng)
    h = np.asarray(arena.sample_channels(grid, 200, N))
    assert h.shape == (3, 200, N)
    for s in range(3):
        assert h[s].min() >= grid.min_gain[s]
        assert h[s].max() <= grid.max_gain[s]
    # larger mean_gain must shift the realised mean up
    assert h[0].mean() < h[1].mean() < h[2].mean()
    # deterministic in the grid seeds
    h2 = np.asarray(arena.sample_channels(grid, 200, N))
    np.testing.assert_array_equal(h, h2)


# -- 2-device CPU scenario sharding ----------------------------------------

_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")
    import numpy as np, jax
    from repro.core import paper_default_params
    from repro.data import synthetic_image_classification
    from repro.fl import ClientConfig, RoundEngine
    from repro.launch.mesh import make_fl_mesh
    from repro.models import MLPTask
    from repro.sim import Arena, ScenarioGrid

    assert jax.device_count() == 2, jax.devices()
    N, BS, T, S = 6, 16, 3, 4
    sizes = [64] * N
    x, y = synthetic_image_classification(sum(sizes), (8, 8, 1), 4,
                                          noise=0.3, seed=3)
    offs = np.cumsum([0] + sizes)
    cd = [(x[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]])
          for i in range(N)]
    task = MLPTask(input_dim=64, num_classes=4, hidden=32)
    eng = RoundEngine(task, ClientConfig(local_epochs=2, batch_size=BS))
    bank = eng.make_bank(cd, tiered="single")
    sp = paper_default_params(num_devices=N, sample_count=4,
                              data_sizes=np.asarray(sizes, np.float32))
    params0 = task.init(jax.random.PRNGKey(0))
    grid = ScenarioGrid.create(
        controllers=["lroa", "uni_d", "uni_s", "lroa"], seeds=[0, 1, 2, 3],
        V=[100.0, 50.0, 0.0, 200.0], lam=0.5, sample_count=4)
    lr = np.full(T, 0.1, np.float32)
    plain = Arena(eng)
    h_all = plain.sample_channels(grid, T, N)
    sharded = Arena(eng, mesh=make_fl_mesh())
    rep_1 = plain.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    rep_2 = sharded.run(params0, sp, bank, grid, T, lr, h_all=h_all)
    for a, b in zip(jax.tree_util.tree_leaves(rep_1.params),
                    jax.tree_util.tree_leaves(rep_2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)
    for name in rep_1.metrics:
        np.testing.assert_allclose(rep_1.metrics[name],
                                   rep_2.metrics[name], rtol=1e-5,
                                   atol=1e-4)
    np.testing.assert_allclose(rep_1.queues, rep_2.queues, rtol=1e-5,
                               atol=1e-4)
    # indivisible scenario counts are a clear error, not silent padding
    bad = ScenarioGrid.create(controllers=["lroa"] * 3, seeds=[0, 1, 2],
                              V=1.0, lam=1.0, sample_count=4)
    try:
        sharded.run(params0, sp, bank, bad, T, lr)
        raise SystemExit("expected divisibility error")
    except ValueError as e:
        assert "divisible" in str(e)
    print("ARENA-SHARDED-OK")
""")


def test_scenario_sharded_arena_matches_unsharded(tmp_path):
    """Whole-rollout-per-shard over a 2-device CPU ('data',) mesh (forced
    host devices in a subprocess) must reproduce the unsharded arena —
    the scenario axis has no cross-shard communication."""
    script = tmp_path / "arena_shard_check.py"
    script.write_text(_SHARD_SCRIPT)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ,
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ARENA-SHARDED-OK" in out.stdout
