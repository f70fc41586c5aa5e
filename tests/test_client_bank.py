"""ClientBank data plane: bank-gathered rounds are bit-identical to the
PR-1 host-stacked path, zero client data crosses the host boundary after
bank construction, the mesh-sharded round matches single-device, and the
sharded/partial aggregation primitives match their references."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic_image_classification
from repro.data.pipeline import (bucket_examples, stack_client_arrays)
from repro.fl import (ChannelConfig, ChannelProcess, ClientBank,
                      ClientConfig, RoundEngine, aggregate_fused,
                      aggregate_stacked, ParamRavel)
from repro.models import MLPTask

BS = 16


def _client_data(sizes, seed=3):
    total = sum(sizes)
    x, y = synthetic_image_classification(total, (8, 8, 1), num_classes=4,
                                          noise=0.3, seed=seed)
    offs = np.cumsum([0] + list(sizes))
    return [(x[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]])
            for i in range(len(sizes))]


def _engine_and_bank(sizes, **engine_kw):
    task = MLPTask(input_dim=64, num_classes=4, hidden=32)
    eng = RoundEngine(task, ClientConfig(local_epochs=2, batch_size=BS),
                      **engine_kw)
    # this file pins the single-global-bucket ClientBank contract; the
    # bucket-ladder bank has its own suite (tests/test_tiered_bank.py)
    bank = eng.make_bank(_client_data(sizes), tiered="single")
    params = task.init(jax.random.PRNGKey(0))
    return eng, bank, params


def _round_args(k, seed=5):
    rng = np.random.default_rng(seed)
    selected = rng.integers(0, 6, k)
    coeffs = rng.dirichlet(np.ones(k)).astype(np.float32)
    rngs = jax.random.split(jax.random.PRNGKey(seed), k)
    return selected, coeffs, rngs


def _assert_trees_bitwise(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- bank construction -----------------------------------------------------


def test_stack_client_arrays_contract():
    sizes = [40, 17, 64]
    cd = [(np.arange(n, dtype=np.float32)[:, None] + 100.0 * j,
           np.full(n, j)) for j, n in enumerate(sizes)]
    xs, ys, steps, n_ex = stack_client_arrays(cd, BS)
    b = bucket_examples(sizes, BS)
    assert xs.shape == (3, b, 1) and ys.shape == (3, b)
    assert b >= max(sizes)
    for j, n in enumerate(sizes):
        np.testing.assert_array_equal(xs[j, :, 0],
                                      (np.arange(b) % n) + 100.0 * j)
    np.testing.assert_array_equal(steps, [max(n // BS, 1) for n in sizes])
    np.testing.assert_array_equal(n_ex, sizes)


def test_bank_uniform_flag_and_device_args():
    eng, bank, _ = _engine_and_bank([64] * 6)
    assert bank.uniform and bank.bucket_examples == 64
    xs, ys, ns, ne = bank.device_args()
    assert ns is None and ne is None            # cheap unmasked trace
    assert isinstance(xs, jax.Array)
    eng, bank, _ = _engine_and_bank([64, 10, 33, 64, 100, 17])
    assert not bank.uniform
    _, _, ns, ne = bank.device_args()
    assert ns.shape == ne.shape == (6,)


# -- tentpole: bank path == PR-1 host-stacked path, bit for bit ------------


@pytest.mark.parametrize("sizes", [
    [64] * 6,                        # n_i == B everywhere: unmasked trace
    [64, 10, 33, 64, 100, 17],       # ragged incl. n < bs: masked trace
], ids=["uniform", "padded"])
def test_bank_round_matches_host_stacked_bitwise(sizes):
    eng, bank, params = _engine_and_bank(sizes)
    selected, coeffs, rngs = _round_args(k=4)
    p_bank, l_bank = eng.round_step(params, bank, selected, coeffs, 0.1,
                                    rngs)
    xs, ys, ns, ne = bank.gather_host(selected)
    p_host, l_host = eng.round_step_stacked(params, xs, ys, coeffs, 0.1,
                                            rngs, ns, ne)
    _assert_trees_bitwise(p_bank, p_host)
    np.testing.assert_array_equal(np.asarray(l_bank), np.asarray(l_host))


def test_bank_masked_trace_matches_unmasked_host_trace_bitwise():
    """A ragged bank always gathers with masks, but a selection of only
    exact-fill clients takes the UNMASKED trace on the host path — the
    shared epoch-permutation keys must make the two traces bit-identical."""
    sizes = [128, 10, 33, 64]        # bucket = 128 -> client 0 fills it
    eng, bank, params = _engine_and_bank(sizes)
    assert not bank.uniform and bank.bucket_examples == 128
    selected = np.asarray([0, 0])
    coeffs = np.asarray([0.5, 0.5], np.float32)
    rngs = jax.random.split(jax.random.PRNGKey(2), 2)
    p_bank, l_bank = eng.round_step(params, bank, selected, coeffs, 0.1,
                                    rngs)
    xs, ys, ns, ne = bank.gather_host(selected)
    assert ns is None and ne is None             # host takes unmasked trace
    p_host, l_host = eng.round_step_stacked(params, xs, ys, coeffs, 0.1,
                                            rngs)
    _assert_trees_bitwise(p_bank, p_host)
    np.testing.assert_array_equal(np.asarray(l_bank), np.asarray(l_host))


# -- acceptance: zero per-round host->device transfers of client data ------


def test_round_step_reads_no_host_data_after_bank_construction():
    """Numpy inputs touch the engine only at bank construction: corrupting
    the source arrays (and the bank's host mirror) after construction must
    not change any round — every round reads the device-resident stacks."""
    sizes = [64, 10, 33, 64, 100, 17]
    cd = _client_data(sizes)
    task = MLPTask(input_dim=64, num_classes=4, hidden=32)
    eng = RoundEngine(task, ClientConfig(local_epochs=2, batch_size=BS))
    bank_ctl = eng.make_bank([(x.copy(), y.copy()) for x, y in cd],
                             tiered="single")
    bank = eng.make_bank(cd, tiered="single")
    assert isinstance(bank.xs, jax.Array)
    for x, y in cd:                      # scribble over the source data
        x[:] = np.nan
        y[:] = -1
    params = task.init(jax.random.PRNGKey(0))
    selected, coeffs, rngs = _round_args(k=4)
    p_ctl, l_ctl = eng.round_step(params, bank_ctl, selected, coeffs, 0.1,
                                  rngs)
    p, l = eng.round_step(params, bank, selected, coeffs, 0.1, rngs)
    assert np.all(np.isfinite(np.asarray(l)))
    _assert_trees_bitwise(p, p_ctl)
    np.testing.assert_array_equal(np.asarray(l), np.asarray(l_ctl))
    # the sequential-path view is the bank's private copy, also immune to
    # caller mutation...
    vx, _ = bank.client_view(0)
    assert np.all(np.isfinite(vx))
    # ...and no tiled [N, B, ...] host mirror is retained on the hot path
    # (gather_host builds one lazily for tests/benches only)
    assert bank._tiled is None
    bank.gather_host(selected)
    assert bank._tiled is not None


def test_round_step_rejects_out_of_range_selection():
    """jnp.take clips inside the jit, so the engine must keep the host
    path's IndexError for a selection outside the bank."""
    eng, bank, params = _engine_and_bank([64] * 4)
    coeffs = np.asarray([1.0], np.float32)
    rngs = jax.random.split(jax.random.PRNGKey(0), 1)
    with pytest.raises(IndexError):
        eng.round_step(params, bank, np.asarray([4]), coeffs, 0.1, rngs)
    with pytest.raises(IndexError):
        eng.round_step(params, bank, np.asarray([-1]), coeffs, 0.1, rngs)


# -- mesh sharding: 2-device CPU == single device --------------------------

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

    assert jax.device_count() == 2, jax.devices()
    for sizes in ([64] * 8, [64, 10, 33, 64, 100, 17, 48, 64]):
        total = sum(sizes)
        x, y = synthetic_image_classification(total, (8, 8, 1), 4,
                                              noise=0.3, seed=3)
        offs = np.cumsum([0] + list(sizes))
        cd = [(x[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]])
              for i in range(len(sizes))]
        task = MLPTask(input_dim=64, num_classes=4, hidden=32)
        cfg = ClientConfig(local_epochs=2, batch_size=16)
        eng_s = RoundEngine(task, cfg, mesh=make_fl_mesh())
        eng_1 = RoundEngine(task, cfg)
        bank_s = eng_s.make_bank(cd, tiered="single")
        bank_1 = eng_1.make_bank(cd, tiered="single")
        assert "data" in str(bank_s.xs.sharding)
        params = task.init(jax.random.PRNGKey(0))
        sel = np.asarray([0, 2, 5, 7])
        coeffs = np.asarray([.2, .3, .1, .4], np.float32)
        rngs = jax.random.split(jax.random.PRNGKey(5), 4)
        p_s, l_s = eng_s.round_step(params, bank_s, sel, coeffs, .1, rngs)
        p_1, l_1 = eng_1.round_step(params, bank_1, sel, coeffs, .1, rngs)
        for a, b in zip(jax.tree_util.tree_leaves(p_s),
                        jax.tree_util.tree_leaves(p_1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
        np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_1),
                                   atol=1e-6)
        sp = paper_default_params(num_devices=len(sizes), sample_count=4,
                                  data_sizes=np.asarray(sizes, np.float32))
        h = np.random.default_rng(0).uniform(
            0.05, 0.4, (3, len(sizes))).astype(np.float32)
        lr = np.full(3, .1, np.float32)
        _, _, m_s = eng_s.run_scan(params, sp, bank_s, h, lr,
                                   jax.random.PRNGKey(1), policy="uni_d")
        _, _, m_1 = eng_1.run_scan(params, sp, bank_1, h, lr,
                                   jax.random.PRNGKey(1), policy="uni_d")
        np.testing.assert_allclose(m_s["loss"], m_1["loss"], atol=1e-6)
        # the tiered bank's tier loop must ride the same shard_map:
        # mesh-sharded multi-tier round == single-device multi-tier round
        tb_s = eng_s.make_bank(cd, tiered="tiered")
        tb_1 = eng_1.make_bank(cd, tiered="tiered")
        if tb_1.num_tiers > 1:
            sel_m = np.asarray([1, 4, 0, 5])   # spans several tiers
            assert len(np.unique(tb_1.tier_of[sel_m])) > 1
            p_s, l_s = eng_s.round_step(params, tb_s, sel_m, coeffs, .1,
                                        rngs)
            p_1, l_1 = eng_1.round_step(params, tb_1, sel_m, coeffs, .1,
                                        rngs)
            for a, b in zip(jax.tree_util.tree_leaves(p_s),
                            jax.tree_util.tree_leaves(p_1)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-6)
            np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_1),
                                       atol=1e-6)
            _, _, mt_s = eng_s.run_scan(params, sp, tb_s, h, lr,
                                        jax.random.PRNGKey(1),
                                        policy="uni_d")
            _, _, mt_1 = eng_1.run_scan(params, sp, tb_1, h, lr,
                                        jax.random.PRNGKey(1),
                                        policy="uni_d")
            np.testing.assert_allclose(mt_s["loss"], mt_1["loss"],
                                       atol=1e-6)
    print("SHARDED-OK")
""")


def test_sharded_round_matches_single_device(tmp_path):
    """shard_map over a 2-device CPU ('data',) mesh (forced host devices
    in a subprocess — the parent's jax is already initialised with one)
    must reproduce the single-device round and scan."""
    script = tmp_path / "shard_check.py"
    script.write_text(_SHARD_SCRIPT)
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ,
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED-OK" in out.stdout


# -- sharded / partial aggregation primitives ------------------------------


def test_fl_delta_reduce_matches_reference():
    from repro.kernels import fl_delta_reduce
    rng = np.random.default_rng(0)
    deltas = rng.normal(size=(5, 257)).astype(np.float32)
    coeffs = rng.dirichlet(np.ones(5)).astype(np.float32)
    out = fl_delta_reduce(jnp.asarray(deltas), jnp.asarray(coeffs))
    np.testing.assert_allclose(np.asarray(out), coeffs @ deltas, atol=1e-6)


def test_aggregate_fused_leaf_chunked_off_tpu_matches_ravelled():
    """Off-TPU, aggregate_fused dispatches leaf-chunked (per-leaf
    tensordot, no ravel/concat) — same math as the ravelled kernel path
    (forced interpret)."""
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (9, 5)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (5,))}
    deltas = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 2),
                                    (3,) + p.shape), params)
    coeffs = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    out_auto = aggregate_fused(params, deltas, coeffs)          # leaf path
    out_kernel = aggregate_fused(params, deltas, coeffs,
                                 impl="pallas")                 # ravelled
    out_ref = aggregate_stacked(params, deltas, coeffs)
    for a, b, c in zip(jax.tree_util.tree_leaves(out_auto),
                       jax.tree_util.tree_leaves(out_kernel),
                       jax.tree_util.tree_leaves(out_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)
        np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=1e-6)


def test_aggregate_fused_psum_single_shard_matches_unsharded():
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.fl import aggregate_fused_psum
    mesh = jax.make_mesh((1,), ("data",))
    key = jax.random.PRNGKey(3)
    params = {"w": jax.random.normal(key, (4, 3))}
    deltas = {"w": jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 3))}
    coeffs = jnp.asarray([0.7, 0.3], jnp.float32)
    body = partial(aggregate_fused_psum, axis_name="data")
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(P(), P("data"), P("data")),
                        out_specs=P(), check_vma=False)(params, deltas,
                                                        coeffs)
    expected = aggregate_fused(params, deltas, coeffs)
    _assert_trees_bitwise(out, expected)


# -- vectorised channel process --------------------------------------------


def test_channel_sample_vectorised_in_range_and_deterministic():
    cfg = ChannelConfig(seed=7)
    a = ChannelProcess(32, cfg).sample()
    b = ChannelProcess(32, cfg).sample()
    np.testing.assert_array_equal(a, b)
    assert a.shape == (32,) and a.dtype == np.float32
    assert np.all(a >= cfg.min_gain) and np.all(a <= cfg.max_gain)


def test_channel_sample_sequence_matches_chunking_and_range():
    cfg = ChannelConfig(seed=1)
    h = ChannelProcess(12, cfg).sample_sequence(300, max_block=128)
    assert h.shape == (300, 12)
    assert np.all(h >= cfg.min_gain) and np.all(h <= cfg.max_gain)
    # truncated-exponential mean sits between the bounds, near mean_gain
    assert 0.05 < h.mean() < 0.2
    # empty rollout edge case
    assert ChannelProcess(12, cfg).sample_sequence(0).shape == (0, 12)


def test_channel_sample_jax_device_resident():
    cfg = ChannelConfig(seed=0)
    proc = ChannelProcess(16, cfg)
    h_seq = proc.sample_jax(jax.random.PRNGKey(0), 20)
    assert isinstance(h_seq, jax.Array)
    assert h_seq.shape == (20, 16) and h_seq.dtype == jnp.float32
    h = np.asarray(h_seq)
    assert np.all(h >= cfg.min_gain) and np.all(h <= cfg.max_gain)
    h1 = proc.sample_jax(jax.random.PRNGKey(1))
    assert h1.shape == (16,)
    # T=0 is an empty sequence, not one phantom round
    assert proc.sample_jax(jax.random.PRNGKey(2), 0).shape == (0, 16)
