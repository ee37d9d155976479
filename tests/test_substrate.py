"""Substrate tests: optimizer, checkpointing, compression, data, simulator."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


def test_adam_converges_quadratic():
    from repro.optim import adam_init, adam_update

    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = adam_init(params)
    for _ in range(500):
        grads = {"w": 2 * (params["w"] - target)}
        params, state = adam_update(params, grads, state, 0.05)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_adam_moments_fp32_for_bf16_params():
    from repro.optim import adam_init, adam_update

    params = {"w": jnp.ones(4, jnp.bfloat16)}
    state = adam_init(params)
    assert state["m"]["w"].dtype == jnp.float32
    new_p, new_s = adam_update(params, {"w": jnp.ones(4, jnp.bfloat16)},
                               state, 1e-2)
    assert new_p["w"].dtype == jnp.bfloat16
    assert new_s["v"]["w"].dtype == jnp.float32


def test_schedules():
    from repro.optim import linear_warmup_cosine

    fn = linear_warmup_cosine(1.0, warmup=10, total_steps=110)
    assert float(fn(0)) == 0.0
    np.testing.assert_allclose(float(fn(10)), 1.0, rtol=1e-5)
    assert float(fn(110)) < 0.1


# ---------------------------------------------------------------------------
# checkpointing (fault tolerance)
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import CheckpointManager

    tree = {"a": jnp.arange(5, dtype=jnp.float32),
            "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    mgr.save(1, tree, {"round": 1})
    mgr.save(2, jax.tree.map(lambda x: x * 2, tree), {"round": 2})
    restored, meta = mgr.restore_latest(tree)
    assert meta["round"] == 2
    np.testing.assert_allclose(np.asarray(restored["a"]),
                               np.arange(5) * 2)
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_retention_and_resume(tmp_path):
    from repro.checkpoint import CheckpointManager

    tree = {"w": jnp.zeros(3)}
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    for s in range(5):
        mgr.save(s, jax.tree.map(lambda x: x + s, tree))
    assert mgr.steps() == [3, 4]  # retention
    mgr2 = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    assert mgr2.latest_step() == 4  # resume across "process restart"


def test_checkpoint_structure_mismatch_rejected(tmp_path):
    from repro.checkpoint import restore_tree, save_tree

    save_tree(str(tmp_path / "x"), {"a": jnp.ones(3)})
    with pytest.raises(ValueError):
        restore_tree(str(tmp_path / "x"), {"a": jnp.ones(3),
                                           "b": jnp.ones(2)})


def test_engine_state_checkpoint_roundtrip(tmp_path):
    """Full FL server state survives a simulated preemption."""
    from repro.checkpoint import CheckpointManager
    from repro.core.engine import FedConfig, FedRun
    from repro.core.strategies import get_strategy
    from repro.core.tasks import MMTask
    from repro.data import make_har_dataset, mm_config_for
    from repro.sim import make_fleet

    ds = make_har_dataset("pamap2", windows_per_subject=40, seed=0)
    fleet = make_fleet(2, 1, 1, M=4)
    cfg = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                        cnn_ch=(8, 16))
    task, tr0 = MMTask.create(cfg, KEY)
    fed = FedConfig(rounds=2, local_epochs=1, steps_per_epoch=1,
                    batch_size=8, eval_every=2)
    run = FedRun.create(task, tr0, get_strategy("relief"), fleet, fed)
    run.round(ds)
    mgr = CheckpointManager(str(tmp_path / "fed"), keep=1)
    mgr.save(run.state.round, {"trainable": run.state.trainable},
             {"dbar": run.state.dbar.tolist(), "round": run.state.round})
    restored, meta = mgr.restore_latest({"trainable": run.state.trainable})
    assert meta["round"] == 1
    for a, b in zip(jax.tree.leaves(restored["trainable"]),
                    jax.tree.leaves(run.state.trainable)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 10**6))
def test_int8_quantization_error_bound(seed):
    from repro.dist import dequantize_int8, quantize_int8

    rng = np.random.default_rng(seed)
    tree = {"w": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)}
    qt, sc = quantize_int8(tree)
    assert qt["w"].dtype == jnp.int8
    back = dequantize_int8(qt, sc)
    max_err = float(jnp.max(jnp.abs(back["w"] - tree["w"])))
    assert max_err <= float(sc["w"]) * 0.5 + 1e-7  # half-step rounding


def test_topk_error_feedback_accumulates():
    from repro.dist import topk_sparsify

    x = {"w": jnp.asarray([1.0, 0.1, 0.01, -2.0])}
    sparse, err = topk_sparsify(x, frac=0.25)  # keep 1 of 4
    assert int(jnp.sum(sparse["w"] != 0)) == 1
    assert float(sparse["w"][3]) == -2.0
    # error feedback: dropped mass resurfaces next round
    sparse2, err2 = topk_sparsify({"w": jnp.zeros(4)}, frac=0.25, error=err)
    assert float(sparse2["w"][0]) == 1.0


def test_compressed_size_accounting():
    from repro.dist import compressed_size_bytes

    tree = {"w": jnp.zeros((100,))}
    assert compressed_size_bytes(tree, "none") == 400
    assert compressed_size_bytes(tree, "int8") == 104
    assert compressed_size_bytes(tree, "topk", 0.1) == 80


def test_compressed_size_matches_actual_payload_bytes():
    """The comm-simulator accounting equals the bytes a real int8 payload
    occupies: q.nbytes per leaf + one fp32 scale per leaf."""
    from repro.dist import compressed_size_bytes, quantize_int8

    rng = np.random.default_rng(3)
    tree = {"a": jnp.asarray(rng.normal(size=(17, 5)), jnp.float32),
            "b": {"c": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}}
    qt, sc = quantize_int8(tree)
    actual = sum(np.asarray(q).nbytes for q in jax.tree.leaves(qt)) + \
        sum(np.asarray(s).nbytes for s in jax.tree.leaves(sc))
    assert compressed_size_bytes(tree, "int8") == actual
    assert compressed_size_bytes(tree, "none") == \
        sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 10**6))
def test_int8_error_feedback_telescopes(seed):
    """Over T rounds, sum(dequantized uploads) + final residual ==
    sum(raw updates): the EF stream is unbiased up to the carried residual."""
    from repro.dist import dequantize_int8, quantize_int8_ef

    rng = np.random.default_rng(seed)
    T = 6
    updates = [{"w": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)}
               for _ in range(T)]
    err = None
    shipped = jnp.zeros((16, 4))
    for u in updates:
        qt, sc, err = quantize_int8_ef(u, err)
        shipped = shipped + dequantize_int8(qt, sc)["w"]
    total = sum(np.asarray(u["w"]) for u in updates)
    np.testing.assert_allclose(np.asarray(shipped + err["w"]), total,
                               rtol=1e-4, atol=1e-5)
    # the carried residual itself stays bounded by one quantization step
    assert float(jnp.max(jnp.abs(err["w"]))) <= float(sc["w"]) * 0.5 + 1e-7


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 10**6))
def test_int8_stacked_matches_per_client(seed):
    """Stacked per-client quantization == quantizing each client's slice
    separately (scales leaves are [K], one symmetric scale per client)."""
    from repro.dist import (dequantize_int8_stacked, quantize_int8,
                            quantize_int8_stacked)

    rng = np.random.default_rng(seed)
    K = 4
    stack = {"w": jnp.asarray(rng.normal(size=(K, 6, 3)), jnp.float32)}
    qt, sc, resid = quantize_int8_stacked(stack)
    assert qt["w"].dtype == jnp.int8 and sc["w"].shape == (K,)
    for k in range(K):
        qk, sk = quantize_int8({"w": stack["w"][k]})
        np.testing.assert_array_equal(np.asarray(qt["w"][k]),
                                      np.asarray(qk["w"]))
        np.testing.assert_allclose(float(sc["w"][k]), float(sk["w"]),
                                   rtol=1e-6)
    # residual is exactly the round-trip error
    back = dequantize_int8_stacked(qt, sc)
    np.testing.assert_allclose(np.asarray(resid["w"]),
                               np.asarray(stack["w"] - back["w"]),
                               atol=1e-7)


def test_topk_error_feedback_telescopes_over_rounds():
    """Same telescoping contract for the top-k codec across many rounds."""
    from repro.dist import topk_sparsify

    rng = np.random.default_rng(0)
    T = 8
    updates = [{"w": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
               for _ in range(T)]
    err = None
    shipped = jnp.zeros(32)
    for u in updates:
        sparse, err = topk_sparsify(u, frac=0.25, error=err)
        shipped = shipped + sparse["w"]
    total = sum(np.asarray(u["w"]) for u in updates)
    np.testing.assert_allclose(np.asarray(shipped + err["w"]), total,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_har_dataset_shapes_and_noniid():
    from repro.data import make_har_dataset

    ds = make_har_dataset("pamap2", windows_per_subject=60, seed=0)
    assert ds.n_subjects == 8
    assert ds.channels() == 10  # 3+3+3+1
    assert all(x.shape[1:] == (256, 10) for x in ds.train_x)
    # non-IID: per-subject class histograms differ
    h = [np.bincount(y, minlength=12) / len(y) for y in ds.train_y]
    dists = [np.abs(h[i] - h[j]).sum() for i in range(8) for j in range(i)]
    assert np.mean(dists) > 0.2

    ds2 = make_har_dataset("mhealth", windows_per_subject=40, seed=1)
    assert ds2.n_subjects == 10
    assert ds2.channels() == 11  # 3+3+3+2 (ECG 2 leads)


def test_har_classes_are_separable():
    """A class-conditional mean classifier beats chance by a wide margin —
    the synthetic signals carry learnable class structure."""
    from repro.data import make_har_dataset

    ds = make_har_dataset("pamap2", windows_per_subject=120, seed=0)

    def feats(xs):  # channel means + amplitudes (class-dependent)
        return np.concatenate([xs.mean(1), xs.std(1)], axis=-1)

    x = feats(np.concatenate(ds.train_x))
    y = np.concatenate(ds.train_y)
    xt = feats(np.concatenate(ds.test_x))
    yt = np.concatenate(ds.test_y)
    mus = np.stack([x[y == c].mean(0) if (y == c).any() else
                    np.zeros(x.shape[1]) for c in range(12)])
    pred = np.argmin(((xt[:, None] - mus[None]) ** 2).sum(-1), axis=1)
    assert (pred == yt).mean() > 0.25  # chance = 1/12


def test_token_stream_learnable():
    from repro.data import synthetic_token_batches

    batches = list(synthetic_token_batches(64, 4, 32, 3, seed=0))
    assert len(batches) == 3
    assert batches[0]["tokens"].shape == (4, 32)
    # order-1 structure: conditional entropy < marginal entropy
    toks = np.concatenate([b["tokens"].reshape(-1) for b in batches])


# ---------------------------------------------------------------------------
# simulator
# ---------------------------------------------------------------------------


def test_simulate_round_straggler_and_energy():
    from repro.sim import make_fleet
    from repro.sim.timing import simulate_round

    fleet = make_fleet(1, 1, 1, M=4)
    sel = np.ones(3, bool)
    fl = np.array([1e12, 1e12, 1e12])
    up = np.array([1e6, 1e6, 1e6])
    cost = simulate_round(fleet, sel, fl, np.zeros(3), up, t_overhead=0.0,
                          utilization=1.0)
    # round bound by slowest (5 TOPS) device
    expect = 1e12 / (5e12)
    assert abs(cost.round_time_s - (expect + 8 * 1e6 / 1e8)) < 0.05
    assert cost.fleet_energy_j > 0
    # idle time only for the fast devices
    assert cost.per_device_idle_s[0] > cost.per_device_idle_s[2] - 1e-9


@pytest.mark.parametrize("mode", ["flop_proportional", "fwd_aware"])
def test_cycle_flops(mode):
    """The client-cycle cost model against values worked by hand: 3 groups
    of 10/20/30 FLOPs per example, 2 x 3 x 4 = 24 examples a cycle."""
    from types import SimpleNamespace

    from repro.sim.timing import cycle_flops

    layout = SimpleNamespace(flops=np.array([10.0, 20.0, 30.0]))
    fed = SimpleNamespace(sim_mode=mode, local_epochs=2, steps_per_epoch=3,
                          batch_size=4)
    S = np.array([[1, 0, 1], [0, 0, 0], [1, 1, 1]], bool)
    forward_calls = []

    def forward():
        forward_calls.append(1)
        return 100.0

    trained, fixed = cycle_flops(layout, fed, S, forward)
    if mode == "flop_proportional":
        # trained groups x mean group cost (20) x 24 examples x 3
        np.testing.assert_array_equal(trained, [2880.0, 0.0, 4320.0])
        np.testing.assert_array_equal(fixed, [0.0, 0.0, 0.0])
        assert not forward_calls  # the forward is not charged here
    else:
        # selected groups' own cost (40, 0, 60) x 24 examples x 2, plus the
        # full forward (100) x 24 examples for every client
        np.testing.assert_array_equal(trained, [1920.0, 0.0, 2880.0])
        np.testing.assert_array_equal(fixed, [2400.0, 2400.0, 2400.0])


def test_hetero_scaling():
    from repro.sim import make_fleet

    f10 = make_fleet(1, 1, 1, hetero_scale=10.0)
    f100 = make_fleet(1, 1, 1, hetero_scale=100.0)
    assert f10.tops[0] / f10.tops[2] == pytest.approx(10.0)
    assert f100.tops[0] / f100.tops[2] == pytest.approx(100.0)
