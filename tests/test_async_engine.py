"""Event-driven async runtime tests: sync parity, straggler decoupling,
cohort freezing, and the whole-cohort reduction of the server step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as AG
from repro.core import divergence as DV
from repro.core import mdlora
from repro.core.async_engine import AsyncFedConfig, AsyncFedRun
from repro.core.engine import FedConfig, FedRun
from repro.core.strategies import (async_accessible, async_fedbuff,
                                   async_relief, get_strategy)
from repro.core.tasks import MMTask
from repro.data import make_har_dataset, mm_config_for
from repro.sim import make_fleet
from repro.sim.events import EventQueue

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def setup():
    ds = make_har_dataset("pamap2", windows_per_subject=60, seed=0)
    cfg = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                        cnn_ch=(8, 16))
    task, tr0 = MMTask.create(cfg, KEY)
    return ds, task, tr0


@pytest.fixture(scope="module")
def tx_task():
    """Backbone 2 (layer-stacked encoder LoRA): its layout has all three
    leaf classes — the fusion leaf, axis-0 group leaves, whole-group
    leaves — where the CNN's has no axis-0 leaves."""
    cfg = mm_config_for("pamap2", backbone="transformer", d_feat=8,
                        d_fused=32, enc_layers=2, enc_d=16, enc_ff=32)
    return MMTask.create(cfg, KEY)


# ---------------------------------------------------------------------------
# event queue
# ---------------------------------------------------------------------------


def test_event_queue_deterministic_fifo_ties():
    q = EventQueue()
    q.push(2.0, client=0)
    q.push(1.0, client=1)
    q.push(1.0, client=2)
    q.push(1.5, client=3)
    batch = q.pop_simultaneous()
    assert [e.client for e in batch] == [1, 2]  # FIFO within the tie
    assert [e.client for e in q.drain()] == [3, 0]


# ---------------------------------------------------------------------------
# sync parity: the anchor for everything else
# ---------------------------------------------------------------------------


def test_parity_with_sync_engine(setup):
    """Homogeneous fleet + buffer K=N + zero staleness discount must
    reproduce the synchronous engine's global trainable bit-for-bit after
    one logical round (same rng)."""
    ds, task, tr0 = setup
    fleet = make_fleet(4, 0, 0, M=4)  # identical devices, full modalities
    kw = dict(rounds=1, local_epochs=1, steps_per_epoch=2, batch_size=8,
              eval_every=10, seed=0)
    sync = FedRun.create(task, tr0, get_strategy("relief"), fleet,
                         FedConfig(**kw))
    sync.round(ds)

    arun = AsyncFedRun.create(
        task, tr0, async_relief(buffer_size=fleet.N, staleness_exponent=0.0),
        fleet, AsyncFedConfig(**kw))
    arun.run(ds, total_updates=fleet.N)

    assert arun.state.round == 1  # exactly one flush
    for a, b in zip(jax.tree.leaves(sync.state.trainable),
                    jax.tree.leaves(arun.state.trainable)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parity_streaming_pallas_interpret(setup):
    """Same parity flush through the Pallas (interpret) cohort-agg path —
    kernel and XLA oracle agree to float tolerance on the fused leaf."""
    ds, task, tr0 = setup
    fleet = make_fleet(4, 0, 0, M=4)
    kw = dict(rounds=1, local_epochs=1, steps_per_epoch=2, batch_size=8,
              eval_every=10, seed=0)
    runs = {}
    for impl in ("xla", "pallas"):
        r = AsyncFedRun.create(
            task, tr0,
            async_relief(buffer_size=fleet.N, staleness_exponent=0.0),
            fleet, AsyncFedConfig(agg_impl=impl, agg_interpret=True, **kw))
        r.run(ds, total_updates=fleet.N)
        runs[impl] = r.state.trainable
    for a, b in zip(jax.tree.leaves(runs["xla"]),
                    jax.tree.leaves(runs["pallas"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


# ---------------------------------------------------------------------------
# straggler decoupling at 100x heterogeneity
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~40s: two full federated runs on 1 CPU core
def test_async_beats_sync_wallclock_at_100x(setup):
    """At 100x compute heterogeneity the async runtime reaches the sync
    FedAvg run's target loss in less simulated wall-clock, and absorbs the
    same total client work in strictly less time (no straggler barrier)."""
    ds, task, tr0 = setup
    fleet = make_fleet(3, 3, 2, M=4, hetero_scale=100.0)
    R = 8
    kw = dict(rounds=R, local_epochs=2, steps_per_epoch=3, batch_size=32,
              eval_every=100, seed=0, utilization=1e-4, t_overhead=1e-3)
    sync = FedRun.create(task, tr0, get_strategy("fedavg"), fleet,
                         FedConfig(**kw))
    hs = sync.run(ds)
    sync_times = np.cumsum(hs["round_time_s"])
    sync_total = float(sync_times[-1])
    target = float(np.mean(hs["loss"][-2:]))

    arun = AsyncFedRun.create(
        task, tr0, async_relief(buffer_size=2, staleness_exponent=0.5),
        fleet, AsyncFedConfig(**kw))
    ha = arun.run(ds)  # same total client updates: R * N

    # same total work, strictly less simulated wall-clock
    assert arun.state.sim_time < sync_total
    # time-to-target-loss (running mean over 3 flushes vs sync final loss)
    smoothed = np.convolve(ha["loss"], np.ones(3) / 3.0, mode="valid")
    reached = np.where(smoothed <= target)[0]
    assert reached.size > 0, (target, smoothed.min())
    t_async = ha["sim_time_s"][int(reached[0]) + 2]
    # sync hits its target only at its final round
    assert t_async < sync_total
    # fast devices actually cycle more often than stragglers
    ups = arun.trace.per_client_updates
    assert ups[np.argmax(fleet.tops)] > ups[np.argmin(fleet.tops)]


# ---------------------------------------------------------------------------
# cohort safety under partial buffers
# ---------------------------------------------------------------------------


def test_empty_cohort_buffers_freeze_blocks(setup):
    """No buffered client owns modalities 2/3 -> their fusion blocks and
    encoder groups stay exactly frozen across flushes; nothing goes NaN."""
    ds, task, tr0 = setup
    fleet = make_fleet(0, 2, 2, M=4)  # mid: {0,1}, low: {0} — 2,3 absent
    fed = AsyncFedConfig(rounds=3, local_epochs=1, steps_per_epoch=2,
                         batch_size=8, eval_every=100, seed=0)
    arun = AsyncFedRun.create(task, tr0,
                              async_accessible(buffer_size=2,
                                               staleness_exponent=0.5),
                              fleet, fed)
    arun.run(ds)
    assert arun.state.round >= 3
    layout = task.layout
    frozen_groups = {g for g in range(layout.G)
                     if layout.modality[g] in (2, 3)}
    leaves0 = jax.tree_util.tree_flatten_with_path(tr0)[0]
    leaves1 = jax.tree_util.tree_flatten_with_path(arun.state.trainable)[0]
    rg = layout.row_group_vector(
        next(l for p, l in leaves0
             if mdlora.path_str(p) == layout.fusion_a_path).shape[0])
    for (p0, l0), (_, l1) in zip(leaves0, leaves1):
        a0, a1 = np.asarray(l0, np.float32), np.asarray(l1, np.float32)
        assert np.isfinite(a1).all(), mdlora.path_str(p0)
        p = mdlora.path_str(p0)
        if p == layout.fusion_a_path:
            frozen_rows = np.isin(rg, list(frozen_groups))
            np.testing.assert_array_equal(a0[frozen_rows], a1[frozen_rows])
        elif layout.leaf_group.get(p) in frozen_groups:
            np.testing.assert_array_equal(a0, a1)


def test_staleness_discount_downweights_stale_clients(setup):
    _, task, _ = setup
    layout = task.layout
    trained = jnp.ones((2, layout.G))
    mmask = jnp.ones((2, layout.n_modalities))
    disc = AG.staleness_discounts(np.array([0.0, 3.0]), 1.0)  # 1 and 1/4
    W = AG.cohort_weights(layout, trained, mmask, client_scale=disc)
    Wn = np.asarray(W)
    nz = layout.sizes > 0
    assert (Wn[0, nz] > Wn[1, nz]).all()
    np.testing.assert_allclose(Wn[:, nz].sum(0), 1.0, rtol=1e-6)
    # exponent 0 == no discounting
    W0 = AG.cohort_weights(layout, trained, mmask,
                           client_scale=AG.staleness_discounts(
                               np.array([0.0, 3.0]), 0.0))
    np.testing.assert_array_equal(np.asarray(W0)[:, nz], 0.5)


# ---------------------------------------------------------------------------
# the cohort reduction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["fp32", "int8"])
def test_cohort_reduce_matches_oracles(tx_task, codec):
    """cohort_reduce over a whole cohort == weighted_combine +
    group_divergence, on every leaf class. An int8 cohort is checked
    against the oracles over its dequantized stack, with the staleness
    discount deferred into the reduction (W from defer_scale) and applied
    up front for the oracle."""
    from repro import dist

    task, tr0 = tx_task
    layout = task.layout
    rng = np.random.default_rng(0)
    N = 6
    deltas = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=(N,) + x.shape), jnp.float32),
        tr0)
    trained = jnp.asarray(rng.random((N, layout.G)) > 0.4, jnp.float32)
    mmask = jnp.asarray(rng.random((N, layout.n_modalities)) > 0.3,
                        jnp.float32)
    C = trained
    if codec == "fp32":
        W = AG.cohort_weights(layout, trained, mmask)
        ref_x, W_ref, kw = deltas, W, {}
    else:
        q, scales, _ = dist.quantize_int8_stacked(deltas)
        staleness = jnp.asarray(rng.integers(0, 5, N), jnp.float32)
        disc = AG.staleness_discounts(staleness, 0.5)
        W = AG.cohort_weights(layout, trained, mmask, client_scale=disc,
                              defer_scale=True)
        W_ref = AG.cohort_weights(layout, trained, mmask, client_scale=disc)
        ref_x = dist.dequantize_int8_stacked(q, scales)
        deltas = q
        kw = dict(scales=scales, staleness=staleness, exponent=0.5)

    ref_agg = mdlora.weighted_combine(layout, ref_x, W_ref)
    ref_d = DV.group_divergence(layout, ref_x, C)
    agg, d, cnt = AG.cohort_reduce(layout, deltas, W, C, **kw)
    for a, b in zip(jax.tree.leaves(ref_agg), jax.tree.leaves(agg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    d, ref_d = np.asarray(d), np.asarray(ref_d)
    if codec == "fp32":
        np.testing.assert_allclose(d, ref_d, rtol=1e-4, atol=1e-4)
    else:
        # in units of each group's largest squared client norm: d =
        # E|x|^2 - |Ex|^2 cancels, and a one-member cohort whose int8
        # scales are applied inside the reduction leaves ~10 eps of it
        norms = np.asarray(jax.vmap(
            lambda t: mdlora.group_norms(layout, t))(ref_x))
        s = np.maximum(norms.max(0), 1e-12)
        np.testing.assert_allclose(d / s, ref_d / s, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(C.sum(0)))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_quantized_reduce_matches_dequantized(setup, impl):
    """Fused int8 ingest (cohort_reduce with ``scales``) == dequantizing
    client-side and reducing fp32 (without ``scales``), with the staleness
    discount folded in (defer_scale)."""
    from repro import dist

    _, task, tr0 = setup
    layout = task.layout
    rng = np.random.default_rng(1)
    N = 5
    deltas = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=(N,) + x.shape) * 1e-2,
                              jnp.float32), tr0)
    q, scales, _ = dist.quantize_int8_stacked(deltas)
    deq = dist.dequantize_int8_stacked(q, scales)
    trained = jnp.asarray(rng.random((N, layout.G)) > 0.3, jnp.float32)
    mmask = jnp.asarray(rng.random((N, layout.n_modalities)) > 0.2,
                        jnp.float32)
    staleness = jnp.asarray(rng.integers(0, 5, N), jnp.float32)
    a = 0.5
    disc = AG.staleness_discounts(staleness, a)
    W_full = AG.cohort_weights(layout, trained, mmask, client_scale=disc)
    W_def = AG.cohort_weights(layout, trained, mmask, client_scale=disc,
                              defer_scale=True)
    C = trained
    kw = dict(impl=impl, interpret=True)

    ref_agg, ref_d, ref_cnt = AG.cohort_reduce(layout, deq, W_full, C, **kw)
    agg, d, cnt = AG.cohort_reduce(layout, q, W_def, C, scales=scales,
                                   staleness=staleness, exponent=a, **kw)

    for x, y in zip(jax.tree.leaves(ref_agg), jax.tree.leaves(agg)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)
    np.testing.assert_allclose(np.asarray(d), np.asarray(ref_d), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(ref_cnt))


def _eager_flush(run, deltas, S, staleness, mmask_rows):
    """The server flush as it ran eagerly, op by op: the oracle of the
    compiled server step -> (trainable, d, cnt, dbar, mag_ema)."""
    from repro import dist

    layout, strat, fed = run.task.layout, run.strategy, run.fed
    K = len(staleness)
    quant = isinstance(deltas, AG.QuantizedStack)
    fresh = np.ones(K, bool)
    if strat.max_staleness is not None:
        fresh = staleness <= strat.max_staleness
        S = S * fresh[:, None]
    trained = jnp.asarray(S, jnp.float32)
    mmask = jnp.asarray(mmask_rows, jnp.float32)
    a = strat.staleness_exponent
    wkw = dict(client_scale=(None if a == 0.0
                             else AG.staleness_discounts(staleness, a)),
               defer_scale=quant)
    if strat.agg == "cohort":
        W = AG.cohort_weights(layout, trained, mmask, **wkw)
    else:
        ones = jnp.asarray(np.tile(layout.sizes[None, :] > 0, (K, 1))
                           & fresh[:, None], jnp.float32)
        W = AG.cohort_weights(layout, ones, jnp.ones_like(mmask), **wkw)
    C = jnp.asarray(layout.accessible(mmask_rows) & (S > 0), jnp.float32)
    kw = dict(impl=fed.agg_impl, interpret=True, robust=strat.robust)
    if quant:
        agg, d, cnt = AG.cohort_reduce(
            layout, deltas.q, W, C, scales=deltas.scales,
            staleness=jnp.asarray(staleness, jnp.float32), exponent=a, **kw)
        src = dist.dequantize_int8_stacked(deltas.q, deltas.scales)
    else:
        agg, d, cnt = AG.cohort_reduce(layout, deltas, W, C, **kw)
        src = deltas
    tr = jax.tree.map(lambda t, g: t + fed.server_lr * g, run.proto, agg)
    d, cnt = np.asarray(d), np.asarray(cnt)
    dbar = np.ones(layout.G) * 1e-6
    dbar[cnt > 0] = (fed.gamma * d + (1 - fed.gamma) * dbar)[cnt > 0]
    norms = np.asarray(jax.vmap(lambda t: mdlora.group_norms(layout, t))(src))
    mag = (norms * S).sum(0) / np.maximum(S.sum(0), 1)
    mag_ema = np.ones(layout.G)
    mag_ema[S.any(0)] = (0.5 * mag_ema + 0.5 * mag)[S.any(0)]
    return tr, d, cnt, dbar, mag_ema


@pytest.mark.parametrize("codec,agg,a,max_stale,robust,impl", [
    ("int8", "cohort", 0.5, None, "mean", "xla"),
    ("none", "cohort", 0.5, None, "mean", "xla"),
    ("int8", "cohort", 0.0, None, "mean", "xla"),
    ("none", "fedavg", 0.0, None, "mean", "xla"),
    ("int8", "fedavg", 0.5, None, "mean", "xla"),
    ("int8", "cohort", 0.5, 2, "mean", "xla"),
    ("none", "fedavg", 0.5, 2, "mean", "xla"),
    ("int8", "cohort", 0.5, None, "median", "xla"),
    ("none", "cohort", 0.0, None, "median", "xla"),
    ("int8", "cohort", 0.5, None, "mean", "pallas"),
    ("none", "fedavg", 0.5, None, "mean", "pallas"),
])
def test_server_step_matches_eager_reduction(setup, codec, agg, a,
                                             max_stale, robust, impl):
    """The compiled server step (one program: cohort weights, fused ingest,
    fold-in, divergence and magnitude statistics) == the eager reduction it
    replaced, to fp32 tolerance."""
    from repro import dist

    _, task, tr0 = setup
    layout = task.layout
    mk = async_relief if agg == "cohort" else async_fedbuff
    strat = mk(buffer_size=6, staleness_exponent=a, max_staleness=max_stale,
               robust=robust)
    fleet = make_fleet(3, 3, 2, M=4)
    run = AsyncFedRun.create(task, tr0, strat, fleet, AsyncFedConfig(
        rounds=1, eval_every=0, seed=0, uplink_codec=codec, agg_impl=impl,
        agg_interpret=True))
    rng = np.random.default_rng(7)
    K = 6
    deltas = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=(K,) + x.shape) * 1e-2,
                              jnp.float32), tr0)
    if codec == "int8":
        deltas = AG.QuantizedStack(*dist.quantize_int8_stacked(deltas)[:2])
    S = rng.random((K, layout.G)) > 0.3
    staleness = rng.integers(0, 5, K).astype(np.float64)
    mmask_rows = fleet.modality_mask[rng.integers(0, fleet.N, K)]
    tr, d, cnt, dbar, mag_ema = _eager_flush(run, deltas, S.copy(),
                                             staleness, mmask_rows)

    rec = run._flush_arrays(deltas, S.copy(), np.arange(K), rng.random(K),
                            staleness, mmask_rows=mmask_rows)
    for x, y in zip(jax.tree.leaves(tr), jax.tree.leaves(run.state.trainable)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                                   atol=1e-7)
    # d = E|x|^2 - |Ex|^2 cancels: a one-member cohort leaves fp32 noise
    # of the size of eps * |x|^2 (|x|^2 ~ 1e-2 here)
    np.testing.assert_allclose(rec["divergence"], d, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(run.state.dbar, dbar, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(run.state.mag_ema, mag_ema, rtol=1e-5)
    fresh = (staleness <= max_stale if max_stale is not None
             else np.ones(K, bool))
    stack = deltas.q if codec == "int8" else deltas
    _, stats = run.server_step(
        tr0, stack, deltas.scales if codec == "int8" else None,
        (S * fresh[:, None]).astype(np.float32), mmask_rows,
        staleness.astype(np.float32), fresh,
        bd=AG.fusion_block(layout, stack, quant=codec == "int8",
                           impl=run.fed.agg_impl,
                           interpret=run.fed.agg_interpret))
    np.testing.assert_array_equal(np.asarray(stats[1]), cnt)


def test_uplink_codec_int8_end_to_end(setup):
    """uplink_codec='int8' runs both runtimes with finite losses, ~4x less
    upload than fp32, heap/vectorized parity, and bounded model drift."""
    from repro.core.async_engine import VectorizedAsyncFedRun

    ds, task, tr0 = setup
    fleet = make_fleet(3, 3, 2, M=4)
    kw = dict(rounds=2, local_epochs=1, steps_per_epoch=2, batch_size=8,
              eval_every=100, seed=0)
    strat = lambda: async_relief(buffer_size=3, staleness_exponent=0.5)  # noqa: E731

    r32 = AsyncFedRun.create(task, tr0, strat(), fleet, AsyncFedConfig(**kw))
    h32 = r32.run(ds)
    r8 = AsyncFedRun.create(task, tr0, strat(), fleet,
                            AsyncFedConfig(uplink_codec="int8", **kw))
    h8 = r8.run(ds)
    assert np.isfinite(h8["loss"]).all()
    # int8 uplink: 1 byte/param instead of 4
    assert h8["upload_mb"][-1] < h32["upload_mb"][-1] / 3.5
    # quantization noise stays small relative to the model update
    for a, b in zip(jax.tree.leaves(r32.state.trainable),
                    jax.tree.leaves(r8.state.trainable)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32), atol=5e-2)

    rv = VectorizedAsyncFedRun.create(task, tr0, strat(), fleet,
                                      AsyncFedConfig(uplink_codec="int8",
                                                     **kw))
    hv = rv.run(ds)
    np.testing.assert_allclose(hv["loss"], h8["loss"], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(r8.state.trainable),
                    jax.tree.leaves(rv.state.trainable)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32), atol=2e-5)


def test_uplink_codec_validated():
    from repro.core.async_engine import AsyncFedConfig as C

    with pytest.raises(ValueError, match="uplink_codec"):
        from repro.core.async_engine import AsyncFedRun as R
        cfg = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                            cnn_ch=(8, 16))
        task, tr0 = MMTask.create(cfg, KEY)
        R.create(task, tr0, async_relief(buffer_size=2),
                 make_fleet(2, 0, 0, M=4), C(rounds=1, uplink_codec="int4"))


def test_async_fedbuff_runs_and_improves(setup):
    """The modality-unaware async baseline runs end to end with finite
    losses and a valid F1."""
    ds, task, tr0 = setup
    fleet = make_fleet(3, 3, 2, M=4)
    fed = AsyncFedConfig(rounds=2, local_epochs=1, steps_per_epoch=2,
                         batch_size=8, eval_every=100, seed=0)
    arun = AsyncFedRun.create(task, tr0,
                              async_fedbuff(buffer_size=3,
                                            staleness_exponent=0.5),
                              fleet, fed)
    h = arun.run(ds)
    assert np.isfinite(h["loss"]).all()
    assert 0.0 <= h["f1"][-1] <= 1.0
    assert arun.trace.completions == 2 * fleet.N
    assert (np.diff(h["sim_time_s"]) >= 0).all()  # time moves forward
