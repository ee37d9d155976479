"""Byzantine-robust cohort reducer tests (core/aggregation.py): hypothesis
property tests for the array-level estimators (permutation invariance,
mean agreement, breakdown boundedness, degenerate-trim/median equivalence)
plus layout-level unit tests for robust_combine / Krum / the robust modes of
cohort_reduce and their strategies.py wiring."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import dist
from repro.core import aggregation as AG
from repro.core import mdlora
from repro.core.async_engine import AsyncFedConfig, AsyncFedRun
from repro.core.strategies import (get_strategy, relief_krum, relief_median,
                                   relief_trimmed)
from repro.core.tasks import MMTask
from repro.data import make_har_dataset, mm_config_for

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def cnn_task():
    cfg = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                        cnn_ch=(8, 16))
    return MMTask.create(cfg, KEY)


def _stack(tree, n, key):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: jax.tree.map(
        lambda x: jax.random.normal(k, x.shape, jnp.float32), tree))(keys)


def _full_WC(lay, n):
    """Weights/cohort for n clients owning everything, training everything."""
    mm = jnp.ones((n, lay.n_modalities))
    trained = jnp.ones((n, lay.G)) * jnp.asarray(lay.sizes > 0)
    W = AG.cohort_weights(lay, trained, mm)
    C = trained
    return W, C


# ---------------------------------------------------------------------------
# property tests — array-level estimators
# ---------------------------------------------------------------------------

_vals = st.lists(st.floats(-100.0, 100.0, allow_nan=False, width=32),
                 min_size=3, max_size=9, unique=True)


@settings(max_examples=50, deadline=None)
@given(_vals, st.floats(0.0, 0.45), st.integers(0, 2**31 - 1))
def test_prop_permutation_invariance(vals, trim_frac, seed):
    """Shuffling the cohort rows never changes a robust estimate (values
    kept distinct: with exact duplicates and non-uniform weights the
    rank-based trim may keep a different duplicate, which is only
    value-equivalent under unique inputs)."""
    x = np.asarray(vals, np.float32)[:, None]
    w = (np.abs(x) * 0.1 + 0.5).astype(np.float32)  # positive, row-specific
    perm = np.random.default_rng(seed).permutation(len(vals))
    np.testing.assert_allclose(
        AG.trimmed_mean(x, w, trim_frac), AG.trimmed_mean(x[perm], w[perm],
                                                          trim_frac),
        rtol=1e-4, atol=1e-3)  # fp32 sums reassociate under permutation
    np.testing.assert_allclose(
        AG.coordinate_median(x, w > 0),
        AG.coordinate_median(x[perm], w[perm] > 0), rtol=1e-6)


@settings(max_examples=50, deadline=None)
@given(_vals)
def test_prop_trim_zero_is_weighted_mean(vals):
    """beta = 0 trims nothing: exactly the weighted mean sum(wx)/sum(w)."""
    x = np.asarray(vals, np.float32)[:, None]
    w = (np.abs(x) * 0.1 + 0.5).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(AG.trimmed_mean(x, w, 0.0))[0],
        float((w * x).sum() / w.sum()),
        rtol=1e-4, atol=1e-3)  # atol: near-cancelling sums in fp32


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(-1.0, 1.0, allow_nan=False, width=32),
                min_size=4, max_size=8, unique=True),
       st.floats(-1e6, 1e6, allow_nan=False, width=32))
def test_prop_bounded_under_one_adversary(honest, evil):
    """Breakdown property: one adversarial row of arbitrary magnitude
    cannot push the trimmed mean (beta >= 1/k) or the median outside the
    honest values' range — while the plain mean follows the attacker."""
    x = np.asarray(honest + [evil], np.float32)[:, None]
    w = np.ones_like(x)
    lo, hi = min(honest), max(honest)
    t = float(AG.trimmed_mean(x, w, 0.25)[0])  # k>=5 => trims >=1 each side
    m = float(AG.coordinate_median(x, w > 0)[0])
    assert lo - 1e-5 <= t <= hi + 1e-5
    assert lo - 1e-5 <= m <= hi + 1e-5


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(-50.0, 50.0, allow_nan=False, width=32),
                min_size=3, max_size=9, unique=True))
def test_prop_degenerate_trim_equals_median(vals):
    """At beta -> 1/2 the trimmed mean degenerates to the median element
    (exactly so for odd cohorts, where a single middle value survives)."""
    if len(vals) % 2 == 0:
        vals = vals[:-1]
    x = np.asarray(vals, np.float32)[:, None]
    w = np.ones_like(x)
    np.testing.assert_allclose(AG.trimmed_mean(x, w, 0.5),
                               AG.coordinate_median(x, w > 0), rtol=1e-6)


# deterministic anchors for the same properties (run even without hypothesis)
def test_reducers_deterministic_anchor():
    x = np.array([[1.0], [3.0], [2.0], [1000.0]], np.float32)
    w = np.array([[0.1], [0.2], [0.3], [0.4]], np.float32)
    np.testing.assert_allclose(np.asarray(AG.trimmed_mean(x, w, 0.0))[0],
                               float((w * x).sum() / w.sum()), rtol=1e-5)
    assert 1.0 <= float(AG.trimmed_mean(x, w, 0.25)[0]) <= 3.0
    np.testing.assert_allclose(AG.coordinate_median(x, w > 0), [2.5])
    np.testing.assert_allclose(
        AG.trimmed_mean(x[:3], np.ones((3, 1), np.float32), 0.5),
        AG.coordinate_median(x[:3], np.ones((3, 1), bool)))
    # empty coordinate -> 0, never NaN
    assert float(AG.trimmed_mean(x, np.zeros_like(w), 0.1)[0]) == 0.0
    assert float(AG.coordinate_median(x, np.zeros_like(w, bool))[0]) == 0.0


# ---------------------------------------------------------------------------
# layout-level: robust_combine / Krum
# ---------------------------------------------------------------------------


def test_robust_mean_and_trim_zero_match_weighted_combine(cnn_task):
    """kind="mean" falls through to weighted_combine, and beta=0 trimming
    reproduces it exactly (cohort_weights columns sum to 1, so the trimmed
    mean's renormalization is a no-op)."""
    task, tr = cnn_task
    lay = task.layout
    deltas = _stack(tr, 5, KEY)
    W, _ = _full_WC(lay, 5)
    ref = mdlora.weighted_combine(lay, deltas, W)
    for kind, kw in (("mean", {}), ("trimmed", {"trim_frac": 0.0})):
        out = AG.robust_combine(lay, deltas, W, kind, **kw)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=kind)


def test_krum_select_rejects_outlier(cnn_task):
    """Per modality block, Krum scores by distance to the k-f-2 nearest
    co-members: a lone large outlier is never the selected client."""
    task, tr = cnn_task
    lay = task.layout
    deltas = _stack(tr, 5, KEY)
    evil = 2
    deltas = jax.tree.map(
        lambda x: x.at[evil].set(x[evil] * 1e3 + 50.0), deltas)
    member = np.ones((5, lay.G), bool)
    d2 = AG.group_pairwise_sq(lay, deltas)
    sel = np.asarray(AG.krum_select(d2, jnp.asarray(member), f=1))
    nonempty = lay.sizes > 0
    assert (sel[nonempty] != evil).all()
    # and the Krum aggregate is one honest member's block — bounded
    agg = AG.robust_combine(lay, deltas, jnp.asarray(member, jnp.float32)
                            / 5.0, "krum", krum_f=1)
    honest_max = max(float(jnp.max(jnp.abs(jax.tree.leaves(deltas)[i])))
                    for i in range(len(jax.tree.leaves(deltas))))
    for leaf in jax.tree.leaves(agg):
        assert float(jnp.max(jnp.abs(leaf))) <= honest_max


def test_robust_combine_rejects_unknown_kind(cnn_task):
    task, tr = cnn_task
    deltas = _stack(tr, 3, KEY)
    with pytest.raises(ValueError, match="robust kind"):
        AG.robust_combine(task.layout, deltas, jnp.ones((3, task.layout.G)),
                          "huber")


# ---------------------------------------------------------------------------
# cohort_reduce robust modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("robust", ["trimmed", "median", "krum"])
def test_buffer_robust_bounded_where_mean_diverges(cnn_task, robust):
    """One corrupted client (x1000 blow-up) in a 5-client cohort: the plain
    mean follows the attacker; every robust mode stays within the honest
    aggregate's magnitude scale. Divergence stats are unchanged by design
    (they are always the plain Eq. 5 sufficient statistics)."""
    task, tr = cnn_task
    lay = task.layout
    deltas = _stack(tr, 5, KEY)
    corrupted = jax.tree.map(lambda x: x.at[0].mul(1000.0), deltas)
    W, C = _full_WC(lay, 5)

    def agg_norm(robust_kind, d):
        agg, div, cnt = AG.cohort_reduce(lay, d, W, C, robust=robust_kind,
                                         trim_frac=0.25, krum_f=1)
        return (np.sqrt(sum(float(jnp.sum(jnp.square(x)))
                            for x in jax.tree.leaves(agg))), np.asarray(div))

    honest_norm, _ = agg_norm("mean", deltas)
    mean_norm, div_mean = agg_norm("mean", corrupted)
    rob_norm, div_rob = agg_norm(robust, corrupted)
    assert mean_norm > 50 * honest_norm  # the mean diverged
    assert rob_norm < 5 * honest_norm  # the robust estimate did not
    np.testing.assert_allclose(div_rob, div_mean, rtol=1e-4)


def test_buffer_robust_quantized_dequantizes_first(cnn_task):
    """An int8 cohort under a robust mode is dequantized first and reduced
    in fp32 (order statistics cannot be taken over int8 codes): the aggregate
    equals robust_combine over the dequantized stack with the staleness
    discount folded into the weights, and divergence matches the mean
    mode's quantized stats."""
    task, tr = cnn_task
    lay = task.layout
    deltas = _stack(tr, 5, KEY)
    q, scales, _ = dist.quantize_int8_stacked(deltas)
    deq = dist.dequantize_int8_stacked(q, scales)
    W, C = _full_WC(lay, 5)
    stale = jnp.asarray([0.0, 1.0, 2.0, 0.0, 3.0])
    a = 0.5

    qkw = dict(scales=scales, staleness=stale, exponent=a)
    agg, div, cnt = AG.cohort_reduce(lay, q, W, C, robust="median", **qkw)

    disc = 1.0 / (1.0 + np.asarray(stale)) ** a
    ref = AG.robust_combine(lay, deq, W * jnp.asarray(disc)[:, None],
                            "median")
    for x, y in zip(jax.tree.leaves(agg), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)

    _, div_mean, _ = AG.cohort_reduce(lay, q, W, C, **qkw)
    np.testing.assert_allclose(np.asarray(div), np.asarray(div_mean),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# strategies wiring + end-to-end smoke
# ---------------------------------------------------------------------------


def test_strategy_registry_exposes_robust_entries():
    for name, kind in (("relief_trimmed", "trimmed"),
                       ("relief_median", "median"),
                       ("relief_krum", "krum")):
        s = get_strategy(name)
        assert s.robust == kind and s.agg == "cohort"
    assert get_strategy("async_relief").robust == "mean"
    assert relief_trimmed(trim_frac=0.3).trim_frac == 0.3
    assert relief_krum(krum_f=2).krum_f == 2
    assert relief_median().name == "relief_median"


def test_robust_strategy_end_to_end(cnn_task):
    """relief_median survives a sign-flip attack on a small fleet: the run
    completes, the aggregate stays finite, and the run reduces in median
    mode."""
    from repro.sim import FaultModel, make_fleet, scale_fleet
    ds = make_har_dataset("pamap2", windows_per_subject=40, seed=0)
    task, tr0 = cnn_task
    fleet = scale_fleet(make_fleet(2, 2, 1, M=4), 24,
                        np.random.default_rng(5))
    fm = FaultModel(seed=1, byzantine_frac=0.3, corruption="sign_flip",
                    corruption_scale=50.0)
    run = AsyncFedRun.create(
        task, tr0, relief_median(buffer_size=8),
        fleet, AsyncFedConfig(rounds=1, local_epochs=1, steps_per_epoch=1,
                              batch_size=4, eval_every=0, seed=0, faults=fm))
    assert run.strategy.robust == "median"
    hist = run.run(ds, total_updates=40)
    assert np.isfinite(hist["loss"]).all()
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(run.state.trainable))


def test_check_strategy_rejects_bad_robust(cnn_task):
    task, tr0 = cnn_task
    from repro.sim import make_fleet
    s = dataclasses.replace(relief_median(), robust="bogus")
    with pytest.raises(ValueError, match="robust"):
        AsyncFedRun.create(task, tr0, s, make_fleet(2, 1, 1, M=4),
                           AsyncFedConfig())
