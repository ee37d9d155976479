"""Pallas kernels vs. pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(KEY.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# mdlora
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,D,F,r", [(64, 64, 128, 4), (128, 256, 64, 8),
                                     (256, 128, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mdlora_kernel_sweep(T, D, F, r, dtype):
    from repro.kernels.mdlora.ops import block_row_mask, mdlora_matmul

    x = randn((T, D), dtype)
    w0 = randn((D, F), dtype, 0.05)
    a = randn((D, r), dtype, 0.1)
    b = randn((r, F), dtype, 0.1)
    mask = block_row_mask([D // 2, D // 4, D // 4], [1.0, 0.0, 1.0])
    ref = mdlora_matmul(x, w0, a, b, mask, impl="xla")
    got = mdlora_matmul(x, w0, a, b, mask, impl="pallas", interpret=True,
                        bt=64, bf=64, bd=64)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_mdlora_masked_blocks_are_inert():
    """Absent-modality rows must not influence the output at all."""
    from repro.kernels.mdlora.ops import block_row_mask, mdlora_matmul

    T, D, F, r = 64, 128, 64, 8
    x = randn((T, D))
    w0, a, b = randn((D, F), scale=0.1), randn((D, r)), randn((r, F))
    mask = block_row_mask([64, 64], [1.0, 0.0])
    y1 = mdlora_matmul(x, w0, a, b, mask, impl="pallas", interpret=True,
                       bt=64, bf=64, bd=64)
    x2 = x.at[:, 64:].add(randn((T, 64), scale=100.0))  # poison masked rows
    y2 = mdlora_matmul(x2, w0, a, b, mask, impl="pallas", interpret=True,
                       bt=64, bf=64, bd=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


@pytest.mark.parametrize("B,D,F,r,A", [(8, 64, 128, 4, 3), (16, 128, 64, 8, 16),
                                       (4, 256, 128, 16, 2)])
def test_mdlora_multi_gathered_matches_per_row_loop(B, D, F, r, A):
    """One gathered call == B single-adapter calls with each row's adapter."""
    from repro.kernels.mdlora.ops import (block_row_masks, mdlora_matmul,
                                          mdlora_matmul_multi)

    x = randn((B, D))
    w0 = randn((D, F), scale=0.05)
    a = randn((A, D, r), scale=0.1)
    b = randn((A, r, F), scale=0.1)
    idx = jnp.asarray(KEY.integers(0, A, B), jnp.int32)
    masks = block_row_masks([D // 2, D // 2],
                            (KEY.random((B, 2)) < 0.7).astype(np.float32))
    for impl in ("xla", "pallas"):
        got = mdlora_matmul_multi(x, w0, a, b, idx, row_mask=masks,
                                  impl=impl, interpret=True)
        rows = [mdlora_matmul(x[i:i + 1], w0, a[int(idx[i])], b[int(idx[i])],
                              masks[i], impl="xla") for i in range(B)]
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jnp.concatenate(rows)),
                                   atol=1e-5, rtol=1e-5)


def test_mdlora_multi_permutation_invariance():
    """Row order must not change any row's result (continuous batching
    shuffles which slot a request occupies)."""
    from repro.kernels.mdlora.ops import mdlora_matmul_multi

    B, D, F, r, A = 16, 128, 128, 8, 5
    x = randn((B, D))
    w0, a = randn((D, F), scale=0.05), randn((A, D, r), scale=0.1)
    b = randn((A, r, F), scale=0.1)
    idx = jnp.asarray(KEY.integers(0, A, B), jnp.int32)
    mask = jnp.asarray(KEY.random((B, D)) < 0.8, jnp.float32)
    perm = jnp.asarray(KEY.permutation(B), jnp.int32)
    y = mdlora_matmul_multi(x, w0, a, b, idx, row_mask=mask,
                            impl="pallas", interpret=True)
    yp = mdlora_matmul_multi(x[perm], w0, a, b, idx[perm],
                             row_mask=mask[perm], impl="pallas",
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(y)[np.asarray(perm)],
                                  np.asarray(yp))


def test_mdlora_multi_matches_single_when_uniform():
    """All rows on one adapter == the single-adapter kernel."""
    from repro.kernels.mdlora.ops import mdlora_matmul, mdlora_matmul_multi

    B, D, F, r = 32, 64, 64, 4
    x = randn((B, D))
    w0, a = randn((D, F), scale=0.05), randn((1, D, r), scale=0.1)
    b = randn((1, r, F), scale=0.1)
    mask = jnp.ones((D,), jnp.float32)
    y1 = mdlora_matmul(x, w0, a[0], b[0], mask, impl="xla")
    y2 = mdlora_matmul_multi(x, w0, a, b, jnp.zeros(B, jnp.int32),
                             impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_mdlora_autotune_blocks_and_roofline_plan():
    """Autotuner returns VMEM-feasible divisors; roofline plan is coherent."""
    from repro.kernels.cohort_agg.autotune import (clear_cache,
                                                   mdlora_candidates,
                                                   select_mdlora_blocks)
    from repro.launch.roofline import mdlora_block_plan

    clear_cache()
    try:
        bt, bf, bd = select_mdlora_blocks((16, 192, 384, 8), multi=True,
                                          n_adapters=4)
        assert bt == 1 and 384 % bf == 0 and 192 % bd == 0
        for cell in mdlora_candidates(48, 192, 384, 8, multi=False):
            assert 48 % cell[0] == 0 and 384 % cell[1] == 0 \
                and 192 % cell[2] == 0
        plan = mdlora_block_plan([
            {"T": 16, "D": 192, "F": 384, "r": 8, "multi": True,
             "n_adapters": 4},
            {"T": 64, "D": 128, "F": 128, "r": 4}])
        assert len(plan) == 2
        for row in plan:
            assert row["flops"] > 0 and row["bytes"] > 0
            assert row["dominant"] in ("compute", "memory")
            assert row["F"] % row["bf"] == 0 and row["D"] % row["bd"] == 0
        assert plan[0]["bt"] == 1 and plan[0]["multi"]
    finally:
        clear_cache()


# ---------------------------------------------------------------------------
# cohort_agg
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D,r", [(4, 64, 4), (9, 128, 8), (16, 256, 1)])
def test_cohort_agg_kernel_sweep(N, D, r):
    from repro.kernels.cohort_agg.ops import cohort_agg_divergence

    deltas = randn((N, D, r))
    W = jnp.asarray(KEY.random((N, D)) * (KEY.random((N, D)) < 0.7),
                    jnp.float32)
    C = jnp.asarray(KEY.random((N, D)) < 0.6, jnp.float32)
    ref = cohort_agg_divergence(deltas, W, C, impl="xla")
    got = cohort_agg_divergence(deltas, W, C, impl="pallas", interpret=True,
                                bd=64)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


def test_cohort_agg_divergence_reduction_matches_eq5():
    """Kernel stats -> Eq. 5 divergence == direct computation."""
    from repro.kernels.cohort_agg.ops import cohort_agg_divergence
    from repro.kernels.cohort_agg.ref import divergence_from_stats

    N, D, r = 6, 32, 4
    deltas = randn((N, D, r))
    C = jnp.asarray(KEY.random((N,)) < 0.8, jnp.float32)
    Cd = jnp.tile(C[:, None], (1, D))
    _, sq, mean, cnt = cohort_agg_divergence(deltas, Cd, Cd, impl="pallas",
                                             interpret=True, bd=32)
    rows = jnp.zeros(D, jnp.int32).at[D // 2:].set(1)  # two blocks
    d = divergence_from_stats(sq, mean, cnt, rows, 2)
    # direct Eq. 5 per block
    nC = float(C.sum())
    for blk, sl in enumerate([slice(0, D // 2), slice(D // 2, D)]):
        x = np.asarray(deltas[:, sl, :], np.float64)
        c = np.asarray(C, bool)
        mu = x[c].mean(0)
        want = float(np.mean([np.sum((x[i] - mu) ** 2)
                              for i in range(N) if c[i]]))
        np.testing.assert_allclose(float(d[blk]), want, rtol=1e-4)


def _quant_inputs(N, D, r):
    q = jnp.asarray(KEY.integers(-127, 128, (N, D, r)), jnp.int8)
    scales = jnp.asarray(KEY.uniform(1e-3, 1e-1, N), jnp.float32)
    W = jnp.asarray(KEY.random((N, D)) * (KEY.random((N, D)) < 0.7),
                    jnp.float32)
    C = jnp.asarray(KEY.random((N, D)) < 0.6, jnp.float32)
    staleness = jnp.asarray(KEY.integers(0, 6, N), jnp.float32)
    return q, scales, W, C, staleness


def _unfused_oracle(q, scales, W, C, staleness, exponent):
    """Materialize the fp32 stack, discount the weights, aggregate."""
    from repro.kernels.cohort_agg.ops import cohort_agg_divergence

    deltas = q.astype(jnp.float32) * scales[:, None, None]
    W_eff = W * jnp.power(1.0 + staleness, -exponent)[:, None]
    return cohort_agg_divergence(deltas, W_eff, C, impl="xla")


@pytest.mark.parametrize("N,D,r", [(4, 64, 4), (9, 96, 8), (16, 100, 1)])
@pytest.mark.parametrize("exponent", [0.0, 0.5])
def test_cohort_agg_quant_matches_unfused(N, D, r, exponent):
    """Fused int8 ingest == dequantize -> discount -> aggregate, for both
    impls, including non-divisible D (96, 100 vs default block caps)."""
    from repro.kernels.cohort_agg.ops import cohort_agg_divergence_quant

    q, scales, W, C, staleness = _quant_inputs(N, D, r)
    want = _unfused_oracle(q, scales, W, C, staleness, exponent)
    for impl in ("xla", "pallas"):
        got = cohort_agg_divergence_quant(q, scales, W, C, staleness,
                                          exponent=exponent, impl=impl,
                                          interpret=True)
        for a, b in zip(want, got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-4, atol=1e-4)


def test_cohort_agg_quant_empty_cohort():
    """All-zero W and C (nobody trained / nobody in cohort) stays finite."""
    from repro.kernels.cohort_agg.ops import cohort_agg_divergence_quant

    N, D, r = 5, 64, 4
    q, scales, _, _, staleness = _quant_inputs(N, D, r)
    Z = jnp.zeros((N, D), jnp.float32)
    for impl in ("xla", "pallas"):
        agg, sq, mean, cnt = cohort_agg_divergence_quant(
            q, scales, Z, Z, staleness, exponent=0.5, impl=impl,
            interpret=True)
        for x in (agg, sq, mean, cnt):
            assert np.isfinite(np.asarray(x)).all()
        np.testing.assert_array_equal(np.asarray(agg), 0.0)
        np.testing.assert_array_equal(np.asarray(cnt), 0.0)


def test_cohort_agg_explicit_bd_snaps_to_divisor():
    """bd larger than (or not dividing) D must snap to a legal block, not
    silently misindex."""
    from repro.kernels.cohort_agg.ops import cohort_agg_divergence

    N, D, r = 6, 96, 4
    deltas = randn((N, D, r))
    W = jnp.asarray(KEY.random((N, D)), jnp.float32)
    C = jnp.asarray(KEY.random((N, D)) < 0.5, jnp.float32)
    ref = cohort_agg_divergence(deltas, W, C, impl="xla")
    for bd in (256, 64, 7):  # no 128k divisor of 96: all snap to 96
        got = cohort_agg_divergence(deltas, W, C, impl="pallas",
                                    interpret=True, bd=bd)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-4)


def test_cohort_agg_autotune_candidates():
    from repro.kernels import runtime
    from repro.kernels.cohort_agg import autotune

    assert runtime.legal_tile(1024, 256) == 256
    assert runtime.legal_tile(384, 256) == 128  # largest 128k divisor <= cap
    assert runtime.legal_tile(96, 64) == 96  # no 128k divisor: whole dim
    assert runtime.legal_tile(1600, 512) == 1600  # hymba d_model
    assert runtime.legal_tile(1024, 64) == 128  # nothing fits under the cap
    for D in (96, 100, 112, 256, 1600, 4096):
        cands = autotune.candidate_bds(D, r=4)
        assert cands and all(D % bd == 0 and (bd % 128 == 0 or bd == D)
                             for bd in cands)
    assert autotune.candidate_bds(1600, r=8) == [1600]
    bd = autotune.select_block_size((8, 256, 4), impl="pallas",
                                    interpret=True, quant=False)
    assert 256 % bd == 0
    # second call hits the process-level cache (same key -> same choice)
    assert autotune.select_block_size((8, 256, 4), impl="pallas",
                                      interpret=True, quant=False) == bd


def test_cohort_agg_default_interpret_tracks_backend():
    """interpret=None must resolve to interpret-mode only on CPU, so
    impl='pallas' is safe by default everywhere."""
    from repro.kernels.runtime import default_interpret, resolve_interpret

    on_cpu = jax.default_backend() == "cpu"
    assert default_interpret() is on_cpu
    assert resolve_interpret(None) is on_cpu
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache is the
    fixed <repo>/.jax_cache."""
    import pathlib

    from repro.kernels import runtime

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert runtime.enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("bd", [64, 96, 200])
def test_kernels_reject_illegal_tiles(bd):
    """A block that is neither a multiple of 128 dividing the dim nor the
    whole dim is refused at the kernel entry, as the TPU compiler would."""
    from repro.kernels.cohort_agg.kernel import cohort_agg_divergence_pallas
    from repro.kernels.mdlora.kernel import mdlora_matmul_pallas

    N, D, r = 4, 256, 4
    with pytest.raises(ValueError, match="legal TPU block"):
        cohort_agg_divergence_pallas(randn((N, D, r)), randn((N, D)),
                                     randn((N, D)), bd=bd, interpret=True)
    with pytest.raises(ValueError, match="legal TPU block"):
        mdlora_matmul_pallas(randn((16, D)), randn((D, 128)), randn((D, r)),
                             randn((r, 128)), jnp.ones((D,)), 2.0, bt=16,
                             bf=128, bd=bd, interpret=True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,K,G,hd,window,softcap", [
    (64, 2, 2, 16, None, None),
    (128, 1, 4, 32, 32, None),
    (128, 4, 1, 64, None, 50.0),
    (64, 2, 3, 16, 16, 30.0),
])
def test_flash_attention_sweep(S, K, G, hd, window, softcap):
    from repro.kernels.flash_attention.ops import flash_attention

    B = 2
    q = randn((B, S, K, G, hd))
    k = randn((B, S, K, hd))
    v = randn((B, S, K, hd))
    pos = jnp.arange(S, dtype=jnp.int32)
    ref = flash_attention(q, k, v, pos, pos, window, softcap, impl="xla")
    got = flash_attention(q, k, v, pos, pos, window, softcap, impl="pallas",
                          interpret=True, bq=32, bt=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_decode_ring_cache():
    from repro.kernels.flash_attention.ops import flash_attention

    B, T, K, G, hd = 2, 64, 2, 2, 16
    q = randn((B, 1, K, G, hd))
    k = randn((B, T, K, hd))
    v = randn((B, T, K, hd))
    kvpos = jnp.where(jnp.arange(T) < 50, jnp.arange(T), -1).astype(jnp.int32)
    qpos = jnp.array([49], jnp.int32)
    ref = flash_attention(q, k, v, qpos, kvpos, None, None, impl="xla")
    got = flash_attention(q, k, v, qpos, kvpos, None, None, impl="pallas",
                          interpret=True, bq=1, bt=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import flash_attention

    B, S, K, G, hd = 1, 64, 2, 2, 32
    q = randn((B, S, K, G, hd), jnp.bfloat16)
    k = randn((B, S, K, hd), jnp.bfloat16)
    v = randn((B, S, K, hd), jnp.bfloat16)
    pos = jnp.arange(S, dtype=jnp.int32)
    ref = flash_attention(q, k, v, pos, pos, None, None, impl="xla")
    got = flash_attention(q, k, v, pos, pos, None, None, impl="pallas",
                          interpret=True, bq=32, bt=32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,h,p,n,chunk,bh", [
    (64, 4, 16, 8, 16, 2), (128, 8, 8, 16, 32, 8), (32, 2, 32, 4, 32, 1),
])
def test_ssd_kernel_sweep(s, h, p, n, chunk, bh):
    from repro.kernels.ssd.ops import ssd

    b = 2
    x = randn((b, s, h, p))
    dt = jax.nn.softplus(randn((b, s, h)))
    A_log = randn((h,))
    Bm = randn((b, s, n))
    Cm = randn((b, s, n))
    yr, fr = ssd(x, dt, A_log, Bm, Cm, chunk=chunk, impl="xla")
    yp, fp = ssd(x, dt, A_log, Bm, Cm, chunk=chunk, impl="pallas",
                 interpret=True, bh=bh)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(fp), np.asarray(fr), atol=1e-4)


def test_ssd_kernel_matches_sequential_recurrence():
    """End-to-end: kernel == token-by-token recurrent decode."""
    from repro.kernels.ssd.ops import ssd
    from repro.models.ssm import ssd_decode_step

    b, s, h, p, n = 1, 32, 2, 8, 4
    x = randn((b, s, h, p))
    dt = jax.nn.softplus(randn((b, s, h)))
    A_log = randn((h,))
    Bm = randn((b, s, n))
    Cm = randn((b, s, n))
    y, fs = ssd(x, dt, A_log, Bm, Cm, chunk=8, impl="pallas", interpret=True,
                bh=2)
    state = jnp.zeros((b, h, p, n))
    for t in range(s):
        yt, state = ssd_decode_step(state, x[:, t], dt[:, t], A_log,
                                    Bm[:, t], Cm[:, t])
        np.testing.assert_allclose(np.asarray(y[:, t]), np.asarray(yt),
                                   atol=1e-4)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(state), atol=1e-4)


