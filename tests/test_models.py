"""Per-architecture smoke tests + cross-path consistency checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import api

KEY = jax.random.PRNGKey(0)
ARCHS = base.list_archs()


def make_batch(cfg, B=2, S=32):
    if cfg.n_codebooks:
        tok = jax.random.randint(KEY, (B, S, cfg.n_codebooks), 0, cfg.vocab)
        return {"tokens": tok, "labels": tok}
    tok = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(KEY, (B, cfg.n_patches,
                                                   cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_grad_decode(arch):
    cfg = base.get_arch(arch).SMOKE
    params = api.init_model(KEY, cfg)
    batch = make_batch(cfg)
    loss = api.loss_fn(params, cfg, batch)
    assert jnp.isfinite(loss)
    grads = jax.grad(lambda p: api.loss_fn(p, cfg, batch))(params)
    gn = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0
    caches = api.init_caches(cfg, 2, 16)
    tok = batch["tokens"][:, :1]
    logits, _ = api.decode_step(params, cfg, caches, tok, jnp.int32(0))
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ["phi3-medium-14b", "gemma2-27b",
                                  "mixtral-8x7b", "mamba2-1.3b",
                                  "hymba-1.5b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode must reproduce the full-sequence forward."""
    cfg = base.get_arch(arch).SMOKE
    if cfg.n_experts:
        # capacity drops are a train-path semantic; decode (S=1) never
        # drops, so compare at a no-drop capacity factor
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    params = api.init_model(KEY, cfg)
    B, S = 2, 16
    tok = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    full_logits, _ = api.forward(params, cfg, {"tokens": tok})
    caches = api.init_caches(cfg, B, S)
    outs = []
    for t in range(S):
        lg, caches = api.decode_step(params, cfg, caches, tok[:, t:t + 1],
                                     jnp.int32(t))
        outs.append(lg[:, 0])
    step_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step_logits, np.float32),
                               np.asarray(full_logits, np.float32),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """FULL configs carry the exact published hyperparameters."""
    expected = {
        "phi3-medium-14b": dict(n_layers=40, d_model=5120, n_heads=40,
                                n_kv_heads=10, d_ff=17920, vocab=100352),
        "gemma2-27b": dict(n_layers=46, d_model=4608, n_heads=32,
                           n_kv_heads=16, d_ff=36864, vocab=256000),
        "granite-34b": dict(n_layers=88, d_model=6144, n_heads=48,
                            n_kv_heads=1, d_ff=24576, vocab=49152),
        "granite-3-8b": dict(n_layers=40, d_model=4096, n_heads=32,
                             n_kv_heads=8, d_ff=12800, vocab=49155),
        "llava-next-34b": dict(n_layers=60, d_model=7168, n_heads=56,
                               n_kv_heads=8, d_ff=20480, vocab=64000),
        "musicgen-large": dict(n_layers=48, d_model=2048, n_heads=32,
                               n_kv_heads=32, d_ff=8192, vocab=2048,
                               n_codebooks=4),
        "mixtral-8x7b": dict(n_layers=32, d_model=4096, n_heads=32,
                             n_kv_heads=8, d_ff=14336, vocab=32000,
                             n_experts=8, top_k=2),
        "mixtral-8x22b": dict(n_layers=56, d_model=6144, n_heads=48,
                              n_kv_heads=8, d_ff=16384, vocab=32768,
                              n_experts=8, top_k=2),
        "mamba2-1.3b": dict(n_layers=48, d_model=2048, vocab=50280,
                            ssm_state=128),
        "hymba-1.5b": dict(n_layers=32, d_model=1600, n_heads=25,
                           n_kv_heads=5, d_ff=5504, vocab=32001,
                           ssm_state=16),
    }[arch]
    cfg = base.get_arch(arch).FULL
    for k, v in expected.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_moe_capacity_drops_are_bounded():
    """Sort-based dispatch keeps >= (1 - small) of routed mass at cf=1.25."""
    cfg = base.get_arch("mixtral-8x7b").SMOKE
    params = api.init_model(KEY, cfg)
    from repro.models.moe import moe_mlp

    x = jax.random.normal(KEY, (4, 64, cfg.d_model))
    p0 = jax.tree.map(lambda a: a[0], params["base"]["layers"]["mlp"])
    out, aux = moe_mlp(p0, x, top_k=cfg.top_k, capacity_factor=1.25)
    assert out.shape == x.shape
    assert float(aux) >= 1.0 - 1e-3  # load-balance loss lower bound is 1


def test_moe_matches_dense_reference():
    """Capacity-gather MoE == explicit per-token expert mixture (high cf)."""
    from repro.models import moe as MOE

    d, f, E, T = 16, 32, 4, 24
    p = MOE.init_moe_mlp(KEY, d, f, E)
    x = jax.random.normal(KEY, (1, T, d))
    out, _ = MOE.moe_mlp(p, x, top_k=2, capacity_factor=float(E))  # no drops
    logits = x.reshape(T, d) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gv, ei = jax.lax.top_k(probs, 2)
    gv = gv / gv.sum(-1, keepdims=True)
    want = np.zeros((T, d), np.float32)
    xf = np.asarray(x.reshape(T, d))
    for t in range(T):
        for j in range(2):
            e = int(ei[t, j])
            h = (np.asarray(jax.nn.silu(xf[t] @ p["wg"][e]))
                 * np.asarray(xf[t] @ p["wi"][e]))
            want[t] += float(gv[t, j]) * (h @ np.asarray(p["wo"][e]))
    np.testing.assert_allclose(np.asarray(out.reshape(T, d)), want,
                               atol=1e-4)


def test_vocab_padding_transparent():
    cfg = base.get_arch("granite-3-8b").SMOKE  # vocab 99 -> padded 128
    from repro.models.transformer import padded_vocab
    assert padded_vocab(cfg) == 128
    params = api.init_model(KEY, cfg)
    assert params["base"]["embed"].shape[0] == 128
    logits, _ = api.forward(params, cfg, {"tokens": jnp.zeros((1, 8),
                                                              jnp.int32)})
    assert logits.shape[-1] == cfg.vocab  # sliced back to the true vocab


# ---------------------------------------------------------------------------
# hybrid prefill: one pass over the prompt == the exact token loop
# ---------------------------------------------------------------------------

# SMOKE with 4-token SSD chunks, so that a prompt inside the 16-slot ring
# holds whole chunks and a remainder
_HY = dataclasses.replace(base.get_arch("hymba-1.5b").SMOKE, ssd_chunk=4)
_HY_MAX_LEN = 24  # ring = min(sliding_window 16, 24)
_decode = jax.jit(api.decode_step, static_argnums=1)
_prefill = jax.jit(api.prefill_with_cache, static_argnums=1)


@pytest.fixture(scope="module")
def hybrid_client():
    """SMOKE hymba (float32) with one client's adapter: LoRA b moved away
    from zero, so a wrong adapter or mask changes the logits."""
    params = api.init_model(KEY, _HY)
    params["lora"] = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(3),
                                               x.shape, x.dtype),
        params["lora"])
    return params


@pytest.mark.parametrize("blocks", [(1, 1), (1, 0), (0, 1)],
                         ids=["both", "attn_only", "ssd_only"])
@pytest.mark.parametrize("S", [1, 3, 8, 11, 16, 21],
                         ids=["1", "3", "chunks", "remainder", "ring",
                              "past_ring"])
def test_hybrid_one_pass_prefill_matches_token_loop(hybrid_client, S, blocks):
    from repro.kernels.mdlora import block_row_mask

    cfg, params = _HY, hybrid_client
    fresh = api.init_caches(cfg, 1, _HY_MAX_LEN, per_row_pos=True)
    ring = fresh["attn"]["k"].shape[2]
    assert ring == 16 and api.prefill_one_pass(cfg, fresh, S) == (S <= ring)
    fmask = block_row_mask(api.fusion_block_dims(cfg),
                           jnp.asarray(blocks, jnp.float32))[None]
    tokens = jax.random.randint(jax.random.PRNGKey(S), (1, S), 0, cfg.vocab)
    caches = fresh
    for t in range(S):
        want, caches = _decode(params, cfg, caches, tokens[:, t:t + 1],
                               jnp.int32(t), fusion_mask=fmask)
    got, got_caches = _prefill(params, cfg, fresh, tokens, fusion_mask=fmask)
    assert got.shape == want.shape == (1, 1, cfg.vocab)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert jax.tree.structure(got_caches) == jax.tree.structure(caches)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_caches),
                            jax.tree.leaves(caches)):
        assert a.dtype == b.dtype, path
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_prefill_one_pass_needs_ring_and_whole_query_chunks():
    """One forward only where the ring holds the prompt and the attention
    tiles it in whole q_chunk blocks; the ssm family always loops."""
    cfg = dataclasses.replace(_HY, q_chunk=8)
    fresh = api.init_caches(cfg, 1, _HY_MAX_LEN, per_row_pos=True)
    assert [api.prefill_one_pass(cfg, fresh, S)
            for S in (1, 8, 12, 16, 17)] == [True, True, False, True, False]
    ssm = base.get_arch("mamba2-1.3b").SMOKE
    assert not api.prefill_one_pass(ssm, api.init_caches(ssm, 1, 8), 4)


@pytest.mark.parametrize("S", [2, 4, 7, 12],
                         ids=["2", "chunk", "remainder", "chunks"])
def test_mamba_mixer_multi_token_from_cache(S):
    """S tokens through the mixer from a nonzero cache == S single-token
    calls, in outputs and in the cache it returns (4-token SSD chunks)."""
    from repro.models import ssm as SM

    cfg = _HY
    dm = SM.mixer_dims(cfg)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(11), 4)
    p = SM.init_mamba_mixer(k1, cfg)
    p["dt_bias"] = 0.5 * jax.random.normal(k4, p["dt_bias"].shape)
    x = jax.random.normal(k2, (2, S, cfg.d_model))
    kc, ks = jax.random.split(k3)
    cache = {"conv": jax.random.normal(kc, (2, cfg.conv_kernel - 1,
                                            dm["conv_dim"])),
             "state": jax.random.normal(ks, (2, dm["n_heads"], dm["p"],
                                             dm["n"]))}
    got, got_cache = SM.mamba_mixer(p, cfg, x, ssm_cache=cache)
    want, c = [], cache
    for t in range(S):
        y, c = SM.mamba_mixer(p, cfg, x[:, t:t + 1], ssm_cache=c)
        want.append(y)
    np.testing.assert_allclose(got, jnp.concatenate(want, 1),
                               rtol=1e-5, atol=1e-5)
    for name in ("conv", "state"):
        np.testing.assert_allclose(got_cache[name], c[name],
                                   rtol=1e-5, atol=1e-5, err_msg=name)
