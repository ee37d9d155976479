"""Hymba-style hybrid blocks: parallel attention heads + Mamba(SSD) heads.

Each layer runs an attention mixer and an SSD mixer *in parallel on the same
normalized input*; their outputs are concatenated and fused by a single
output projection (arXiv:2411.13676).  That fusion projection's input axis is
an ordered concatenation of the two head families — exactly the paper's
"modality-aligned column block" structure (Eq. 1) — so RELIEF's MDLoRA blocks
attach natively here: block 0 = attention features, block 1 = SSM features.
Meta tokens from the Hymba paper are out of scope (frontend-level, stubbed).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.transformer import unembed

Array = jax.Array


def hybrid_dims(cfg: ModelConfig) -> dict:
    dm = S.mixer_dims(cfg)
    attn_out = cfg.n_heads * cfg.head_dim
    return dm | {"attn_out": attn_out, "fused": attn_out + dm["d_inner"]}


def init_hybrid_layer(key: Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ka, km, ko, kf = jax.random.split(key, 4)
    dm = hybrid_dims(cfg)
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(ka, 3)
    return {
        "attn": {
            "wq": L.dense_init(kq, d, h * hd, dtype),
            "wk": L.dense_init(kk, d, k * hd, dtype),
            "wv": L.dense_init(kv, d, k * hd, dtype),
        },
        "mamba": init_mamba_headless(km, cfg, dtype),
        # fusion projection: input = [attn_out ; ssm_out] (RELIEF block axis)
        "wo": L.dense_init(ko, dm["fused"], d, dtype),
        "mlp": L.init_glu_mlp(kf, d, cfg.d_ff, dtype),
        "ln1": L.init_rmsnorm(d, dtype),
        "ln2": L.init_rmsnorm(d, dtype),
    }


def init_mamba_headless(key: Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    """Mamba mixer without its own out_proj (fusion happens in wo)."""
    p = S.init_mamba_mixer(key, cfg, dtype)
    del p["out_proj"]
    return p


def _attn_heads(p: dict, lp: dict | None, cfg: ModelConfig, x: Array,
                positions: Array, cache: dict | None, window,
                ctx: dict | None = None):
    from repro.models.transformer import _cache_scatter, _pos_scatter, _proj

    B, Sq, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = _proj(p["wq"], lp, "wq", x, cfg, ctx).reshape(B, Sq, H, hd)
    k = _proj(p["wk"], lp, "wk", x, cfg, ctx).reshape(B, Sq, K, hd)
    v = _proj(p["wv"], lp, "wv", x, cfg, ctx).reshape(B, Sq, K, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is None:
        kk, vv, kv_pos = k, v, positions
    else:
        T = cache["k"].shape[1]
        slots = positions % T
        kk = _cache_scatter(cache["k"], slots, k.astype(cache["k"].dtype))
        vv = _cache_scatter(cache["v"], slots, v.astype(cache["v"].dtype))
        kv_pos = _pos_scatter(cache["pos"], slots, positions)
        new_cache = {"k": kk, "v": vv, "pos": kv_pos}

    qg = q.reshape(B, Sq, K, H // K, hd)
    o = L._chunked_attention(qg, kk, vv, positions, kv_pos, window,
                             cfg.attn_softcap, cfg.q_chunk)
    return o.reshape(B, Sq, H * hd), new_cache


def hybrid_layer(p: dict, lp: dict | None, cfg: ModelConfig, x: Array,
                 positions: Array, caches: dict | None, window,
                 ctx: dict | None = None):
    """caches = {"attn": kv-cache, "ssm": {"conv","state"}} or None."""
    from repro.models.transformer import _proj

    h = L.rmsnorm(p["ln1"], x)
    attn_cache = None if caches is None else caches["attn"]
    ssm_cache = None if caches is None else caches["ssm"]

    attn_out, new_attn = _attn_heads(p["attn"], lp, cfg, h, positions,
                                     attn_cache, window, ctx)
    ssm_out, new_ssm = S.mamba_mixer(p["mamba"], cfg, h, ssm_cache=ssm_cache,
                                     return_fused_input=True)
    fused = jnp.concatenate([attn_out, ssm_out], axis=-1)
    y = _proj(p["wo"], lp, "wo", fused, cfg, ctx)
    x = x + y
    h2 = L.rmsnorm(p["ln2"], x)
    x = x + L.glu_mlp(p["mlp"], h2, cfg.activation)
    new_caches = None if caches is None else {"attn": new_attn, "ssm": new_ssm}
    return x, new_caches


# ---------------------------------------------------------------------------
# LM wrapper
# ---------------------------------------------------------------------------


def init_hybrid_lora(key: Array, cfg: ModelConfig) -> dict:
    dt = jnp.float32 if cfg.lora_dtype == "float32" else cfg.p_dtype()
    r = cfg.lora_rank
    dm = hybrid_dims(cfg)
    d = cfg.d_model
    shapes = {"wq": (d, cfg.n_heads * cfg.head_dim),
              "wv": (d, cfg.n_kv_heads * cfg.head_dim),
              "wo": (dm["fused"], d)}

    def one_layer(k):
        out = {}
        for name, (din, dout) in shapes.items():
            if name not in cfg.lora_targets and not (
                    name == "wo" and "wo_fusion" in cfg.lora_targets):
                continue
            k, ka = jax.random.split(k)
            out[name] = {"a": (jax.random.normal(ka, (din, r)) /
                               math.sqrt(din)).astype(dt),
                         "b": jnp.zeros((r, dout), dtype=dt)}
        return out

    return jax.vmap(one_layer)(jax.random.split(key, cfg.n_layers))


def init_hybrid_lm(key: Array, cfg: ModelConfig, with_lora: bool = True) -> dict:
    from repro.models.transformer import padded_vocab

    ke, kl, klo = jax.random.split(key, 3)
    dt = cfg.p_dtype()
    params = {"base": {
        "embed": L.embed_init(ke, padded_vocab(cfg), cfg.d_model, dt),
        "layers": jax.vmap(lambda k: init_hybrid_layer(k, cfg, dt))(
            jax.random.split(kl, cfg.n_layers)),
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
    }}
    if with_lora:
        params["lora"] = {"layers": init_hybrid_lora(klo, cfg)}
    return params


def _window(cfg: ModelConfig):
    import numpy as np
    return cfg.sliding_window if cfg.sliding_window is not None else \
        np.iinfo(np.int32).max


def hybrid_forward(params: dict, cfg: ModelConfig, tokens: Array,
                   caches=None, skip_unembed: bool = False
                   ) -> tuple[Array, Any, Array]:
    x = jnp.take(params["base"]["embed"], tokens, axis=0).astype(cfg.runtime_dtype())
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    lora_layers = params.get("lora", {}).get("layers")

    def body(x, step):
        p, lp = step
        x, _ = hybrid_layer(p, lp, cfg, x, positions, None, _window(cfg))
        return x, None

    if cfg.remat != "none":
        body = jax.checkpoint(body)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, (params["base"]["layers"], lora_layers))
    else:  # unrolled (dry-run accounting)
        for t in range(cfg.n_layers):
            x, _ = body(x, jax.tree.map(
                lambda a: a[t], (params["base"]["layers"], lora_layers)))
    x = L.rmsnorm(params["base"]["final_norm"], x)
    if skip_unembed:
        return x, None, jnp.float32(0.0)
    return unembed(params, cfg, x), None, jnp.float32(0.0)


def init_hybrid_caches(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=None, per_row_pos: bool = False) -> dict:
    dtype = dtype or cfg.runtime_dtype()
    dm = hybrid_dims(cfg)
    T = int(min(_window(cfg), max_len))
    Lyr = cfg.n_layers
    pos_shape = (Lyr, batch, T) if per_row_pos else (Lyr, T)
    return {
        "attn": {"k": jnp.zeros((Lyr, batch, T, cfg.n_kv_heads, cfg.head_dim), dtype),
                 "v": jnp.zeros((Lyr, batch, T, cfg.n_kv_heads, cfg.head_dim), dtype),
                 "pos": jnp.full(pos_shape, -1, jnp.int32)},
        "ssm": {"conv": jnp.zeros((Lyr, batch, cfg.conv_kernel - 1, dm["conv_dim"]), dtype),
                "state": jnp.zeros((Lyr, batch, dm["n_heads"], dm["p"], dm["n"]),
                                   jnp.float32)},
    }


def _cached_layers(params: dict, cfg: ModelConfig, caches: dict,
                   tokens: Array, positions: Array, ctx: dict | None):
    """Every layer over ``tokens`` [B, S] at ``positions``, reading and
    writing ``caches``; -> (hidden before the final norm, new caches)."""
    x = jnp.take(params["base"]["embed"], tokens, axis=0).astype(cfg.runtime_dtype())
    lora_layers = params.get("lora", {}).get("layers")

    def body(x, step):
        p, lp, cache = step
        x, nc = hybrid_layer(p, lp, cfg, x, positions, cache, _window(cfg),
                             ctx)
        return x, nc

    if cfg.scan_layers:
        return jax.lax.scan(
            body, x, (params["base"]["layers"], lora_layers, caches))
    ncs = []
    for t in range(cfg.n_layers):
        x, nc = body(x, jax.tree.map(
            lambda a: a[t], (params["base"]["layers"], lora_layers, caches)))
        ncs.append(nc)
    return x, jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)


def hybrid_decode_step(params: dict, cfg: ModelConfig, caches: dict,
                       token: Array, pos: Array,
                       adapter_idx: Array | None = None,
                       fusion_mask: Array | None = None,
                       lora_impl: str = "xla"):
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    ctx = None
    if adapter_idx is not None or fusion_mask is not None:
        ctx = {"adapter_idx": adapter_idx, "fusion_mask": fusion_mask,
               "lora_impl": lora_impl}
    x, new_caches = _cached_layers(params, cfg, caches, token, positions, ctx)
    x = L.rmsnorm(params["base"]["final_norm"], x)
    return unembed(params, cfg, x), new_caches


def hybrid_prefill(params: dict, cfg: ModelConfig, caches: dict,
                   tokens: Array, fusion_mask: Array | None = None):
    """One forward over the whole prompt ``tokens`` [B, S] into fresh
    ``caches`` (from position 0): the attention heads write all S positions
    into the ring (so S must not exceed it), the SSD heads scan the prompt
    in chunks from the cached state. -> (last-position logits [B, 1, V],
    caches), as S ``hybrid_decode_step`` calls would give."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    ctx = None if fusion_mask is None else {"fusion_mask": fusion_mask}
    x, new_caches = _cached_layers(params, cfg, caches, tokens, positions, ctx)
    x = L.rmsnorm(params["base"]["final_norm"], x[:, -1:])
    return unembed(params, cfg, x), new_caches
