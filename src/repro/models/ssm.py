"""Mamba-2 (SSD — state-space duality) blocks and LM.

Implements the chunked SSD block decomposition from arXiv:2405.21060:
intra-chunk (quadratic within a chunk, dual attention form) + inter-chunk
state recurrence (scan over chunk states). Training/prefill use the chunked
form; decode is the O(1) recurrent state update. The Pallas kernel
(kernels/ssd) implements the same decomposition tiled for VMEM; the functions
here are the XLA path and the oracle the kernel is tested against.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Array = jax.Array


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def ssd_chunked(x: Array, dt: Array, A_log: Array, Bm: Array, Cm: Array,
                chunk: int, initial_state: Array | None = None,
                impl: str = "xla") -> tuple[Array, Array]:
    """Chunked SSD scan.

    x:  [b, s, h, p]   per-head inputs
    dt: [b, s, h]      softplus-ed step sizes
    A_log: [h]         log of -A (per-head scalar decay)
    Bm, Cm: [b, s, n]  input/output projections (single group, broadcast over h)
    -> (y [b, s, h, p], final_state [b, h, p, n])
    """
    if impl == "pallas":
        from repro.kernels.ssd import ops as ssd_ops
        return ssd_ops.ssd(x, dt, A_log, Bm, Cm, chunk, initial_state)

    b, s, h, p = x.shape
    n = Bm.shape[-1]
    nc = s // chunk
    assert s % chunk == 0, f"seq {s} not divisible by ssd chunk {chunk}"
    f32 = jnp.float32

    a = (-jnp.exp(A_log.astype(f32)) * dt.astype(f32))  # [b, s, h] log-decay
    xd = x.astype(f32) * dt.astype(f32)[..., None]  # dt-weighted input

    ac = a.reshape(b, nc, chunk, h)
    xc = xd.reshape(b, nc, chunk, h, p)
    Bc = Bm.astype(f32).reshape(b, nc, chunk, n)
    Cc = Cm.astype(f32).reshape(b, nc, chunk, n)

    cum = jnp.cumsum(ac, axis=2)  # [b, nc, q, h]
    # intra-chunk decay matrix L[i,j] = exp(cum_i - cum_j), i >= j
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [b,nc,q,k,h]
    tril = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask in log domain BEFORE exp: the upper triangle holds large positive
    # values whose exp is inf, and inf*0 => NaN in the backward pass.
    diff = jnp.where(tril[None, None, :, :, None], diff, -jnp.inf)
    Lmat = jnp.exp(diff)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)
    # explicit contraction order: a free einsum path may materialize the
    # [b,c,q,k,h,p] product (275 GB at prefill_32k shapes — §Perf log)
    sl = scores[..., None] * Lmat  # [b,nc,q,k,h]
    y_diag = jnp.einsum("bcqkh,bckhp->bcqhp", sl, xc)

    # per-chunk end states
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [b, nc, q, h]
    xde = xc * decay_to_end[..., None]  # [b, nc, q, h, p]
    chunk_states = jnp.einsum("bcqn,bcqhp->bchpn", Bc, xde)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [b, nc, h] total chunk decay

    # inter-chunk recurrence
    s0 = (jnp.zeros((b, h, p, n), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(state, inp):
        cs, cd = inp  # [b,h,p,n], [b,h]
        prev = state
        new = prev * cd[..., None, None] + cs
        return new, prev

    final_state, prev_states = jax.lax.scan(
        step, s0, (chunk_states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # [b, nc, h, p, n]

    state_decay = jnp.exp(cum)  # decay from chunk start to position q
    y_off = jnp.einsum("bcqn,bchpn->bcqhp", Cc, prev_states) \
        * state_decay[..., None]

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_from_state(x: Array, dt: Array, A_log: Array, Bm: Array, Cm: Array,
                   chunk: int, state: Array) -> tuple[Array, Array]:
    """Chunked SSD scan of any length ``s`` from ``state`` [b, h, p, n]
    (XLA path: the Pallas kernel takes no initial state). The whole
    ``chunk``-sized chunks go first, then the ``s % chunk`` remainder as one
    chunk from their final state; shapes as ``ssd_chunked``."""
    s = x.shape[1]
    whole = s - s % chunk
    parts = [(0, whole, chunk)] if whole else []
    if s > whole:
        parts.append((whole, s, s - whole))
    ys = []
    for lo, hi, c in parts:
        y, state = ssd_chunked(x[:, lo:hi], dt[:, lo:hi], A_log, Bm[:, lo:hi],
                               Cm[:, lo:hi], c, initial_state=state)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), state


def ssd_decode_step(state: Array, x: Array, dt: Array, A_log: Array,
                    Bm: Array, Cm: Array) -> tuple[Array, Array]:
    """O(1) recurrent update. state: [b,h,p,n]; x: [b,h,p]; dt: [b,h];
    Bm, Cm: [b,n] -> (y [b,h,p], new_state)."""
    f32 = jnp.float32
    decay = jnp.exp(-jnp.exp(A_log.astype(f32)) * dt.astype(f32))  # [b,h]
    upd = (dt.astype(f32)[..., None] * x.astype(f32))[..., None] * \
        Bm.astype(f32)[:, None, None, :]
    new_state = state.astype(f32) * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(f32))
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Mamba-2 mixer (in_proj -> conv -> SSD -> gate -> out_proj)
# ---------------------------------------------------------------------------


def mixer_dims(cfg: ModelConfig) -> dict:
    d_inner = cfg.d_inner or 2 * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_state
    return dict(d_inner=d_inner, n_heads=n_heads, conv_dim=conv_dim,
                n=cfg.ssm_state, p=cfg.ssm_head_dim)


def init_mamba_mixer(key: Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    dm = mixer_dims(cfg)
    ki, kc, ko, kd = jax.random.split(key, 4)
    d_in_proj = 2 * dm["d_inner"] + 2 * dm["n"] + dm["n_heads"]
    return {
        "in_proj": L.dense_init(ki, cfg.d_model, d_in_proj, dtype),
        "conv": L.init_conv1d(kc, 1, 1, cfg.conv_kernel, dtype) | {
            # depthwise conv over conv_dim channels: w [k, conv_dim]
            "w": (jax.random.normal(kc, (cfg.conv_kernel, dm["conv_dim"]))
                  / math.sqrt(cfg.conv_kernel)).astype(dtype),
            "b": jnp.zeros((dm["conv_dim"],), dtype),
        },
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, dm["n_heads"])).astype(jnp.float32),
        "D": jnp.ones((dm["n_heads"],), jnp.float32),
        "dt_bias": jnp.zeros((dm["n_heads"],), jnp.float32),
        "norm": L.init_rmsnorm(dm["d_inner"], dtype),
        "out_proj": L.dense_init(ko, dm["d_inner"], cfg.d_model, dtype),
    }


def _causal_depthwise_conv(w: Array, b: Array, x: Array,
                           conv_state: Array | None = None):
    """x: [B, S, C]; w: [k, C] depthwise causal. Returns (y, new_state[k-1])."""
    k = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # [B, S+k-1, C]
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(k))
    new_state = xp[:, -(k - 1):, :] if k > 1 else None
    return jax.nn.silu(y + b[None, None, :]), new_state


def _lora(lp, name, x, cfg):
    if lp is None or name not in lp:
        return 0.0
    a, b = lp[name]["a"], lp[name]["b"]
    return (((x.astype(a.dtype) @ a) @ b) * (cfg.lora_alpha / cfg.lora_rank)
            ).astype(x.dtype)


def mamba_mixer(p: dict, cfg: ModelConfig, x: Array,
                ssm_cache: dict | None = None,
                return_fused_input: bool = False, lp: dict | None = None):
    """x: [B, S, d] -> (y [B, S, d], new_cache).

    ssm_cache = {"conv": [B, k-1, conv_dim], "state": [B, h, p, n]}: the
    S tokens continue from it (S == 1: the recurrent decode update; S > 1:
    the chunked scan from the cached state, as in a one-pass prefill).
    ``return_fused_input`` exposes the pre-out_proj hidden (RELIEF fusion hook).
    """
    dm = mixer_dims(cfg)
    B_, S, _ = x.shape
    zxbcdt = x @ p["in_proj"] + _lora(lp, "in_proj", x, cfg)
    z, xin, Bm, Cm, dt = jnp.split(
        zxbcdt, [dm["d_inner"], 2 * dm["d_inner"], 2 * dm["d_inner"] + dm["n"],
                 2 * dm["d_inner"] + 2 * dm["n"]], axis=-1)
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)
    conv_state = None if ssm_cache is None else ssm_cache["conv"]
    conv_out, new_conv = _causal_depthwise_conv(p["conv"]["w"], p["conv"]["b"],
                                                conv_in, conv_state)
    xin, Bm, Cm = jnp.split(conv_out, [dm["d_inner"], dm["d_inner"] + dm["n"]],
                            axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # [B,S,h]
    xh = xin.reshape(B_, S, dm["n_heads"], dm["p"])

    if ssm_cache is None:
        y, final_state = ssd_chunked(xh, dt, p["A_log"], Bm, Cm,
                                     min(cfg.ssd_chunk, S), impl=cfg.attn_impl
                                     if cfg.attn_impl == "pallas" else "xla")
    elif S == 1:
        yh, final_state = ssd_decode_step(ssm_cache["state"], xh[:, 0],
                                          dt[:, 0], p["A_log"], Bm[:, 0],
                                          Cm[:, 0])
        y = yh[:, None]
    else:
        y, final_state = ssd_from_state(xh, dt, p["A_log"], Bm, Cm,
                                        cfg.ssd_chunk, ssm_cache["state"])
    y = y + xh * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B_, S, dm["d_inner"])
    y = L.rmsnorm(p["norm"], y) * jax.nn.silu(z)
    new_cache = (None if ssm_cache is None and final_state is None else
                 {"conv": new_conv, "state": final_state})
    if return_fused_input:
        return y, new_cache
    return y @ p["out_proj"] + _lora(lp, "out_proj", y, cfg), new_cache


# ---------------------------------------------------------------------------
# Mamba-2 LM
# ---------------------------------------------------------------------------


def init_mamba_lora(key: Array, cfg: ModelConfig) -> dict:
    """LoRA on the mixer in/out projections (paper technique on SSM archs;
    DESIGN.md §4: channel groups of in_proj are the block analogue)."""
    dm = mixer_dims(cfg)
    dt = jnp.float32 if cfg.lora_dtype == "float32" else cfg.p_dtype()
    r = cfg.lora_rank
    d_in_proj = 2 * dm["d_inner"] + 2 * dm["n"] + dm["n_heads"]
    shapes = {"in_proj": (cfg.d_model, d_in_proj),
              "out_proj": (dm["d_inner"], cfg.d_model)}

    def one_layer(k):
        out = {}
        for name, (din, dout) in shapes.items():
            k, ka = jax.random.split(k)
            out[name] = {"a": (jax.random.normal(ka, (din, r)) /
                               math.sqrt(din)).astype(dt),
                         "b": jnp.zeros((r, dout), dtype=dt)}
        return out

    return jax.vmap(one_layer)(jax.random.split(key, cfg.n_layers))


def init_mamba_lm(key: Array, cfg: ModelConfig, with_lora: bool = True) -> dict:
    from repro.models.transformer import padded_vocab

    ke, kl, klo = jax.random.split(key, 3)
    dt = cfg.p_dtype()

    def one_layer(k):
        km, = jax.random.split(k, 1)
        return {"mixer": init_mamba_mixer(km, cfg, dt),
                "ln": L.init_rmsnorm(cfg.d_model, dt)}

    params = {"base": {
        "embed": L.embed_init(ke, padded_vocab(cfg), cfg.d_model, dt),
        "layers": jax.vmap(one_layer)(jax.random.split(kl, cfg.n_layers)),
        "final_norm": L.init_rmsnorm(cfg.d_model, dt),
    }}
    if with_lora:
        params["lora"] = {"layers": init_mamba_lora(klo, cfg)}
    return params


def mamba_forward(params: dict, cfg: ModelConfig, tokens: Array,
                  caches=None, skip_unembed: bool = False
                  ) -> tuple[Array, Any, Array]:
    from repro.models.transformer import unembed  # shared unembed/tied head

    x = jnp.take(params["base"]["embed"], tokens, axis=0).astype(cfg.runtime_dtype())
    lora_layers = params.get("lora", {}).get("layers")

    def body(x, step):
        p, lp = step
        h = L.rmsnorm(p["ln"], x)
        y, _ = mamba_mixer(p["mixer"], cfg, h, lp=lp)
        return x + y, None

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


def init_mamba_caches(cfg: ModelConfig, batch: int, max_len: int = 0,
                      dtype=None) -> dict:
    dm = mixer_dims(cfg)
    dtype = dtype or cfg.runtime_dtype()
    Lyr = cfg.n_layers
    return {
        "conv": jnp.zeros((Lyr, batch, cfg.conv_kernel - 1, dm["conv_dim"]), dtype),
        "state": jnp.zeros((Lyr, batch, dm["n_heads"], dm["p"], dm["n"]),
                           jnp.float32),
    }


def mamba_decode_step(params: dict, cfg: ModelConfig, caches: dict,
                      token: Array, pos: Array):
    from repro.models.transformer import unembed

    x = jnp.take(params["base"]["embed"], token, axis=0).astype(cfg.runtime_dtype())
    lora_layers = params.get("lora", {}).get("layers")

    def body(x, step):
        p, lp, cache = step
        h = L.rmsnorm(p["ln"], x)
        y, nc = mamba_mixer(p["mixer"], cfg, h, ssm_cache=cache, lp=lp)
        return x + y, nc

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(
            body, x, (params["base"]["layers"], lora_layers, caches))
    else:
        ncs = []
        for t in range(cfg.n_layers):
            x, nc = body(x, jax.tree.map(
                lambda a: a[t],
                (params["base"]["layers"], lora_layers, caches)))
            ncs.append(nc)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
    x = L.rmsnorm(params["base"]["final_norm"], x)
    return unembed(params, cfg, x), new_caches
