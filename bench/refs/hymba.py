"""Plain reference of hymba-1.5b with per-client MDLoRA adapters, and the
weights it is fed.

Imports nothing of the program. The model (arXiv:2411.13676, as this
repository serves it): per layer, on the RMS-normed input, an attention
mixer (RoPE, grouped KV heads, causal sliding window) and an SSD mixer
(in-projection, causal depthwise conv, selective state-space recurrence,
gated RMS norm) run side by side; their outputs are concatenated
([attention ; SSD], the RELIEF Eq. 1 block axis), multiplied by the
client's block mask and fused by one output projection; then a GLU MLP.
LoRA adapters (scale alpha/rank) sit on Q, V and the fused projection.
Embeddings are tied. Departures from the published model, as served here:
every layer uses the sliding window, no meta tokens.

``logits`` runs whole sequences in float32 with
``default_matmul_precision("highest")``, layer by layer so that one layer's
float32 weights are live at a time; the SSD state follows its recurrence
token by token. ``quant="int8"`` is the lower-precision control: every
matrix weight rounded to int8 with one scale per output channel, the
activations in bfloat16.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from bench.refs.relief_flush import key_data


def _cfg_key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


def dims(cfg: dict) -> dict:
    di = cfg["d_inner"]
    h = di // cfg["ssm_head_dim"]
    n = cfg["ssm_state"]
    return dict(d=cfg["d_model"], H=cfg["n_heads"], K=cfg["n_kv_heads"],
                hd=cfg["head_dim"], di=di, h=h, p=cfg["ssm_head_dim"], n=n,
                conv=di + 2 * n, inp=2 * di + 2 * n + h,
                fused=cfg["n_heads"] * cfg["head_dim"] + di, ff=cfg["d_ff"],
                L=cfg["n_layers"], V=cfg["vocab"],
                Vp=-(-cfg["vocab"] // 128) * 128, k=cfg["conv_kernel"],
                r=cfg["lora_rank"])


# ---------------------------------------------------------------------------
# weights from the seed (the served layout: bfloat16, layer-stacked)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _base_fn(cfg_s: str):
    cfg = json.loads(cfg_s)
    m = dims(cfg)
    L, d = m["L"], m["d"]
    bf = jnp.bfloat16

    def dense(k, shape):
        return jax.random.normal(k, shape, bf) * bf(1.0 / math.sqrt(shape[-2]))

    def small(k, shape, s=0.1):
        return (s * jax.random.normal(k, shape)).astype(bf)

    def make(kd):
        ks = iter(jax.random.split(jax.random.wrap_key_data(kd), 32))
        dt0 = jnp.exp(jax.random.uniform(next(ks), (L, m["h"]),
                                         minval=math.log(1e-3),
                                         maxval=math.log(1e-1)))
        layers = {
            "attn": {"wq": dense(next(ks), (L, d, m["H"] * m["hd"])),
                     "wk": dense(next(ks), (L, d, m["K"] * m["hd"])),
                     "wv": dense(next(ks), (L, d, m["K"] * m["hd"]))},
            "mamba": {
                "in_proj": dense(next(ks), (L, d, m["inp"])),
                "conv": {"w": (jax.random.normal(next(ks), (L, m["k"], m["conv"]))
                               / math.sqrt(m["k"])).astype(bf),
                         "b": small(next(ks), (L, m["conv"]))},
                "A_log": jnp.log(jax.random.uniform(next(ks), (L, m["h"]),
                                                    minval=1.0, maxval=16.0)),
                "D": 1.0 + 0.1 * jax.random.normal(next(ks), (L, m["h"])),
                "dt_bias": jnp.log(jnp.expm1(dt0)),
                "norm": small(next(ks), (L, m["di"])),
            },
            "wo": dense(next(ks), (L, m["fused"], d)),
            "mlp": {"wi": dense(next(ks), (L, d, m["ff"])),
                    "wg": dense(next(ks), (L, d, m["ff"])),
                    "wo": dense(next(ks), (L, m["ff"], d))},
            "ln1": small(next(ks), (L, d)),
            "ln2": small(next(ks), (L, d)),
        }
        embed = (0.02 * jax.random.normal(next(ks), (m["Vp"], d), bf)).astype(bf)
        return {"embed": embed, "layers": layers,
                "final_norm": small(next(ks), (d,))}

    return jax.jit(make)


def make_base(cfg: dict, seed: int) -> dict:
    """The base weights, on the device, in one jitted call."""
    return _base_fn(_cfg_key(cfg))(key_data(seed, 11))


def lora_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    return {"wq": (m["d"], m["H"] * m["hd"]), "wv": (m["d"], m["K"] * m["hd"]),
            "wo": (m["fused"], m["d"])}


@functools.lru_cache(maxsize=None)
def _adapters_fn(cfg_s: str):
    cfg = json.loads(cfg_s)
    m = dims(cfg)
    A, L, r = cfg["n_adapters"], m["L"], m["r"]
    probs = jnp.asarray(cfg["fusion_block_probs"], jnp.float32)

    def make(kd):
        ks = iter(jax.random.split(jax.random.wrap_key_data(kd), 16))
        store = {}
        for name, (din, dout) in lora_shapes(cfg).items():
            store[name] = {
                "a": jax.random.normal(next(ks), (L, A, din, r)) / math.sqrt(din),
                "b": cfg["adapter_b_std"] * jax.random.normal(next(ks),
                                                             (L, A, r, dout))}
        # block patterns: both blocks, attention only, SSD only
        pat = jax.random.categorical(next(ks), jnp.log(probs), shape=(A,))
        blocks = jnp.stack([pat != 2, pat != 1], -1).astype(jnp.float32)
        rows = jnp.concatenate(
            [jnp.repeat(blocks[:, :1], m["H"] * m["hd"], 1),
             jnp.repeat(blocks[:, 1:], m["di"], 1)], 1)
        return {"layers": store}, rows

    return jax.jit(make)


def make_adapters(cfg: dict, seed: int):
    """-> (store {"layers": {target: {"a": [L, A, din, r], "b": [L, A, r,
    dout]}}} float32, fusion row masks [A, fused] float32)."""
    return _adapters_fn(_cfg_key(cfg))(key_data(seed, 12))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _rms(w, x):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _q8(w, axis):
    """int8 round trip with one scale per slice along ``axis`` (the output
    channel): max |w| over the other axes / 127."""
    w = w.astype(jnp.float32)
    red = tuple(i for i in range(w.ndim) if i != axis % w.ndim)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=red, keepdims=True), 1e-12) / 127
    return jnp.clip(jnp.round(w / s), -127, 127) * s


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_s: str, quant: str | None):
    cfg = json.loads(cfg_s)
    m = dims(cfg)
    dt = jnp.bfloat16 if quant else jnp.float32
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    W = cfg["sliding_window"]

    def w_(x):  # a matrix weight [din, dout] as the forward uses it
        return (_q8(x, -1) if quant else x.astype(jnp.float32)).astype(dt)

    def layer(x, lp, ad, fmask, pos):
        """x [R, S, d]; lp one layer's weights; ad per-request adapters
        {target: {"a": [R, din, r], "b": [R, r, dout]}}; fmask [R, fused]."""
        R, S, d = x.shape

        def lora(name, v):
            a, b = ad[name]["a"].astype(dt), ad[name]["b"].astype(dt)
            return jnp.einsum("rsk,rkj->rsj",
                              jnp.einsum("rsi,rik->rsk", v, a), b) * scale

        h = _rms(lp["ln1"], x)
        at = lp["attn"]
        q = (h @ w_(at["wq"]) + lora("wq", h)).reshape(R, S, m["H"], m["hd"])
        k = (h @ w_(at["wk"])).reshape(R, S, m["K"], m["hd"])
        v = (h @ w_(at["wv"]) + lora("wv", h)).reshape(R, S, m["K"], m["hd"])
        q = _rope(q, pos, cfg["rope_theta"])
        k = _rope(k, pos, cfg["rope_theta"])
        G = m["H"] // m["K"]
        qg = q.reshape(R, S, m["K"], G, m["hd"]).astype(jnp.float32)
        s = jnp.einsum("rikgh,rjkh->rkgij", qg, k.astype(jnp.float32))
        s = s / math.sqrt(m["hd"])
        i, j = pos[:, None], pos[None, :]
        keep = (j <= i) & (i - j < W)
        s = jnp.where(keep, s, -jnp.inf)
        o = jnp.einsum("rkgij,rjkh->rikgh", jax.nn.softmax(s, -1),
                       v.astype(jnp.float32))
        attn = o.reshape(R, S, m["H"] * m["hd"]).astype(dt)

        mb = lp["mamba"]
        zx = h @ w_(mb["in_proj"])
        di, n = m["di"], m["n"]
        z, xin, Bm, Cm, dtr = jnp.split(zx, [di, 2 * di, 2 * di + n,
                                             2 * di + 2 * n], -1)
        cin = jnp.concatenate([xin, Bm, Cm], -1)
        kk = m["k"]
        pad = jnp.concatenate([jnp.zeros((R, kk - 1, m["conv"]), cin.dtype),
                               cin], 1)
        cw = mb["conv"]["w"].astype(dt)
        conv = sum(pad[:, t:t + S] * cw[t] for t in range(kk))
        conv = jax.nn.silu(conv + mb["conv"]["b"].astype(dt))
        xin, Bm, Cm = jnp.split(conv, [di, di + n], -1)
        delta = jax.nn.softplus(dtr.astype(jnp.float32) + mb["dt_bias"])
        decay = jnp.exp(-jnp.exp(mb["A_log"]) * delta)  # [R, S, h]
        xh = xin.reshape(R, S, m["h"], m["p"]).astype(jnp.float32)

        def step(state, inp):
            dc, dl, xt, bt, ct = inp
            state = state * dc[..., None, None] + (
                (dl[..., None] * xt)[..., None] * bt[:, None, None, :])
            return state, jnp.einsum("rhpn,rn->rhp", state, ct)

        tm = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        _, y = jax.lax.scan(step, jnp.zeros((R, m["h"], m["p"], n)),
                            (tm(decay), tm(delta), tm(xh),
                             tm(Bm.astype(jnp.float32)),
                             tm(Cm.astype(jnp.float32))))
        y = jnp.moveaxis(y, 0, 1) + xh * mb["D"][:, None]
        y = y.reshape(R, S, di).astype(dt)
        y = _rms(mb["norm"], y) * jax.nn.silu(z)

        fused = jnp.concatenate([attn, y], -1) * fmask[:, None, :].astype(dt)
        x = x + fused @ w_(lp["wo"]) + lora("wo", fused)
        h2 = _rms(lp["ln2"], x)
        mlp = lp["mlp"]
        x = x + (jax.nn.silu(h2 @ w_(mlp["wg"])) * (h2 @ w_(mlp["wi"]))
                 ) @ w_(mlp["wo"])
        return x

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _ends_fn(cfg_s: str, quant: str | None):
    cfg = json.loads(cfg_s)
    V = cfg["vocab"]
    dt = jnp.bfloat16 if quant else jnp.float32

    def table(e):
        return (_q8(e, 0) if quant else e.astype(jnp.float32)).astype(dt)

    def embed(e, tokens):
        return jnp.take(table(e), tokens, axis=0)

    def unembed(e, fn, x):
        x = _rms(fn, x)
        return (x @ table(e)[:V].T).astype(jnp.float32)

    return jax.jit(embed), jax.jit(unembed)


def logits(cfg: dict, base: dict, adapters: dict, fmask, tokens,
           quant: str | None = None):
    """tokens [R, S] -> logits [R, S, vocab] (float32, on the device).

    ``adapters``: {target: {"a": [L, R, din, r], "b": [L, R, r, dout]}},
    each request's own adapter; ``fmask`` [R, fused] its block mask."""
    cs = _cfg_key(cfg)
    embed, unembed = _ends_fn(cs, quant)
    layer = _layer_fn(cs, quant)
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    ctx = (jax.default_matmul_precision("highest") if quant is None
           else jax.default_matmul_precision("default"))
    with ctx:
        x = embed(base["embed"], tokens)
        for li in range(cfg["n_layers"]):
            lp = jax.tree.map(lambda a, li=li: a[li], base["layers"])
            ad = jax.tree.map(lambda a, li=li: a[li], adapters)
            x = layer(x, lp, ad, fmask, pos)
        return unembed(base["embed"], base["final_norm"], x)
