"""Operations and bytes from shapes, and the chip's peaks.

Every count here is the least work the computation needs, whatever program
implements it: matrix products at 2 FLOPs per multiply-add, each weight read
once per call, caches read only where they hold live positions. A faster
implementation can approach these numbers but never beat them, so a share
of a peak computed from them cannot pass 100 %.

The configurations are the benchmark's own files (``bench/configs``), read
as plain dicts.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table of one chip kind; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# RELIEF multimodal model (Backbone 2: frozen patch transformer + LoRA)
# ---------------------------------------------------------------------------


def relief_encoder_flops(cfg: dict, channels: int, d_feat: int) -> float:
    """Required FLOPs of one encoder for one training sample: forward,
    the input-gradient backward through frozen weights (their weight
    gradients are not required), and forward + both backward products of
    the LoRA adapters on Q, V and the FFN input."""
    n = cfg["window"] // cfg["patch"]
    d, ff, r = cfg["enc_d"], cfg["enc_ff"], cfg["lora_rank"]
    patch = 2 * n * cfg["patch"] * channels * d  # input is data: forward only
    frozen = 2 * n * (4 * d * d + 3 * d * ff)  # wq wk wv wo, wi wg wo
    attn = 4 * n * n * d  # q.k and p.v
    lora = 2 * n * r * ((d + d) + (d + d) + (d + ff))
    layer = 2 * frozen + 3 * attn + 3 * lora
    proj = 2 * 2 * d * d_feat  # after the mean pool; forward + input grad
    return float(patch + cfg["enc_layers"] * layer + proj)


def relief_sample_flops(cfg: dict, held: list[bool]) -> float:
    """Required FLOPs of one training sample of a client that holds the
    modalities ``held``: the encoders it holds, the fusion projection
    (frozen w0: forward + input grad; LoRA: x3) and the head (x3)."""
    mods = cfg["modalities"]
    D = sum(m["d_feat"] for m in mods)
    r, df, hh = cfg["lora_rank"], cfg["d_fused"], cfg["head_hidden"]
    enc = sum(relief_encoder_flops(cfg, m["channels"], m["d_feat"])
              for m, h in zip(mods, held) if h)
    fusion = 2 * (2 * D * df) + 3 * (2 * r * (D + df))
    head = 3 * 2 * (df * hh + hh * cfg["n_classes"])
    return float(enc + fusion + head)


def relief_update_flops(cfg: dict, held: list[bool]) -> float:
    """Required FLOPs of one client update (all local steps)."""
    samples = cfg["local_epochs"] * cfg["steps_per_epoch"] * cfg["batch_size"]
    return samples * relief_sample_flops(cfg, held)


# ---------------------------------------------------------------------------
# hymba hybrid LM (parallel attention + SSD heads, fused output projection)
# ---------------------------------------------------------------------------


def hymba_dims(cfg: dict) -> dict:
    d_inner = cfg["d_inner"]
    h_ssm = d_inner // cfg["ssm_head_dim"]
    n = cfg["ssm_state"]
    attn_out = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    return dict(d=cfg["d_model"], attn_out=attn_out, kv=kv, d_inner=d_inner,
                h_ssm=h_ssm, n=n, p=cfg["ssm_head_dim"],
                conv_dim=d_inner + 2 * n, in_proj=2 * d_inner + 2 * n + h_ssm,
                fused=attn_out + d_inner, ff=cfg["d_ff"],
                L=cfg["n_layers"], V=cfg["vocab"], r=cfg["lora_rank"],
                k=cfg["conv_kernel"])


def hymba_lora_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    m = hymba_dims(cfg)
    return {"wq": (m["d"], m["attn_out"]), "wv": (m["d"], m["kv"]),
            "wo": (m["fused"], m["d"])}


def hymba_layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that a token multiplies (no norms, no conv)."""
    m = hymba_dims(cfg)
    d = m["d"]
    return (d * m["attn_out"] + 2 * d * m["kv"] + d * m["in_proj"]
            + m["fused"] * d + 3 * d * m["ff"])


def hymba_layer_params(cfg: dict) -> int:
    m = hymba_dims(cfg)
    small = (m["k"] * m["conv_dim"] + m["conv_dim"] + 3 * m["h_ssm"]
             + m["d_inner"] + 2 * m["d"])
    return hymba_layer_matmul_params(cfg) + small


def hymba_base_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Base weights read once: every layer and the (tied) embedding table's
    vocab rows for the unembedding."""
    m = hymba_dims(cfg)
    return float(itemsize * (m["L"] * hymba_layer_params(cfg)
                             + m["V"] * m["d"] + m["d"]))


def hymba_adapter_bytes(cfg: dict, itemsize: int = 4) -> float:
    """One client adapter (all layers, all targets)."""
    m = hymba_dims(cfg)
    per_layer = sum(m["r"] * (a + b) for a, b in hymba_lora_shapes(cfg).values())
    return float(itemsize * m["L"] * per_layer)


def hymba_token_flops(cfg: dict, ctx: int) -> float:
    """FLOPs of one token through the whole model with its adapter, where
    attention sees ``ctx`` live positions (itself included)."""
    m = hymba_dims(cfg)
    layer = 2 * hymba_layer_matmul_params(cfg)
    layer += 2 * sum(m["r"] * (a + b) for a, b in hymba_lora_shapes(cfg).values())
    layer += 4 * m["attn_out"] * min(ctx, cfg["sliding_window"])  # q.k, p.v
    layer += 2 * m["k"] * m["conv_dim"] + 4 * m["h_ssm"] * m["p"] * m["n"]
    return float(m["L"] * layer + 2 * m["d"] * m["V"])


def hymba_kv_bytes_per_pos(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one position over all layers, each layer its own (the
    served model shares no KV across layers)."""
    m = hymba_dims(cfg)
    return float(m["L"] * 2 * m["kv"] * itemsize)


def hymba_state_bytes(cfg: dict, act_itemsize: int = 2) -> float:
    """SSD state (f32) and conv window of one sequence over all layers."""
    m = hymba_dims(cfg)
    return float(m["L"] * (m["h_ssm"] * m["p"] * m["n"] * 4
                           + (m["k"] - 1) * m["conv_dim"] * act_itemsize))


def hymba_decode_step_bytes(cfg: dict, rows: int, live: int,
                            n_adapters: int) -> float:
    """Least bytes of one batched decode step of ``rows`` sequences that
    attend to ``live`` cached positions in all: base weights once, each
    distinct adapter of the batch once, the live KV positions read and each
    row's new one written, each row's SSD state and conv window read and
    written."""
    kv = hymba_kv_bytes_per_pos(cfg)
    return (hymba_base_bytes(cfg) + n_adapters * hymba_adapter_bytes(cfg)
            + live * kv + rows * kv + 2 * rows * hymba_state_bytes(cfg))


def hymba_prefill_min_s(cfg: dict, prompt_len: int, pk: dict) -> float:
    """Least time of a ``prompt_len``-token prefill with one adapter: the
    larger of its FLOPs over peak and (weights once + adapter + the cache
    row it writes) over peak bandwidth."""
    flops = sum(hymba_token_flops(cfg, t + 1) for t in range(prompt_len))
    bytes_ = (hymba_base_bytes(cfg) + hymba_adapter_bytes(cfg)
              + prompt_len * hymba_kv_bytes_per_pos(cfg)
              + hymba_state_bytes(cfg))
    return max(flops / pk["bf16_flops_per_s"], bytes_ / pk["hbm_bytes_per_s"])


def mdlora_call_bytes(din: int, dout: int, rank: int, rows: int,
                      n_adapters: int, w_itemsize: int = 2,
                      a_itemsize: int = 4, x_itemsize: int = 2) -> float:
    """Least bytes of one gathered multi-adapter projection: the shared w0
    once, each distinct adapter's a and b once, the inputs and outputs."""
    return float(din * dout * w_itemsize
                 + n_adapters * rank * (din + dout) * a_itemsize
                 + rows * (din + dout) * x_itemsize)


def mdlora_call_flops(din: int, dout: int, rank: int, rows: int) -> float:
    return float(2 * rows * (din * dout + rank * (din + dout)))


def hymba_served_flops(cfg: dict, steps, admits) -> float:
    """Required FLOPs of a serving window: every active row of every decode
    step (``rows``, and ``sum_ctx`` live positions attended), and every
    admission's whole prompt (``admits``: prompt lengths)."""
    base = hymba_token_flops(cfg, 0)  # everything but attention
    per_pos = hymba_token_flops(cfg, 1) - base  # attention, per position
    total = sum(s.rows * base + s.sum_ctx * per_pos for s in steps)
    return total + sum(hymba_token_flops(cfg, t + 1) for p in admits
                       for t in range(p))
