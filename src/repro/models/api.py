"""Family-dispatching model API — the single entry point used by training,
serving, the federated engine and the dry-run launcher.

  init_model(key, cfg)                  -> params {"base":..., "lora":...}
  forward(params, cfg, batch)           -> (logits, aux_loss)
  loss_fn(params, cfg, batch)           -> scalar loss
  init_caches(cfg, batch_size, max_len) -> decode caches
  decode_step(params, cfg, caches, token, pos) -> (logits, caches)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import hybrid as HY
from repro.models import layers as L
from repro.models import ssm as SM
from repro.models import transformer as TF

Array = jax.Array

_TF_FAMILIES = ("dense", "moe", "vlm", "audio")


def init_model(key: Array, cfg: ModelConfig, with_lora: bool = True) -> dict:
    if cfg.family in _TF_FAMILIES:
        return TF.init_lm(key, cfg, with_lora)
    if cfg.family == "ssm":
        return SM.init_mamba_lm(key, cfg)
    if cfg.family == "hybrid":
        return HY.init_hybrid_lm(key, cfg, with_lora)
    raise ValueError(f"unknown family {cfg.family}")


def forward(params: dict, cfg: ModelConfig, batch: dict) -> tuple[Array, Array]:
    if cfg.family in _TF_FAMILIES:
        logits, _, aux = TF.lm_forward(params, cfg, batch["tokens"],
                                       patches=batch.get("patches"))
    elif cfg.family == "ssm":
        logits, _, aux = SM.mamba_forward(params, cfg, batch["tokens"])
    elif cfg.family == "hybrid":
        logits, _, aux = HY.hybrid_forward(params, cfg, batch["tokens"])
    else:
        raise ValueError(cfg.family)
    return logits, aux


def forward_hidden(params: dict, cfg: ModelConfig, batch: dict):
    """Forward up to the final norm (pre-unembed). Used by chunked-CE
    training and by prefill (which unembeds only the last position)."""
    if cfg.family in _TF_FAMILIES:
        return TF.lm_forward(params, cfg, batch["tokens"],
                             patches=batch.get("patches"), skip_unembed=True)
    if cfg.family == "ssm":
        return SM.mamba_forward(params, cfg, batch["tokens"],
                                skip_unembed=True)
    if cfg.family == "hybrid":
        return HY.hybrid_forward(params, cfg, batch["tokens"],
                                 skip_unembed=True)
    raise ValueError(cfg.family)


def chunked_ce(params: dict, cfg: ModelConfig, h: Array, labels: Array,
               n_chunks: int) -> Array:
    """CE over vocab computed per sequence-chunk: the [B, S, V] logits
    transient shrinks to [B, S/n_chunks, V] (production large-vocab path)."""
    from repro.models import transformer as TF

    B, S, _ = h.shape
    assert S % n_chunks == 0, (S, n_chunks)
    hc = h.reshape(B, n_chunks, S // n_chunks, -1).transpose(1, 0, 2, 3)
    tail = labels.shape[2:]  # audio: [B, S, n_codebooks]
    lc = labels.reshape(B, n_chunks, S // n_chunks, *tail)
    lc = jnp.moveaxis(lc, 1, 0)

    def one(c, args):
        hi, li = args
        logits = TF.unembed(params, cfg, hi)
        return c + L.cross_entropy_logits(logits, li), None

    total, _ = jax.lax.scan(one, jnp.float32(0.0), (hc, lc))
    return total / n_chunks


def loss_fn(params: dict, cfg: ModelConfig, batch: dict,
            aux_weight: float = 0.01) -> Array:
    labels = batch["labels"]
    if cfg.loss_chunks > 1 and cfg.family in _TF_FAMILIES:
        from repro.models import transformer as TF
        h, _, aux = TF.lm_forward(params, cfg, batch["tokens"],
                                  patches=batch.get("patches"),
                                  skip_unembed=True)
        if "patches" in batch and batch["patches"] is not None:
            h = h[:, batch["patches"].shape[1]:]
        return chunked_ce(params, cfg, h, labels, cfg.loss_chunks) \
            + aux_weight * aux
    logits, aux = forward(params, cfg, batch)
    if "patches" in batch and batch["patches"] is not None:
        # llava: loss only over the text positions (after the patch prefix)
        n_patch = batch["patches"].shape[1]
        logits = logits[:, n_patch:]
    return L.cross_entropy_logits(logits, labels) + aux_weight * aux


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                per_row_pos: bool = False) -> Any:
    """``per_row_pos`` gives every batch row its own cache position leaf so
    rows can sit at different sequence depths (continuous batching)."""
    if cfg.family in _TF_FAMILIES:
        return TF.init_kv_caches(cfg, batch, max_len, per_row_pos=per_row_pos)
    if cfg.family == "ssm":
        return SM.init_mamba_caches(cfg, batch, max_len)  # positionless state
    if cfg.family == "hybrid":
        return HY.init_hybrid_caches(cfg, batch, max_len,
                                     per_row_pos=per_row_pos)
    raise ValueError(cfg.family)


def decode_step(params: dict, cfg: ModelConfig, caches: Any, token: Array,
                pos: Array, adapter_idx: Array | None = None,
                fusion_mask: Array | None = None,
                lora_impl: str = "xla") -> tuple[Array, Any]:
    """One decode step. ``pos`` is a scalar (all rows at the same depth) or
    [B] (per-row depths; needs ``init_caches(per_row_pos=True)``).
    ``adapter_idx`` [B] selects per-row adapters from [A, ...]-stacked LoRA
    leaves (gathered multi-tenant decode); ``fusion_mask`` [B, fusion_dim]
    zeroes absent-modality blocks of the fusion projection input."""
    if cfg.family in _TF_FAMILIES:
        return TF.lm_decode_step(params, cfg, caches, token, pos,
                                 adapter_idx=adapter_idx,
                                 fusion_mask=fusion_mask, lora_impl=lora_impl)
    if cfg.family == "ssm":
        if adapter_idx is not None or fusion_mask is not None:
            raise ValueError("ssm family has no fusion projection; "
                             "multi-adapter decode is not supported")
        return SM.mamba_decode_step(params, cfg, caches, token, pos)
    if cfg.family == "hybrid":
        return HY.hybrid_decode_step(params, cfg, caches, token, pos,
                                     adapter_idx=adapter_idx,
                                     fusion_mask=fusion_mask,
                                     lora_impl=lora_impl)
    raise ValueError(cfg.family)


def fusion_block_dims(cfg: ModelConfig) -> tuple[int, ...]:
    """Modality-aligned column blocks of the fusion (``wo``) input axis.

    hybrid: (attention features, SSD features) — the RELIEF Eq. 1 layout.
    Attention families: one block per KV group (the concatenated-head axis
    is K-major after the [B, S, K, G, hd] reshape), giving head-group
    granularity for modality masks.
    """
    if cfg.family == "hybrid":
        dm = HY.hybrid_dims(cfg)
        return (dm["attn_out"], dm["d_inner"])
    if cfg.family in _TF_FAMILIES:
        g = cfg.n_heads // cfg.n_kv_heads
        return (g * cfg.head_dim,) * cfg.n_kv_heads
    raise ValueError(f"{cfg.family} has no fusion projection")


def prefill_one_pass(cfg: ModelConfig, caches: Any, S: int) -> bool:
    """Whether ``prefill_with_cache`` runs an S-token prompt as one forward
    (else as S decode steps): an attention or hybrid family, and every
    attention ring of ``caches`` holds the whole prompt (a longer prompt
    would overwrite ring slots mid-forward) in whole query chunks."""
    if cfg.family == "hybrid":
        rings = [caches["attn"]["k"].shape[2]]
    elif cfg.family in _TF_FAMILIES:
        rings = [c["k"].shape[2] for c in caches.get("__per_sub__", [caches])]
    else:
        return False
    return S <= min(rings) and (S <= cfg.q_chunk or S % cfg.q_chunk == 0)


def prefill_with_cache(params: dict, cfg: ModelConfig, caches: Any,
                       tokens: Array, patches: Array | None = None,
                       fusion_mask: Array | None = None
                       ) -> tuple[Array, Any]:
    """Prefill ``tokens`` [B, S] into ``caches``; -> (last-position logits
    [B, 1, V], updated caches).

    Where ``prefill_one_pass`` allows, one forward over the whole prompt:
    attention families run the q_chunk-tiled attention forward; the hybrid
    family runs it beside the chunked SSD scan from the cached state. Else
    the exact loop of S decode steps: prompts longer than an attention
    ring, and the ssm family, whose forward threads no caches.
    Assumes fresh caches (prefill starts at position 0).
    """
    S = tokens.shape[1]
    if cfg.family not in _TF_FAMILIES + ("ssm", "hybrid"):
        raise ValueError(cfg.family)
    if prefill_one_pass(cfg, caches, S):
        if cfg.family == "hybrid":
            return HY.hybrid_prefill(params, cfg, caches, tokens,
                                     fusion_mask=fusion_mask)
        positions = jnp.arange(S, dtype=jnp.int32)
        h, caches, _ = TF.lm_forward(params, cfg, tokens, patches=patches,
                                     positions=positions, caches=caches,
                                     skip_unembed=True,
                                     fusion_mask=fusion_mask)
        return TF.unembed(params, cfg, h[:, -1:]), caches
    logits = None
    for t in range(S):
        logits, caches = decode_step(
            params, cfg, caches, tokens[:, t:t + 1], jnp.int32(t),
            fusion_mask=None if cfg.family == "ssm" else fusion_mask)
    return logits, caches


def param_count(params: Any) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
