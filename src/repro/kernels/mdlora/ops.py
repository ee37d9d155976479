"""jit'd public wrappers for the fused block-LoRA projections.

``interpret=None`` resolves to the backend default (interpret only on CPU —
see kernels/runtime.py). Block sizes default to ``None`` and resolve through
the shared autotuner (kernels/cohort_agg/autotune.py): largest-divisor
heuristic on interpret/XLA backends, timed sweep on compiled Pallas.
Explicit block sizes are snapped to the largest legal TPU block of the
tiled axis (kernels/runtime.py ``legal_tile``), so blocking survives
non-divisible shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cohort_agg.autotune import select_mdlora_blocks
from repro.kernels.mdlora.kernel import (mdlora_matmul_multi_pallas,
                                         mdlora_matmul_pallas)
from repro.kernels.mdlora.ref import (mdlora_matmul_multi_ref,
                                      mdlora_matmul_ref)
from repro.kernels.runtime import legal_tile, resolve_interpret


def block_row_mask(block_dims, modality_mask) -> jnp.ndarray:
    """[M] modality availability -> [D] row mask over the fusion input."""
    reps = np.asarray(block_dims, np.int32)
    mm = jnp.asarray(modality_mask, jnp.float32)
    return jnp.repeat(mm, jnp.asarray(reps), total_repeat_length=int(reps.sum()))


def block_row_masks(block_dims, modality_masks) -> jnp.ndarray:
    """[B, M] per-request availability -> [B, D] row masks (batched)."""
    reps = np.asarray(block_dims, np.int32)
    mm = jnp.asarray(modality_masks, jnp.float32)
    return jnp.repeat(mm, jnp.asarray(reps), axis=-1,
                      total_repeat_length=int(reps.sum()))


def _resolve_blocks(T, D, F, r, impl, interpret, bt, bf, bd, multi=False,
                    n_adapters=1):
    if bt is None or bf is None or bd is None:
        tt, tf, td = select_mdlora_blocks((T, D, F, r), impl=impl,
                                          interpret=interpret, multi=multi,
                                          n_adapters=n_adapters)
        bt, bf, bd = bt or tt, bf or tf, bd or td
    return (1 if multi else legal_tile(T, bt, 16), legal_tile(F, bf),
            legal_tile(D, bd))


@functools.partial(jax.jit, static_argnames=("scale", "impl", "interpret",
                                             "bt", "bf", "bd"))
def _matmul_jit(x, w0, a, b, row_mask, scale, impl, interpret, bt, bf, bd):
    if impl == "pallas":
        return mdlora_matmul_pallas(x, w0, a, b, row_mask, scale,
                                    bt=bt, bf=bf, bd=bd, interpret=interpret)
    return mdlora_matmul_ref(x, w0, a, b, row_mask, scale)


def mdlora_matmul(x, w0, a, b, row_mask, scale: float = 2.0,
                  impl: str = "xla", interpret: bool | None = None,
                  bt: int | None = None, bf: int | None = None,
                  bd: int | None = None):
    """y = (x*mask)@W0 + ((x*mask)@a)@b*scale.

    impl="pallas" is the TPU deployment path (interpret resolves per
    backend); impl="xla" is the portable fallback the CPU dry-run compiles.
    """
    interpret = resolve_interpret(interpret)
    bt, bf, bd = _resolve_blocks(x.shape[0], x.shape[1], w0.shape[1],
                                 a.shape[1], impl, interpret, bt, bf, bd)
    return _matmul_jit(x, w0, a, b, row_mask, float(scale), impl, interpret,
                       bt, bf, bd)


@functools.partial(jax.jit, static_argnames=("scale", "impl", "interpret",
                                             "bf", "bd"))
def _matmul_multi_jit(x, w0, a, b, adapter_idx, row_mask, scale, impl,
                      interpret, bf, bd):
    if row_mask is None:
        row_mask = jnp.ones(x.shape, jnp.float32)
    if impl == "pallas":
        return mdlora_matmul_multi_pallas(x, w0, a, b, adapter_idx, row_mask,
                                          scale, bf=bf, bd=bd,
                                          interpret=interpret)
    return mdlora_matmul_multi_ref(x, w0, a, b, adapter_idx, row_mask, scale)


def mdlora_matmul_multi(x, w0, a, b, adapter_idx, row_mask=None,
                        scale: float = 2.0, impl: str = "xla",
                        interpret: bool | None = None, bf: int | None = None,
                        bd: int | None = None):
    """Gathered multi-adapter projection: one fused call serves a batch of
    requests that each carry their own modality-block adapter.

    x: [B, D] (one token per request); w0: [D, F] shared base; a: [A, D, r] /
    b: [A, r, F] stacked adapter store; adapter_idx: [B] row -> slot;
    row_mask: [B, D] per-request modality row masks (None = all present).
    """
    interpret = resolve_interpret(interpret)
    _, bf, bd = _resolve_blocks(x.shape[0], x.shape[1], w0.shape[1],
                                a.shape[2], impl, interpret, 1, bf, bd,
                                multi=True, n_adapters=a.shape[0])
    adapter_idx = jnp.asarray(adapter_idx, jnp.int32)
    return _matmul_multi_jit(x, w0, a, b, adapter_idx, row_mask,
                             float(scale), impl, interpret, bf, bd)
