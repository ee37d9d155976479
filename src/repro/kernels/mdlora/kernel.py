"""Fused block-masked LoRA projection as a Pallas TPU kernel.

One pass over the D (contraction) axis accumulates BOTH the frozen base
matmul x@W0 and the LoRA bottleneck u = x@a in VMEM scratch; the final grid
step applies u @ b * scale into the output tile. The modality row-mask is
folded into the x tile load, so absent-modality blocks cost no MXU work
beyond the masked multiply (and, on the A side, allow XLA to skip dead
blocks entirely when the mask is static).

Tiling: grid = (T/bt, F/bf, D/bd). Every block obeys the TPU (8, 128) rule
(kernels/runtime.py): bf and bd are multiples of 128 or the whole dim, bt a
multiple of the sublane tile or all of T; the row mask travels as a [1, D]
row so its block is 2-D. The base matmul runs in the weights' dtype with an
fp32 accumulator, so a bf16 W0 tile is never widened in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import check_tile, compiler_params, sublane


def _base_and_bottleneck(xm, w0, a):
    """(xm @ w0, xm @ a) with fp32 accumulation; ``xm`` [rows, bd]."""
    dt = jnp.promote_types(xm.dtype, w0.dtype)
    base = jnp.dot(xm.astype(dt), w0.astype(dt),
                   preferred_element_type=jnp.float32)
    u = jnp.dot(xm.astype(jnp.float32), a.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return base, u


def _kernel(x_ref, w0_ref, a_ref, b_ref, mask_ref, o_ref, acc_ref, u_ref, *,
            scale: float, n_d: int):
    d_idx = pl.program_id(2)

    @pl.when(d_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    xm = x_ref[...] * mask_ref[...].astype(x_ref.dtype)  # [bt, bd]
    base, u = _base_and_bottleneck(xm, w0_ref[...], a_ref[...])
    acc_ref[...] += base
    u_ref[...] += u

    @pl.when(d_idx == n_d - 1)
    def _finish():
        lora = jnp.dot(u_ref[...], b_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


def _multi_kernel(idx_ref, x_ref, w0_ref, a_ref, b_ref, mask_ref, o_ref,
                  acc_ref, u_ref, *, scale: float, n_d: int):
    del idx_ref  # consumed by the BlockSpec index maps (adapter gather)
    d_idx = pl.program_id(1)
    row = pl.program_id(2)

    @pl.when(d_idx == 0)
    def _init():
        acc_ref[row] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
        u_ref[row] = jnp.zeros(u_ref.shape[1:], jnp.float32)

    xm = x_ref[0] * mask_ref[0].astype(x_ref.dtype)  # [1, bd]
    base, u = _base_and_bottleneck(xm, w0_ref[...], a_ref[0])
    acc_ref[row] += base
    u_ref[row] += u

    @pl.when(d_idx == n_d - 1)
    def _finish():
        lora = jnp.dot(u_ref[row], b_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        o_ref[0] = (acc_ref[row] + scale * lora).astype(o_ref.dtype)


def _nbytes(shape, dtype) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n * jnp.dtype(dtype).itemsize


def mdlora_matmul_multi_pallas(x, w0, a, b, adapter_idx, row_mask, scale,
                               bf: int = 256, bd: int = 256,
                               interpret: bool = False):
    """Gathered multi-adapter decode: one fused pass serves a mixed batch.

    x: [B, D]; w0: [D, F]; a: [A, D, r]; b: [A, r, F]; adapter_idx: [B];
    row_mask: [B, D] -> [B, F].

    ``adapter_idx`` is scalar-prefetched so the BlockSpec index maps can DMA
    each row's adapter tiles straight out of the stacked [A, ...] store —
    the per-request [B, D, r] gathered weight copies never exist. Rows run
    innermost (each row may use a different adapter), so a W0 tile is
    fetched once per (F, D) tile and reused by the whole batch; every row
    keeps its base accumulator and LoRA bottleneck u in VMEM scratch across
    the D axis. Rows travel as [B, 1, D] / [B, 1, F] so their blocks are
    (1, 128k) rows, legal for the TPU tiling.
    """
    B, D = x.shape
    F = w0.shape[1]
    r = a.shape[2]
    bf, bd = min(bf, F), min(bd, D)
    check_tile(F, bf, name="bf")
    check_tile(D, bd, name="bd")
    n_d = D // bd

    grid = (F // bf, n_d, B)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bd), lambda j, k, i, idx: (i, 0, k)),  # x
            pl.BlockSpec((bd, bf), lambda j, k, i, idx: (k, j)),  # w0
            pl.BlockSpec((1, bd, r), lambda j, k, i, idx: (idx[i], k, 0)),
            pl.BlockSpec((1, r, bf), lambda j, k, i, idx: (idx[i], 0, j)),
            pl.BlockSpec((1, 1, bd), lambda j, k, i, idx: (i, 0, k)),  # mask
        ],
        out_specs=pl.BlockSpec((1, 1, bf), lambda j, k, i, idx: (i, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((B, 1, bf), jnp.float32),
            pltpu.VMEM((B, 1, r), jnp.float32),
        ],
    )
    block_bytes = (_nbytes((bd, bf), w0.dtype) + _nbytes((bd, 128), a.dtype)
                   + _nbytes((8, bd), x.dtype) + _nbytes((8, bd), jnp.float32)
                   + _nbytes((8, bf), x.dtype) + _nbytes((8, bf), b.dtype)
                   + B * _nbytes((8, bf + 128), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_multi_kernel, scale=float(scale), n_d=n_d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, F), x.dtype),
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), block_bytes),
        interpret=interpret,
    )(adapter_idx, x[:, None, :], w0, a, b, row_mask[:, None, :])
    return out[:, 0, :]


def mdlora_matmul_pallas(x, w0, a, b, row_mask, scale,
                         bt: int = 256, bf: int = 256, bd: int = 256,
                         interpret: bool = False):
    """x: [T, D]; w0: [D, F]; a: [D, r]; b: [r, F]; row_mask: [D] -> [T, F]."""
    T, D = x.shape
    F = w0.shape[1]
    r = a.shape[1]
    bt, bf, bd = min(bt, T), min(bf, F), min(bd, D)
    check_tile(T, bt, sublane(x.dtype), "bt")
    check_tile(F, bf, name="bf")
    check_tile(D, bd, name="bd")
    n_d = D // bd

    grid = (T // bt, F // bf, n_d)
    block_bytes = (_nbytes((bt, bd), x.dtype) + _nbytes((bd, bf), w0.dtype)
                   + _nbytes((bd, 128), a.dtype) + _nbytes((8, bf), b.dtype)
                   + _nbytes((8, bd), jnp.float32)
                   + _nbytes((bt, bf), x.dtype)
                   + _nbytes((bt, bf + 128), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), n_d=n_d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bd), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((bd, bf), lambda i, j, k: (k, j)),  # w0
            pl.BlockSpec((bd, r), lambda i, j, k: (k, 0)),  # a
            pl.BlockSpec((r, bf), lambda i, j, k: (0, j)),  # b
            pl.BlockSpec((1, bd), lambda i, j, k: (0, k)),  # row_mask
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        scratch_shapes=[
            # fp32 accumulators live in VMEM across the D-axis grid steps
            pltpu.VMEM((bt, bf), jnp.float32),
            pltpu.VMEM((bt, r), jnp.float32),
        ],
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"), block_bytes),
        interpret=interpret,
    )(x, w0, a, b, row_mask[None, :])
