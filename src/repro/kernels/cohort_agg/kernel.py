"""Fused cohort-masked aggregation + divergence statistics (Pallas).

The server-side hot loop at fleet scale is a masked reduction over the client
axis N of the stacked update tensor [N, D, r] — bandwidth-bound. This kernel
streams the client axis through VMEM once, producing the Eq. 3 aggregate and
the Eq. 5 sufficient statistics (sqsum, cohort mean, count) in the same pass,
instead of the three separate reductions the naive implementation issues.

Layout: the wrapper hands the kernel the stack as [r, N, D] so every tile is
lane-dense along D and the client axis sits on sublanes — each of the r
columns is a [bn, bd] tile multiplied by the [bn, bd] weight / cohort tiles
and reduced over sublanes. Outputs come back transposed ([r, D] and [1, D])
for the same reason. Every block obeys the TPU (8, 128) rule: ``bd`` is a
multiple of 128 or all of D, ``bn`` a multiple of 32 (the int8 sublane tile)
or all of N.

Grid: (D/bd, N/bn) — N innermost; the output blocks do not move along N,
so they stay resident in VMEM and serve as the accumulators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import LANE, check_tile, compiler_params

# clients per grid step: a multiple of the int8 sublane tile (32), so the
# same tiling serves the fp32 and the int8 stack
CLIENT_TILE = 128


def _client_tile(N: int) -> int:
    return N if N <= CLIENT_TILE else CLIENT_TILE


def _accumulate(d_ref, w_agg, w_mean, w_sq, cnt_rows, agg_ref, sq_ref,
                mean_ref, cnt_ref, *, r: int, n_steps: int):
    """Shared body: ``d_ref`` [r, bn, bd] tile, per-element weights
    [bn, bd] for the aggregate / cohort mean / square sum."""
    n_idx = pl.program_id(1)

    @pl.when(n_idx == 0)
    def _init():
        agg_ref[...] = jnp.zeros_like(agg_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)
        mean_ref[...] = jnp.zeros_like(mean_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    sq = jnp.zeros(w_sq.shape, jnp.float32)
    for j in range(r):
        d = d_ref[j].astype(jnp.float32)  # [bn, bd]
        agg_ref[j:j + 1, :] += jnp.sum(w_agg * d, axis=0, keepdims=True)
        mean_ref[j:j + 1, :] += jnp.sum(w_mean * d, axis=0, keepdims=True)
        sq = sq + d * d
    sq_ref[...] += jnp.sum(w_sq * sq, axis=0, keepdims=True)
    cnt_ref[...] += jnp.sum(cnt_rows, axis=0, keepdims=True)

    @pl.when(n_idx == n_steps - 1)
    def _finish():
        mean_ref[...] = mean_ref[...] / jnp.maximum(cnt_ref[...], 1.0)


def _kernel(d_ref, w_ref, c_ref, agg_ref, sq_ref, mean_ref, cnt_ref, *,
            r: int, n_steps: int):
    c = c_ref[...]
    _accumulate(d_ref, w_ref[...], c, c, c, agg_ref, sq_ref, mean_ref,
                cnt_ref, r=r, n_steps=n_steps)


def _quant_kernel(q_ref, s_ref, ws_ref, w_ref, c_ref, agg_ref, sq_ref,
                  mean_ref, cnt_ref, *, r: int, n_steps: int):
    """Quantized-ingest variant: the int8 tile is dequantized in VMEM and
    the FedBuff staleness discount folded into the combine weight, in the
    same accumulation — the fp32 client stack never exists in HBM."""
    s = s_ref[...]  # [bn, 1] dequant scale
    c = c_ref[...]
    _accumulate(q_ref, w_ref[...] * ws_ref[...], c * s, c * (s * s), c,
                agg_ref, sq_ref, mean_ref, cnt_ref, r=r, n_steps=n_steps)


def _reduce(kernel, stack, row_args, client_args, bd: int, interpret: bool):
    """Shared pallas_call: ``stack`` [N, D, r]; ``row_args`` [N, D] arrays;
    ``client_args`` [N] per-client scalars (passed as [N, 1] columns).
    Returns (agg [D, r], sqsum [D], mean [D, r], cnt [D])."""
    N, D, r = stack.shape
    check_tile(D, bd, name="bd")
    bn = _client_tile(N)
    pad = -N % bn
    x = jnp.transpose(stack, (2, 0, 1))  # [r, N, D]: D on lanes
    cols = [a.astype(jnp.float32)[:, None] for a in client_args]
    rows = [a.astype(jnp.float32) for a in row_args]
    if pad:  # zero-weight clients contribute nothing to any statistic
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        cols = [jnp.pad(a, ((0, pad), (0, 0))) for a in cols]
        rows = [jnp.pad(a, ((0, pad), (0, 0))) for a in rows]
    n_steps = (N + pad) // bn
    row_spec = pl.BlockSpec((bn, bd), lambda d, n: (n, d))
    col_spec = pl.BlockSpec((bn, 1), lambda d, n: (n, 0))
    wide = pl.BlockSpec((r, bd), lambda d, n: (0, d))
    flat = pl.BlockSpec((1, bd), lambda d, n: (0, d))
    block_bytes = (r * bn * bd * x.dtype.itemsize + len(rows) * bn * bd * 4
                   + len(cols) * bn * LANE * 4 + (2 * r + 2) * bd * 4)
    aggT, sq, meanT, cnt = pl.pallas_call(
        functools.partial(kernel, r=r, n_steps=n_steps),
        grid=(D // bd, n_steps),
        in_specs=([pl.BlockSpec((r, bn, bd), lambda d, n: (0, n, d))]
                  + [col_spec] * len(cols) + [row_spec] * len(rows)),
        out_specs=[wide, flat, wide, flat],
        out_shape=[jax.ShapeDtypeStruct((r, D), jnp.float32),
                   jax.ShapeDtypeStruct((1, D), jnp.float32),
                   jax.ShapeDtypeStruct((r, D), jnp.float32),
                   jax.ShapeDtypeStruct((1, D), jnp.float32)],
        compiler_params=compiler_params(("parallel", "arbitrary"),
                                        block_bytes),
        interpret=interpret,
    )(x, *cols, *rows)
    return aggT.T, sq[0], meanT.T, cnt[0]


def cohort_agg_divergence_pallas(deltas, W, C, bd: int = LANE,
                                 interpret: bool = False):
    """deltas [N, D, r], W/C [N, D] -> (agg [D, r], sqsum [D], mean [D, r],
    cnt [D]). ``bd`` must be a multiple of 128 dividing D, or D."""
    return _reduce(_kernel, deltas, (W, C), (), min(bd, deltas.shape[1]),
                   interpret)


def cohort_agg_divergence_quant_pallas(q, scales, W, C, staleness,
                                       exponent: float, bd: int = LANE,
                                       interpret: bool = False):
    """q [N, D, r] int8, scales [N] per-(client, leaf) dequant scales,
    W/C [N, D], staleness [N] -> same outputs as the fp32 kernel for
    effective deltas q*scale and effective weights W/(1+staleness)^a."""
    s = scales.astype(jnp.float32)
    if exponent == 0.0:
        ws = s
    else:  # w_eff = W * 1/(1+s)^a, per-client scalar
        ws = s * jnp.power(1.0 + staleness.astype(jnp.float32),
                           -float(exponent))
    return _reduce(_quant_kernel, q, (W, C), (s, ws),
                   min(bd, q.shape[1]), interpret)
