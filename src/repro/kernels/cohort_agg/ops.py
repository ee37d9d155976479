"""jit'd wrappers for the fused cohort aggregation + divergence kernels.

``interpret=None`` resolves to the backend default (interpret only on CPU —
see kernels/runtime.py), so ``impl="pallas"`` is safe everywhere without the
caller knowing the hardware. ``bd=None`` resolves through the autotuner
(kernels/cohort_agg/autotune.py) at trace time; an explicit ``bd`` is
snapped to the largest legal TPU block of D that does not exceed it
(kernels/runtime.py ``legal_tile``).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.cohort_agg.autotune import select_block_size
from repro.kernels.cohort_agg.kernel import (
    cohort_agg_divergence_pallas, cohort_agg_divergence_quant_pallas)
from repro.kernels.cohort_agg.ref import (cohort_agg_divergence_quant_ref,
                                          cohort_agg_divergence_ref)
from repro.kernels.runtime import legal_tile, resolve_interpret


def _resolve_bd(shape, impl: str, interpret: bool, bd: int | None,
                quant: bool) -> int:
    if bd is None:
        return select_block_size(shape, impl=impl, interpret=interpret,
                                 quant=quant)
    return legal_tile(shape[1], bd)


@functools.partial(jax.jit, static_argnames=("impl", "interpret", "bd"))
def _agg_jit(deltas, W, C, impl, interpret, bd):
    if impl == "pallas":
        return cohort_agg_divergence_pallas(deltas, W, C, bd=bd,
                                            interpret=interpret)
    return cohort_agg_divergence_ref(deltas, W, C)


def cohort_agg_divergence(deltas, W, C, impl: str = "xla",
                          interpret: bool | None = None,
                          bd: int | None = None):
    """deltas [N, D, r], W [N, D] (Eq.3/4 weights), C [N, D] (Eq.5 cohort)
    -> (agg [D,r], sqsum [D], mean [D,r], cnt [D])."""
    interpret = resolve_interpret(interpret)
    bd = _resolve_bd(deltas.shape, impl, interpret, bd, quant=False)
    return _agg_jit(deltas, W, C, impl, interpret, bd)


@functools.partial(jax.jit,
                   static_argnames=("exponent", "impl", "interpret", "bd"))
def _quant_jit(q, scales, W, C, staleness, exponent, impl, interpret, bd):
    if impl == "pallas":
        return cohort_agg_divergence_quant_pallas(q, scales, W, C, staleness,
                                                  exponent, bd=bd,
                                                  interpret=interpret)
    return cohort_agg_divergence_quant_ref(q, scales, W, C, staleness,
                                           exponent)


def cohort_agg_divergence_quant(q, scales, W, C, staleness,
                                exponent: float = 0.0, impl: str = "xla",
                                interpret: bool | None = None,
                                bd: int | None = None):
    """Fused quantized-ingest aggregation: one pass over the int8 uplink.

    q [N, D, r] int8 client chunks, scales [N] per-(client, leaf) dequant
    scales, W/C [N, D], staleness [N] server versions since pull. Equals
    ``cohort_agg_divergence(q * scales, W / (1+staleness)**exponent, C)``
    without ever materializing the fp32 [N, D, r] stack.
    """
    interpret = resolve_interpret(interpret)
    bd = _resolve_bd(q.shape, impl, interpret, bd, quant=True)
    return _quant_jit(q, scales, W, C, staleness, float(exponent), impl,
                      interpret, bd)
