"""Block-size selection for the cohort-agg and mdlora kernels.

The kernel tiles the row dimension D of the fusion leaf into ``bd``-row
blocks; N streams innermost so the four accumulators stay VMEM-resident.
The right ``bd`` balances per-step DMA size against grid overhead and is
shape- and backend-dependent, so instead of a hardcoded value the wrappers
resolve ``bd=None`` here, once per shape (process-cached):

* interpret mode / XLA impl: timing is meaningless (interpret) or unused
  (the einsum oracle ignores ``bd``), so take the largest legal block
  within the VMEM accumulator budget — the fewest-launches heuristic.
* compiled Pallas (real TPU/GPU backend): run a bench_roofline.py-style
  sweep over the candidate cells on dummy data and keep the fastest
  (median of ``_SWEEP_REPS`` timed reps after a compile warm-up).

Every candidate is a tiling the TPU compiler accepts (kernels/runtime.py
``legal_tile``): a multiple of 128 that divides the axis, or the whole
axis — so a dim with no such divisor (hymba's d_model = 1600) has exactly
one candidate, the full dim. An explicit block size snaps to the largest
legal one that does not exceed it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.runtime import legal_tile

# power-of-two caps the sweep considers (snapped to legal tiles)
_CANDIDATE_CAPS = (128, 256, 512)
_SWEEP_REPS = 3
# accumulators are 2*(bd*r + bd) f32 plus the streamed (bd, r) input tile;
# stay well under the ~16 MB/core VMEM so double buffering has headroom
_VMEM_ACC_BUDGET = 4 * 2**20

_CACHE: dict[tuple, int] = {}


def candidate_bds(D: int, r: int) -> list[int]:
    """Distinct legal block sizes for row dimension D, VMEM-feasible ones
    first; the smallest legal block when none fits the budget."""
    legal = sorted({legal_tile(D, cap) for cap in _CANDIDATE_CAPS})
    fits = [bd for bd in legal
            if 4 * (2 * bd * (r + 1) + bd * r) <= _VMEM_ACC_BUDGET]
    return fits or legal[:1]


def clear_cache() -> None:
    _CACHE.clear()


def select_block_size(shape: tuple[int, int, int], impl: str = "pallas",
                      interpret: bool = True, quant: bool = False) -> int:
    """Resolve ``bd`` for a [N, D, r] reduction (cached per shape/backend)."""
    N, D, r = (int(x) for x in shape)
    key = (N, D, r, impl, bool(interpret), bool(quant),
           jax.default_backend())
    if key not in _CACHE:
        cands = candidate_bds(D, r)
        if impl != "pallas" or interpret or len(cands) == 1:
            _CACHE[key] = cands[-1]
        else:
            _CACHE[key] = _timed_select(N, D, r, cands, quant)
    return _CACHE[key]


def _timed_select(N: int, D: int, r: int, cands: list[int],
                  quant: bool) -> int:
    from repro.kernels.cohort_agg.kernel import (
        cohort_agg_divergence_pallas, cohort_agg_divergence_quant_pallas)

    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.random((N, D)), jnp.float32)
    C = jnp.asarray(rng.random((N, D)) < 0.5, jnp.float32)
    if quant:
        q = jnp.asarray(rng.integers(-127, 128, (N, D, r)), jnp.int8)
        s = jnp.asarray(rng.random((N,)) * 1e-2, jnp.float32)
        t = jnp.asarray(rng.integers(0, 4, (N,)), jnp.float32)

        def run(bd):
            return cohort_agg_divergence_quant_pallas(
                q, s, W, C, t, 0.5, bd=bd, interpret=False)
    else:
        deltas = jnp.asarray(rng.normal(size=(N, D, r)), jnp.float32)

        def run(bd):
            return cohort_agg_divergence_pallas(deltas, W, C, bd=bd,
                                                interpret=False)

    best, best_t = cands[-1], float("inf")
    for bd in cands:
        jax.block_until_ready(run(bd))  # compile warm-up
        ts = []
        for _ in range(_SWEEP_REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(run(bd))
            ts.append(time.perf_counter() - t0)
        med = sorted(ts)[len(ts) // 2]
        if med < best_t:
            best, best_t = bd, med
    return best


# ---------------------------------------------------------------------------
# mdlora (fused block-LoRA projection) block selection
# ---------------------------------------------------------------------------
#
# The projection kernels tile (T, F, D) -> (bt, bf, bd); the gathered
# multi-adapter variant pins bt=1 (each batch row may use a different
# adapter) and tunes (bf, bd) only. Same policy as the cohort-agg selector:
# largest-divisor fewest-launches heuristic on interpret/XLA backends, a
# timed sweep of the VMEM-feasible candidate cells on compiled Pallas.


def _mdlora_vmem_bytes(bt: int, bf: int, bd: int, r: int) -> int:
    # x tile + w0 tile + a tile + b tile + acc/u scratch, fp32
    return 4 * (bt * bd + bd * bf + bd * r + r * bf + bt * (bf + r))


def mdlora_candidates(T: int, D: int, F: int, r: int,
                      multi: bool) -> list[tuple[int, int, int]]:
    """Distinct legal (bt, bf, bd) cells (bt = 1 when ``multi``), the
    VMEM-feasible ones; the smallest legal cell when none fits."""
    cells = set()
    for cap in _CANDIDATE_CAPS:
        # bt: multiple of the bf16 sublane tile (16) or all of T
        bt = 1 if multi else legal_tile(T, cap, 16)
        cells.add((bt, legal_tile(F, cap), legal_tile(D, cap)))
    cells = sorted(cells)
    fits = [c for c in cells if _mdlora_vmem_bytes(*c, r) <= _VMEM_ACC_BUDGET]
    return fits or cells[:1]


def select_mdlora_blocks(shape: tuple[int, int, int, int],
                         impl: str = "pallas", interpret: bool = True,
                         multi: bool = False,
                         n_adapters: int = 1) -> tuple[int, int, int]:
    """Resolve (bt, bf, bd) for a [T, D] x [D, F] (rank r) projection."""
    T, D, F, r = (int(x) for x in shape)
    key = ("mdlora", T, D, F, r, impl, bool(interpret), bool(multi),
           int(n_adapters), jax.default_backend())
    if key not in _CACHE:
        cands = mdlora_candidates(T, D, F, r, multi)
        if impl != "pallas" or interpret or len(cands) == 1:
            _CACHE[key] = cands[-1]
        else:
            _CACHE[key] = _timed_select_mdlora(T, D, F, r, cands, multi,
                                               n_adapters)
    return _CACHE[key]


def _timed_select_mdlora(T: int, D: int, F: int, r: int,
                         cands: list[tuple[int, int, int]], multi: bool,
                         n_adapters: int) -> tuple[int, int, int]:
    from repro.kernels.mdlora.kernel import (mdlora_matmul_multi_pallas,
                                             mdlora_matmul_pallas)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w0 = jnp.asarray(rng.normal(size=(D, F)) * 0.05, jnp.float32)
    if multi:
        A = max(int(n_adapters), 1)
        a = jnp.asarray(rng.normal(size=(A, D, r)) * 0.1, jnp.float32)
        b = jnp.asarray(rng.normal(size=(A, r, F)) * 0.1, jnp.float32)
        idx = jnp.asarray(rng.integers(0, A, T), jnp.int32)
        mask = jnp.asarray(rng.random((T, D)) < 0.8, jnp.float32)

        def run(cell):
            _, bf, bd = cell
            return mdlora_matmul_multi_pallas(x, w0, a, b, idx, mask, 2.0,
                                              bf=bf, bd=bd, interpret=False)
    else:
        a = jnp.asarray(rng.normal(size=(D, r)) * 0.1, jnp.float32)
        b = jnp.asarray(rng.normal(size=(r, F)) * 0.1, jnp.float32)
        mask = jnp.asarray(rng.random(D) < 0.8, jnp.float32)

        def run(cell):
            bt, bf, bd = cell
            return mdlora_matmul_pallas(x, w0, a, b, mask, 2.0, bt=bt,
                                        bf=bf, bd=bd, interpret=False)

    best, best_t = cands[-1], float("inf")
    for cell in cands:
        jax.block_until_ready(run(cell))  # compile warm-up
        ts = []
        for _ in range(_SWEEP_REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(run(cell))
            ts.append(time.perf_counter() - t0)
        med = sorted(ts)[len(ts) // 2]
        if med < best_t:
            best, best_t = cell, med
    return best
