"""Server-side aggregation rules.

* ``cohort_weights`` + ``aggregate``   — RELIEF (paper Eq. 3-4): each group is
  averaged only over the clients that trained it; the shared fusion
  projection B uses normalized modality-count weighting; the head averages
  over its uploaders. All three rules collapse into one [N, G] weight matrix
  consumed by ``mdlora.weighted_combine`` — on a TPU mesh this is a single
  masked reduce over the client axis.
* ``fedavg_weights``                   — naive FedAvg over all N participants
  (zero-padded deltas included): the paper's interference-prone baseline.
* ``robust_combine``                   — Byzantine-robust within-cohort
  location estimates (trimmed mean, coordinate median, blockwise Krum).
* ``cohort_reduce``                    — the async server step's whole-cohort
  reduction: Eq. 3 aggregate and Eq. 5 divergence statistics in one pass,
  fp32 or int8 uplink, through the fused ``kernels/cohort_agg`` ingest.
* ``lemma1_decomposition``             — the bias^2/variance/interference
  split of Lemma 1, used by diagnostics and tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mdlora

Array = jax.Array


def cohort_weights(layout: mdlora.GroupLayout, trained: Array,
                   modality_mask: Array,
                   client_scale: Array | None = None,
                   defer_scale: bool = False) -> Array:
    """RELIEF combine weights W: [N, G].

    trained: [N, G] float/bool — which groups each client trained+uploaded
    (the active cohort C~_m^r for fusion blocks / encoders).
    modality_mask: [N, M] — possession, for Eq. 4's w_n = (|M_n|/M)/sum(...).
    client_scale: optional [N] multiplicative per-client weight applied
    *inside* the normalization (the async runtime passes its staleness
    discounts here, so a stale update shrinks relative to its cohort).
    defer_scale: keep ``client_scale`` in the denominator but *not* the
    numerator — for consumers that re-apply the per-client factor inside a
    fused reduction (the quantized-ingest kernel computes W * 1/(1+s)^a on
    the fly), so W_deferred * client_scale == W_full up to fp rounding.
    Empty cohort => all-zero column (the block stays frozen this round).
    """
    trained = jnp.asarray(trained, jnp.float32)
    M = layout.n_modalities
    mcount = jnp.sum(jnp.asarray(modality_mask, jnp.float32), axis=1)  # [N]
    kinds = np.array(layout.kinds)
    is_b = jnp.asarray(kinds == mdlora.KIND_FUSION_B)  # [G]

    u = jnp.where(is_b[None, :], (mcount / M)[:, None], 1.0)  # [N, G]
    w = num = trained * u
    if client_scale is not None:
        w = w * jnp.asarray(client_scale, jnp.float32)[:, None]
        if not defer_scale:
            num = w
    denom = jnp.sum(w, axis=0, keepdims=True)  # [1, G]
    return jnp.where(denom > 0, num / jnp.maximum(denom, 1e-12), 0.0)


def staleness_discounts(staleness: Array, exponent: float) -> Array:
    """FedBuff-style polynomial staleness discount 1/(1+s)^a. s is measured
    in server model versions (flushes) since the client pulled."""
    s = jnp.asarray(staleness, jnp.float32)
    return 1.0 / jnp.power(1.0 + s, exponent)


def fedavg_weights(n_clients: int, G: int, participating: Array | None = None
                   ) -> Array:
    """Naive FedAvg: every participant weighted 1/N for every group."""
    if participating is None:
        participating = jnp.ones((n_clients,), jnp.float32)
    p = jnp.asarray(participating, jnp.float32)
    return jnp.tile((p / jnp.maximum(jnp.sum(p), 1.0))[:, None], (1, G))


def aggregate(layout: mdlora.GroupLayout, global_trainable: Any,
              deltas: Any, W: Array, server_lr: float = 1.0) -> Any:
    """theta^{r+1} = theta^r + server_lr * sum_n W[n,g] * delta_n (Eq. 3)."""
    agg = mdlora.weighted_combine(layout, deltas, W)
    return jax.tree.map(
        lambda t, d: (t.astype(jnp.float32) + server_lr * d).astype(t.dtype),
        global_trainable, agg)


# ---------------------------------------------------------------------------
# Byzantine-robust within-cohort reducers
# ---------------------------------------------------------------------------
#
# RELIEF's cohort interface (Eq. 3) makes rare-modality cohorts small by
# construction, so one corrupted client can dominate a whole modality block.
# These reducers replace the weighted mean with bounded-breakdown location
# estimates computed *within each group's cohort* (membership = W > 0, the
# trained+fresh clients of cohort_weights): beta-trimmed weighted mean,
# coordinate-wise median, and blockwise Krum. Divergence statistics (Eq. 5)
# are unchanged — only the aggregate is robustified.

ROBUST_AGGREGATORS = ("mean", "trimmed", "median", "krum")


def trimmed_mean(x: Array, w: Array, trim_frac: float) -> Array:
    """Coordinate-wise beta-trimmed weighted mean along axis 0.

    x: [K, ...] values; w: non-negative weights broadcastable to x — w > 0
    marks cohort membership, its magnitude the combine weight. Per
    coordinate, the t = floor(beta * k) smallest and largest member values
    are discarded (k = member count; t is clamped to (k-1)//2 so at least
    one value survives) and the survivors are averaged with their weights
    renormalized. beta = 0 is exactly the weighted mean ``sum(w x)/sum(w)``
    and beta >= 1/2 degenerates to the median element(s). Empty coordinates
    (k = 0) -> 0.
    """
    x = jnp.asarray(x, jnp.float32)
    w = jnp.broadcast_to(jnp.asarray(w, jnp.float32), x.shape)
    member = w > 0
    k = jnp.sum(member, axis=0)
    t = jnp.minimum(jnp.floor(trim_frac * k),
                    jnp.maximum((k - 1) // 2, 0)).astype(jnp.int32)
    # rank members per coordinate; non-members sort to the top (stable, so
    # ranks 0..k-1 land exactly on the members)
    order = jnp.argsort(jnp.where(member, x, jnp.inf), axis=0, stable=True)
    ranks = jnp.argsort(order, axis=0, stable=True)
    keep = member & (ranks >= t) & (ranks < k - t)
    wk = jnp.where(keep, w, 0.0)
    denom = jnp.sum(wk, axis=0)
    return jnp.where(denom > 0,
                     jnp.sum(wk * x, axis=0) / jnp.maximum(denom, 1e-12),
                     0.0)


def coordinate_median(x: Array, member: Array) -> Array:
    """Coordinate-wise median over member rows along axis 0.

    member: bool mask broadcastable to x. Even member counts average the
    two middle order statistics; empty coordinates -> 0. Breakdown point
    1/2 per coordinate — the strongest of the three rules, at the price of
    ignoring combine weights (every member counts once).
    """
    x = jnp.asarray(x, jnp.float32)
    member = jnp.broadcast_to(jnp.asarray(member, bool), x.shape)
    k = jnp.sum(member, axis=0)
    s = jnp.sort(jnp.where(member, x, jnp.inf), axis=0)
    lo = jnp.take_along_axis(s, jnp.maximum((k - 1) // 2, 0)[None], axis=0)
    hi = jnp.take_along_axis(s, jnp.maximum(k // 2, 0)[None], axis=0)
    return jnp.where(k > 0, 0.5 * (lo + hi)[0], 0.0)


def group_pairwise_sq(layout: mdlora.GroupLayout, deltas: Any) -> Array:
    """Per-group pairwise squared distances: [K, K, G].

    d2[i, j, g] = || delta_i - delta_j ||^2 restricted to group g's
    parameters, accumulated over the layout's three leaf classes (fusion
    row blocks, layer-stacked slices, whole leaves).
    """
    leaves = jax.tree_util.tree_flatten_with_path(deltas)[0]
    K = leaves[0][1].shape[0]
    acc = jnp.zeros((K, K, layout.G), jnp.float32)
    for path, leaf in leaves:
        p = mdlora.path_str(path)
        x = leaf.astype(jnp.float32)
        d = x[:, None] - x[None, :]  # [K, K, ...]
        if p == layout.fusion_a_path:
            rg = layout.row_group_vector(leaf.shape[1])
            per_row = jnp.sum(jnp.square(d), axis=tuple(range(3, d.ndim)))
            onehot = jnp.asarray(rg[:, None] == np.arange(layout.G)[None, :],
                                 jnp.float32)
            acc = acc + jnp.einsum("ijd,dg->ijg", per_row, onehot)
        elif p in layout.leaf_axis0_groups:
            ids = layout.leaf_axis0_groups[p]
            per_l = jnp.sum(jnp.square(d), axis=tuple(range(3, d.ndim)))
            onehot = jnp.asarray(ids[:, None] == np.arange(layout.G)[None, :],
                                 jnp.float32)
            acc = acc + jnp.einsum("ijl,lg->ijg", per_l, onehot)
        elif p in layout.leaf_group:
            g = layout.leaf_group[p]
            acc = acc.at[:, :, g].add(
                jnp.sum(jnp.square(d), axis=tuple(range(2, d.ndim))))
    return acc


def krum_select(d2: Array, member: Array, f: int) -> Array:
    """Blockwise Krum selection (Blanchard et al., NeurIPS'17).

    d2: [K, K, G] per-group pairwise squared distances; member: [K, G]
    cohort membership. Per group, score_i = sum of the distances to i's
    k - f - 2 nearest co-members (clamped to >= 1 neighbor) and the
    lowest-scoring member is selected -> [G] int32 selected client row
    (0 for empty groups — mask with ``member.any(0)``).
    """
    member = jnp.asarray(member, bool)
    K = member.shape[0]
    k = jnp.sum(member, axis=0)  # [G]
    pair = (member[:, None, :] & member[None, :, :]
            & ~jnp.eye(K, dtype=bool)[:, :, None])
    ds = jnp.sort(jnp.where(pair, d2, jnp.inf), axis=1)  # [K, K, G]
    csum = jnp.cumsum(jnp.where(jnp.isfinite(ds), ds, 0.0), axis=1)
    nn = jnp.clip(k - f - 2, 1, jnp.maximum(k - 1, 1))  # [G]
    idx = jnp.broadcast_to((nn - 1)[None, None, :], (K, 1, member.shape[1]))
    score = jnp.take_along_axis(csum, idx, axis=1)[:, 0, :]  # [K, G]
    return jnp.argmin(jnp.where(member, score, jnp.inf), axis=0)


def robust_combine(layout: mdlora.GroupLayout, deltas: Any, W: Array,
                   kind: str, trim_frac: float = 0.1,
                   krum_f: int = 1) -> Any:
    """Robust replacement for ``weighted_combine``: per-group location
    estimates of the member deltas (membership = W > 0).

    Same output scale as the Eq. 3 weighted mean (cohort_weights columns
    sum to 1), so ``aggregate`` / the server flush consume it unchanged.
    ``kind="mean"`` falls through to ``weighted_combine``; "krum" takes the
    selected member's block verbatim via a one-hot weight matrix.
    """
    if kind not in ROBUST_AGGREGATORS:
        raise ValueError(f"robust kind must be one of {ROBUST_AGGREGATORS}, "
                         f"got {kind!r}")
    W = jnp.asarray(W, jnp.float32)
    if kind == "mean":
        return mdlora.weighted_combine(layout, deltas, W)
    if kind == "krum":
        d2 = group_pairwise_sq(layout, deltas)
        sel = krum_select(d2, W > 0, krum_f)
        nonempty = jnp.any(W > 0, axis=0)
        W_sel = jnp.zeros_like(W).at[sel, jnp.arange(W.shape[1])].set(
            nonempty.astype(jnp.float32))
        return mdlora.weighted_combine(layout, deltas, W_sel)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(deltas)
    out = []
    for path, leaf in leaves:
        p = mdlora.path_str(path)
        x = leaf.astype(jnp.float32)
        if p == layout.fusion_a_path:
            w = W[:, jnp.asarray(layout.row_group_vector(leaf.shape[1]))]
            w = w.reshape(w.shape + (1,) * (x.ndim - 2))
        elif p in layout.leaf_axis0_groups:
            w = W[:, jnp.asarray(layout.leaf_axis0_groups[p])]
            w = w.reshape(w.shape + (1,) * (x.ndim - 2))
        elif p in layout.leaf_group:
            w = W[:, layout.leaf_group[p]]
            w = w.reshape(w.shape + (1,) * (x.ndim - 1))
        else:
            out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
            continue
        if kind == "trimmed":
            out.append(trimmed_mean(x, w, trim_frac))
        else:  # median
            out.append(coordinate_median(x, w > 0))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the cohort reduction (async server step)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QuantizedStack:
    """A client-stacked int8 uplink payload: ``q`` leaves are [K, ...] int8
    and ``scales`` leaves are the matching [K] per-(client, leaf) dequant
    scales, as produced by ``dist.quantize_int8_stacked``. The server step
    ingests this natively through ``cohort_reduce(..., scales=)`` — the
    fp32 client stack is never rebuilt in HBM."""
    q: Any
    scales: Any

    @property
    def n_clients(self) -> int:
        return jax.tree.leaves(self.q)[0].shape[0]


def cohort_reduce(layout: mdlora.GroupLayout, deltas: Any, W: Array,
                  C: Array, *, scales: Any = None,
                  staleness: Array | None = None, exponent: float = 0.0,
                  robust: str = "mean", trim_frac: float = 0.1,
                  krum_f: int = 1, impl: str = "xla",
                  interpret: bool | None = None, bd: int | None = None
                  ) -> tuple[Any, Array, Array]:
    """One whole cohort in one pass -> (aggregate tree, per-group
    divergence [G], cohort counts [G]). Pure, so the async server step
    compiles it.

    deltas: client-stacked pytree ([K, ...] leaves); W/C: [K, G] Eq. 3-4
    combine weights and Eq. 5 divergence-cohort mask. With ``scales`` (the
    [K] per-(client, leaf) dequant scales of ``dist.quantize_int8_stacked``)
    the leaves are int8 codes, and the dequant scale and the staleness
    discount 1/(1+staleness)^exponent are applied inside the reduction: on
    the fly in the fused kernel for the fusion leaf (the fp32 [K, D, r]
    stack is never materialized), folded into the [K, G] einsum weights
    everywhere else. W must then come from ``cohort_weights(...,
    defer_scale=True)`` when the discount takes part in the normalization.

    The row-blocked fusion leaf goes through ``kernels/cohort_agg``:
    ``impl="pallas"`` runs the fused Pallas kernel (interpret mode on the
    CPU when ``interpret`` is None), ``impl="xla"`` its einsum oracle; both
    give the aggregate and the per-row (sqsum, mean, count) statistics in
    one pass. ``bd`` is the kernel's block size: resolve it with
    ``fusion_block`` outside a trace (None lets the kernel choose, which
    needs concrete arrays under Pallas). The other leaves use the masked
    einsums of ``weighted_combine``. Divergence uses the
    sufficient-statistics identity E||d - mean||^2 = E||d||^2 - ||mean||^2
    over each group's cohort; an empty cohort gives a zero aggregate and
    zero divergence (a frozen block), never NaN.

    ``robust`` picks the within-cohort location estimate for the aggregate
    only (``robust_combine``); the divergence statistics stay the plain
    Eq. 5 ones. Order statistics cannot be taken over int8 codes with
    per-client scales, so a robust int8 cohort is dequantized first, with
    the discount folded into W (one [K, ...] fp32 stack).
    """
    from repro.kernels.cohort_agg import (cohort_agg_divergence,
                                          cohort_agg_divergence_quant)

    W = jnp.asarray(W, jnp.float32)
    C = jnp.asarray(C, jnp.float32)
    if scales is not None:
        from repro.kernels.cohort_agg.ref import staleness_discount_ref

        if staleness is None:
            staleness = jnp.zeros((W.shape[0],), jnp.float32)
        staleness = jnp.asarray(staleness, jnp.float32)
        disc = staleness_discount_ref(staleness, exponent)
        if robust != "mean":
            # the dequant scale rides along in the fp32 stack, so W * disc
            # matches the fused einsum weights W * disc * f exactly
            from repro import dist
            deltas = dist.dequantize_int8_stacked(deltas, scales)
            W, scales = W * disc[:, None], None
    leaves, treedef = jax.tree_util.tree_flatten_with_path(deltas)
    f_leaves = ([None] * len(leaves) if scales is None
                else jax.tree.leaves(scales))
    agg_out, csum_out = [], []
    sq = jnp.zeros((layout.G,), jnp.float32)
    for (path, leaf), f in zip(leaves, f_leaves):
        p = mdlora.path_str(path)
        if f is not None:
            f = jnp.asarray(f, jnp.float32)  # [K] dequant scales
        if p == layout.fusion_a_path:
            rg_j = jnp.asarray(layout.row_group_vector(leaf.shape[1]))
            if f is None:
                terms = cohort_agg_divergence(
                    leaf.astype(jnp.float32), W[:, rg_j], C[:, rg_j],
                    impl=impl, interpret=interpret, bd=bd)
            else:
                terms = cohort_agg_divergence_quant(
                    leaf, f, W[:, rg_j], C[:, rg_j], staleness, exponent,
                    impl=impl, interpret=interpret, bd=bd)
            agg_a, sq_rows, mean_rows, cnt_rows = terms
            agg_out.append(agg_a)
            csum_out.append(mean_rows * cnt_rows[:, None])
            sq = sq.at[rg_j].add(sq_rows)
        elif p in layout.leaf_axis0_groups:
            ids = jnp.asarray(layout.leaf_axis0_groups[p])
            x = leaf.astype(jnp.float32)
            w = W[:, ids]
            if f is not None:
                w = w * (disc * f)[:, None]
            agg_out.append(jnp.einsum("nl,nl...->l...", w, x))
            c = C[:, ids]
            if f is not None:
                c = c * f[:, None]
            csum_out.append(jnp.einsum("nl,nl...->l...", c, x))
            per_l = jnp.sum(jnp.square(x),
                            axis=tuple(range(2, x.ndim)))  # [K, L]
            m = per_l * C[:, ids]
            if f is not None:
                m = m * jnp.square(f)[:, None]
            sq = sq.at[ids].add(jnp.sum(m, axis=0))
        elif p in layout.leaf_group:
            g = layout.leaf_group[p]
            x = leaf.astype(jnp.float32)
            w = W[:, g]
            if f is not None:
                w = w * disc * f
            agg_out.append(jnp.einsum("n,n...->...", w, x))
            c = C[:, g]
            if f is not None:
                c = c * f
            csum_out.append(jnp.einsum("n,n...->...", c, x))
            per_n = jnp.sum(jnp.square(x),
                            axis=tuple(range(1, x.ndim)))  # [K]
            m = per_n * C[:, g]
            if f is not None:
                m = m * jnp.square(f)
            sq = sq.at[g].add(jnp.sum(m))
        else:
            agg_out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
            csum_out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
    if robust != "mean":
        agg_out = jax.tree_util.tree_flatten(robust_combine(
            layout, deltas, W, robust, trim_frac, krum_f))[0]
    cnt = jnp.sum(C, axis=0)
    inv = 1.0 / jnp.maximum(cnt, 1.0)
    mean_tree = mdlora.group_gate_tree(
        layout, jax.tree_util.tree_unflatten(treedef, csum_out), inv)
    msq = mdlora.group_norms(layout, mean_tree)
    d = jnp.where(cnt > 0, jnp.maximum(sq * inv - msq, 0.0), 0.0)
    return jax.tree_util.tree_unflatten(treedef, agg_out), d, cnt


def fusion_block(layout: mdlora.GroupLayout, deltas: Any, *, quant: bool,
                 impl: str, interpret: bool | None) -> int | None:
    """The fusion kernel's block size for ``cohort_reduce``, resolved on
    concrete arrays: the autotuner of compiled Pallas times real arrays and
    cannot run inside a trace. None on the XLA path, which has no blocks."""
    if impl != "pallas":
        return None
    from repro.kernels.cohort_agg.ops import _resolve_bd
    from repro.kernels.runtime import resolve_interpret

    for path, leaf in jax.tree_util.tree_flatten_with_path(deltas)[0]:
        if mdlora.path_str(path) == layout.fusion_a_path:
            return _resolve_bd(leaf.shape, "pallas",
                               resolve_interpret(interpret), None, quant)
    return None


# ---------------------------------------------------------------------------
# Lemma 1 diagnostics
# ---------------------------------------------------------------------------


def lemma1_decomposition(block_deltas: Array, cohort: Array) -> dict:
    """Empirical version of Lemma 1 for one fusion block.

    block_deltas: [N, d, r] per-client updates to one block A_m.
    cohort: [N] bool — C_m (possession).
    Returns the scaling/interference/intra-cohort terms and the exact FedAvg
    error; tests assert error <= sum of bound terms (Eq. 12-13).
    """
    c = jnp.asarray(cohort, jnp.float32)
    N = block_deltas.shape[0]
    nC = jnp.sum(c)
    g_bar = jnp.einsum("n,n...->...", c / jnp.maximum(nC, 1.0), block_deltas)
    g_hat = jnp.mean(block_deltas, axis=0)  # FedAvg over all N
    eps_hat = jnp.einsum("n,n...->...", (1 - c) / jnp.maximum(N - nC, 1.0),
                         block_deltas)
    err = jnp.sum(jnp.square(g_hat - g_bar))
    scaling = (1 - nC / N) ** 2 * jnp.sum(jnp.square(g_bar))
    interference = ((N - nC) / N) ** 2 * jnp.sum(jnp.square(eps_hat))
    intra = jnp.einsum("n,n->", c / jnp.maximum(nC, 1.0),
                       jnp.sum(jnp.square(block_deltas - g_bar),
                               axis=tuple(range(1, block_deltas.ndim))))
    return {"error": err, "scaling": scaling, "interference": interference,
            "intra_cohort": intra,
            "bound": 2 * scaling + 2 * interference + intra / jnp.maximum(nC, 1.0)}
