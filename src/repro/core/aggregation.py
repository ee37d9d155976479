"""Server-side aggregation rules.

* ``cohort_weights`` + ``aggregate``   — RELIEF (paper Eq. 3-4): each group is
  averaged only over the clients that trained it; the shared fusion
  projection B uses normalized modality-count weighting; the head averages
  over its uploaders. All three rules collapse into one [N, G] weight matrix
  consumed by ``mdlora.weighted_combine`` — on a TPU mesh this is a single
  masked reduce over the client axis.
* ``fedavg_weights``                   — naive FedAvg over all N participants
  (zero-padded deltas included): the paper's interference-prone baseline.
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
# streaming cohort aggregation (async runtime / fleet-scale server)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class QuantizedStack:
    """A client-stacked int8 uplink payload: ``q`` leaves are [K, ...] int8
    and ``scales`` leaves are the matching [K] per-(client, leaf) dequant
    scales, as produced by ``dist.quantize_int8_stacked``. The server flush
    paths ingest this natively through ``CohortAggBuffer.push_quantized`` —
    the fp32 client stack is never rebuilt in HBM."""
    q: Any
    scales: Any

    @property
    def n_clients(self) -> int:
        return jax.tree.leaves(self.q)[0].shape[0]


class CohortAggBuffer:
    """Streaming/accumulating variant of the fused cohort-agg reduction.

    The synchronous engine materializes the full [N, ...] delta stack and
    reduces it in one shot; the async runtime receives *partial buffers*
    (FedBuff cohorts of K clients) and at fleet scale even a sync server
    would stream arrivals. This class accumulates Eq. 3 aggregates and the
    Eq. 5 divergence sufficient statistics chunk by chunk:

        push(deltas [K,...], W [K,G], C [K,G])            fp32 uplink
        push_quantized(q, scales, W, C, staleness, a)     int8 uplink
        finalize() -> (agg tree, divergence [G], cohort counts [G])

    ``reduce`` gives one whole cohort's ``finalize`` result as a pure,
    traceable function (the async server step compiles it).

    The row-blocked fusion leaf goes through ``kernels/cohort_agg`` —
    ``impl="pallas"`` runs the fused Pallas kernel (interpret-mode on CPU —
    auto-detected when ``interpret`` is None), ``impl="xla"`` its einsum
    oracle; both produce the aggregate and the per-row (sqsum, mean, count)
    stats in one pass over the chunk. ``bd=None`` autotunes the kernel block
    size per shape; explicit values snap to the largest divisor of D, so
    blocking survives non-divisible row dimensions. All other leaves use the
    same masked einsum reductions as ``weighted_combine``. Empty cohorts
    finalize to zero aggregate and zero divergence (frozen block), never
    NaN.

    ``robust`` selects the within-cohort location estimate for the
    *aggregate* ("mean" | "trimmed" | "median" | "krum"); divergence stats
    are always the plain Eq. 5 sufficient statistics. Order statistics do
    not stream — robust modes require exactly one ``push`` per finalize
    (the async runtime flushes whole FedBuff cohorts, so this holds there
    by construction) and a second chunked push raises.
    """

    def __init__(self, layout: mdlora.GroupLayout, proto: Any,
                 impl: str = "xla", interpret: bool | None = None,
                 bd: int | None = None, robust: str = "mean",
                 trim_frac: float = 0.1, krum_f: int = 1):
        if robust not in ROBUST_AGGREGATORS:
            raise ValueError(f"robust must be one of {ROBUST_AGGREGATORS}, "
                             f"got {robust!r}")
        self.layout = layout
        self.impl = impl
        self.interpret = interpret
        self.bd = bd
        self.robust = robust
        self.trim_frac = trim_frac
        self.krum_f = krum_f
        # zero prototypes are derived once; reset() re-points the
        # accumulators at them (jnp arrays are immutable, sharing is safe),
        # so a long-lived buffer serves many flushes without re-allocating
        self._zero_tree = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), proto)
        self._zero_g = jnp.zeros((layout.G,), jnp.float32)
        self.reset()

    def reset(self) -> None:
        """Clear accumulated state so the buffer can serve the next flush."""
        self._agg = self._zero_tree
        self._csum = self._zero_tree
        self._sq = self._zero_g
        self._cnt = self._zero_g
        self._pushes = 0

    def _count_push(self) -> None:
        if self.robust != "mean":
            if self._pushes > 0:
                raise RuntimeError(
                    f"robust={self.robust!r} aggregation needs the whole "
                    "cohort in one push; chunked pushes are mean-only")
            self._pushes += 1

    def _commit(self, agg: Any, csum: Any, sq: Array, cnt: Array) -> None:
        self._agg = jax.tree.map(jnp.add, self._agg, agg)
        self._csum = jax.tree.map(jnp.add, self._csum, csum)
        self._sq = self._sq + sq
        self._cnt = self._cnt + cnt

    def push(self, deltas: Any, W: Array, C: Array) -> None:
        """deltas: client-stacked pytree ([K, ...] leaves); W/C: [K, G]
        combine weights and divergence-cohort mask for this chunk."""
        self._count_push()
        self._commit(*self._terms(deltas, W, C, self.bd))

    def push_quantized(self, q: Any, scales: Any, W: Array, C: Array,
                       staleness: Array | None = None,
                       exponent: float = 0.0) -> None:
        """One-pass compressed ingest: int8 client chunks, dequantized and
        staleness-discounted inside the reduction.

        q: client-stacked pytree ([K, ...] int8 leaves); scales: matching
        [K] per-(client, leaf) dequant scales (``dist.quantize_int8_stacked``
        layout). W/C: [K, G] as in ``push`` — W must be built with
        ``cohort_weights(..., defer_scale=True)`` when the staleness
        discount participates in normalization, because the effective weight
        W * 1/(1+staleness)^exponent is applied *here*: on the fly inside
        the fused kernel for the fusion leaf (the fp32 [K, D, r] stack is
        never materialized), folded into the [K, G] einsum weights for
        everything else.
        """
        self._count_push()
        self._commit(*self._terms_quantized(q, scales, W, C, staleness,
                                            exponent, self.bd))

    def finalize(self) -> tuple[Any, Array, Array]:
        """-> (aggregate tree, per-group divergence [G], cohort counts [G]).

        Divergence uses the sufficient-statistics identity
        E||d - mean||^2 = E||d||^2 - ||mean||^2 over each group's cohort.
        """
        return _finalize(self.layout, self._agg, self._csum, self._sq,
                         self._cnt)

    def reduce(self, deltas: Any, W: Array, C: Array, scales: Any = None,
               staleness: Array | None = None, exponent: float = 0.0,
               bd: int | None = None) -> tuple[Any, Array, Array]:
        """One whole cohort in one pass: what ``reset``, one ``push`` (or
        ``push_quantized`` when ``scales`` is given) and ``finalize`` give,
        as a pure function of its inputs that leaves the accumulators
        alone, so a compiled server step can trace it. ``bd`` overrides
        the buffer's block size: resolve it with ``fusion_bd`` outside the
        trace."""
        bd = self.bd if bd is None else bd
        if scales is None:
            terms = self._terms(deltas, W, C, bd)
        else:
            terms = self._terms_quantized(deltas, scales, W, C, staleness,
                                          exponent, bd)
        return _finalize(self.layout, *terms)

    def fusion_bd(self, deltas: Any, quant: bool) -> int | None:
        """The kernel block size for this chunk's fusion leaf, resolved on
        concrete shapes: the autotuner of compiled Pallas times real
        arrays and cannot run inside a trace. ``self.bd`` where nothing
        needs timing (it is snapped, or the XLA path ignores it)."""
        if self.impl != "pallas" or self.bd is not None:
            return self.bd
        from repro.kernels.cohort_agg.ops import _resolve_bd
        from repro.kernels.runtime import resolve_interpret

        for path, leaf in jax.tree_util.tree_flatten_with_path(deltas)[0]:
            if mdlora.path_str(path) == self.layout.fusion_a_path:
                return _resolve_bd(leaf.shape, "pallas",
                                   resolve_interpret(self.interpret), None,
                                   quant)
        return None

    def _terms(self, deltas: Any, W: Array, C: Array, bd: int | None
               ) -> tuple[Any, Any, Array, Array]:
        """One chunk's (aggregate tree, cohort-sum tree, squared norms [G],
        cohort counts [G]); pure."""
        from repro.kernels.cohort_agg import cohort_agg_divergence

        layout = self.layout
        W = jnp.asarray(W, jnp.float32)
        C = jnp.asarray(C, jnp.float32)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(deltas)
        agg_out, csum_out = [], []
        sq = jnp.zeros((layout.G,), jnp.float32)
        for path, leaf in leaves:
            p = mdlora.path_str(path)
            x = leaf.astype(jnp.float32)
            if p == layout.fusion_a_path:
                rg_j = jnp.asarray(layout.row_group_vector(leaf.shape[1]))
                agg_a, sq_rows, mean_rows, cnt_rows = cohort_agg_divergence(
                    x, W[:, rg_j], C[:, rg_j], impl=self.impl,
                    interpret=self.interpret, bd=bd)
                agg_out.append(agg_a)
                csum_out.append(mean_rows * cnt_rows[:, None])
                sq = sq.at[rg_j].add(sq_rows)
            elif p in layout.leaf_axis0_groups:
                ids = jnp.asarray(layout.leaf_axis0_groups[p])
                agg_out.append(jnp.einsum("nl,nl...->l...", W[:, ids], x))
                csum_out.append(jnp.einsum("nl,nl...->l...", C[:, ids], x))
                per_l = jnp.sum(jnp.square(x),
                                axis=tuple(range(2, x.ndim)))  # [K, L]
                sq = sq.at[ids].add(jnp.sum(per_l * C[:, ids], axis=0))
            elif p in layout.leaf_group:
                g = layout.leaf_group[p]
                agg_out.append(jnp.einsum("n,n...->...", W[:, g], x))
                csum_out.append(jnp.einsum("n,n...->...", C[:, g], x))
                per_n = jnp.sum(jnp.square(x),
                                axis=tuple(range(1, x.ndim)))  # [K]
                sq = sq.at[g].add(jnp.sum(per_n * C[:, g]))
            else:
                agg_out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
                csum_out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
        if self.robust != "mean":
            # divergence stats above stay the plain sufficient statistics;
            # only the aggregate is swapped for the robust estimate
            agg_out = jax.tree_util.tree_flatten(robust_combine(
                layout, deltas, W, self.robust, self.trim_frac,
                self.krum_f))[0]
        return (jax.tree_util.tree_unflatten(treedef, agg_out),
                jax.tree_util.tree_unflatten(treedef, csum_out), sq,
                jnp.sum(C, axis=0))

    def _terms_quantized(self, q: Any, scales: Any, W: Array, C: Array,
                         staleness: Array | None, exponent: float,
                         bd: int | None) -> tuple[Any, Any, Array, Array]:
        """``_terms`` of an int8 chunk, discount and dequant scales applied
        inside the reduction; pure."""
        from repro.kernels.cohort_agg import cohort_agg_divergence_quant
        from repro.kernels.cohort_agg.ref import staleness_discount_ref

        layout = self.layout
        W = jnp.asarray(W, jnp.float32)
        C = jnp.asarray(C, jnp.float32)
        if staleness is None:
            staleness = jnp.zeros((W.shape[0],), jnp.float32)
        staleness = jnp.asarray(staleness, jnp.float32)
        disc = staleness_discount_ref(staleness, exponent)
        if self.robust != "mean":
            # Order statistics cannot be taken over int8 codes with
            # per-client scales, so the fused compressed ingest does not
            # apply: dequantize the chunk and take the fp32 path, folding
            # the staleness discount into the weights up front (the dequant
            # scale f rides along in x, so W*disc*f matches the fused
            # einsum weights exactly). Costs one [K, ...] fp32 stack.
            from repro import dist
            x = dist.dequantize_int8_stacked(q, scales)
            return self._terms(x, W * disc[:, None], C, bd)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(q)
        scale_leaves = jax.tree.leaves(scales)
        agg_out, csum_out = [], []
        sq = jnp.zeros((layout.G,), jnp.float32)
        for (path, leaf), f in zip(leaves, scale_leaves):
            p = mdlora.path_str(path)
            f = jnp.asarray(f, jnp.float32)  # [K] dequant scales
            if p == layout.fusion_a_path:
                rg_j = jnp.asarray(layout.row_group_vector(leaf.shape[1]))
                agg_a, sq_rows, mean_rows, cnt_rows = (
                    cohort_agg_divergence_quant(
                        leaf, f, W[:, rg_j], C[:, rg_j], staleness, exponent,
                        impl=self.impl, interpret=self.interpret, bd=bd))
                agg_out.append(agg_a)
                csum_out.append(mean_rows * cnt_rows[:, None])
                sq = sq.at[rg_j].add(sq_rows)
            elif p in layout.leaf_axis0_groups:
                ids = jnp.asarray(layout.leaf_axis0_groups[p])
                x = leaf.astype(jnp.float32)
                agg_out.append(jnp.einsum("nl,nl...->l...",
                                          W[:, ids] * (disc * f)[:, None],
                                          x))
                csum_out.append(jnp.einsum("nl,nl...->l...",
                                           C[:, ids] * f[:, None], x))
                per_l = jnp.sum(jnp.square(x),
                                axis=tuple(range(2, x.ndim)))  # [K, L]
                sq = sq.at[ids].add(jnp.sum(
                    per_l * C[:, ids] * jnp.square(f)[:, None], axis=0))
            elif p in layout.leaf_group:
                g = layout.leaf_group[p]
                x = leaf.astype(jnp.float32)
                agg_out.append(jnp.einsum("n,n...->...", W[:, g] * disc * f,
                                          x))
                csum_out.append(jnp.einsum("n,n...->...", C[:, g] * f, x))
                per_n = jnp.sum(jnp.square(x),
                                axis=tuple(range(1, x.ndim)))  # [K]
                sq = sq.at[g].add(jnp.sum(per_n * C[:, g] * jnp.square(f)))
            else:
                agg_out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
                csum_out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
        return (jax.tree_util.tree_unflatten(treedef, agg_out),
                jax.tree_util.tree_unflatten(treedef, csum_out), sq,
                jnp.sum(C, axis=0))


def _finalize(layout: mdlora.GroupLayout, agg: Any, csum: Any, sq: Array,
              cnt: Array) -> tuple[Any, Array, Array]:
    """Accumulated sums -> (aggregate tree, divergence [G], counts [G])."""
    inv = 1.0 / jnp.maximum(cnt, 1.0)
    mean_tree = mdlora.group_gate_tree(layout, csum, inv)
    msq = mdlora.group_norms(layout, mean_tree)
    d = jnp.where(cnt > 0, jnp.maximum(sq * inv - msq, 0.0), 0.0)
    return agg, d, cnt


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
