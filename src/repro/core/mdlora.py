"""MDLoRA: modality-aligned column-block structure (paper Eq. 1) and the
parameter-group layout that is RELIEF's *unified interface* for aggregation,
elastic training and communication.

The fusion-layer LoRA projection A in R^{rho x D} (stored transposed as
``a: [D, rho]``) is partitioned into M contiguous blocks along D, one per
modality. All trainable parameters are organized into G groups
(paper Sec. III-B):

    G = M fusion blocks + 1 shared B + sum_m L_m encoder groups + L_H head

A ``GroupLayout`` indexes every trainable leaf (or row-range of the fusion
``a`` leaf, or axis-0 slice of a layer-stacked leaf) to a group id and
carries per-group metadata (kind, modality, size, flops). Everything
downstream — cohort-wise aggregation (Eq. 3-4), divergence (Eq. 5), elastic
allocation (Eq. 7), on-demand upload (Eq. 8) and the timing/energy simulator
— consumes this one structure.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

KIND_FUSION_BLOCK = "fusion_block"
KIND_FUSION_B = "fusion_b"
KIND_ENCODER = "encoder"
KIND_HEAD = "head"


def path_str(path) -> str:
    return jax.tree_util.keystr(path)


@dataclasses.dataclass
class GroupLayout:
    names: list[str]
    kinds: list[str]
    modality: np.ndarray  # [G] int, -1 for none
    sizes: np.ndarray  # [G] param counts
    flops: np.ndarray  # [G] relative per-round training cost
    leaf_group: dict[str, int]  # whole-leaf path -> group id
    leaf_axis0_groups: dict[str, np.ndarray]  # stacked leaf -> per-slice gid
    fusion_a_path: str | None  # the row-blocked leaf
    fusion_rows: list[tuple[int, int, int]]  # (row_start, row_end, group_id)
    n_modalities: int

    @property
    def G(self) -> int:
        return len(self.names)

    def group_ids(self, kind: str) -> np.ndarray:
        return np.array([i for i, k in enumerate(self.kinds) if k == kind],
                        np.int32)

    # -- vectorized fleet helpers --------------------------------------------

    def accessible(self, modality_mask: np.ndarray, xp=np) -> np.ndarray:
        """modality_mask: [N, M] -> accessible groups G_n: [N, G] bool.
        A group without a modality is open to all; an empty group (e.g. no
        B matrix in B1) to none. ``xp=jnp`` traces it."""
        mm = xp.asarray(modality_mask, bool)
        src = xp.concatenate([mm, xp.ones((mm.shape[0], 1), bool)], axis=1)
        col = np.where(self.modality < 0, mm.shape[1], self.modality)
        return src[:, col] & (self.sizes > 0)[None, :]

    def mandatory(self, modality_mask: np.ndarray) -> np.ndarray:
        """Mandatory inclusion {A_m : m in M_n} (paper IV-B2b): [N, G]."""
        mm = np.asarray(modality_mask, bool)
        out = np.zeros((mm.shape[0], self.G), bool)
        for g in range(self.G):
            if self.kinds[g] == KIND_FUSION_BLOCK:
                out[:, g] = mm[:, self.modality[g]]
        return out

    def row_group_vector(self, D: int) -> np.ndarray:
        """[D] group id per row of the fusion ``a`` leaf."""
        rg = np.zeros(D, np.int32)
        for s, e, g in self.fusion_rows:
            rg[s:e] = g
        return rg


# ---------------------------------------------------------------------------
# layout construction for the multimodal model (models/multimodal.py)
# ---------------------------------------------------------------------------


def mm_group_layout(cfg, trainable: dict) -> GroupLayout:
    """Build the paper's G-group layout from an MMConfig + a trainable
    subtree (full params for Backbone 1; {lora, head} for Backbone 2)."""
    names: list[str] = []
    kinds: list[str] = []
    modality: list[int] = []
    sizes: list[int] = []
    leaf_group: dict[str, int] = {}
    leaf_axis0_groups: dict[str, np.ndarray] = {}
    fusion_rows: list[tuple[int, int, int]] = []
    fusion_a_path: str | None = None

    def new_group(name, kind, mod):
        names.append(name)
        kinds.append(kind)
        modality.append(mod)
        sizes.append(0)
        return len(names) - 1

    # fusion blocks first (stable ids 0..M-1), then B
    off = 0
    for i, m in enumerate(cfg.modalities):
        g = new_group(f"A_{m.name}", KIND_FUSION_BLOCK, i)
        fusion_rows.append((off, off + m.d_feat, g))
        off += m.d_feat
    b_gid = new_group("B_shared", KIND_FUSION_B, -1)

    leaves = jax.tree_util.tree_flatten_with_path(trainable)[0]
    mod_index = {m.name: i for i, m in enumerate(cfg.modalities)}
    enc_groups: dict[tuple[int, str], int] = {}
    head_groups: dict[str, int] = {}

    for path, leaf in leaves:
        p = path_str(path)
        is_fusion = "fusion" in p
        if is_fusion and p.endswith("['a']"):
            fusion_a_path = p
            rho = leaf.shape[1]
            for s, e, g in fusion_rows:
                sizes[g] += (e - s) * rho
            continue
        if "fusion_w0" in p:  # Backbone 1: the FC weight itself is blocked
            fusion_a_path = p
            dout = leaf.shape[1]
            for s, e, g in fusion_rows:
                sizes[g] += (e - s) * dout
            continue
        if is_fusion and p.endswith("['b']"):
            leaf_group[p] = b_gid
            sizes[b_gid] += leaf.size
            continue
        enc_mod = next((mod_index[nm] for nm in mod_index
                        if f"['{nm}']" in p), None)
        if enc_mod is not None:
            mname = cfg.modalities[enc_mod].name
            if "layers" in p:  # layer-stacked leaf: one group per layer slice
                n_l = leaf.shape[0]
                gids = []
                for l in range(n_l):
                    kk = (enc_mod, f"L{l}")
                    if kk not in enc_groups:
                        enc_groups[kk] = new_group(f"E_{mname}_L{l}",
                                                   KIND_ENCODER, enc_mod)
                    gids.append(enc_groups[kk])
                    sizes[enc_groups[kk]] += leaf.size // n_l
                leaf_axis0_groups[p] = np.array(gids, np.int32)
            else:  # per-module leaf (conv1/conv2/proj/patch)
                toks = re.findall(r"\['(\w+)'\]", p)
                label = toks[min(toks.index(mname) + 1, len(toks) - 1)]
                kk = (enc_mod, label)
                if kk not in enc_groups:
                    enc_groups[kk] = new_group(f"E_{mname}_{label}",
                                               KIND_ENCODER, enc_mod)
                leaf_group[p] = enc_groups[kk]
                sizes[enc_groups[kk]] += leaf.size
            continue
        # head (and any remaining global leaf): one group per head layer
        label = re.findall(r"\['(\w+)'\]", p)[-1]
        if label not in head_groups:
            head_groups[label] = new_group(f"H_{label}", KIND_HEAD, -1)
        leaf_group[p] = head_groups[label]
        sizes[head_groups[label]] += leaf.size

    sizes_np = np.array(sizes, np.int64)
    flops = np.maximum(sizes_np.astype(np.float64), 1.0)
    return GroupLayout(names, kinds, np.array(modality, np.int32), sizes_np,
                       flops, leaf_group, leaf_axis0_groups, fusion_a_path,
                       fusion_rows, cfg.M)


# ---------------------------------------------------------------------------
# group-gated tree ops (vmap-able over a leading client axis)
# ---------------------------------------------------------------------------


def group_gate_tree(layout: GroupLayout, trainable: Any, gate: Array) -> Any:
    """gate: [G] float -> pytree like ``trainable`` with per-group gates
    applied (fusion ``a`` rows and stacked-layer slices get per-slice gates).
    Used to mask gradients (elastic training) and uploads (Eq. 8)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(trainable)
    out = []
    for path, leaf in leaves:
        p = path_str(path)
        if p == layout.fusion_a_path:
            rg = jnp.asarray(layout.row_group_vector(leaf.shape[0]))
            g = gate[rg].astype(leaf.dtype)
            out.append(leaf * g[:, None])
        elif p in layout.leaf_axis0_groups:
            ids = jnp.asarray(layout.leaf_axis0_groups[p])
            g = gate[ids].astype(leaf.dtype)
            out.append(leaf * g.reshape((-1,) + (1,) * (leaf.ndim - 1)))
        elif p in layout.leaf_group:
            out.append(leaf * gate[layout.leaf_group[p]].astype(leaf.dtype))
        else:
            out.append(leaf * 0)
    return jax.tree_util.tree_unflatten(treedef, out)


def group_norms(layout: GroupLayout, tree: Any) -> Array:
    """Per-group squared Frobenius norms: -> [G] float32."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    acc = jnp.zeros((layout.G,), jnp.float32)
    for path, leaf in leaves:
        p = path_str(path)
        x32 = leaf.astype(jnp.float32)
        if p == layout.fusion_a_path:
            rg = jnp.asarray(layout.row_group_vector(leaf.shape[0]))
            per_row = jnp.sum(jnp.square(x32), axis=tuple(range(1, leaf.ndim)))
            acc = acc.at[rg].add(per_row)
        elif p in layout.leaf_axis0_groups:
            ids = jnp.asarray(layout.leaf_axis0_groups[p])
            per_l = jnp.sum(jnp.square(x32), axis=tuple(range(1, leaf.ndim)))
            acc = acc.at[ids].add(per_l)
        elif p in layout.leaf_group:
            acc = acc.at[layout.leaf_group[p]].add(jnp.sum(jnp.square(x32)))
    return acc


def weighted_combine(layout: GroupLayout, deltas: Any, W: Array) -> Any:
    """Aggregate client-stacked deltas with per-(client, group) weights.

    deltas: pytree with leading client axis N on every leaf.
    W: [N, G] combine weights (rows need not sum to 1; caller normalizes).
    -> pytree without the client axis: sum_n W[n, g_leaf] * delta_n.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(deltas)
    out = []
    for path, leaf in leaves:
        p = path_str(path)
        x = leaf.astype(jnp.float32)
        if p == layout.fusion_a_path:
            rg = jnp.asarray(layout.row_group_vector(leaf.shape[1]))
            w = W[:, rg]  # [N, D]
            out.append(jnp.einsum("nd,nd...->d...", w, x))
        elif p in layout.leaf_axis0_groups:
            ids = jnp.asarray(layout.leaf_axis0_groups[p])
            w = W[:, ids]  # [N, L]
            out.append(jnp.einsum("nl,nl...->l...", w, x))
        elif p in layout.leaf_group:
            w = W[:, layout.leaf_group[p]]  # [N]
            out.append(jnp.einsum("n,n...->...", w, x))
        else:
            out.append(jnp.zeros(leaf.shape[1:], jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)
