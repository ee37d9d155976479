"""Batched multi-LoRA personalized serving engine.

RELIEF personalizes one modality-block LoRA adapter per client; at traffic
each request therefore carries its *own* adapter + modality mask. Serving
them one model at a time wastes the accelerator: every request re-runs the
full base model at batch 1. This engine instead:

* keeps client adapters in an ``AdapterRegistry`` — one [L, A, din, r]
  stacked store per LoRA target, ingesting per-client blocks straight from
  trainer output / ``CohortAggBuffer`` aggregates (no per-request weight
  copies, no merge step);
* runs **continuous batching**: requests join and leave the decode batch at
  step granularity. Admission prefalls the prompt into a fresh
  single-request cache and scatters that row into the shared paged
  KV/SSM cache (``models/api.init_caches(per_row_pos=True)``), so a new
  request never perturbs the rows already mid-stream;
* decodes the whole mixed batch with ONE fused gathered projection per
  LoRA target (``kernels/mdlora.mdlora_matmul_multi``): per-row
  ``adapter_idx`` gathers each request's adapter blocks inside the kernel
  and per-row fusion masks zero absent-modality blocks.

``naive_serve`` is the baseline the bench compares against: sequential
per-request decode with merged single-adapter params.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import api

Array = jax.Array


# jitted step functions are cached at module level (ModelConfig is a frozen
# hashable dataclass) so constructing a new engine or re-running the naive
# baseline reuses compiled code instead of retracing per instance; each
# returns greedy token ids directly so a serving step is ONE dispatch


@functools.lru_cache(maxsize=None)
def _decode_fn(cfg: ModelConfig, lora_impl: str):
    def f(base, store, fmasks, caches, token, pos, aidx):
        fmask = jnp.take(fmasks, aidx, axis=0)
        logits, caches = api.decode_step({"base": base, "lora": store}, cfg,
                                         caches, token, pos,
                                         adapter_idx=aidx, fusion_mask=fmask,
                                         lora_impl=lora_impl)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), caches
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _admit_fn(cfg: ModelConfig):
    """Admission as one fused call: gather the adapter from the store,
    prefill into a fresh single-row cache, scatter that row into the shared
    cache at ``slot`` and return the first greedy token."""
    def f(base, store, fmasks, fresh, big, tokens, aslot, slot):
        lora = jax.tree.map(lambda x: x[:, aslot], store)
        logits, small = api.prefill_with_cache(
            {"base": base, "lora": lora}, cfg, fresh, tokens,
            fusion_mask=fmasks[aslot][None])
        big = jax.tree.map(
            lambda b, o: b.at[:, slot].set(o[:, 0].astype(b.dtype)),
            big, small)
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), big
    return jax.jit(f)  # jit's shape cache handles varying prompt lengths


@functools.lru_cache(maxsize=None)
def _single_prefill_fn(cfg: ModelConfig):
    def f(base, store, fmasks, fresh, tokens, aslot):
        lora = jax.tree.map(lambda x: x[:, aslot], store)
        logits, caches = api.prefill_with_cache(
            {"base": base, "lora": lora}, cfg, fresh, tokens,
            fusion_mask=fmasks[aslot][None])
        return jnp.argmax(logits[:, -1], -1).astype(jnp.int32), caches
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _single_decode_fn(cfg: ModelConfig):
    def f(base, store, fmasks, caches, token, pos, aslot):
        lora = jax.tree.map(lambda x: x[:, aslot], store)
        logits, caches = api.decode_step(
            {"base": base, "lora": lora}, cfg, caches, token, pos,
            fusion_mask=fmasks[aslot][None])
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32), caches
    return jax.jit(f)


@dataclasses.dataclass
class Request:
    rid: str
    prompt: np.ndarray  # [P] int tokens
    adapter: str  # registry name
    max_new_tokens: int = 16
    submit_t: float = 0.0


# ---------------------------------------------------------------------------
# adapter registry
# ---------------------------------------------------------------------------


class AdapterRegistry:
    """Capacity-slotted store of per-client MDLoRA adapters.

    Leaves are stacked [L, capacity, din, r] so the model's layer-scan
    slicing ([L] leading axis) is untouched and the per-row gather happens
    inside the mdlora kernel. Registration writes one slot; eviction frees
    it. ``ingest_update`` applies a server-side delta (trainer /
    CohortAggBuffer.finalize output with the same [L, din, r] leaf layout)
    to a registered adapter in place — the serve path sees fresh weights on
    the next decode step without any repacking.
    """

    def __init__(self, key: Array, cfg: ModelConfig, capacity: int):
        self.cfg = cfg
        self.capacity = capacity
        proto = api.init_model(key, cfg)["lora"]
        # zeroed store: empty slots behave as base-model (b=0 -> delta 0)
        self.store = jax.tree.map(
            lambda x: jnp.zeros((x.shape[0], capacity) + x.shape[1:],
                                x.dtype), proto)
        self.block_dims = api.fusion_block_dims(cfg)
        df = int(sum(self.block_dims))
        self.fusion_masks = jnp.ones((capacity, df), jnp.float32)
        self._slots: dict[str, int] = {}
        self._free = list(range(capacity))

    def slot(self, name: str) -> int:
        return self._slots[name]

    def register(self, name: str, lora_tree: Any,
                 modality_mask=None) -> int:
        """lora_tree: [L, din, r]-leaf adapter (e.g. params["lora"]);
        modality_mask: [M] availability over ``api.fusion_block_dims``."""
        from repro.kernels.mdlora import block_row_mask

        if name in self._slots:
            s = self._slots[name]
        else:
            if not self._free:
                raise RuntimeError("adapter registry full")
            s = self._free.pop(0)
            self._slots[name] = s
        self.store = jax.tree.map(
            lambda big, leaf: big.at[:, s].set(leaf.astype(big.dtype)),
            self.store, lora_tree)
        mask = (jnp.ones((int(sum(self.block_dims)),), jnp.float32)
                if modality_mask is None
                else block_row_mask(self.block_dims, modality_mask))
        self.fusion_masks = self.fusion_masks.at[s].set(mask)
        return s

    def ingest_update(self, name: str, delta_tree: Any,
                      server_lr: float = 1.0) -> None:
        s = self._slots[name]
        self.store = jax.tree.map(
            lambda big, d: big.at[:, s].add(
                (server_lr * d).astype(big.dtype)),
            self.store, delta_tree)

    def evict(self, name: str) -> None:
        s = self._slots.pop(name)
        self.store = jax.tree.map(lambda big: big.at[:, s].set(0.0),
                                  self.store)
        self.fusion_masks = self.fusion_masks.at[s].set(1.0)
        self._free.append(s)

    def lora_view(self, name: str) -> Any:
        """Single-adapter [L, din, r] tree (naive baseline / admission)."""
        s = self._slots[name]
        return jax.tree.map(lambda big: big[:, s], self.store)


# ---------------------------------------------------------------------------
# continuous-batching engine
# ---------------------------------------------------------------------------


class ServingEngine:
    """Continuous-batching decode loop over ``batch_slots`` cache rows.

    Every step: (1) free slots are filled from the admission queue — the
    prompt is prefilled into a fresh single-row cache (chunked forward for
    attention archs, exact token loop for recurrent ones) and the row is
    scattered into the shared cache; (2) one jitted batched decode step
    advances all active rows, each applying its own adapter via the
    gathered mdlora kernel. Finished rows are recycled immediately.

    ``lora_impl`` picks the gathered projection: "pallas" (the fused
    kernel) or "xla" (its einsum oracle). None takes the kernel wherever it
    compiles and the oracle on the CPU, where the kernel only interprets.
    """

    def __init__(self, params: dict, cfg: ModelConfig,
                 registry: AdapterRegistry, batch_slots: int, max_len: int,
                 lora_impl: str | None = None):
        from repro.kernels.runtime import default_interpret

        if lora_impl is None:
            lora_impl = "xla" if default_interpret() else "pallas"
        self.lora_impl = lora_impl
        self.cfg = cfg
        self.registry = registry
        self.B = batch_slots
        self.max_len = max_len
        self.params = {"base": params["base"]}
        self.caches = api.init_caches(cfg, batch_slots, max_len,
                                      per_row_pos=True)
        self.queue: list[Request] = []
        # per-slot host state
        self.active = np.zeros(batch_slots, bool)
        self.pos = np.zeros(batch_slots, np.int32)
        self.remaining = np.zeros(batch_slots, np.int32)
        self.adapter_idx = np.zeros(batch_slots, np.int32)
        self.rids: list[str | None] = [None] * batch_slots
        self.cur = np.zeros((batch_slots, 1), np.int32)
        self.outputs: dict[str, list[int]] = {}
        self.latency: dict[str, float] = {}
        self.step_times: list[float] = []
        self._submit_times: dict[str, float] = {}
        self._decode = _decode_fn(cfg, lora_impl)
        self._admit_step = _admit_fn(cfg)
        # immutable zeroed single-row cache reused by every admission
        self._fresh_row = api.init_caches(cfg, 1, max_len, per_row_pos=True)

    def submit(self, req: Request) -> None:
        req.submit_t = time.perf_counter()
        self._submit_times[req.rid] = req.submit_t
        self.queue.append(req)
        self.outputs[req.rid] = []

    # -- admission ---------------------------------------------------------

    def _admit(self, slot: int, req: Request) -> None:
        aslot = self.registry.slot(req.adapter)
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        # one fused dispatch: gather adapter, prefill the fresh row, scatter
        # it into the shared cache. The fresh row fully overwrites the slot
        # (pos=-1 beyond the prompt), so recycled slots carry no ghost KV
        # entries from the previous occupant.
        tok, self.caches = self._admit_step(
            self.params["base"], self.registry.store,
            self.registry.fusion_masks, self._fresh_row, self.caches,
            tokens, jnp.int32(aslot), jnp.int32(slot))
        first = int(tok[0])
        self.active[slot] = True
        self.pos[slot] = len(req.prompt)
        self.remaining[slot] = req.max_new_tokens
        self.adapter_idx[slot] = aslot
        self.rids[slot] = req.rid
        self.cur[slot, 0] = first
        self.outputs[req.rid].append(first)
        self.remaining[slot] -= 1
        if self.remaining[slot] <= 0:
            self._retire(slot)

    def _retire(self, slot: int) -> None:
        rid = self.rids[slot]
        self.latency[rid] = (time.perf_counter()
                             - self._submit_times.get(rid, 0.0))
        self.active[slot] = False
        self.rids[slot] = None

    # -- decode loop -------------------------------------------------------

    def step(self) -> int:
        """Admit what fits, run one batched decode step; -> #active rows."""
        for slot in range(self.B):
            if not self.active[slot] and self.queue:
                self._admit(slot, self.queue.pop(0))
        if not self.active.any():
            return 0
        t0 = time.perf_counter()
        tok, self.caches = self._decode(
            self.params["base"], self.registry.store,
            self.registry.fusion_masks, self.caches,
            jnp.asarray(self.cur), jnp.asarray(self.pos),
            jnp.asarray(self.adapter_idx))
        nxt = np.asarray(tok)
        self.step_times.append(time.perf_counter() - t0)
        for slot in range(self.B):
            if not self.active[slot]:
                continue
            self.pos[slot] += 1
            self.cur[slot, 0] = nxt[slot]
            self.outputs[self.rids[slot]].append(int(nxt[slot]))
            self.remaining[slot] -= 1
            if (self.remaining[slot] <= 0
                    or self.pos[slot] >= self.max_len - 1):
                self._retire(slot)
        return int(self.active.sum())

    def run(self) -> dict:
        """Drain queue + active rows; -> outputs and timing stats."""
        t0 = time.perf_counter()
        n_steps = 0
        while self.queue or self.active.any():
            self.step()
            n_steps += 1
        wall = time.perf_counter() - t0
        n_tok = sum(len(v) for v in self.outputs.values())
        lat = sorted(self.latency.values()) or [0.0]
        return {
            "outputs": dict(self.outputs),
            "n_steps": n_steps,
            "wall_s": wall,
            "generated_tokens": n_tok,
            "tok_s": n_tok / max(wall, 1e-9),
            "latency_p50_s": lat[len(lat) // 2],
            "latency_p99_s": lat[min(len(lat) - 1,
                                     int(np.ceil(0.99 * len(lat))) - 1)],
            "decode_step_times": list(self.step_times),
        }


# ---------------------------------------------------------------------------
# naive baseline: one merged model per request, sequential
# ---------------------------------------------------------------------------


def naive_serve(params: dict, cfg: ModelConfig, registry: AdapterRegistry,
                requests: list[Request], max_len: int) -> dict:
    """Per-request decode with merged single-adapter params — what serving
    N personalized clients costs without the gathered batched path. The
    per-step functions are jitted (cached per prompt length) so the
    comparison against the engine isolates batching + gathering, not
    dispatch overhead."""
    _prefill = _single_prefill_fn(cfg)
    _decode = _single_decode_fn(cfg)
    fresh = api.init_caches(cfg, 1, max_len)
    outputs: dict[str, list[int]] = {}
    t0 = time.perf_counter()
    for req in requests:
        aslot = jnp.int32(registry.slot(req.adapter))
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        tok, caches = _prefill(params["base"], registry.store,
                               registry.fusion_masks, fresh, tokens, aslot)
        toks = [int(tok[0])]
        pos = len(req.prompt)
        while len(toks) < req.max_new_tokens and pos < max_len - 1:
            cur = jnp.asarray([[toks[-1]]], jnp.int32)
            tok, caches = _decode(params["base"], registry.store,
                                  registry.fusion_masks, caches, cur,
                                  jnp.int32(pos), aslot)
            toks.append(int(tok[0]))
            pos += 1
        outputs[req.rid] = toks
    wall = time.perf_counter() - t0
    n_tok = sum(len(v) for v in outputs.values())
    return {"outputs": outputs, "wall_s": wall, "generated_tokens": n_tok,
            "tok_s": n_tok / max(wall, 1e-9)}
