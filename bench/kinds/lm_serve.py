"""Personalized multi-LoRA serving as users run it: ``ServingEngine``.

The system under test is ``launch/serving_engine.ServingEngine`` with the
configuration's model, ``batch_slots`` rows and its default gathered
decode, fed with base weights and a store of per-client adapters that the
benchmark makes from the seed (``bench/refs/hymba.py``; non-zero LoRA ``b``
and per-adapter block masks, so a wrong gather changes the logits). The
benchmark drives ``engine.step()`` itself: requests from
``bench/traffic.py`` are submitted when due (open loop) or as soon as
another finishes (closed loop), and every token is stamped with the host
time at which ``step()`` handed it back.

Set-up: weights, adapters, one admission of each prompt length and one
decode step (every program the traffic uses), then ``warmup_s`` of the
loop itself so that the slots reach a steady state. The window follows
without a break. End-to-end numbers over the window:

    tokens_per_s   tokens handed back in the window / its length
    ttft_p95_ms    95th percentile over the requests due in the window of
                   first token - scheduled arrival; a request with no
                   token by the window's end counts at its wait so far
                   and as failed
    tbt_p95_ms     95th percentile of every gap between consecutive tokens
                   of a request, over the tokens handed back in the window

``correct``: once the window has closed, a sample drawn from the seed of
the finished requests, the longest among them, is run through the plain
float32 reference (prompt and served tokens). At each served token, the
gap is how far its reference logit lies below the reference's best at that
position (0 where the engine chose the reference's first token);
``mean_logit_gap`` is its mean over the sample's served tokens. (The
widest gap, logged beside it, swings too much from seed to seed to be told
apart from the int8 control's; see PERF.md.)
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as TR
from bench.harness import log
from bench.refs import hymba as H

MODEL_KEYS = ("arch", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
              "head_dim", "d_ff", "vocab", "ssm_state", "ssm_head_dim",
              "d_inner", "conv_kernel", "ssd_chunk", "layer_pattern",
              "sliding_window", "activation", "tie_embeddings", "dtype",
              "param_dtype", "lora_dtype", "q_chunk", "remat", "lora_targets",
              "lora_rank", "lora_alpha", "rope_theta")


class Adapters:
    """The registry surface the engine reads: a stacked store, the block
    masks, and the slot of an adapter (its name is its slot)."""

    def __init__(self, store, fusion_masks):
        self.store = store
        self.fusion_masks = fusion_masks

    def slot(self, name: str) -> int:
        return int(name)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    rows: int  # active rows
    sum_ctx: int  # live positions the active rows attend to
    distinct: int  # distinct adapters of the active rows
    distinct_all: int  # distinct adapter indices passed (all rows)


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    kw = {k: cfg[k] for k in MODEL_KEYS}
    kw["lora_targets"] = tuple(kw["lora_targets"])
    return ModelConfig(**kw)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, opts):
        self.cfg, self.traffic, self.seed, self.opts = cfg, traffic, seed, opts
        self.steps: list[Step] = []
        self.admits: list[tuple[float, float, int]] = []
        self.queue_len: list[tuple[float, int]] = []  # (loop time, queued)

    # -- the program, instrumented from the outside -------------------------

    def _instrument(self, eng) -> None:
        decode, admit = eng._decode, eng._admit_step
        steps, admits = self.steps, self.admits
        window = self.cfg["sliding_window"]
        fault = self.opts.fault

        def dec(*args):
            act = eng.active.copy()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.decode"):
                tok, caches = jax.block_until_ready(decode(*args))
            if fault == "token":  # a token altered where it is produced
                tok = jnp.where(jnp.asarray(act), (tok + 1) % self.cfg["vocab"], tok)
            ctx = np.minimum(eng.pos[act] + 1, window)
            steps.append(Step(t0, time.perf_counter(), int(act.sum()),
                              int(ctx.sum()),
                              len(set(eng.adapter_idx[act].tolist())),
                              len(set(eng.adapter_idx.tolist()))))
            return tok, caches

        def adm(*args):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.admit"):
                out = jax.block_until_ready(admit(*args))
            admits.append((t0, time.perf_counter(), int(args[5].shape[1])))
            return out

        eng._decode, eng._admit_step = dec, adm

    # -- the harness protocol ------------------------------------------------

    def setup(self) -> None:
        from repro.launch.serving_engine import Request, ServingEngine

        cfg, tr = self.cfg, self.traffic
        self.mc = model_config(cfg)
        base = H.make_base(cfg, self.seed)
        store, fmask = H.make_adapters(cfg, self.seed)
        jax.block_until_ready((base, store))
        log("weights made")
        self.max_len = TR.max_len(tr)
        self.eng = ServingEngine({"base": base}, self.mc, Adapters(store, fmask),
                                 cfg["slots"], self.max_len,
                                 lora_impl=cfg["lora_impl"])
        self._instrument(self.eng)
        for i, P in enumerate(TR.prompt_lens(tr)):  # every program once
            self.eng.submit(Request(f"warm{i}", np.zeros(P, np.int32), "0",
                                    max_new_tokens=2))
        self.eng.run()
        log("every program warmed up")
        self.steps.clear()
        self.admits.clear()
        self.Request = Request

    def window(self, seconds: float, tracer) -> dict:
        eng, tr = self.eng, self.traffic
        warm = float(tr["warmup_s"])
        n = TR.count_needed(tr, seconds)
        reqs = TR.generate(tr, self.seed, n, self.cfg["vocab"],
                           self.cfg["n_adapters"])
        self.reqs = {q.rid: q for q in reqs}
        stamps: dict[str, list[float]] = {}
        arrival: dict[str, float] = {}
        seen: dict[str, int] = {}
        live: set[str] = set()
        self.finished: set[str] = set()
        late = []
        closed = tr["loop"] == "closed"
        nxt = 0

        def submit(q, now):
            eng.submit(self.Request(q.rid, q.prompt, str(q.adapter),
                                    max_new_tokens=q.max_new))
            arrival[q.rid] = now if closed else q.arrival_s
            if not closed:
                late.append(now - q.arrival_s)
            stamps[q.rid], seen[q.rid] = [], 0
            live.add(q.rid)

        base = time.perf_counter()
        w0, w1 = warm, warm + seconds
        t_window_start = base + w0
        traced = False
        if closed:
            for q in reqs[: tr["outstanding"]]:
                submit(q, 0.0)
            nxt = tr["outstanding"]
        while True:
            now = time.perf_counter() - base
            if not traced and now >= w0:
                tracer.start()
                traced = True
            if now >= w1:
                break
            while not closed and nxt < n and reqs[nxt].arrival_s <= now:
                submit(reqs[nxt], now)
                nxt += 1
            if eng.queue or eng.active.any():
                with jax.profiler.TraceAnnotation("bench.step"):
                    eng.step()
                t = time.perf_counter() - base
                self.queue_len.append((t, len(eng.queue)))
                for rid in list(live):
                    k = len(eng.outputs[rid])
                    if k > seen[rid]:
                        stamps[rid].extend([t] * (k - seen[rid]))
                        seen[rid] = k
                    if rid in eng.latency:
                        live.discard(rid)
                        self.finished.add(rid)
                        if closed and nxt < n:
                            submit(reqs[nxt], t)
                            nxt += 1
            elif nxt < n:
                wait = min(reqs[nxt].arrival_s, w1) - now
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(min(wait, 0.05))
            base += tracer.poll()  # the loop's clock stands still meanwhile
        end = w1
        toks, gaps, ttft, failed = 0, [], [], 0
        for rid, st in stamps.items():
            a = np.asarray(st)
            inw = (a >= w0) & (a < end)
            toks += int(inw.sum())
            if len(a) > 1:
                g = np.diff(a)
                gaps.extend(g[inw[1:]].tolist())
            if w0 <= arrival[rid] < end:
                if len(a) and a[0] < end:
                    ttft.append(a[0] - arrival[rid])
                else:
                    ttft.append(end - arrival[rid])
                    failed += 1
        self.outputs = {rid: list(eng.outputs[rid]) for rid in self.finished}
        self.lateness = late
        metrics = {"tokens_per_s": toks / seconds}
        if ttft:
            metrics["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
        if gaps:
            metrics["tbt_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
            log(f"{toks} tokens, {len(gaps)} gaps; gap p99 "
                f"{1e3 * float(np.percentile(gaps, 99)):.1f} ms")
        if late:
            log(f"submitted late by p99 {1e3 * float(np.percentile(late, 99)):.1f} ms")
        longest_admit = max((b - a for a, b, _ in self.admits), default=0.0)
        longest_step = max((s.t1 - s.t0 for s in self.steps), default=0.0)
        log(f"longest admission {1e3 * longest_admit:.1f} ms, longest decode "
            f"step {1e3 * longest_step:.1f} ms")
        attempted = len(ttft) if not closed else sum(
            1 for rid in arrival if w0 <= arrival[rid] < end)
        return {"metrics": metrics, "attempted": attempted,
                "failed": failed if not closed else 0,
                "t_window_start": t_window_start}

    def release(self) -> None:
        # the instrumented engine refers to itself: collect it now, so that
        # the reference runs on a chip the program no longer holds
        self.eng._decode = self.eng._admit_step = None
        self.eng = None
        gc.collect()

    # -- correctness ---------------------------------------------------------

    def sample(self) -> list[str]:
        done = sorted(self.finished, key=lambda r: int(r[1:]))
        if not done:
            return []
        longest = max(done, key=lambda r: (len(self.outputs[r]), -int(r[1:])))
        rest = [r for r in done if r != longest]
        rng = np.random.default_rng([int(self.seed), 0xC4EC])
        k = min(int(self.traffic["check_requests"]) - 1, len(rest))
        pick = rng.choice(len(rest), k, replace=False) if k else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def check(self) -> list[tuple[str, float]]:
        rids = self.sample()
        if not rids:
            return [("mean_logit_gap", float("inf"))]
        cfg = self.cfg
        R, S = int(self.traffic["check_requests"]), self.max_len
        toks = np.zeros((R, S), np.int32)
        served = np.full((R, S), -1, np.int32)  # token predicted at each pos
        aidx = np.zeros(R, np.int32)
        for i, rid in enumerate(rids):
            q, out = self.reqs[rid], self.outputs[rid]
            seq = np.concatenate([q.prompt, np.asarray(out, np.int32)])[:S]
            toks[i, : len(seq)] = seq
            P = len(q.prompt)
            served[i, P - 1: P - 1 + len(out)] = out[: S - P + 1]
            aidx[i] = q.adapter
        base = H.make_base(cfg, self.seed)
        store, fmask = H.make_adapters(cfg, self.seed)
        ad = jax.tree.map(lambda a: a[:, aidx], store["layers"])
        fm = fmask[aidx]
        del store
        ref = H.logits(cfg, base, ad, fm, toks)
        best = jnp.max(ref, -1)
        if self.opts.control:  # the lower-precision model's own choices
            ctl = H.logits(cfg, base, ad, fm, toks, quant="int8")
            pick = jnp.argmax(ctl, -1)
        else:
            pick = jnp.asarray(np.maximum(served, 0))
        at = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
        gap = np.asarray(best - at)[served >= 0]
        log(f"compared {gap.size} served tokens of {len(rids)} requests; "
            f"widest gap {float(gap.max()):.4f}, "
            f"{int((gap > 0).sum())} not the reference's first")
        return [("mean_logit_gap", float(gap.mean()))]
