"""Serving launcher: batched prefill + decode with a KV/SSM cache on the
host mesh. Demonstrates the serve path end-to-end (continuous greedy decode
over a batch of synthetic prompts) for any assigned architecture.

Prefill goes through ``models/api.prefill_with_cache``: attention archs run
one chunked forward over the whole prompt (P-fold fewer dispatches than the
historical per-token loop); recurrent archs (ssm/hybrid) keep the exact
token loop their state recurrence requires.

``--engine`` demos the continuous-batching multi-LoRA path instead: N
personalized adapters, requests joining/leaving the decode batch mid-stream
(launch/serving_engine.py).

Usage:
  python -m repro.launch.serve --arch hymba-1.5b --smoke --prompt-len 64 \
      --decode-steps 32 --batch 4
  python -m repro.launch.serve --arch phi3-medium-14b --smoke --engine \
      --n-adapters 4 --batch 4
"""
from __future__ import annotations

import argparse
import time


def build_engine(cfg, *, n_adapters: int, batch: int, prompt_len: int,
                 decode_steps: int, seed: int = 0):
    """The personalized-serving setup: random base weights, ``n_adapters``
    client adapters with random modality masks, a ``batch``-slot engine and
    2x ``batch`` requests (slots recycle), prompts of prompt_len/2..prompt_len
    tokens. -> (engine, requests); nothing is submitted yet."""
    import jax
    import numpy as np

    from repro.launch.serving_engine import (AdapterRegistry, Request,
                                             ServingEngine)
    from repro.models import api

    params = api.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    reg = AdapterRegistry(jax.random.PRNGKey(1), cfg, capacity=n_adapters)
    n_blocks = len(reg.block_dims)
    for i in range(n_adapters):
        lora = api.init_model(jax.random.PRNGKey(100 + i), cfg)["lora"]
        mm = (rng.random(n_blocks) < 0.8).astype(np.float32)
        mm[int(rng.integers(n_blocks))] = 1.0  # >=1 modality present
        reg.register(f"client-{i}", lora, modality_mask=mm)

    max_len = prompt_len + decode_steps + 2
    eng = ServingEngine(params, cfg, reg, batch_slots=batch, max_len=max_len)
    reqs = []
    for r in range(batch * 2):  # 2x oversubscribed: slots recycle
        plen = int(rng.integers(max(2, prompt_len // 2), prompt_len + 1))
        reqs.append(Request(
            rid=f"req-{r}", prompt=rng.integers(0, cfg.vocab, plen),
            adapter=f"client-{r % n_adapters}", max_new_tokens=decode_steps))
    return eng, reqs


def _run_engine(args, cfg):
    eng, reqs = build_engine(cfg, n_adapters=args.n_adapters,
                             batch=args.batch, prompt_len=args.prompt_len,
                             decode_steps=args.decode_steps, seed=args.seed)
    for req in reqs:
        eng.submit(req)
    res = eng.run()
    print(f"[serve/engine] {args.arch}: {len(res['outputs'])} requests, "
          f"{res['generated_tokens']} tokens in {res['wall_s']:.2f}s "
          f"({res['tok_s']:.1f} tok/s, p50 {res['latency_p50_s']:.3f}s, "
          f"p99 {res['latency_p99_s']:.3f}s)")
    sample = next(iter(res["outputs"].values()))
    print("[serve/engine] sample:", sample[:16])
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching multi-LoRA engine demo")
    ap.add_argument("--n-adapters", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.kernels.runtime import enable_compile_cache

    enable_compile_cache()

    from repro.configs import base
    from repro.launch import step_fns as SF
    from repro.models import api

    mod = base.get_arch(args.arch)
    cfg = mod.SMOKE if args.smoke else mod.FULL
    if args.engine:
        return _run_engine(args, cfg)
    key = jax.random.PRNGKey(args.seed)
    params = api.init_model(key, cfg)
    B, P = args.batch, args.prompt_len
    max_len = P + args.decode_steps

    tok_shape = (B, P, cfg.n_codebooks) if cfg.n_codebooks else (B, P)
    prompts = jax.random.randint(key, tok_shape, 0, cfg.vocab)

    serve_step = jax.jit(SF.make_serve_step(cfg))
    caches = api.init_caches(cfg, B, max_len)

    # chunked prefill (attention archs: one forward; ssm/hybrid: the cache
    # path is the recurrence, so api falls back to the exact token loop)
    t0 = time.time()
    logits, caches = api.prefill_with_cache(params, cfg, caches, prompts)
    tok = jnp.argmax(logits, axis=-1).astype(prompts.dtype)
    if cfg.n_codebooks:
        tok = tok.reshape(B, 1, cfg.n_codebooks)
    t_prefill = time.time() - t0

    out = []
    t0 = time.time()
    for pos in range(P, max_len):
        tok, caches = serve_step(params, caches, tok, jnp.int32(pos))
        out.append(tok)
    t_decode = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    tps = args.decode_steps * B / max(t_decode, 1e-9)
    print(f"[serve] {args.arch}: prefill {P} toks in {t_prefill:.2f}s; "
          f"decoded {args.decode_steps}x{B} in {t_decode:.2f}s "
          f"({tps:.1f} tok/s)")
    print("[serve] sample:", gen[0].reshape(-1)[:16].tolist())
    assert bool(jnp.all(gen >= 0)) and bool(jnp.all(gen < cfg.vocab))
    return gen


if __name__ == "__main__":
    main()
