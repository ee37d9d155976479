"""Bring-up smoke: the RELIEF main paths, once each, on a TPU.

One process drives the chip through the same entry points a user calls.

(a) Training flush, paper widths. A ``sim.scenarios`` run of PAMAP2 with
    Backbone 2 (``configs/relief_har.PAMAP2_B2``: d_feat 32, d_fused 128, 4
    encoder layers of width 128), ``async_relief`` with a 64-client FedBuff
    buffer over a 128-client fleet, int8 uplink, on the vectorized fleet
    runtime. It runs twice on the same seed — cohort ingest by XLA, then by
    the Pallas kernel — and checks: losses are finite; after the first
    flush the Pallas run's applied aggregate and divergence statistics
    equal the XLA run's within fp32 tolerance (both runs use fp32 matmul
    precision, so the XLA reference is exact fp32 too); the kernel runs
    compiled, not interpreted, and the ingest program holds a Mosaic call.
(b) Personalized serving, full width. ``launch/serve.build_engine`` with
    hymba-1.5b at its published config (bf16): 4 client adapters, 8
    requests of 4-8 prompt tokens over 4 slots, continuous batching with
    the gathered multi-LoRA decode kernel. Checks: every request retires
    with its token budget, every token is in [0, vocab), and two requests
    decoded alone in an otherwise idle engine give the tokens they got in
    the full batch.

``--chips 4`` runs only the cross-chip path instead: two LoRA train steps of
phi3-medium-14b at its published widths, depth cut to 4 layers, through
``launch/train.run_backbone`` — fsdp-sharded over a 4-chip host mesh, then
the same config on one chip — and checks the losses agree within bf16
tolerance.

Weights and data are made from ``--seed``; the script reads no generated
state (no benchmark run cache, no checkpoint). Times it prints are smoke
timings of single cold runs, compilation included — not measurements.
The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, it exits nonzero before that line.

Usage:
  python chip_smoke.py [--seed 0]
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FLUSH_RTOL = 1e-4  # fp32: pallas vs XLA ingest, relative to the leaf's max
LOSS_RTOL = 2e-2  # bf16 (eps 2^-7 = 7.8e-3): 4-chip vs 1-chip loss


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _max_rel_diff(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# ---------------------------------------------------------------------------
# (a) training flush
# ---------------------------------------------------------------------------


def flush_spec(seed: int, *, small_model: bool = False, n_clients: int = 128,
               buffer: int = 64, windows: int = 64):
    from repro.sim.scenarios import ScenarioSpec

    return ScenarioSpec(
        "chip_smoke_pamap2_b2", dataset="pamap2", backbone="transformer",
        small_model=small_model, strategy="async_relief",
        strategy_args=(("buffer_size", buffer),), uplink_codec="int8",
        n_clients=n_clients, windows_per_subject=windows, grad_mode="cohort",
        eval_every=0, seed=seed)


def run_flushes(spec, agg_impl: str, n_flushes: int) -> dict:
    """``n_flushes`` flushes of one run; keeps the first flush's applied
    aggregate (model after - model before) and divergence statistics."""
    import jax
    import numpy as np

    from repro.sim.scenarios import make_run

    run, sc = make_run(spec, vectorized=True, agg_impl=agg_impl)
    K = run.strategy.buffer_size
    before = jax.tree.map(np.array, run.state.trainable)  # a copy
    t0 = time.perf_counter()
    run.run(sc.dataset, total_updates=K)  # exactly the first flush
    first = {
        "update": jax.tree.map(lambda a, b: np.asarray(a) - b,
                               run.state.trainable, before),
        "divergence": np.asarray(run.history["divergence"][0]),
        "dbar": run.state.dbar.copy(),
    }
    run.run(sc.dataset, total_updates=K * (n_flushes - 1))
    return {"run": run, "first": first, "seconds": time.perf_counter() - t0}


def ingest_program_text(N: int, D: int, r: int, exponent: float) -> str:
    """Compiled text of the fused int8 cohort ingest at the flush's shape."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.cohort_agg import cohort_agg_divergence_quant

    fn = functools.partial(cohort_agg_divergence_quant, exponent=exponent,
                           impl="pallas")
    S = jax.ShapeDtypeStruct
    return jax.jit(fn).lower(
        S((N, D, r), jnp.int8), S((N,), jnp.float32), S((N, D), jnp.float32),
        S((N, D), jnp.float32), S((N,), jnp.float32)).compile().as_text()


def flush_phase(seed: int, n_flushes: int = 3, min_deltas: int = 64,
                kernel_check: bool = True, **spec_kw) -> dict:
    import jax
    import numpy as np

    from repro.kernels.runtime import resolve_interpret

    spec = flush_spec(seed, **spec_kw)
    with jax.default_matmul_precision("float32"):
        ref = run_flushes(spec, "xla", n_flushes)
        got = run_flushes(spec, "pallas", n_flushes)
    run = got["run"]
    K, N = run.strategy.buffer_size, run.fleet.N
    rec = {"phase": "flush", "config": "PAMAP2_B2", "clients": N,
           "deltas_per_flush": K, "uplink": "int8"}
    for name, r in (("xla", ref), ("pallas", got)):
        losses = np.asarray(r["run"].history["loss"], np.float64)
        check(len(losses) == n_flushes,
              f"{name}: {len(losses)} flushes, want {n_flushes}")
        check(bool(np.isfinite(losses).all()), f"{name}: non-finite loss")
        rec[f"{name}_losses"] = losses.tolist()
    check(K >= min_deltas, f"flush stacks {K} deltas, want >= {min_deltas}")
    check(run.aggbuf.impl == "pallas", "pallas run did not use the kernel")
    interpret = resolve_interpret(run.aggbuf.interpret)
    check(interpret == (jax.default_backend() == "cpu"),
          "kernel interprets off the CPU")
    rec["kernel_interpreted"] = interpret

    upd = {"update": max(
        _max_rel_diff(a, b) for a, b in zip(
            jax.tree.leaves(got["first"]["update"]),
            jax.tree.leaves(ref["first"]["update"])))}
    for key in ("divergence", "dbar"):
        upd[key] = _max_rel_diff(got["first"][key], ref["first"][key])
    rec["first_flush_max_rel_diff"] = upd
    rec["rtol"] = FLUSH_RTOL
    check(all(v <= FLUSH_RTOL for v in upd.values()),
          f"pallas ingest differs from XLA after flush 1: {upd}")

    if kernel_check:
        D, r = _fusion_shape(run)
        t0 = time.perf_counter()
        text = ingest_program_text(K, D, r,
                                   run.strategy.staleness_exponent)
        rec["ingest_compile_s"] = time.perf_counter() - t0
        rec["ingest_shape"] = [K, D, r]
        check("tpu_custom_call" in text,
              "compiled ingest holds no tpu_custom_call")
        rec["ingest_has_tpu_custom_call"] = True
    rec["smoke_timings_s"] = {"xla_run": ref["seconds"],
                              "pallas_run": got["seconds"]}
    return rec


def _fusion_shape(run) -> tuple[int, int]:
    import jax

    from repro.core import mdlora

    for path, leaf in jax.tree_util.tree_flatten_with_path(
            run.state.trainable)[0]:
        if mdlora.path_str(path) == run.task.layout.fusion_a_path:
            return int(leaf.shape[0]), int(leaf.shape[1])
    raise SmokeFailure("no fusion leaf in the trainable tree")


# ---------------------------------------------------------------------------
# (b) personalized serving
# ---------------------------------------------------------------------------


def serve_phase(cfg, seed: int, *, n_adapters: int = 4, batch: int = 4,
                prompt_len: int = 8, decode_steps: int = 8) -> dict:
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.launch.serving_engine import ServingEngine

    t0 = time.perf_counter()
    eng, reqs = build_engine(cfg, n_adapters=n_adapters, batch=batch,
                             prompt_len=prompt_len, decode_steps=decode_steps,
                             seed=seed)
    t_build = time.perf_counter() - t0
    for req in reqs:
        eng.submit(dataclasses.replace(req))
    t0 = time.perf_counter()
    res = eng.run()
    t_run = time.perf_counter() - t0
    outs = res["outputs"]
    check(set(outs) == {q.rid for q in reqs}, "requests went missing")
    check(all(len(outs[q.rid]) == q.max_new_tokens for q in reqs),
          "a request retired short of its token budget")
    check(set(eng.latency) == set(outs) and not eng.active.any(),
          "a request never retired")
    toks = np.concatenate([np.asarray(v) for v in outs.values()])
    check(bool(((toks >= 0) & (toks < cfg.vocab)).all()),
          "token outside [0, vocab)")

    # decode two requests alone: same engine shape, every other slot idle
    alone = {}
    for req in (reqs[0], reqs[-1]):
        solo = ServingEngine(eng.params, cfg, eng.registry, eng.B,
                             eng.max_len, lora_impl=eng.lora_impl)
        solo.submit(dataclasses.replace(req))
        alone[req.rid] = solo.run()["outputs"][req.rid]
        check(alone[req.rid] == outs[req.rid],
              f"{req.rid}: alone {alone[req.rid]} != batched "
              f"{outs[req.rid]}")
    return {"phase": "serve", "arch": cfg.arch, "lora_impl": eng.lora_impl,
            "requests": len(outs), "slots": eng.B, "adapters": n_adapters,
            "prompt_lens": sorted({len(q.prompt) for q in reqs}),
            "generated_tokens": res["generated_tokens"],
            "decode_steps": res["n_steps"],
            "alone_equals_batched": sorted(alone),
            "smoke_timings_s": {"build": t_build, "run": t_run}}


# ---------------------------------------------------------------------------
# --chips 4: sharded backbone training vs one chip
# ---------------------------------------------------------------------------


def sharded_train_phase(cfg, strategy: str, n_devices: int, *, seed: int,
                        steps: int = 2, batch: int = 8, seq: int = 128
                        ) -> dict:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import run_backbone

    devices = jax.devices()
    check(len(devices) >= n_devices,
          f"{len(devices)} devices, want {n_devices}")
    kw = dict(steps=steps, batch=batch, seq=seq, seed=seed,
              strategy=strategy, log_every=0)
    t0 = time.perf_counter()
    many = run_backbone(cfg, make_host_mesh(devices=devices[:n_devices]),
                        **kw)
    t_many = time.perf_counter() - t0
    t0 = time.perf_counter()
    one = run_backbone(cfg, make_host_mesh(devices=devices[:1]), **kw)
    t_one = time.perf_counter() - t0
    rel = [abs(a - b) / abs(b) for a, b in zip(many, one)]
    check(all(map(math.isfinite, many + one)), "non-finite loss")
    check(max(rel) <= LOSS_RTOL,
          f"{n_devices}-chip losses {many} vs 1-chip {one}")
    return {"phase": "sharded_train", "arch": cfg.arch,
            "layers": cfg.n_layers, "strategy": strategy,
            "chips": n_devices, f"losses_{n_devices}_chips": many,
            "losses_1_chip": one, "max_rel_diff": max(rel),
            "rtol": LOSS_RTOL,
            "smoke_timings_s": {f"{n_devices}_chips": t_many,
                                "1_chip": t_one}}


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit({"phase": "device", **device})

    from repro.configs import base
    from repro.dist.sharding import pick_strategy
    from repro.kernels.runtime import enable_compile_cache

    emit({"phase": "compile_cache", "dir": enable_compile_cache()})
    if args.chips == 4:
        mod = base.get_arch("phi3-medium-14b")
        cfg = dataclasses.replace(mod.FULL, n_layers=4)
        emit(sharded_train_phase(cfg, pick_strategy(mod.FULL, "train"), 4,
                                 seed=args.seed))
    else:
        emit(flush_phase(args.seed))
        emit(serve_phase(base.get_arch("hymba-1.5b").FULL, args.seed))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
