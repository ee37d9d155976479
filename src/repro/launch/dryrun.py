"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract memory/cost/collective evidence for EXPERIMENTS.md §Dry-run/§Roofline.

A CPU-only rehearsal tool: it pins JAX to the CPU platform itself and asks
the host platform for 512 virtual devices, so the 16x16 single-pod and
2x16x16 multi-pod production meshes can be built and compiled on any
machine — including one with a TPU attached, which this tool never touches.
Compiling for a real chip is done by describing its topology instead
(tests/test_tpu_compile.py); running on one is chip_smoke.py.

Usage:
  python -m repro.launch.dryrun --arch phi3-medium-14b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod] \
      [--out-dir benchmarks/results/dryrun]

Each cell writes <out-dir>/<arch>__<shape>__<mesh>.json; existing files are
skipped (the full grid is resumable after interruption — the same mechanism
a real cluster launcher uses for preemption tolerance).
"""
import os

# Both MUST precede every jax import: jax fixes the platform and the host
# device count on first initialisation. Any XLA_FLAGS already set are kept.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS", ""),
    "--xla_force_host_platform_device_count=512")))

import argparse
import dataclasses
import json
import sys
import time
import traceback


def _compile_step(cfg, mod, shape, mesh, train_mode):
    """Lower + compile one step function on ``mesh``; returns the compiled
    artifact. Buffers are donated (params/opt for train, caches for decode)
    so memory_analysis reflects in-place updates."""
    import jax

    from repro.dist import sharding as SH
    from repro.launch import step_fns as SF

    params = SF.abstract_params(cfg)
    strategy = SH.pick_strategy(cfg, shape.kind)
    n_devices = mesh.devices.size
    if (strategy == "fsdp" and shape.global_batch % n_devices != 0):
        # multi-pod: global_batch (256) < chips (512) — pure FSDP leaves the
        # model axis without a batch dim; hybrid TP(model) x DP(pod,data)
        # keeps every chip busy (EXPERIMENTS.md §Dry-run note)
        strategy = "tp"
    if strategy in ("fsdp", "replicated"):
        batch_axes = SH.data_axes(mesh) + (("model",) if "model" in
                                           mesh.axis_names else ())
    else:
        batch_axes = SH.data_axes(mesh)
    SH.set_activation_mesh(mesh, batch_axes=batch_axes,
                           tp=(strategy == "tp"))
    pspec = SH.param_specs(cfg, params, mesh, train=(shape.kind == "train"),
                           strategy=strategy)
    shard = lambda t: SH.to_named(mesh, t)
    with mesh:
        if shape.kind == "train":
            tr, _ = SF.split_trainable(params, train_mode)
            opt = SF.abstract_opt_state(tr)
            # trainable specs = matching SUBTREE of the full param specs
            pspec_tr = pspec["lora"] if train_mode == "lora" else pspec
            ospec = SH.opt_state_specs(pspec_tr, opt, mesh)
            batch = mod.input_specs(shape, cfg)
            bspec = SH.batch_specs(batch, mesh, cfg, strategy)
            fn = SF.make_train_step(cfg, train_mode=train_mode)
            lowered = jax.jit(fn, in_shardings=(
                shard(pspec), shard(ospec), shard(bspec)),
                donate_argnums=(0, 1)).lower(params, opt, batch)
        elif shape.kind == "prefill":
            batch = mod.input_specs(shape, cfg)
            bspec = SH.batch_specs(batch, mesh, cfg)
            fn = SF.make_prefill_step(cfg)
            lowered = jax.jit(fn, in_shardings=(
                shard(pspec), shard(bspec))).lower(params, batch)
        else:  # decode
            specs_in = mod.input_specs(shape, cfg)
            caches = SF.abstract_caches(cfg, shape.global_batch,
                                        shape.seq_len)
            cspec = SH.cache_specs(cfg, caches, mesh)
            tok_spec = SH.batch_specs(specs_in["token"], mesh, cfg)
            fn = SF.make_serve_step(cfg)
            lowered = jax.jit(fn, in_shardings=(
                shard(pspec), shard(cspec), shard(tok_spec), None),
                donate_argnums=(1,)).lower(params, caches,
                                           specs_in["token"],
                                           specs_in["pos"])
        return lowered.compile()


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             train_mode: str = "lora", hillclimb: dict | None = None,
             probes: tuple = (), full_scan: bool = True) -> dict:
    """One dry-run cell.

    Two compilations per cell:
      1. FULL depth, scan-over-layers  -> proves the production graph
         compiles on the mesh + exact peak-memory analysis (the bwd
         activation stack appears in the scanned graph's buffers).
      2. Unrolled depth-L probes (L = n_sub, 2*n_sub) -> exact per-layer
         FLOPs/bytes/collective bytes (XLA cost_analysis counts while-loop
         bodies ONCE - measured; see roofline.py), extrapolated linearly:
         metric(L) = const + per_layer * L.
    """
    import jax

    from repro.configs import base
    from repro.dist import sharding as SH
    from repro.launch import roofline as RL
    from repro.launch.mesh import make_production_mesh

    mod = base.get_arch(arch)
    cfg0 = mod.FULL
    shape = base.SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    n_chips = 512 if multi_pod else 256

    if not base.supports(cfg0, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped",
                "reason": "long_500k requires sub-quadratic attention "
                          "(pure full-attention arch; DESIGN.md par.4)"}

    tweaks: dict = {"q_chunk": 256}
    if cfg0.family == "moe":
        tweaks |= {"moe_impl": "dense"}  # §Perf Cell B: sparse dispatch is
        # GSPMD-pathological at mesh scale; dense mixture is the baseline
    if shape.kind == "train":
        # bf16 LoRA compute on TPU (fp32 Adam moments regardless): fp32
        # adapters promoted whole activation tensors to f32 around every
        # LoRA matmul, doubling AG/AR bytes (§Perf phi3 iteration 2)
        tweaks |= {"remat": "full", "seq_shard": True, "loss_chunks": 8,
                   "lora_dtype": "bfloat16"}
    if hillclimb:
        tweaks |= hillclimb
    cfg = dataclasses.replace(cfg0, **tweaks)

    mesh = make_production_mesh(multi_pod=multi_pod)
    SH.set_activation_mesh(mesh)

    from repro.models.transformer import pattern
    n_sub = pattern(cfg)[0] if cfg.family in ("dense", "moe", "vlm",
                                              "audio") else 1

    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "status": "ok", "n_chips": n_chips,
              "train_mode": train_mode if shape.kind == "train" else None,
              "config_tweaks": tweaks, "hillclimb": hillclimb or {}}

    # --- 1. full-depth scanned compile: shardability + memory ---------------
    t0 = time.time()
    if full_scan:
        full_cfg = dataclasses.replace(cfg, scan_layers=True)
        compiled = _compile_step(full_cfg, mod, shape, mesh, train_mode)
        ma = compiled.memory_analysis()
        raw = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        # The CPU backend does not implement buffer donation, so donated
        # outputs (params/opt for train, caches for decode) are double
        # counted; on TPU they alias their inputs. Report both.
        donated = (ma.output_size_in_bytes if shape.kind in ("train",
                                                             "decode")
                   else 0)
        adj = raw - donated
        result["compile_s"] = round(time.time() - t0, 1)
        result["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "donation_adjusted_bytes": adj,
            "per_device_gb_raw": round(raw / 2**30, 3),
            "per_device_gb": round(adj / 2**30, 3),
            "fits_16gb_hbm": adj < 16 * 2**30,
        }
        del compiled

    # --- 2. depth probes: exact per-layer roofline terms --------------------
    if probes == "skip":  # multi-pod pass: compile+memory proof only
        return result
    probes = probes or (n_sub, 2 * n_sub)
    probe_stats = []
    for L in probes:
        pcfg = dataclasses.replace(cfg, n_layers=L, scan_layers=False)
        t1 = time.time()
        compiled = _compile_step(pcfg, mod, shape, mesh, train_mode)
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = RL.parse_collectives(hlo)
        probe_stats.append({
            "layers": L,
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll.bytes_entry + coll.bytes_scanned),
            "coll_counts": coll.counts,
            "compile_s": round(time.time() - t1, 1),
        })
        del compiled, hlo

    (p1, p2) = probe_stats[-2:]
    L_full = cfg.n_layers

    def extrap(key):
        per_layer = (p2[key] - p1[key]) / max(p2["layers"] - p1["layers"], 1)
        const = p1[key] - per_layer * p1["layers"]
        return max(const + per_layer * L_full, 0.0), per_layer

    flops, flops_pl = extrap("flops")
    byts, bytes_pl = extrap("bytes")
    cbytes, cbytes_pl = extrap("coll_bytes")
    terms = {
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": byts,
        "collective_bytes_per_device": cbytes,
        "per_layer": {"flops": flops_pl, "bytes": bytes_pl,
                      "coll_bytes": cbytes_pl},
        "collective_counts_probe": p2["coll_counts"],
        "t_compute_s": flops / RL.PEAK_FLOPS,
        "t_memory_s": byts / RL.HBM_BW,
        "t_collective_s": cbytes / RL.LINK_BW,
    }
    terms["dominant"] = max(
        (("compute", terms["t_compute_s"]), ("memory", terms["t_memory_s"]),
         ("collective", terms["t_collective_s"])), key=lambda kv: kv[1])[0]
    mf = RL.model_flops(cfg0, shape, train_mode)
    result["probes"] = probe_stats
    result["roofline"] = terms
    result["model_flops"] = mf
    result["useful_flops_ratio"] = (mf["model_flops"] / n_chips
                                    / max(flops, 1.0))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--train-mode", default="lora", choices=["lora", "full"])
    ap.add_argument("--out-dir", default="benchmarks/results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--hillclimb-json", default=None,
                    help="JSON dict of ModelConfig overrides (perf iteration)")
    ap.add_argument("--skip-probes", action="store_true",
                    help="compile+memory only (multi-pod shardability pass)")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    from repro.configs import base

    archs = base.list_archs() if args.arch == "all" else [args.arch]
    shapes = list(base.SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    hc = json.loads(args.hillclimb_json) if args.hillclimb_json else None

    os.makedirs(args.out_dir, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if hc:
                    tag += "__hc" + "-".join(f"{k}={v}" for k, v in
                                             sorted(hc.items()))
                out = os.path.join(args.out_dir, tag + ".json")
                if os.path.exists(out) and not args.force:
                    print(f"[skip cached] {tag}")
                    continue
                print(f"[run] {tag} ...", flush=True)
                try:
                    res = run_cell(arch, shape, mp, args.train_mode, hc,
                                   probes="skip" if args.skip_probes else ())
                except Exception as e:  # noqa: BLE001 — record and continue
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                    failures += 1
                with open(out, "w") as f:
                    json.dump(res, f, indent=1)
                msg = res["status"]
                if res["status"] == "ok" and "roofline" not in res:
                    msg += (f" compile={res.get('compile_s')}s "
                            f"mem={res.get('memory', {}).get('per_device_gb')}GB")
                elif res["status"] == "ok":
                    r = res["roofline"]
                    msg += (f" compile={res['compile_s']}s "
                            f"mem={res['memory']['per_device_gb']}GB "
                            f"dom={r['dominant']} "
                            f"tc={r['t_compute_s']:.4f} "
                            f"tm={r['t_memory_s']:.4f} "
                            f"tx={r['t_collective_s']:.4f}")
                print(f"[done] {tag}: {msg}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
