"""Training launcher — runs the real training loop on whatever devices exist.

Two modes:
  backbone   LoRA fine-tune (or full-param train) one of the assigned
             architectures on synthetic token streams, sharded over the
             host mesh, with checkpoint/restart.
  federated  the paper's RELIEF protocol on synthetic PAMAP2/MHEALTH
             (delegates to repro.core.engine; see examples/ for drivers).

Usage:
  python -m repro.launch.train --arch phi3-medium-14b --smoke --steps 20
  python -m repro.launch.train --mode federated --dataset pamap2 \
      --backbone cnn --strategy relief --rounds 40
"""
from __future__ import annotations

import argparse
import time


def run_backbone(cfg, mesh, *, steps: int, batch: int, seq: int,
                 lr: float = 1e-3, train_mode: str = "lora", seed: int = 0,
                 strategy: str | None = None, log_every: int = 10,
                 ckpt=None, ckpt_every: int = 25) -> list[float]:
    """Train ``cfg`` on synthetic token streams over ``mesh``; -> losses.

    Params, optimizer state and batches are placed with the
    ``dist.sharding`` specs of ``strategy`` (default: ``pick_strategy`` for
    training this config) and the jitted step keeps them there. ``ckpt``
    (a CheckpointManager, or None) resumes from and saves to a directory.
    """
    import jax
    import jax.numpy as jnp

    from repro.data.tokens import synthetic_token_batches
    from repro.dist import sharding as SH
    from repro.launch import step_fns as SF
    from repro.models import api
    from repro.optim import adam_init

    strategy = strategy or SH.pick_strategy(cfg, "train")
    batch_axes = SH.data_axes(mesh)
    if strategy in ("fsdp", "replicated") and "model" in mesh.axis_names:
        batch_axes += ("model",)  # every chip carries examples (batch_specs)
    SH.set_activation_mesh(mesh, batch_axes=batch_axes,
                           tp=(strategy == "tp"))

    params = api.init_model(jax.random.PRNGKey(seed), cfg)
    tr, _ = SF.split_trainable(params, train_mode)
    opt = adam_init(tr)
    start_step = 0
    if ckpt is not None:
        restored = ckpt.restore_latest({"params": params, "opt": opt})
        if restored is not None:
            (state, meta) = restored
            params, opt = state["params"], state["opt"]
            start_step = meta["step"]
            print(f"[train] resumed from step {start_step}")

    shard = lambda t: SH.to_named(mesh, t)  # noqa: E731
    pspec = SH.param_specs(cfg, params, mesh, train=True, strategy=strategy)
    ospec = SH.opt_state_specs(
        pspec["lora"] if train_mode == "lora" else pspec, opt, mesh)
    params = jax.device_put(params, shard(pspec))
    opt = jax.device_put(opt, shard(ospec))
    batches = synthetic_token_batches(cfg.vocab, batch, seq, steps, seed=seed,
                                      n_codebooks=cfg.n_codebooks)
    bshard = None
    jit_step = None
    losses = []
    t0 = time.time()
    with mesh:
        for i, b in enumerate(batches):
            step = start_step + i
            b = {k: jnp.asarray(v) for k, v in b.items()}
            if cfg.family == "vlm":
                b["patches"] = jnp.zeros((batch, cfg.n_patches, cfg.d_model),
                                         cfg.runtime_dtype())
            if jit_step is None:
                bshard = shard(SH.batch_specs(b, mesh, cfg, strategy))
                jit_step = jax.jit(
                    SF.make_train_step(cfg, lr=lr, train_mode=train_mode),
                    in_shardings=(shard(pspec), shard(ospec), bshard),
                    out_shardings=(shard(pspec), shard(ospec), None),
                    donate_argnums=(0, 1))
            params, opt, metrics = jit_step(params, opt,
                                            jax.device_put(b, bshard))
            losses.append(float(metrics["loss"]))
            if log_every and (step + 1) % log_every == 0:
                print(f"[train] step {step+1} loss {losses[-1]:.4f} "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)")
            if ckpt is not None and (step + 1) % ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt},
                          {"arch": cfg.arch})
    SH.set_activation_mesh(None)
    return losses


def train_backbone(args):
    from repro.checkpoint import CheckpointManager
    from repro.configs import base
    from repro.launch.mesh import make_host_mesh

    mod = base.get_arch(args.arch)
    cfg = mod.SMOKE if args.smoke else mod.FULL
    losses = run_backbone(
        cfg, make_host_mesh(args.model_parallel), steps=args.steps,
        batch=args.batch, seq=args.seq, lr=args.lr,
        train_mode=args.train_mode, seed=args.seed,
        log_every=args.log_every,
        ckpt=CheckpointManager(args.ckpt_dir, keep=2),
        ckpt_every=args.ckpt_every)
    final = losses[-1]
    print(f"[train] done after {len(losses)} steps, loss {final:.4f}")
    return final


def train_federated(args):
    import jax

    from repro.core.engine import FedConfig, FedRun
    from repro.core.strategies import get_strategy
    from repro.core.tasks import MMTask
    from repro.data import make_har_dataset, mm_config_for
    from repro.sim import make_fleet

    ds = make_har_dataset(args.dataset, windows_per_subject=args.windows,
                          seed=args.seed)
    n_low = 2 if args.dataset == "pamap2" else 4
    fleet = make_fleet(3, 3, n_low, M=4)
    cfg = mm_config_for(args.dataset, backbone={"cnn": "cnn", "b1": "cnn",
                                                "b2": "transformer"}.get(
        args.backbone, args.backbone))
    task, tr0 = MMTask.create(cfg, jax.random.PRNGKey(args.seed))
    fed = FedConfig(rounds=args.rounds, eval_every=args.eval_every,
                    seed=args.seed, utilization=2e-5)
    run = FedRun.create(task, tr0, get_strategy(args.strategy), fleet, fed)
    run.run(ds, log_every=args.eval_every)
    print(f"[federated] {args.strategy} final F1 {run.history['f1'][-1]:.4f}")
    return run.history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="backbone",
                    choices=["backbone", "federated"])
    ap.add_argument("--arch", default="phi3-medium-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--train-mode", default="lora", choices=["lora", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    # federated
    ap.add_argument("--dataset", default="pamap2")
    ap.add_argument("--backbone", default="cnn")
    ap.add_argument("--strategy", default="relief")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--windows", type=int, default=160)
    args = ap.parse_args()
    from repro.kernels.runtime import enable_compile_cache

    enable_compile_cache()
    if args.mode == "backbone":
        train_backbone(args)
    else:
        train_federated(args)


if __name__ == "__main__":
    main()
