"""A RELIEF training flush as users run it: the vectorized async runtime.

The system under test is ``core.async_engine.VectorizedAsyncFedRun`` built
as ``sim.scenarios.make_run(spec, vectorized=True)`` builds it (the fleet,
the strategy and the runtime settings from a ``ScenarioSpec``), fed with
weights and data that the benchmark makes from the seed
(``bench/refs/relief_flush.py``). One unit of work is one server flush:
``run.run(dataset, total_updates=K)``.

Set-up builds the run and drives its first flushes through that same
call; the cohort of each (clients, versions pulled, tickets, gates,
modalities held) is recorded as it enters the local update. The window
then runs flushes until ``--seconds`` have passed. After it, the reference
recomputes the first flushes from the seed and the cohorts, and these
numbers are compared (each against its limit in ``bench/limits``):

    loss_gap     worst relative gap of a flush's mean client loss
    grad1_gap    worst leaf of the first flush's update (the server's
                 pseudo-gradient, model after flush 1 - model before):
                 | |prog| - |ref| | / max(|ref|, median leaf |ref|)
    change3_gap  the same for the change after the first flushes
    gates_wrong  recorded dispatches, of every flush of the run, whose
                 gates break the paper's allocation rule as the reference
                 applies it (budgets, mandatory fusion blocks, accessible
                 groups, highest smoothed divergence; exact, limit 0)

Leaves whose reference change is under a thousandth of the median leaf's
are left out of the two norm gaps (they move by rounding alone). The gap
of the smoothed divergence after the first flushes (``dbar_gap``: worst
group, | prog - ref | over the reference's EMA of that group's mean
squared update norm) is logged, not compared (PERF.md).
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import log
from bench.refs import relief_flush as R


def build_run(cfg: dict, buffer_size: int, seed: int, params: dict):
    """The program's runtime for this configuration, as ``make_run`` wires
    it, with the benchmark's weights."""
    from repro.core import mdlora
    from repro.core.async_engine import AsyncFedConfig, VectorizedAsyncFedRun
    from repro.core.tasks import MMTask
    from repro.models.multimodal import MMConfig, ModalitySpec
    from repro.sim.scenarios import ScenarioSpec, build_fleet

    spec = ScenarioSpec(
        "bench", dataset=cfg["dataset"], alpha=cfg["alpha"],
        windows_per_subject=cfg["windows_per_subject"],
        missing=cfg["missing"], fleet=tuple(cfg["fleet"]),
        n_clients=cfg["n_clients"], strategy=cfg["strategy"],
        strategy_args=(("buffer_size", buffer_size),
                       ("staleness_exponent", cfg["staleness_exponent"])),
        uplink_codec=cfg["uplink_codec"], backbone=cfg["backbone"],
        small_model=False, local_epochs=cfg["local_epochs"],
        steps_per_epoch=cfg["steps_per_epoch"],
        batch_size=cfg["batch_size"], lr=cfg["lr"],
        eval_every=cfg["eval_every"], grad_mode=cfg["grad_mode"], seed=seed)
    fleet = build_fleet(spec)
    fed = AsyncFedConfig.from_scenario(
        spec, fleet=fleet, agg_impl=cfg["agg_impl"], gamma=cfg["gamma"],
        server_lr=cfg["server_lr"], snapshot_ring=cfg["snapshot_ring"])
    mm = MMConfig(
        name=cfg["dataset"], n_classes=cfg["n_classes"],
        modalities=tuple(ModalitySpec(m["name"], m["channels"], m["d_feat"])
                         for m in cfg["modalities"]),
        window=cfg["window"], backbone=cfg["backbone"],
        d_fused=cfg["d_fused"], head_hidden=cfg["head_hidden"],
        enc_layers=cfg["enc_layers"], enc_d=cfg["enc_d"],
        enc_heads=cfg["enc_heads"], enc_ff=cfg["enc_ff"], patch=cfg["patch"],
        lora_rank=cfg["lora_rank"], lora_alpha=cfg["lora_alpha"],
        dtype=cfg["dtype"])
    trainable, static = R.split_params(params)
    layout = mdlora.mm_group_layout(mm, trainable)
    task = MMTask(mm, static, layout,
                  lambda t, s: {"base": dict(s) | {"head": t["head"]},
                                "lora": t["lora"]})
    layout.flops = task.group_compute_flops()
    run = VectorizedAsyncFedRun.create(task, trainable, spec.build_strategy(),
                                       fleet, fed)
    return run


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)


def norm_gap(prog: dict, ref: dict, rel_floor: float = 1e-3) -> float:
    """Worst leaf: | |prog| - |ref| | / max(|ref|, median leaf |ref|),
    over the leaves whose |ref| is at least ``rel_floor`` x the median's."""
    p = {jax.tree_util.keystr(k): float(np.linalg.norm(v))
         for k, v in jax.tree_util.tree_flatten_with_path(prog)[0]}
    r = {jax.tree_util.keystr(k): float(np.linalg.norm(v))
         for k, v in jax.tree_util.tree_flatten_with_path(ref)[0]}
    med = float(np.median(list(r.values())))
    gaps = [abs(p[k] - r[k]) / max(r[k], med) for k in r
            if r[k] >= rel_floor * med]
    return max(gaps)


def sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, opts):
        self.cfg, self.traffic, self.seed, self.opts = cfg, traffic, seed, opts
        self.K = int(traffic["buffer_size"])
        self.n_first = int(traffic["first_flushes"])
        self.cohorts: list[dict] = []  # every flush's cohort, in order
        self.flush_times: list[tuple[float, float]] = []  # window flushes

    # -- the program, instrumented from the outside -------------------------

    def _instrument(self, run) -> None:
        cohort_update = run._cohort_update
        local_update = run.local_update
        flush_arrays = run._flush_arrays
        cohorts = self.cohorts
        fault = self.opts.fault

        def lu(start, batches, *rest):
            if fault == "half_batch":  # half of each batch, mean over the rest
                half = batches["x"].shape[2] // 2
                batches = {k: v[:, :, :half] for k, v in batches.items()}
            with jax.profiler.TraceAnnotation("bench.local_update"):
                return jax.block_until_ready(local_update(start, batches,
                                                          *rest))

        def cu(dataset, ids, versions, tickets, S, mmask_rows):
            if fault == "gates":  # an allocation that breaks the rule
                S[0, 0] = not S[0, 0]
            cohorts.append({"ids": np.array(ids), "versions": np.array(versions),
                            "tickets": np.array(tickets), "S": np.array(S),
                            "mmask": np.array(mmask_rows)})
            with jax.profiler.TraceAnnotation("bench.cohort_update"):
                return cohort_update(dataset, ids, versions, tickets, S,
                                     mmask_rows)

        def fa(*args, **kw):
            before = run.state.trainable
            with jax.profiler.TraceAnnotation("bench.aggregate"):
                rec = flush_arrays(*args, **kw)
            if fault == "unchanged":  # the step returns its state unchanged
                run.state.trainable = before
            return rec

        run.local_update = lu
        run._cohort_update = cu
        run._flush_arrays = fa

    def _flush(self) -> None:
        with jax.profiler.TraceAnnotation("bench.flush"):
            self.run.run(self.data, total_updates=self.K)

    # -- the harness protocol ------------------------------------------------

    def setup(self) -> None:
        cfg = self.cfg
        self.data = R.make_dataset(cfg, self.seed)
        params = R.make_params(cfg, self.seed)
        log("data and weights made")
        self.run = build_run(cfg, self.K, self.seed, params)
        self.group_names = list(self.run.task.layout.names)
        self._instrument(self.run)
        log("run built")
        self.models = [_host(self.run.state.trainable)]
        self.dbars = [self.run.state.dbar.copy()]
        for i in range(self.n_first):
            self._flush()
            self.models.append(_host(self.run.state.trainable))
            self.dbars.append(self.run.state.dbar.copy())
            log(f"flush {i + 1} of the set-up")
        self.losses = [float(v) for v in self.run.history["loss"]]

    def window(self, seconds: float, tracer) -> dict:
        tracer.start()
        t0 = time.perf_counter()
        n = 0
        while True:
            a = time.perf_counter()
            self._flush()
            self.flush_times.append((a, time.perf_counter()))
            n += 1
            tracer.poll()
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.run.state.trainable)
        t1 = time.perf_counter()
        return {"metrics": {"updates_per_s": n * self.K / (t1 - t0)},
                "attempted": n * self.K, "failed": 0, "t_window_start": t0}

    def release(self) -> None:
        # the instrumented run refers to itself: collect it now, so that
        # the reference runs on a chip the program no longer holds
        self.run = None
        gc.collect()

    def reference(self, dtype=jnp.float32) -> dict:
        return R.follow(self.cfg, self.seed, self.seed, self.data,
                        self.cohorts[: self.n_first], self.group_names,
                        dtype=dtype)

    def readings(self, got: dict, ref: dict) -> list[tuple[str, float]]:
        """``got``/``ref``: {"losses", "models"}."""
        n = self.n_first
        loss = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"][:n], ref["losses"][:n]))
        g1 = norm_gap(sub(got["models"][1], got["models"][0]),
                      sub(ref["models"][1], ref["models"][0]))
        ch = norm_gap(sub(got["models"][n], got["models"][0]),
                      sub(ref["models"][n], ref["models"][0]))
        return [("loss_gap", loss), ("grad1_gap", g1), ("change3_gap", ch)]

    def dbar_gap(self, got: list[dict], ref: dict) -> float:
        """Worst group after the first flushes: | prog - ref | over the
        reference's EMA of the group's mean squared update norm (the
        scale of the program's E|x|^2 - |E x|^2 rounding), over groups
        where that is at least a thousandth of the median group's."""
        m = ref["msq"][self.n_first]
        med = float(np.median(list(m.values())))
        return max(abs(got[self.n_first][g] - ref["dbar"][self.n_first][g])
                   / m[g] for g in m if m[g] >= 1e-3 * med)

    def check(self) -> list[tuple[str, float]]:
        ref = self.reference()
        if self.opts.control:
            c = self.reference(jnp.bfloat16)
            got = {"losses": c["losses"], "models": c["models"]}
            dbars = c["dbar"]
        else:
            got = {"losses": self.losses, "models": self.models}
            dbars = [dict(zip(self.group_names, map(float, d)))
                     for d in self.dbars]
        wrong = R.allocation_errors(self.cfg, self.group_names, self.cohorts,
                                    ref["dbar"])
        log(f"dbar_gap {self.dbar_gap(dbars, ref):.6g}; gates of "
            f"{sum(len(c['ids']) for c in self.cohorts)} dispatches checked "
            f"in {len(self.cohorts)} flushes")
        return self.readings(got, ref) + [("gates_wrong", float(wrong))]
