"""Breakdown-point sweep for Byzantine-robust cohort aggregation.

For each (cohort size K, Byzantine fraction, aggregator) cell, a cohort of
K client deltas is drawn from the real model layout, a seeded fraction is
corrupted through ``sim.faults.FaultModel`` (sign-flip at scale 100 — the
gradient-inversion attack), and the cohort is reduced through the actual
``cohort_reduce`` robust path. The figure of merit is the relative L2
error of the aggregate against the honest-only oracle mean:

    rel_err = || agg(corrupted cohort) - mean(honest rows) ||
              / || mean(honest rows) ||

A cell is *bounded* when the median rel_err over trials stays within
``BOUND + BLOWUP x`` the same aggregator's attack-free (byz = 0) error at
that cohort size — breakdown means the error *blows up* relative to the
rule's own noise floor, not that it crosses an absolute line (Krum selects
a single member, so even attack-free it sits O(sqrt K) from the cohort
mean; that is its floor, and it stays there under attack). The plain mean
diverges at any nonzero attacker fraction (error scales with
corruption_scale), trimmed mean holds up to ~trim_frac, and coordinate
median / Krum hold through 40% — the breakdown table in README's
"Adversarial fleets" section.

Outputs
    benchmarks/results/bench_robust.json  full sweep (schema-stable)
    BENCH_robust.json (repo root)         committed baseline, written by
                                          --update-baseline; --smoke runs
                                          the K=8 column only and exits
                                          nonzero if any cell's bounded /
                                          diverged classification flipped
                                          against it (the CI robustness
                                          gate — draws are seeded, so the
                                          classification is deterministic).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import RESULTS_DIR, SCHEMA_VERSION, write_json

BASELINE_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                             "BENCH_robust.json")
AGGREGATORS = ("mean", "trimmed", "median", "krum")
BYZ_FRACS = (0.0, 0.1, 0.2, 0.3, 0.4)
COHORT_SIZES = (8, 16, 32)
SMOKE_COHORT = 8
SMOKE_FRACS = (0.0, 0.2, 0.4)
TRIALS = 5
SMOKE_TRIALS = 3
CORRUPTION = "sign_flip"
CORRUPTION_SCALE = 100.0
TRIM_FRAC = 0.25
KRUM_F = 1
BOUND = 2.0  # absolute slack of the boundedness test ...
BLOWUP = 3.0  # ... plus this factor of the aggregator's attack-free error;
# diverged cells land near corruption_scale x byz_frac (>= 10), an order of
# magnitude above any bounded cell's threshold


def _build(seed: int = 0):
    import jax

    from repro.core.tasks import MMTask
    from repro.data import mm_config_for

    cfg = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                        cnn_ch=(8, 16))
    return MMTask.create(cfg, jax.random.PRNGKey(seed))


def _tree_norm(tree) -> float:
    import jax
    import jax.numpy as jnp
    return float(np.sqrt(sum(float(jnp.sum(jnp.square(x)))
                             for x in jax.tree.leaves(tree))))


def _tree_dist(a, b) -> float:
    import jax
    diff = jax.tree.map(lambda x, y: x - y, a, b)
    return _tree_norm(diff)


def _cell(task, tr0, k: int, byz_frac: float, trials: int,
          seed: int = 0) -> dict[str, list[float]]:
    """-> {aggregator: [rel_err per trial]} for one (K, frac) cohort cell."""
    import jax
    import jax.numpy as jnp

    from repro.core import aggregation as AG
    from repro.core import mdlora
    from repro.sim import FaultModel

    lay = task.layout
    mm = jnp.ones((k, lay.n_modalities))
    trained = jnp.ones((k, lay.G)) * jnp.asarray(lay.sizes > 0)
    W = AG.cohort_weights(lay, trained, mm)
    C = trained
    errs: dict[str, list[float]] = {a: [] for a in AGGREGATORS}
    for t in range(trials):
        key = jax.random.PRNGKey(seed * 1000 + t)
        keys = jax.random.split(key, k)
        deltas = jax.vmap(lambda kk: jax.tree.map(
            lambda x: jax.random.normal(kk, x.shape, jnp.float32),
            tr0))(keys)
        fm = FaultModel(seed=seed * 1000 + t, byzantine_frac=byz_frac,
                        corruption=CORRUPTION,
                        corruption_scale=CORRUPTION_SCALE)
        byz = fm.byzantine_mask(np.ones((k, lay.n_modalities), bool))
        corrupted = fm.corrupt_stack(deltas, byz, np.arange(k),
                                     np.zeros(k, np.int64))
        # honest-only oracle: Eq. 3 cohort mean over the uncorrupted rows
        honest = ~byz
        W_h = AG.cohort_weights(lay, trained[honest], mm[honest])
        oracle = mdlora.weighted_combine(
            lay, jax.tree.map(lambda x: x[honest], deltas), W_h)
        denom = max(_tree_norm(oracle), 1e-9)
        for agg_kind in AGGREGATORS:
            agg, _, _ = AG.cohort_reduce(lay, corrupted, W, C,
                                         robust=agg_kind,
                                         trim_frac=TRIM_FRAC, krum_f=KRUM_F)
            errs[agg_kind].append(_tree_dist(agg, oracle) / denom)
    return errs


def run_sweep(smoke: bool = False, seed: int = 0) -> list[dict]:
    task, tr0 = _build(seed)
    sizes = (SMOKE_COHORT,) if smoke else COHORT_SIZES
    fracs = SMOKE_FRACS if smoke else BYZ_FRACS
    trials = SMOKE_TRIALS if smoke else TRIALS
    rows = []
    for k in sizes:
        cells = {frac: _cell(task, tr0, k, frac, trials, seed)
                 for frac in fracs}
        floor = {a: float(np.median(cells[0.0][a])) for a in AGGREGATORS}
        for frac in fracs:
            errs = cells[frac]
            for agg_kind in AGGREGATORS:
                e = np.asarray(errs[agg_kind])
                med = float(np.median(e))
                rows.append({
                    "cohort_size": k, "byz_frac": frac,
                    "aggregator": agg_kind, "trials": trials,
                    "rel_err_median": round(med, 4),
                    "rel_err_max": round(float(e.max()), 4),
                    "rel_err_clean": round(floor[agg_kind], 4),
                    "bounded": bool(
                        med <= BOUND + BLOWUP * floor[agg_kind]),
                })
            line = "  ".join(
                f"{a}={float(np.median(errs[a])):.3f}" for a in AGGREGATORS)
            print(f"  K={k:2d} byz={frac:4.0%}  " + line)
    return rows


def check_gate(rows: list[dict]) -> int:
    """CI gate, two layers: (1) hard invariant — at >= 20% Byzantine the
    plain mean must have diverged while median/krum stay bounded, and at
    exactly 20% trimmed must hold too (its theoretical breakdown is at
    trim_frac = 25%, so 30-40% cells are covered by the drift gate only);
    (2) every cell's bounded/diverged classification must match the
    committed baseline (seeded draws: deterministic)."""
    rc = 0
    for r in rows:
        if r["byz_frac"] < 0.2 - 1e-9:
            continue
        if r["aggregator"] == "trimmed" and r["byz_frac"] > 0.2 + 1e-9:
            continue
        want_bounded = r["aggregator"] != "mean"
        if r["bounded"] != want_bounded:
            print(f"INVARIANT FAIL: K={r['cohort_size']} "
                  f"byz={r['byz_frac']:.0%} {r['aggregator']} "
                  f"bounded={r['bounded']} (expected {want_bounded})")
            rc = 1
    if not os.path.exists(BASELINE_PATH):
        print("no committed BENCH_robust.json baseline; skipping drift gate")
        return rc
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    bkey = {(r["cohort_size"], r["byz_frac"], r["aggregator"]): r["bounded"]
            for r in base.get("rows", [])}
    for r in rows:
        k = (r["cohort_size"], r["byz_frac"], r["aggregator"])
        if k in bkey and bkey[k] != r["bounded"]:
            print(f"BASELINE DRIFT: {k} bounded {bkey[k]} -> {r['bounded']}")
            rc = 1
    print("robustness gate:", "OK" if rc == 0 else "FAIL")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="K=8 column only + classification gate (CI)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the committed BENCH_robust.json baseline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rows = run_sweep(smoke=args.smoke, seed=args.seed)
    payload = {"schema_version": SCHEMA_VERSION, "corruption": CORRUPTION,
               "corruption_scale": CORRUPTION_SCALE, "trim_frac": TRIM_FRAC,
               "krum_f": KRUM_F, "bound": BOUND, "rows": rows}
    write_json(os.path.join(RESULTS_DIR, "bench_robust.json"), payload)
    if args.update_baseline:
        write_json(os.path.abspath(BASELINE_PATH), payload)
        print(f"baseline written: {os.path.abspath(BASELINE_PATH)}")
    return check_gate(rows)


if __name__ == "__main__":
    raise SystemExit(main())
