"""Benchmark suite entry point — one bench per paper table/figure.

  python -m benchmarks.run [--quick | --full] [--only main_b1,ablation,...]

Default ("standard") runs reduced-but-faithful configurations suitable for
the 1-core CPU container (DESIGN.md §7): identical fleet topology, compute
gap and protocol as the paper, smaller models/rounds. ``--quick`` is the CI
smoke (few rounds, subset of methods); ``--full`` is paper-scale. Underlying
federated runs are cached under benchmarks/results/runs/, so the suite is
resumable and benches share runs.

Every bench runs inside a failure boundary: the suite always writes
benchmarks/results/summary.json (schema-stable; uploaded as the CI
artifact) and exits nonzero if ANY bench failed — the smoke job gates on
this exit code.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--rounds", type=int, default=None,
                    help="override the per-mode default round count")
    ap.add_argument("--only", default=None,
                    help="comma list: motivation,main_b1,main_b2,ablation,"
                         "sensitivity,convergence,permodality,device,"
                         "async,roofline")
    args = ap.parse_args()
    # "standard" defaults are calibrated to this 1-core CPU container
    # (protocol/fleet identical to the paper; --full restores paper scale)
    rounds = args.rounds or (6 if args.quick else (200 if args.full else 8))
    only = set(args.only.split(",")) if args.only else None
    from repro.kernels.runtime import enable_compile_cache

    enable_compile_cache()

    def want(name):
        return only is None or name in only

    from benchmarks import (bench_ablation, bench_async, bench_convergence,
                            bench_device_profile, bench_main,
                            bench_motivation, bench_permodality,
                            bench_roofline, bench_sensitivity)
    from benchmarks.common import RESULTS_DIR, write_json

    mode = "quick" if args.quick else "full" if args.full else "standard"
    benches = [
        ("motivation", lambda: bench_motivation.run(rounds=min(rounds, 24),
                                                    quick=args.quick)),
        ("main_b1", lambda: bench_main.run("b1", rounds=rounds,
                                           quick=args.quick)),
        ("main_b2", lambda: bench_main.run("b2",
                                           rounds=max(rounds * 2 // 3, 4),
                                           quick=args.quick)),
        ("ablation", lambda: bench_ablation.run(rounds=rounds,
                                                quick=args.quick)),
        ("sensitivity", lambda: bench_sensitivity.run(
            rounds=max(rounds * 2 // 3, 4), quick=args.quick)),
        ("convergence", lambda: bench_convergence.run(rounds=rounds,
                                                      quick=args.quick)),
        ("permodality", lambda: bench_permodality.run(rounds=rounds,
                                                      quick=args.quick)),
        ("device", lambda: bench_device_profile.run(
            rounds=max(rounds * 2 // 3, 4), quick=args.quick)),
        ("async", lambda: bench_async.run(rounds=rounds, quick=args.quick)),
    ]

    t0 = time.time()
    print(f"[benchmarks.run] mode={mode}")
    results = []
    for name, fn in benches:
        if not want(name):
            continue
        t1 = time.time()
        entry = {"bench": name, "status": "ok"}
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — boundary: record + gate
            entry["status"] = "error"
            entry["error"] = repr(e)
            traceback.print_exc()
        entry["duration_s"] = round(time.time() - t1, 1)
        results.append(entry)
        print(f"[benchmarks.run] {name}: {entry['status']} "
              f"({entry['duration_s']}s)")
    if want("roofline"):
        entry = {"bench": "roofline", "status": "ok"}
        try:
            bench_roofline.run("single")
            bench_roofline.run("multi")
        except FileNotFoundError as e:  # dry-run results may not exist yet
            entry["status"] = "skipped"
            entry["reason"] = str(e)
            print(f"[roofline] skipped: {e}")
        except Exception as e:  # noqa: BLE001
            entry["status"] = "error"
            entry["error"] = repr(e)
            traceback.print_exc()
        results.append(entry)

    failed = [r["bench"] for r in results if r["status"] == "error"]
    summary = {"mode": mode, "rounds": rounds,
               "duration_s": round(time.time() - t0, 1),
               "benches": results, "failed": failed,
               "ok": not failed}
    write_json(os.path.join(RESULTS_DIR, "summary.json"), summary)
    print(f"[benchmarks.run] done in {summary['duration_s']}s; "
          f"{'ALL OK' if not failed else 'FAILED: ' + ','.join(failed)}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
