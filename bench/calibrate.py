"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--faults half_batch:1,2,3] [--seconds 10]

For each seed of ``--seeds``: a sound run of the cell (set-up, a window of
``--seconds`` where the cell serves requests; training cells need none)
and its numbers against the plain reference; for those also in
``--control-seeds``, the same numbers of the lower-precision reference put
in the program's place, on that run's own inputs. For each fault and its
seeds: a run with the fault planted in the timed path. One JSON line per
reading on standard output. Limits (``bench/limits/<workload>.json``) lie
above the largest sound reading and below the smallest control or fault
reading, as ``PERF.md`` sets out.

Each seed runs in a process of its own (this one never imports JAX), so
that every reading starts from an empty chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def _seeds(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def one(workload: str, seed: int, fault: str | None, control: bool,
        seconds: float) -> None:
    """One seed's readings, printed as JSON lines."""
    from bench import harness

    cs = harness.cell_spec(workload)
    harness.configure_compile_cache()
    harness.count_compiles()
    device = harness.device_info(int(cs.workload["chips"]))
    kind = harness.load_kind(cs.cfg["kind"])
    opts = harness.Options(fault=fault)
    cell = kind.Cell(cs.cfg, cs.traffic, seed, opts)
    t0 = time.perf_counter()
    cell.setup()
    harness.log(f"seed {seed} {fault or ''}: {harness.compile_note()}")
    cell.window(seconds, harness.Tracer(False, 0))
    cell.release()
    kinds = [fault or "program"]
    readings = [dict(cell.check())]
    if control:  # the same inputs, the lower-precision reference in its place
        opts.control = True
        kinds.append("control")
        readings.append(dict(cell.check()))
    for what, numbers in zip(kinds, readings):
        print(json.dumps({"workload": workload, "seed": seed, "reading": what,
                          "numbers": numbers,
                          "seconds": time.perf_counter() - t0,
                          "device": device}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--one", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.one:  # seed[:fault[:control]] in this process
        seed, fault, control = (args.one.split(":") + ["", ""])[:3]
        one(args.workload, int(seed), fault or None, control == "control",
            args.seconds)
        return 0
    control = set(_seeds(args.control_seeds))
    plan = [f"{s}::{'control' if s in control else ''}"
            for s in _seeds(args.seeds)]
    for f in args.faults:
        name, seeds = f.split(":")
        plan += [f"{s}:{name}:" for s in _seeds(seeds)]
    rc = 0
    for item in plan:
        p = subprocess.run([sys.executable, __file__, "--workload",
                            args.workload, "--seconds", str(args.seconds),
                            "--one", item])
        rc = rc or p.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
