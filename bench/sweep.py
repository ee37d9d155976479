"""Find the highest arrival rate an open-loop serving cell sustains.

    python bench/sweep.py --workload serve.hymba.chat --rates 2,3,4 \
        [--seconds 30] [--seed 1]

For each rate, in the order given, one process runs the cell's traffic at
that rate (set-up, its warm-up, then ``--seconds``) and prints one JSON
line: the end-to-end numbers, the requests due and those that got no
token, and the queue of admissions waiting, averaged over the first and
the second half of the window. A rate is sustained where that queue
averages under one request in the second half; the sweep stops after the
first rate that is not. The last line names the highest sustained rate
(the knee) and four fifths of it, the rate the cell's traffic file fixes.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness

    cs = harness.cell_spec(args.workload)
    harness.configure_compile_cache()
    device = harness.device_info(int(cs.workload["chips"]))
    kind = harness.load_kind(cs.cfg["kind"])
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        cell = kind.Cell(cs.cfg, cs.traffic | {"rate_per_s": rate}, args.seed,
                         harness.Options())
        cell.setup()
        win = cell.window(args.seconds, harness.Tracer(False, 0))
        w0 = float(cs.traffic["warmup_s"])
        mid = w0 + args.seconds / 2
        q = np.array(cell.queue_len)
        first = q[(q[:, 0] >= w0) & (q[:, 0] < mid), 1]
        second = q[q[:, 0] >= mid, 1]
        cell.release()
        print(json.dumps({
            "workload": args.workload, "rate_per_s": rate,
            "metrics": win["metrics"], "attempted": win["attempted"],
            "failed": win["failed"],
            "queue_first_half": float(first.mean()) if len(first) else 0.0,
            "queue_second_half": float(second.mean()) if len(second) else 0.0,
            "lateness_p99_s": float(np.percentile(cell.lateness, 99)),
            "device": device}), flush=True)
        if len(second) and second.mean() >= 1.0:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None else round(0.8 * knee, 2)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
