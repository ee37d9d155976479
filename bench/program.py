"""The program's own spans and counters (``src/repro/obs.py``), as the
benchmark reads them.

Inside a run, a per-layer reader calls ``window(ctx)``: the host intervals
the program kept for each span name and its compile records, cut to the
traced window by their host stamps (the program stamps with the same
``time.perf_counter`` as the harness's ``ctx.t0`` and ``ctx.t1``). With a
program that has no ``repro.obs`` it gives None, and the reader reports
nothing.

From a trace file, ``load`` reads the program's host spans (names that
start with ``repro.``) and its ``repro.clock`` markers, and ``idle_by_span``
names each idle gap of the device by the innermost span of the benchmark's
and the program's together. The markers give the offset from the host's
clock to the trace's, which places the program's stamped records on the
trace: ``slowest_step`` (the serving engine's flight record) and
``placed_compiles``. The result line's ``idle_gaps`` name gaps by the
benchmark's spans alone; to see the rest, run

    PYTHONPATH=src python3 -m bench.program --workload <name> --seed <n> \\
        --seconds <s>

which runs the cell traced and adds to the line's ``breakdown``:
``idle_gaps_program``, ``idle_program_share``, ``compiles_program`` and,
for a serving cell, ``slowest_step``.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import numpy as np

from bench import trace as T

PREFIX = "repro."
CLOCK = PREFIX + "clock"


def program_obs():
    """The program's tracing module, or None where the program has none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


class Window:
    """The program's kept spans and compiles inside [t0, t1) (host clock).
    A span is inside when its midpoint is."""

    def __init__(self, obs, t0: float, t1: float):
        self.obs, self.t0, self.t1 = obs, t0, t1

    def inside(self, stamp: float) -> bool:
        return self.t0 <= stamp < self.t1

    def intervals(self, name: str) -> np.ndarray:
        iv = np.asarray(self.obs.spans(name), np.float64).reshape(-1, 2)
        mid = iv.mean(1)
        return iv[(mid >= self.t0) & (mid < self.t1)]

    def count(self, name: str) -> int:
        return len(self.intervals(name))

    def ms(self, name: str) -> float:
        iv = self.intervals(name)
        return 1e3 * float((iv[:, 1] - iv[:, 0]).sum())

    def compiles(self) -> int:
        return sum(1 for t, _, _ in self.obs.compiles() if self.inside(t))


def window(ctx) -> Window | None:
    obs = program_obs()
    return None if obs is None else Window(obs, ctx.t0, ctx.t1)


# ---------------------------------------------------------------------------
# the program's spans in a trace file
# ---------------------------------------------------------------------------


def load(path: str) -> tuple[T.Events, list[tuple[int, int]]]:
    """-> (the program's host spans, its clock markers as (trace ns,
    perf_counter ns)) in a ``.xplane.pb``."""
    from jax.profiler import ProfileData

    rows, marks = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == CLOCK:
                    perf = dict(e.stats).get("perf_ns")
                    if perf is not None:
                        marks.append((int(e.start_ns), int(perf)))
                elif e.name.startswith(PREFIX):
                    rows.append((int(e.start_ns), int(e.duration_ns), e.name))
    return T.Events.of(rows), marks


def clock_offset_ns(marks: list[tuple[int, int]]) -> int | None:
    """Trace ns minus perf_counter ns, the median over the markers."""
    if not marks:
        return None
    return int(np.median([t - p for t, p in marks]))


def with_program(trace: T.Trace, program: T.Events) -> T.Trace:
    """The trace with the program's spans beside the benchmark's."""
    both = list(zip(trace.spans.start.tolist(), trace.spans.dur.tolist(),
                    trace.spans.name))
    both += zip(program.start.tolist(), program.dur.tolist(), program.name)
    return dataclasses.replace(trace, spans=T.Events.of(both))


def idle_by_span(trace: T.Trace, program: T.Events, device: int = 0,
                 top: int = 10) -> list[list]:
    """Idle seconds per innermost span of both sets, largest first."""
    return T.idle_by_span(with_program(trace, program), device, top)


def program_share(trace: T.Trace, program: T.Events, device: int = 0
                  ) -> float | None:
    """Share of the device's idle time in gaps named by a program span."""
    gaps = T.idle_gaps(with_program(trace, program), device) \
        if trace.n_devices else []
    total = sum(g[2] for g in gaps)
    if not total:
        return None
    return sum(g[2] for g in gaps if g[0].startswith(PREFIX)) / total


def busy_ms(trace: T.Trace, lo: int, hi: int, device: int = 0) -> float:
    """Milliseconds in which the device ran an operation inside [lo, hi)
    (trace ns)."""
    if not trace.n_devices:
        return 0.0
    s, e = T.union(T.clip(trace.ops[device], (lo, hi)))
    return float((e - s).sum()) / 1e6


def slowest_step(steps, t0: float, t1: float, trace: T.Trace | None = None,
                 offset_ns: int | None = None) -> dict | None:
    """The slowest of the engine's steps (``StepTimes``) that started in
    [t0, t1), its phases in ms and, placed on a trace, the device's busy
    ms inside it: a step slow with the device idle was held on the host."""
    inside = [s for s in steps if t0 <= s.t0 < t1]
    if not inside:
        return None
    s = max(inside, key=lambda s: s.total)
    out = {"ms": 1e3 * s.total, "admit_ms": 1e3 * s.admit,
           "decode_ms": 1e3 * s.decode, "host_ms": 1e3 * s.host,
           "queued": s.queued, "admitted": s.admitted, "active": s.active}
    if trace is not None and offset_ns is not None:
        lo = int(s.t0 * 1e9) + offset_ns
        out["device_busy_ms"] = busy_ms(trace, lo, lo + int(s.total * 1e9))
    return out


def placed_compiles(compiles, t0: float, t1: float, trace: T.Trace,
                    offset_ns: int | None) -> list[list]:
    """The compiles that ended in [t0, t1): [innermost span, seconds, end
    in seconds from the trace's start (None without a clock marker)]."""
    out = []
    for stamp, where, secs in compiles:
        if t0 <= stamp < t1:
            at = (None if offset_ns is None else
                  (stamp * 1e9 + offset_ns - trace.window[0]) / 1e9)
            out.append([where, secs, at])
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(
        description="run one cell traced; name its idle gaps by the "
        "program's spans too")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness

    found, seen = {}, {}
    cleanup, context = harness.Tracer.cleanup, harness.ReaderContext

    def keep_context(*a, **kw):
        seen["ctx"] = context(*a, **kw)
        return seen["ctx"]

    def read_then_cleanup(tracer):
        ctx = seen.get("ctx")
        if tracer.dir and ctx is not None:
            path = T.find_xplane(tracer.dir)
            tr, (program, marks) = T.load(path), load(path)
            off = clock_offset_ns(marks)
            found["idle_gaps_program"] = idle_by_span(tr, program)
            found["idle_program_share"] = program_share(tr, program)
            obs = program_obs()
            if obs is not None:
                found["compiles_program"] = placed_compiles(
                    obs.compiles(), ctx.t0, ctx.t1, tr, off)
            stats = getattr(getattr(ctx.cell, "eng", None), "stats", None)
            if stats is not None:
                found["slowest_step"] = slowest_step(
                    stats.ring("step"), ctx.t0, ctx.t1, tr, off)
        cleanup(tracer)

    harness.Tracer.cleanup = read_then_cleanup
    harness.ReaderContext = keep_context
    try:
        record = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, t_start)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        harness.Tracer.cleanup = cleanup
        harness.ReaderContext = context
    record["breakdown"] |= found
    harness.emit(record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
