"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

A trace holds device planes (``/device:TPU:<i>``), each with a line of XLA
programs (``XLA Modules``) and a line of the operations inside them
(``XLA Ops``), and host planes (``/host:CPU``) whose threads carry the
benchmark's own spans (``jax.profiler.TraceAnnotation`` names that start
with ``bench.``). Both kinds of plane share one clock, the device's to
within a millisecond or two.

``load`` turns that into a ``Trace`` of plain arrays; everything below it
works on those arrays, so a test can build a ``Trace`` by hand:

* ``busy_ns``            union of the intervals in which an operation ran,
                         averaged over the device planes;
* ``time_by_name``       device time per program or kernel name;
* ``time_in_spans``      device time of the operations whose midpoint lies
                         inside host spans of one name (attributes the anonymous
                         programs of a serving engine to admission/decode);
* ``span_ns``            host time inside spans of one name;
* ``idle_gaps``          the gaps between busy intervals, each named by the
                         innermost host span around its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Events:
    """Intervals [start, start + dur) in ns, with a name per interval."""
    start: np.ndarray  # int64 ns
    dur: np.ndarray  # int64 ns
    name: list[str]

    @classmethod
    def of(cls, rows) -> Events:
        rows = sorted(rows, key=lambda r: r[0])
        return cls(np.array([r[0] for r in rows], np.int64),
                   np.array([r[1] for r in rows], np.int64),
                   [r[2] for r in rows])

    def __len__(self) -> int:
        return len(self.name)

    @property
    def end(self) -> np.ndarray:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: list[Events]  # per device plane, the operation line
    modules: list[Events]  # per device plane, the program line
    spans: Events  # the benchmark's host spans (all threads)
    window: tuple[int, int]  # traced window [t0, t1) in ns

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _line_events(line) -> list[tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.duration_ns), e.name) for e in line.events]


def op_name(text: str) -> str:
    """An operation event is named by its HLO text
    (``%fusion.3 = bf16[..] fusion(...)``); keep the instruction name."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, window: tuple[int, int] | None = None,
         device_prefix: str = "/device:TPU:") -> Trace:
    """Read an ``.xplane.pb``. ``window`` (ns, trace clock) defaults to the
    span from the first to the last event of any device or span line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            lines = {ln.name: ln for ln in plane.lines}
            op_line = lines.get(OPS_LINE)
            mod_line = lines.get(MODULES_LINE)
            if op_line is None and mod_line is None:
                continue
            ops.append(Events.of([(a, b, op_name(n)) for a, b, n in
                                  _line_events(op_line or mod_line)]))
            modules.append(Events.of(_line_events(mod_line or op_line)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(r for r in _line_events(ln)
                             if r[2].startswith(SPAN_PREFIX))
    sp = Events.of(spans)
    if window is None:
        starts = [e.start[0] for e in ops + [sp] if len(e)]
        ends = [int(e.end.max()) for e in ops + [sp] if len(e)]
        window = (min(starts), max(ends)) if starts else (0, 0)
    return Trace(ops, modules, sp, window)


def clip(ev: Events, window: tuple[int, int]) -> Events:
    """The parts of ``ev`` that lie inside ``window``."""
    s = np.maximum(ev.start, window[0])
    e = np.minimum(ev.end, window[1])
    keep = e > s
    return Events(s[keep], (e - s)[keep],
                  [n for n, k in zip(ev.name, keep) if k])


def union(ev: Events) -> tuple[np.ndarray, np.ndarray]:
    """Merged busy intervals -> (starts, ends), sorted, disjoint."""
    if not len(ev):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(ev.start, kind="stable")
    s, e = ev.start[order], ev.end[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    starts = s[idx]
    ends = np.append(run_end[idx[1:] - 1], run_end[-1])
    return starts, ends


def busy_ns(trace: Trace) -> float:
    """Busy time averaged over the device planes, inside the window."""
    if not trace.n_devices:
        return 0.0
    tot = 0
    for ev in trace.ops:
        s, e = union(clip(ev, trace.window))
        tot += int((e - s).sum())
    return tot / trace.n_devices


def idle_percent(trace: Trace) -> float | None:
    """Share of the window in which no operation ran (averaged over the
    device planes), in per cent."""
    if not trace.window_ns:
        return None
    return 100.0 * (1.0 - busy_ns(trace) / trace.window_ns)


def time_by_name(ev: Events, window: tuple[int, int] | None = None
                 ) -> dict[str, int]:
    """Summed device ns per event name (clipped to ``window``)."""
    if window is not None:
        ev = clip(ev, window)
    out: dict[str, int] = {}
    for n, d in zip(ev.name, ev.dur):
        out[n] = out.get(n, 0) + int(d)
    return out


def matching(ev: Events, pred) -> Events:
    keep = np.array([bool(pred(n)) for n in ev.name], bool)
    return Events(ev.start[keep], ev.dur[keep],
                  [n for n, k in zip(ev.name, keep) if k])


def time_in_spans(ev: Events, spans: Events, span_name: str) -> int:
    """Device ns of events whose midpoint lies inside a host span named
    ``span_name``. The device clock of a trace sits a millisecond or two
    off the host's, so a program that a blocking span brackets can start
    "before" it; its midpoint still lies inside."""
    sel = matching(spans, lambda n: n == span_name)
    if not len(sel) or not len(ev):
        return 0
    s, e = union(sel)
    mid = ev.start + ev.dur // 2
    i = np.searchsorted(s, mid, side="right") - 1
    inside = (i >= 0) & (mid < e[np.maximum(i, 0)])
    return int(ev.dur[inside].sum())


def module_time(trace: Trace, prefix: str) -> float:
    """Device ns of programs whose name starts with ``prefix``, inside the
    window, averaged over the device planes."""
    if not trace.n_devices:
        return 0.0
    tot = 0
    for ev in trace.modules:
        sel = matching(clip(ev, trace.window), lambda n: n.startswith(prefix))
        tot += int(sel.dur.sum())
    return tot / trace.n_devices


def count_spans(spans: Events, span_name: str,
                window: tuple[int, int] | None = None) -> int:
    sel = matching(spans, lambda n: n == span_name)
    if window is not None:
        mid = sel.start + sel.dur // 2
        return int(((mid >= window[0]) & (mid < window[1])).sum())
    return len(sel)


def span_ns(spans: Events, span_name: str,
            window: tuple[int, int] | None = None) -> int:
    """Host ns inside spans named ``span_name`` (those whose midpoint lies
    in ``window``, whole)."""
    sel = matching(spans, lambda n: n == span_name)
    if window is not None:
        mid = sel.start + sel.dur // 2
        keep = (mid >= window[0]) & (mid < window[1])
        return int(sel.dur[keep].sum())
    return int(sel.dur.sum())


def span_at(spans: Events, t: int) -> str:
    """Name of the innermost (shortest) span covering ``t``, else
    ``"host.other"``."""
    cover = (spans.start <= t) & (spans.end > t)
    if not cover.any():
        return "host.other"
    idx = np.flatnonzero(cover)
    return spans.name[int(idx[np.argmin(spans.dur[idx])])]


def idle_gaps(trace: Trace, device: int = 0) -> list[tuple[str, int, int]]:
    """Idle gaps of one device inside the window -> [(span, start, ns)],
    longest first. A gap is named by the host span around its midpoint."""
    s, e = union(clip(trace.ops[device], trace.window))
    w0, w1 = trace.window
    g_start = np.concatenate([[w0], e])
    g_end = np.concatenate([s, [w1]])
    keep = g_end > g_start
    g_start, g_end = g_start[keep], g_end[keep]
    gaps = [(span_at(trace.spans, int((a + b) // 2)), int(a), int(b - a))
            for a, b in zip(g_start, g_end)]
    return sorted(gaps, key=lambda g: -g[2])


def idle_by_span(trace: Trace, device: int = 0, top: int = 10
                 ) -> list[list]:
    """Idle ns summed per naming span -> [[span, seconds], ...] largest
    first (at most ``top``)."""
    if not trace.n_devices:
        return []
    tot: dict[str, int] = {}
    for name, _, ns in idle_gaps(trace, device):
        tot[name] = tot.get(name, 0) + ns
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, ns / 1e9] for n, ns in rows]


def top_programs(trace: Trace, top: int = 10) -> list[list]:
    """Device seconds per program name (device 0), largest first."""
    t = time_by_name(trace.modules[0], trace.window) if trace.n_devices else {}
    rows = sorted(t.items(), key=lambda kv: -kv[1])[:top]
    return [[n, ns / 1e9] for n, ns in rows]
