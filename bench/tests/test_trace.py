"""The trace reduction: busy and idle time, time per program, device time
inside host spans, idle gaps named by the span around them."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    # one device, window [0, 100): programs at [10, 30), [20, 40) (overlap),
    # [60, 70); host spans: admit [5, 45), decode [55, 75), a loop span
    # around everything
    ops = T.Events.of([(10, 20, "fusion.1"), (20, 20, "_matmul_multi_jit.1"),
                       (60, 10, "fusion.2")])
    mods = T.Events.of([(10, 30, "jit_f(1)"), (60, 10, "jit_f(2)")])
    spans = T.Events.of([(0, 100, "bench.loop"), (5, 40, "bench.admit"),
                         (55, 20, "bench.decode")])
    return T.Trace([ops], [mods], spans, (0, 100))


def test_busy_is_the_union_of_operations():
    tr = _trace()
    assert T.busy_ns(tr) == (40 - 10) + (70 - 60)
    assert T.idle_percent(tr) == 60.0
    s, e = T.union(tr.ops[0])
    assert s.tolist() == [10, 60] and e.tolist() == [40, 70]


def test_time_per_program_and_in_spans():
    tr = _trace()
    assert T.time_by_name(tr.modules[0]) == {"jit_f(1)": 30, "jit_f(2)": 10}
    assert T.module_time(tr, "jit_f") == 40
    assert T.time_in_spans(tr.modules[0], tr.spans, "bench.admit") == 30
    assert T.time_in_spans(tr.modules[0], tr.spans, "bench.decode") == 10
    assert T.count_spans(tr.spans, "bench.admit", tr.window) == 1
    kern = T.matching(tr.ops[0], lambda n: n.startswith("_matmul_multi_jit"))
    assert int(kern.dur.sum()) == 20


def test_host_time_in_spans():
    tr = _trace()
    assert T.span_ns(tr.spans, "bench.admit") == 40
    assert T.span_ns(tr.spans, "bench.decode", (0, 60)) == 0  # midpoint 65
    assert T.span_ns(tr.spans, "bench.decode", (60, 100)) == 20


def test_idle_gaps_are_named_by_the_innermost_span():
    tr = _trace()
    gaps = T.idle_gaps(tr)
    # [0,10) admit (its midpoint 5 is inside admit [5,45)), [40,60) loop,
    # [70,100) loop
    assert [(g[0], g[2]) for g in gaps] == [("bench.loop", 30),
                                            ("bench.loop", 20),
                                            ("bench.admit", 10)]
    assert T.idle_by_span(tr) == [["bench.loop", 50e-9], ["bench.admit",
                                                          10e-9]]


def test_clip_to_window():
    tr = _trace()
    tr.window = (15, 65)
    assert T.busy_ns(tr) == (40 - 15) + (65 - 60)


def test_recorded_chip_trace():
    """A trace recorded on one v5e: three host spans, each around a jitted
    matmul and a jitted gathered multi-adapter kernel call, blocked on."""
    tr = T.load(str(DATA / "probe.xplane.pb"))
    assert tr.n_devices == 1
    assert T.count_spans(tr.spans, "bench.probe") == 3
    mods = tr.modules[0]
    assert len(mods) == 6 and all(n.startswith("jit_") for n in mods.name)
    busy = T.busy_ns(tr)
    assert 0 < busy <= tr.window_ns
    assert busy == pytest.approx(sum(T.union(tr.ops[0])[1]
                                     - T.union(tr.ops[0])[0]))
    kern = T.matching(tr.ops[0], lambda n: n.startswith("_matmul_multi_jit"))
    assert len(kern) == 3 and 0 < int(kern.dur.sum()) < busy
    # the device clock runs a little behind the host's: every program lies
    # within 3 ms of the span that issued and awaited it
    s, e = T.union(T.matching(tr.spans, lambda n: n == "bench.probe"))
    mid = mods.start + mods.dur // 2
    near = [(np.abs(mid[:, None] - s[None]) < 3_000_000)
            | ((mid[:, None] >= s[None]) & (mid[:, None] < e[None]))]
    assert near[0].any(1).all()
