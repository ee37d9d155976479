"""The program's spans and counters as the benchmark reads them: the new
per-layer readers on hand-built records, idle gaps named by the innermost
program span, and the program's spans and clock markers in a CPU trace."""
import collections
import glob
import json
import os
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness
from bench import program as P
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
NEW = ["dispatch_ms.flush", "uplink_ms.flush", "agg_stats_ms.flush",
       "compiles.flush", "queue_wait_p95_ms.chat", "loop_host_ms.chat",
       "compiles.chat"]
B = -1.0e6  # hand-built stamps lie far from any real one


@pytest.fixture
def records(monkeypatch):
    """Fresh span and compile records in the program's tracing module."""
    from repro import obs

    monkeypatch.setattr(obs, "SPANS", obs.Rings())
    monkeypatch.setattr(obs, "COMPILES", collections.deque(maxlen=obs.CAP))
    return obs


def _put(obs, name, *intervals):
    for a, b in intervals:
        obs.SPANS.record(name, (B + a, B + b))


def _ctx(cell=None):
    return SimpleNamespace(t0=B, t1=B + 10.0, cell=cell)


def test_flush_readers_on_hand_built_records(records):
    obs = records
    _put(obs, "fed.run", (0, 4), (5, 9), (20, 24))  # the last is outside
    _put(obs, "fed.flush", (1, 3), (6, 8), (21, 23))
    _put(obs, "fed.uplink", (1.5, 1.6), (6.5, 6.6))
    _put(obs, "fed.stats", (2, 2.5), (7, 7.5))
    for t in (1.0, 30.0):
        obs.COMPILES.append((B + t, "fed.assemble", 0.5))
    read = {m: harness.load_reader(m).read(_ctx()) for m in NEW[:4]}
    assert read["dispatch_ms.flush"] == pytest.approx(2000.0)
    assert read["uplink_ms.flush"] == pytest.approx(100.0)
    assert read["agg_stats_ms.flush"] == pytest.approx(500.0)
    assert read["compiles.flush"] == 1


def test_chat_readers_on_hand_built_records(records):
    from repro.launch.serving_engine import RequestTimes

    obs = records
    _put(obs, "serve.step", (0, 1), (1, 2))
    _put(obs, "serve.admit", (0.1, 0.3))
    _put(obs, "serve.decode", (0.4, 0.9), (1.1, 1.6))
    stats = obs.Rings()
    waits = np.linspace(0.1, 2.0, 20)
    for i, w in enumerate(waits):
        stats.record("request", RequestTimes(f"r{i}", B + 5 - w, B + 5))
    stats.record("request", RequestTimes("late", B - 50, B + 11))  # outside
    ctx = _ctx(SimpleNamespace(eng=SimpleNamespace(stats=stats)))
    read = {m: harness.load_reader(m).read(ctx) for m in NEW[4:]}
    assert read["queue_wait_p95_ms.chat"] == pytest.approx(
        1e3 * np.percentile(waits, 95))
    assert read["loop_host_ms.chat"] == pytest.approx(400.0)
    assert read["compiles.chat"] == 0


@pytest.mark.parametrize("metric", NEW)
def test_readers_report_nothing_without_the_program_spans(metric,
                                                         monkeypatch):
    """A program without ``repro.obs`` (the commit before it) gives no
    reading, and no error."""
    monkeypatch.setattr(P, "program_obs", lambda: None)
    cell = SimpleNamespace(eng=SimpleNamespace())
    assert harness.load_reader(metric).read(_ctx(cell)) is None


def _hand_trace():
    # device busy [10, 30) and [60, 70) of [0, 100); the benchmark's spans
    # around everything; the program's inside them
    ops = T.Events.of([(10, 20, "fusion.1"), (60, 10, "fusion.2")])
    spans = T.Events.of([(0, 100, "bench.flush"),
                         (2, 50, "bench.cohort_update")])
    program = T.Events.of([(1, 98, "repro.fed.run"),
                           (2, 45, "repro.fed.assemble"),
                           (75, 20, "repro.fed.stats")])
    return T.Trace([ops], [ops], spans, (0, 100)), program


def test_idle_gaps_are_named_by_the_innermost_program_span():
    tr, program = _hand_trace()
    # gaps: [0, 10) mid 5 in assemble [2, 47); [30, 60) mid 45 in assemble;
    # [70, 100) mid 85 in stats [75, 95)
    assert P.idle_by_span(tr, program) == [["repro.fed.assemble", 40e-9],
                                           ["repro.fed.stats", 30e-9]]
    assert P.program_share(tr, program) == 1.0
    # the benchmark's own breakdown is unchanged
    assert T.idle_by_span(tr) == [["bench.cohort_update", 40e-9],
                                  ["bench.flush", 30e-9]]


def test_no_program_spans_leave_the_breakdown_as_it_was():
    tr, _ = _hand_trace()
    none = T.Events.of([])
    assert P.idle_by_span(tr, none) == T.idle_by_span(tr)
    probe = T.load(str(DATA / "probe.xplane.pb"))
    assert P.idle_by_span(probe, none) == T.idle_by_span(probe)
    assert P.program_share(probe, none) == 0.0


def test_program_spans_and_clock_in_a_cpu_trace(tmp_path):
    from repro import obs

    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            obs.clock()
            with obs.span("fed.flush", K=8, round=i):
                with obs.span("fed.stats"):
                    np.linalg.svd(np.ones((64, 64)))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    program, marks = P.load(path)
    assert program.name.count("repro.fed.flush") == 3
    assert program.name.count("repro.fed.stats") == 3
    assert "repro.clock" not in program.name and len(marks) == 3
    off = P.clock_offset_ns(marks)
    kept = obs.spans("fed.flush")[-3:]
    got = program.start[[n == "repro.fed.flush" for n in program.name]]
    for (t0, _), start in zip(kept, got):
        assert abs(t0 * 1e9 + off - start) < 1e6
    # the benchmark's loader keeps its own spans only
    assert not len(T.load(path, device_prefix="/device:TPU:").spans)


def _steps():
    from repro.launch.serving_engine import StepTimes

    # (t0, total, admit, decode, queued, admitted, active); stamps in s
    return [StepTimes(1.0, 0.05, 0.0, 0.04, 0, 0, 4),
            StepTimes(2.0, 0.30, 0.20, 0.05, 5, 2, 6),
            StepTimes(9.0, 0.90, 0.0, 0.05, 0, 0, 6)]  # outside [0, 5)


def test_slowest_step_is_placed_on_the_trace():
    # device busy [2.00, 2.01) and [2.25, 2.35) s on the trace, whose clock
    # runs 1 s ahead of the host's
    ops = T.Events.of([(int(3.00e9), int(0.01e9), "fusion.1"),
                       (int(3.25e9), int(0.10e9), "fusion.2")])
    tr = T.Trace([ops], [ops], T.Events.of([]), (int(2.5e9), int(3.5e9)))
    got = P.slowest_step(_steps(), 0.0, 5.0, tr, int(1e9))
    assert got == pytest.approx({
        "ms": 300.0, "admit_ms": 200.0, "decode_ms": 50.0, "host_ms": 50.0,
        "queued": 5, "admitted": 2, "active": 6, "device_busy_ms": 60.0})
    assert "device_busy_ms" not in P.slowest_step(_steps(), 0.0, 5.0)
    assert P.slowest_step(_steps(), 20.0, 30.0) is None


def test_compiles_are_placed_on_the_trace():
    tr = T.Trace([], [], T.Events.of([]), (int(5e9), int(9e9)))
    compiles = [(1.0, "fed.assemble", 0.5), (3.0, "-", 0.25),
                (7.0, "serve.admit", 1.0)]
    assert P.placed_compiles(compiles, 0.0, 5.0, tr, int(4e9)) == [
        ["fed.assemble", 0.5, 0.0], ["-", 0.25, 2.0]]
    assert P.placed_compiles(compiles, 5.0, 8.0, tr, None) == [
        ["serve.admit", 1.0, None]]


def test_main_adds_the_program_breakdown(monkeypatch, capsys, records):
    """``python3 -m bench.program`` on a stand-in for ``harness.run_cell``
    that traces, builds the readers' context and cleans up as the harness
    does: the line gains the program's idle gaps, compiles and flight
    record, and the harness is left as it was."""
    obs = records
    cleanup, context = harness.Tracer.cleanup, harness.ReaderContext
    eng = SimpleNamespace(stats=obs.Rings())

    def run_cell(workload, seed, seconds, trace, t_start):
        tracer = harness.Tracer(trace, seconds)
        tracer.start()
        from repro.launch.serving_engine import StepTimes

        c = float(np.random.default_rng().random())  # a fresh HLO
        f = jax.jit(lambda x: x * c)
        for i in range(3):
            obs.clock()
            with obs.span("serve.step") as st:
                with obs.span("serve.admit"):
                    if i == 1:
                        f(np.ones(3)).block_until_ready()
            eng.stats.record("step", StepTimes(st.t0, st.t1 - st.t0, 0.0,
                                               0.0, 1, 1, 1))
        tracer.stop()
        cell = SimpleNamespace(eng=eng)
        harness.ReaderContext(None, cell, None, {}, tracer.t0, tracer.t1)
        tracer.cleanup()
        return {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
                "device": {}, "breakdown": {}, "checks": {}}

    monkeypatch.setattr(harness, "run_cell", run_cell)
    assert P.main(["--workload", "w", "--seed", "1", "--seconds", "60"]) == 0
    assert harness.Tracer.cleanup is cleanup
    assert harness.ReaderContext is context
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    bd = got["breakdown"]
    assert set(bd) == {"idle_gaps_program", "idle_program_share",
                       "compiles_program", "slowest_step"}
    (where, secs, at), = bd["compiles_program"]
    assert where == "serve.admit" and secs > 0 and at is not None
    step = bd["slowest_step"]
    assert step["ms"] == pytest.approx(
        1e3 * max(s.total for s in eng.stats.ring("step")))
    assert step["device_busy_ms"] == 0.0  # no device plane on the CPU
