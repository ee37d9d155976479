"""The program's spans and counters (``repro.obs``): nesting, bounded
records, compile attribution, the clock marker, and the spans of the
training flush and the serving engine as a CPU profiler trace shows them."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.configs import base
from repro.core.async_engine import AsyncFedConfig, VectorizedAsyncFedRun
from repro.core.strategies import async_relief
from repro.core.tasks import MMTask
from repro.data import make_har_dataset, mm_config_for
from repro.launch.serving_engine import (AdapterRegistry, Request,
                                         ServingEngine)
from repro.models import api
from repro.sim import make_fleet, scale_fleet


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler -> the ``repro.`` host events as
    (name, start ns, end ns, stats)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
                        for e in ln.events if e.name.startswith("repro.")]
    return out


def _named(events, name):
    return [e for e in events if e[0] == "repro." + name]


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def _assert_nested(events, child, parent):
    kids, outer = _named(events, child), _named(events, parent)
    assert kids, child
    assert all(_inside(k, outer) for k in kids), (child, parent)


def test_span_keeps_interval_and_innermost_name():
    assert obs.innermost() is None
    with obs.span("test.outer") as outer:
        with obs.span("test.inner", k=1) as inner:
            assert obs.innermost() == "test.inner"
        assert obs.innermost() == "test.outer"
    assert obs.innermost() is None
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert obs.spans("test.inner")[-1] == (inner.t0, inner.t1)

    @obs.traced("test.fn")
    def f(x):
        assert obs.innermost() == "test.fn"
        return x + 1

    assert f(1) == 2 and len(obs.spans("test.fn")) >= 1


@pytest.mark.parametrize("kind", ["counters", "spans"])
def test_rings_stay_at_their_cap(kind):
    if kind == "counters":  # the engine's records
        c = obs.Rings()
        for i in range(10_000):
            c.record("step", i)
        assert len(c.ring("step")) == obs.CAP == 4096
        assert c.ring("step")[0] == 10_000 - 4096
        assert c.ring("step")[-1] == 9999
    else:
        for _ in range(10_000):
            with obs.span("test.ring"):
                pass
        assert len(obs.spans("test.ring")) == obs.CAP


def test_compile_is_counted_under_its_span():
    c = float(np.random.default_rng().random())  # an HLO no cache holds
    x = jnp.ones(7).block_until_ready()

    def here():
        return [r for r in obs.compiles() if r[1] == "test.compile"]

    before = len(here())
    with obs.span("test.compile"):
        jax.jit(lambda x: x * c + 1.0)(x).block_until_ready()
    assert len(here()) == before + 1
    stamp, where, secs = obs.compiles()[-1]
    assert where == "test.compile" and secs > 0
    assert obs.spans("test.compile")[-1][0] <= stamp


def test_clock_marker_maps_stamps_into_the_trace(tmp_path):
    def work():
        for _ in range(5):
            obs.clock()
            with obs.span("test.mapped"):
                np.linalg.svd(np.ones((64, 64)))

    events = _traced(tmp_path, work)
    marks = _named(events, "clock")
    assert len(marks) == 5
    offset = np.median([m[1] - m[3]["perf_ns"] for m in marks])
    kept = obs.spans("test.mapped")[-5:]
    traced = _named(events, "test.mapped")
    for (t0, t1), ev in zip(kept, traced):
        assert abs(t0 * 1e9 + offset - ev[1]) < 1e6  # within 1 ms
        assert abs(t1 * 1e9 + offset - ev[2]) < 1e6


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_parts():
    cfg = base.get_arch("hymba-1.5b").SMOKE
    params = api.init_model(jax.random.PRNGKey(0), cfg)
    reg = AdapterRegistry(jax.random.PRNGKey(1), cfg, capacity=2)
    for i in range(2):
        reg.register(f"c{i}",
                     api.init_model(jax.random.PRNGKey(5 + i), cfg)["lora"])
    return cfg, params, reg


def _submit(eng, cfg, n, new_tokens=3, plen=5):
    rng = np.random.default_rng(0)
    for i in range(n):
        eng.submit(Request(f"r{i}", rng.integers(0, cfg.vocab, plen),
                           f"c{i % 2}", max_new_tokens=new_tokens))


def test_engine_spans_in_a_trace(tmp_path, engine_parts):
    cfg, params, reg = engine_parts
    eng = ServingEngine(params, cfg, reg, batch_slots=2, max_len=16)
    _submit(eng, cfg, 1)
    eng.run()  # compile outside the trace
    _submit(eng, cfg, 3)
    events = _traced(tmp_path, eng.run)
    steps = _named(events, "serve.step")
    assert len(steps) == len(_named(events, "clock")) >= 3
    _assert_nested(events, "serve.admit", "serve.step")
    _assert_nested(events, "serve.decode", "serve.step")
    admits = _named(events, "serve.admit")
    assert sorted(a[3]["rid"] for a in admits) == ["r0", "r1", "r2"]
    assert {a[3]["prompt_len"] for a in admits} == {5}
    assert {a[3]["slot"] for a in admits} <= {0, 1}
    assert steps[0][3]["admitted"] == 2 and steps[0][3]["active"] == 0
    assert all(d[3]["rows"] >= 1 for d in _named(events, "serve.decode"))


def test_admission_records_one_pass(tmp_path, engine_parts):
    """A prompt within the attention ring (16 slots at SMOKE) is admitted in
    one forward, a longer one by the token loop: the ``serve.admit`` span
    and the request's record say which."""
    cfg, params, reg = engine_parts
    eng = ServingEngine(params, cfg, reg, batch_slots=2, max_len=64)
    rng = np.random.default_rng(0)
    for rid, plen in (("fits", 16), ("longer", 20)):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab, plen), "c0",
                           max_new_tokens=2))
    events = _traced(tmp_path, eng.run)
    admits = {a[3]["rid"]: a[3] for a in _named(events, "serve.admit")}
    recs = {r.rid: r for r in eng.stats.ring("request")}
    assert admits["fits"]["prompt_len"] == 16
    assert bool(admits["fits"]["one_pass"]) and recs["fits"].one_pass
    assert admits["longer"]["prompt_len"] == 20
    assert not admits["longer"]["one_pass"] and not recs["longer"].one_pass


def test_engine_records_are_bounded(engine_parts):
    cfg, params, reg = engine_parts
    eng = ServingEngine(params, cfg, reg, batch_slots=2, max_len=64)
    eng.stats = obs.Rings(cap=16)
    _submit(eng, cfg, 12, new_tokens=8)
    out = eng.run()
    assert out["n_steps"] > 16
    assert len(eng.stats.ring("step")) == 16
    assert len(eng.stats.ring("request")) == 12
    assert len(out["decode_step_times"]) == 16
    for s in eng.stats.ring("step"):
        assert s.host == pytest.approx(s.total - s.admit - s.decode)
        assert 0 <= s.admitted <= s.queued and s.active <= 2
    assert set(eng.latency) == {f"r{i}" for i in range(12)}


def test_queue_wait_on_a_scripted_queue(engine_parts, monkeypatch):
    """One slot, three requests submitted at once: each waits in the queue
    until the one before it finished."""
    cfg, params, reg = engine_parts
    ticks = iter(range(10_000))
    monkeypatch.setattr(obs, "now", lambda: float(next(ticks)))
    eng = ServingEngine(params, cfg, reg, batch_slots=1, max_len=16)
    _submit(eng, cfg, 3, new_tokens=2)
    submitted = [q.submit_t for q in eng.queue]
    assert submitted == [0.0, 1.0, 2.0]
    eng.run()
    recs = eng.stats.ring("request")
    assert [r.rid for r in recs] == ["r0", "r1", "r2"]
    assert [r.submit for r in recs] == submitted
    admits = obs.spans("serve.admit")[-3:]
    for r, (a0, a1) in zip(recs, admits):
        assert r.queue_wait == r.admit - r.submit
        assert a0 < r.admit < a1  # stamped inside its admission
        assert eng.latency[r.rid] == r.finish - r.submit
    for prev, nxt in zip(recs, recs[1:]):
        assert nxt.admit > prev.finish
    assert recs[0].queue_wait < recs[1].queue_wait < recs[2].queue_wait


def test_run_keeps_its_keys(engine_parts):
    cfg, params, reg = engine_parts
    eng = ServingEngine(params, cfg, reg, batch_slots=2, max_len=16)
    _submit(eng, cfg, 2)
    out = eng.run()
    assert set(out) == {"outputs", "n_steps", "wall_s", "generated_tokens",
                        "tok_s", "latency_p50_s", "latency_p99_s",
                        "decode_step_times"}
    assert len(out["decode_step_times"]) == out["n_steps"] == 2


# ---------------------------------------------------------------------------
# the training flush
# ---------------------------------------------------------------------------


def test_flush_spans_in_a_trace(tmp_path):
    ds = make_har_dataset("pamap2", windows_per_subject=60, seed=0)
    mm = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                       cnn_ch=(8, 16))
    task, tr0 = MMTask.create(mm, jax.random.PRNGKey(0))
    fleet = scale_fleet(make_fleet(3, 3, 2, M=4), 32,
                        np.random.default_rng(3))
    fed = AsyncFedConfig(rounds=1, local_epochs=1, steps_per_epoch=1,
                         batch_size=4, eval_every=0, seed=0,
                         grad_mode="cohort", uplink_codec="int8")
    run = VectorizedAsyncFedRun.create(task, tr0, async_relief(buffer_size=8),
                                       fleet, fed)
    run.run(ds, total_updates=8)  # compile outside the trace
    events = _traced(tmp_path, lambda: run.run(ds, total_updates=16))
    assert len(_named(events, "fed.run")) == 1
    flushes = _named(events, "fed.flush")
    assert len(flushes) == len(_named(events, "clock")) == 2
    assert [f[3]["K"] for f in flushes] == [8, 8]
    assert [f[3]["round"] for f in flushes] == [1, 2]
    for child, parent in [("fed.flush", "fed.run"), ("fed.dispatch", "fed.run"),
                          ("fed.assemble", "fed.flush"),
                          ("fed.local_update", "fed.flush"),
                          ("fed.uplink", "fed.flush"),
                          ("fed.aggregate", "fed.flush"),
                          ("fed.ingest", "fed.aggregate"),
                          ("fed.stats", "fed.aggregate")]:
        _assert_nested(events, child, parent)
    assert all(d[3]["B"] >= 1 for d in _named(events, "fed.dispatch"))


def test_server_step_compiles_once_and_tags_the_flush(tmp_path):
    """The flush's server half is one compiled program: the first flush
    compiles it, later flushes at the same K compile nothing inside
    ``fed.aggregate``, which carries ``program=1``; the system-only flush
    (no deltas) stays eager, carries ``program=0`` and still advances the
    server version."""
    ds = make_har_dataset("pamap2", windows_per_subject=60, seed=0)
    mm = mm_config_for("pamap2", backbone="cnn", d_feat=8, d_fused=32,
                       cnn_ch=(8, 16))
    task, tr0 = MMTask.create(mm, jax.random.PRNGKey(0))
    fleet = scale_fleet(make_fleet(3, 3, 2, M=4), 32,
                        np.random.default_rng(3))
    kw = dict(rounds=1, local_epochs=1, steps_per_epoch=1, batch_size=4,
              eval_every=0, seed=0, uplink_codec="int8")
    run = VectorizedAsyncFedRun.create(
        task, tr0, async_relief(buffer_size=8), fleet,
        AsyncFedConfig(grad_mode="cohort", **kw))
    sysrun = VectorizedAsyncFedRun.create(
        task, tr0, async_relief(buffer_size=8), fleet,
        AsyncFedConfig(grad_mode="none", **kw))
    server = {"fed.aggregate", "fed.ingest", "fed.stats"}

    def server_compiles(t0):
        return [c for c in obs.compiles() if c[0] > t0 and c[1] in server]

    t0 = obs.now()
    run.run(ds, total_updates=8)
    assert server_compiles(t0)  # the program, compiled on the first flush
    t1 = obs.now()
    events = _traced(tmp_path, lambda: (run.run(ds, total_updates=16),
                                        sysrun.run(None, total_updates=8)))
    assert server_compiles(t1) == []
    assert run.state.round == 3 and sysrun.state.round == 1
    assert [e[3]["program"] for e in _named(events, "fed.aggregate")] == [
        1, 1, 0]
