"""The benchmark harness: one cell, one run, one result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json    sizes and settings, with "kind"
    bench/traffic/<traffic>.json   parameters for bench/traffic.py
    bench/kinds/<kind>.py          builds and drives the system under test
    bench/metrics/<metric>.py      ``read(ctx)`` -> a per-layer number
    bench/limits/<workload>.json   the limit of each number ``correct``
                                   compares

A run: set-up (build from the seed, warm up every shape the traffic uses,
the first steps whose outputs the reference follows), then the measured
window of ``--seconds``, then ``memory_peak_bytes``, then the program's
state is freed and the plain reference decides ``correct``. With
``--trace 1`` the profiler records the window (its first
``trace_seconds``) and the per-layer readers reduce that trace.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    """A progress line on standard error: seconds since the process began."""
    print(f"[bench {time.perf_counter() - T0:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


T0 = time.perf_counter()


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CellSpec:
    workload: dict
    cfg: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict

    @property
    def name(self) -> str:
        return self.workload["name"]


def _load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell_spec(workload: str, root: Path = ROOT) -> CellSpec:
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = _load_json(root / conf["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    lim_path = root / "bench" / "limits" / f"{workload}.json"
    limits = _load_json(lim_path) if lim_path.exists() else {}
    return CellSpec(w, cfg, traffic,
                    [m for m in spec["end_to_end"] if _applies(m, workload)],
                    [m for m in spec["per_layer"] if _applies(m, workload)],
                    limits)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str):
    return load_module(BENCH / "kinds" / f"{kind}.py")


def load_reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py")


# ---------------------------------------------------------------------------
# JAX set-up
# ---------------------------------------------------------------------------


COMPILES = {"cache_hits": 0, "compiled": 0, "compile_s": 0.0,
            "retrieve_s": 0.0}


def _count_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        COMPILES["cache_hits"] += 1


def _count_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES["compiled"] += 1
        COMPILES["compile_s"] += secs
    elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
        COMPILES["retrieve_s"] += secs


def count_compiles() -> None:
    """Count programs compiled and found in the persistent cache (for the
    progress lines: nothing should compile inside the window)."""
    from jax import monitoring

    if not COMPILES.get("listening"):
        monitoring.register_event_listener(_count_event)
        monitoring.register_event_duration_secs_listener(_count_duration)
        COMPILES["listening"] = True


def compile_note() -> str:
    c = COMPILES
    return (f"{c['compiled']} programs compiled ({c['compile_s']:.1f} s), "
            f"{c['cache_hits']} from the cache ({c['retrieve_s']:.1f} s)")


class GcPauses:
    """Times of the interpreter's garbage collections while it is open
    (for the progress lines: a long one stalls the loop)."""

    def __init__(self):
        self.started: float | None = None
        self.pauses: list[float] = []
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.started = time.perf_counter()
        elif self.started is not None:
            self.pauses.append(time.perf_counter() - self.started)

    def close(self) -> None:
        gc.callbacks.remove(self._event)

    def note(self) -> str:
        longest = max(self.pauses, default=0.0)
        return (f"{len(self.pauses)} garbage collections, longest "
                f"{1e3 * longest:.1f} ms")


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names another; every
    program is cached, however small or quick to compile. Call before
    JAX is imported: the program under test reads the same variable."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips if require_tpu else len(devs)}


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class Tracer:
    """Profiler session over the first ``seconds`` of the window.

    ``poll()`` is called by the window loop between units of work; it ends
    the trace once its time is up. A serving loop holds its clock still
    while the trace is written out, so that requests keep flowing and
    finish as in an untraced run. Host times (``perf_counter``) of the
    traced window are kept so that the readers can select the window's own
    counters."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.seconds = seconds
        self.active = False
        self.dir: str | None = None
        self.t0 = self.t1 = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True
        self.t0 = time.perf_counter()

    def poll(self) -> float:
        """Stop the trace once its time is up -> the seconds the stop took
        (writing the trace out blocks the loop for tens of seconds)."""
        if self.active and time.perf_counter() - self.t0 >= self.seconds:
            a = time.perf_counter()
            self.stop()
            return time.perf_counter() - a
        return 0.0

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False

    def load(self):
        """The session's trace; its window runs from the first to the last
        event, on the trace's clock (about ``t1 - t0`` on the host's)."""
        from bench import trace as T

        return T.load(T.find_xplane(self.dir))

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Options:
    """Switches for tests and for reading limits; a benchmark run uses the
    defaults."""
    require_tpu: bool = True
    compile_cache: bool = True
    fault: str | None = None  # plant a fault in the timed path
    control: bool = False  # put the lower-precision reference in its place
    cfg_override: dict | None = None
    traffic_override: dict | None = None


def _num(x: float) -> float:
    return float(x) if x is not None and math.isfinite(float(x)) else x


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, opts: Options | None = None) -> dict:
    """Set-up, window, correctness -> the result record (not printed)."""
    opts = opts or Options()
    cs = cell_spec(workload)
    if opts.cfg_override:
        cs.cfg = cs.cfg | opts.cfg_override
    if opts.traffic_override:
        cs.traffic = cs.traffic | opts.traffic_override
    chips = int(cs.workload["chips"])
    if opts.compile_cache:
        configure_compile_cache()
    device = device_info(chips, opts.require_tpu)
    kind = load_kind(cs.cfg["kind"])
    cell = kind.Cell(cs.cfg, cs.traffic, seed, opts)

    count_compiles()
    log(f"{workload} seed {seed}: set-up")
    cell.setup()
    # what set-up built lives for the whole run: keep the collector from
    # walking it again inside the window, as a long-running server would
    gc.collect()
    gc.freeze()
    log(f"window; so far {compile_note()}")
    before = dict(COMPILES)
    tracer = Tracer(trace, float(cs.traffic.get("trace_seconds", seconds)))
    pauses = GcPauses()
    t_call = time.perf_counter()
    win = cell.window(seconds, tracer)
    tracer.stop()
    pauses.close()
    gc.unfreeze()  # so that release() can collect the program's state
    log(f"window closed; {COMPILES['compiled'] - before['compiled']} programs "
        f"compiled inside it; {pauses.note()}")
    # set-up ends where the window starts (a serving loop warms up first)
    setup_s = win.get("t_window_start", t_call) - t_start
    mem = memory_peak_bytes(chips)
    if mem is not None:
        device["memory_peak_bytes"] = mem

    record: dict[str, Any] = {"attempted": int(win["attempted"]),
                              "failed": int(win["failed"])}
    if trace:
        from bench import trace as T

        log("reading the trace")
        tr = tracer.load()
        busy = T.busy_ns(tr)
        device["busy_s"] = busy / 1e9
        device["window_s"] = tr.window_ns / 1e9
        ctx = ReaderContext(tr, cell, cs, device, tracer.t0, tracer.t1)
        metrics = {}
        for m in cs.per_layer:
            v = load_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": _num(v), "unit": m["unit"]}
        record["metrics"] = metrics
        record["breakdown"] = {"device_ops": T.top_programs(tr),
                               "idle_gaps": T.idle_by_span(tr)}
        tracer.cleanup()
    else:
        vals = dict(win["metrics"]) | {"setup_s": setup_s}
        record["metrics"] = {m["name"]: {"value": _num(vals[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cs.end_to_end}

    cell.release()
    log("reference")
    checks = cell.check()
    log("done")
    out = {}
    for name, value in checks:
        limit = cs.limits.get(name)
        out[name] = {"value": _num(value), "limit": limit}
    record["correct"] = bool(out) and all(
        c["limit"] is not None and c["value"] is not None
        and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in out.values())
    record["device"] = device
    record["checks"] = out
    return record


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer reader sees: the reduced trace of the window, the
    cell object (its counters), the cell's files, the device and the peak
    table, and the host-clock bounds of the traced window."""
    trace: Any
    cell: Any
    spec: CellSpec
    device: dict
    t0: float
    t1: float

    @property
    def peaks(self) -> dict:
        from bench import flops

        return flops.peaks(self.device["kind"])

    @property
    def cfg(self) -> dict:
        return self.spec.cfg

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1


def emit(record: dict) -> None:
    """Checks as the last lines of standard error, then the result as the
    last line of standard output with ``checks`` as its last key."""
    for name, c in record["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    line = {k: record[k] for k in keys}
    if "breakdown" in record:
        line["breakdown"] = record["breakdown"]
    line["checks"] = record["checks"]
    print(json.dumps(line), flush=True)
