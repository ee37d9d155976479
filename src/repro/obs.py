"""Spans and counters inside the program, on one clock.

* ``span(name, **meta)`` marks one layer boundary of a hot path: a
  ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (what a profiler
  trace shows, with ``meta`` as the event's stats), whose host interval is
  also kept in a bounded ring per name (``spans(name)``), so that code in
  the same process can read the program's spans without a trace.
* ``Rings`` holds one component's bounded rings of per-request and
  per-step records.
* ``now()`` stamps everything: ``time.perf_counter``, in seconds.
* ``clock()`` emits the zero-length marker ``repro.clock`` with its stamp as
  ``perf_ns``: in a trace, the marker's start minus ``perf_ns`` is the
  offset that maps any stamp onto the trace's clock.
* Every backend compile is kept (``compiles()``) with its stamp, its
  seconds and the innermost span open in the compiling thread (``"-"``
  outside any).

Nothing here waits on the device: a span's time is the host's time in it,
and the device's time comes from a trace.
"""
from __future__ import annotations

import collections
import functools
import threading
import time

from jax import monitoring
from jax.profiler import TraceAnnotation

PREFIX = "repro."
CAP = 4096  # records a ring keeps (the newest)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

now = time.perf_counter


class Rings:
    """Bounded rings of records: ``r.record(ring, item)``, ``r.ring(ring)``
    (the newest ``cap`` items, oldest first)."""

    def __init__(self, cap: int = CAP):
        self.cap = cap
        self.rings: dict[str, collections.deque] = {}

    def record(self, ring: str, item) -> None:
        r = self.rings.get(ring)
        if r is None:
            r = self.rings[ring] = collections.deque(maxlen=self.cap)
        r.append(item)

    def ring(self, ring: str) -> list:
        return list(self.rings.get(ring, ()))


SPANS = Rings()  # per span name: (start, end) of its recent intervals
COMPILES = collections.deque(maxlen=CAP)  # (stamp, innermost span, seconds)
_local = threading.local()


def _stack() -> list[str]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def innermost() -> str | None:
    """Name of the innermost span open in this thread."""
    s = _stack()
    return s[-1] if s else None


class span:
    """``with span("fed.flush", K=64) as sp:`` -> ``sp.t0``, ``sp.t1``."""

    __slots__ = ("name", "_ann", "t0", "t1")

    def __init__(self, name: str, **meta):
        self.name = name
        self._ann = TraceAnnotation(PREFIX + name, **meta)
        self.t0 = self.t1 = None

    def __enter__(self) -> span:
        self._ann.__enter__()
        _stack().append(self.name)
        self.t0 = now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = now()
        _stack().pop()
        self._ann.__exit__(*exc)
        SPANS.record(self.name, (self.t0, self.t1))


def traced(name: str):
    """Decorator: each call of the function runs inside ``span(name)``."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return wrapper
    return deco


def spans(name: str) -> list[tuple[float, float]]:
    """The kept (start, end) intervals of spans named ``name``."""
    return SPANS.ring(name)


def clock() -> None:
    with TraceAnnotation(PREFIX + "clock", perf_ns=time.perf_counter_ns()):
        pass


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    COMPILES.append((now(), innermost() or "-", secs))


def compiles() -> list[tuple[float, str, float]]:
    """Recent compiles: (stamp at the end, innermost span, seconds)."""
    return list(COMPILES)


monitoring.register_event_duration_secs_listener(_on_duration)
