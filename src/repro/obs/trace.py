"""Structured tracing: bounded span ring buffer, Chrome trace export.

``trace_span(name, **attrs)`` is the only API instrumented code uses.
Its cost contract is the whole design:

* **No tracer installed** (the default): ``trace_span`` returns one
  shared no-op singleton — a module-global ``None`` check plus a
  constant return, no allocation, no clock read.  Tracing that is off
  costs a dict lookup per span site, nothing more
  (tests/test_obs.py pins the singleton identity).  Call sites that
  compute an attribute only for tracing guard it with
  ``span is not NULL_SPAN``.
* **Tracer installed**: spans record (name, start, duration, thread,
  attrs) into a bounded ``deque`` ring — old events fall off the back,
  a long-running session never grows without bound.  Each span also
  opens a ``jax.profiler.TraceAnnotation`` of its own name, so a
  ``jax.profiler`` trace taken meanwhile shows the program's spans
  beside the device ops (a bare annotation took 0.5 µs with no
  profiler running, on the host of a TPU v5e machine).

A span's start (``ts``) is stamped on the profiler's clock
(``clock.wall_ns``: the wall clock, which the JAX profiler stamps host
events with), its duration on ``clock.now``.  So the ring, the Chrome
dump and a profile taken at the same time share one time axis.

Export is the Chrome ``trace_event`` JSON format (complete ``"X"``
events carrying ``ts``/``dur`` in microseconds): load the dump in
``chrome://tracing`` / Perfetto and one query renders as a nested
timeline of plan → anchor-select → window-delta materialize → device
dispatch (→ compile, when one runs) → fetch; one epoch swap as drain →
WAL append/fsync → seal → checkpoint → engine flip → publish.  Nesting
needs no explicit parent ids — same-thread events nest by time
containment, which the with-statement discipline guarantees.

One process-wide tracer slot (not per-session): spans fire on frontend
scheduler threads, swap threads and replica sync loops that have no
session handle, and Chrome's timeline is per (pid, tid) anyway.
``GraphSession.enable_tracing`` installs, ``dump_trace`` exports.
"""
from __future__ import annotations

import json
import os
import threading
from collections import deque

from repro.obs import clock

__all__ = ["Tracer", "trace_span", "install_tracer", "uninstall_tracer",
           "active_tracer", "NULL_SPAN"]

_INSTALLED: "Tracer | None" = None
# jax.profiler.TraceAnnotation, resolved when a tracer is first
# installed (importing jax is not this module's business otherwise)
_ANNOTATION = None


class _NullSpan:
    """The disabled-tracing span: a shared, stateless no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "_t0", "_ts", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._ann = _ANNOTATION(self.name)
        self._ann.__enter__()
        self._ts = clock.wall_ns()
        self._t0 = clock.now()
        return self

    def set(self, **attrs):
        """Attach attributes discovered mid-span (group counts, cache
        hits, ...)."""
        self.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = clock.now()
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self._tracer.record(self.name, self._ts, t1 - self._t0,
                            self.attrs)
        return False


def trace_span(name: str, /, **attrs):
    """A context manager timing one named phase.  Free when no tracer
    is installed (returns the shared ``NULL_SPAN``)."""
    t = _INSTALLED
    if t is None:
        return NULL_SPAN
    return _Span(t, name, attrs)


def install_tracer(tracer: "Tracer") -> "Tracer":
    """Make ``tracer`` the process-wide span sink (replacing any
    previous one)."""
    global _INSTALLED, _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    _INSTALLED = tracer
    return tracer


def uninstall_tracer(tracer: "Tracer | None" = None) -> None:
    """Remove the active sink.  With ``tracer`` given, only if it IS
    the active one — lets two scopes disable independently without one
    clobbering the other's tracer."""
    global _INSTALLED
    if tracer is None or _INSTALLED is tracer:
        _INSTALLED = None


def active_tracer() -> "Tracer | None":
    return _INSTALLED


class Tracer:
    """Bounded in-memory span ring with Chrome ``trace_event`` export.

    ``capacity`` bounds memory: each completed span is one small dict;
    when the ring is full the oldest falls off.  ``seq`` increments per
    recorded span so consumers (the slow-query log) can slice "what
    happened since" without copying the ring.  ``ts`` is µs since the
    epoch on the profiler's clock, ``dur`` µs.
    """

    def __init__(self, capacity: int = 16384):
        self.capacity = int(capacity)
        self._events: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.seq = 0

    def record(self, name: str, start_ns: int, dur: float,
               attrs: dict) -> None:
        """Record one finished span of the calling thread: ``start_ns``
        on ``clock.wall_ns``'s clock, ``dur`` in seconds.  For phases
        timed elsewhere (a compile JAX reports when it ends)."""
        ev = {
            "name": name,
            "ph": "X",
            "cat": "repro",
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "ts": start_ns / 1e3,            # µs, Chrome's unit
            "dur": dur * 1e6,
            "args": attrs,
        }
        with self._lock:
            self.seq += 1
            ev["seq"] = self.seq
            self._events.append(ev)

    # ------------------------------------------------------------- reading

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def events_since(self, seq: int) -> list[dict]:
        """Spans recorded after sequence number ``seq`` (oldest may be
        gone if the ring wrapped)."""
        with self._lock:
            return [e for e in self._events if e["seq"] > seq]

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    # ------------------------------------------------------------- export

    def chrome_trace(self) -> dict:
        """The ``chrome://tracing`` / Perfetto JSON object."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.chrome_trace(), fh)
        os.replace(tmp, path)
        return path
