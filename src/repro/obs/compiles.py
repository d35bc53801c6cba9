"""Compile accounting: one process-wide ``jax.monitoring`` listener.

JAX reports every backend compile — a load from the persistent compile
cache included — as one time-span event on the thread that compiles.
``subscribe(registry)`` registers the listener (once per process,
idempotently) and adds ``registry`` to the set it feeds:

* ``jax_compiles_total{program}`` — counter, one per compile or load,
  labelled by the jitted function's name;
* ``jax_compile_seconds`` — histogram of their wall seconds.

``GraphSession`` subscribes its registry on open and unsubscribes on
close; a registry shared by several sessions is fed once per compile.
While a tracer is installed the listener also records a ``compile``
span (``program=``, ``seconds=``) on the compiling thread, ending at
the event — so it nests inside the ``dispatch`` span that triggered it
and names the shape that compiled there.
"""
from __future__ import annotations

import itertools
import threading

from repro.obs import trace

__all__ = ["subscribe", "unsubscribe", "COMPILE_EVENT"]

#: The event JAX records around each backend compile (or cache load).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_LOCK = threading.Lock()
_SUBS: dict[int, object] = {}       # subscription token -> registry
_TOKENS = itertools.count(1)
_REGISTERED = False


def _on_compile(event: str, start: float, end: float, *,
                fun_name: str = "?", **_) -> None:
    if event != COMPILE_EVENT:
        return
    seconds = end - start
    with _LOCK:
        regs = {id(r): r for r in _SUBS.values()}
    for r in regs.values():
        r.counter("jax_compiles_total",
                  "backend compiles and compile-cache loads",
                  program=fun_name).inc()
        r.histogram("jax_compile_seconds",
                    "wall seconds per backend compile or cache load"
                    ).observe(seconds)
    tracer = trace.active_tracer()
    if tracer is not None:
        tracer.record("compile", int(start * 1e9), seconds,
                      {"program": fun_name, "seconds": seconds})


def _ensure_listener() -> None:
    global _REGISTERED
    if not _REGISTERED:
        from jax import monitoring
        monitoring.register_event_time_span_listener(_on_compile)
        _REGISTERED = True


def subscribe(registry) -> int:
    """Feed ``registry`` from every compile until ``unsubscribe`` with
    the returned token.  Creates ``jax_compile_seconds`` at once, so a
    snapshot taken before any compile reads a count of 0."""
    registry.histogram("jax_compile_seconds",
                       "wall seconds per backend compile or cache load")
    with _LOCK:
        _ensure_listener()
        token = next(_TOKENS)
        _SUBS[token] = registry
    return token


def unsubscribe(token: int) -> None:
    with _LOCK:
        _SUBS.pop(token, None)
