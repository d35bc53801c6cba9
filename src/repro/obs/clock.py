"""The one sanctioned monotonic clock for instrumentation.

Every timing measurement in ``src/repro`` goes through ``now()`` (or,
better, through ``obs.trace.trace_span`` / ``obs.metrics.timed``, which
use it).  ``scripts/ci_lint.py`` rejects bare ``time.perf_counter()``
calls outside this package: scattering raw clock reads is how the
pre-obs codebase grew three incompatible ad-hoc stats surfaces, and
funneling through one symbol keeps all timing swappable (tests can
monkeypatch ``clock.now``) and greppable.

Scheduling deadlines (frontend drain deadlines, backoff sleeps) use the
same clock — they are comparisons against instrumented timestamps, so
mixing clock sources would skew shed/deadline decisions.

``wall_ns`` is for placing, not timing: it stamps where a trace span
starts on the clock the JAX profiler stamps its host events with (the
wall clock, in ns since the epoch; a profile stores them relative to
its ``profile_start_time``), so program spans line up with a
``jax.profiler`` trace without any offset arithmetic.
"""
from __future__ import annotations

import time

#: Monotonic, high-resolution, cheap.  An alias (not a wrapper def) so
#: ``now()`` costs exactly one C call on the ingest hot path.
now = time.perf_counter

#: Wall clock in integer ns: where a span starts, on the profiler's clock.
wall_ns = time.time_ns

__all__ = ["now", "wall_ns"]
