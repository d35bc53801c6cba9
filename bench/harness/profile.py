"""Reduction of a profiler trace to device busy time, idle share, device
time per program family and the longest idle gaps.

A trace is reduced from three lists of ``(name, start_ns, dur_ns)``:

* ``ops``: every operation that ran on the device (busy time);
* ``modules``: every program execution on the device, named by its XLA
  module (``jit_<function>``), which attributes busy time to programs;
* ``host``: host intervals that name what the host was doing — the
  benchmark's ``jax.profiler.TraceAnnotation``s (``bench.submit``) and
  the program's own spans, mapped onto the profiler's clock.

``from_xplane`` reads those lists from a ``.xplane.pb`` file; everything
else works on the lists alone, so a test can feed it a synthetic trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

# XLA module names carry a numeric suffix per compiled instance.
_SUFFIX = re.compile(r"(\.\d+|\(\d+\))+$")


def module_name(name: str) -> str:
    """``jit_batch_evolve(12)`` -> ``jit_batch_evolve``."""
    return _SUFFIX.sub("", name)


@dataclasses.dataclass
class Trace:
    ops: list            # (name, start_ns, dur_ns[, device]) of device ops
    modules: list        # (name, start_ns, dur_ns) of device programs
    host: list           # (name, start_ns, dur_ns) of host intervals
    start_ns: int        # the profiled window
    end_ns: int
    devices: int = 1


def merge(intervals) -> list[tuple[int, int]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Union:
    """Disjoint intervals with prefix sums: covered length of any range."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + e - s)

    def covered(self, lo: int, hi: int) -> int:
        if hi <= lo or not self.starts:
            return 0
        i = bisect.bisect_right(self.ends, lo)       # first ending after lo
        j = bisect.bisect_left(self.starts, hi)      # first starting >= hi
        if i >= j:
            return 0
        total = self.cum[j] - self.cum[i]
        total -= max(0, lo - self.starts[i])
        total -= max(0, self.ends[j - 1] - hi)
        return total


def _clip(events, lo, hi):
    for name, s, d, *_ in events:
        e = s + d
        if e > lo and s < hi:
            yield name, max(s, lo), min(e, hi)


def reduce(trace: Trace, families: dict[str, str] | None = None,
           top: int = 10) -> dict:
    """Busy seconds, window seconds, idle share, device seconds per
    module and per family (``families`` maps a family to a regular
    expression over module names; a module goes to the first family it
    matches), and the ``top`` longest idle gaps named by the host
    interval that covers most of each gap."""
    lo, hi = trace.start_ns, trace.end_ns
    busy = merge((s, e) for _, s, e in _clip(trace.ops, lo, hi))
    union = _Union(busy)
    # busy time per device, averaged over the devices
    per_dev: dict = {}
    for op in trace.ops:
        per_dev.setdefault(op[3] if len(op) > 3 else 0, []).append(op)
    busy_ns = sum(sum(e - s for s, e in merge(
        (s, e) for _, s, e in _clip(evs, lo, hi)))
        for evs in per_dev.values()) / max(trace.devices, 1)
    window_ns = max(hi - lo, 1)
    per_module: dict[str, float] = {}
    for name, s, e in _clip(trace.modules, lo, hi):
        key = module_name(name)
        per_module[key] = per_module.get(key, 0.0) + union.covered(s, e)
    per_family: dict[str, float] = {}
    compiled = [(f, re.compile(p)) for f, p in (families or {}).items()]
    for key, ns in per_module.items():
        fam = next((f for f, p in compiled if p.search(key)), "other")
        per_family[fam] = per_family.get(fam, 0.0) + ns
    gaps = []
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    for k in range(0, len(edges), 2):
        g0, g1 = edges[k], edges[k + 1]
        if g1 > g0:
            gaps.append((g1 - g0, g0, g1))
    gaps.sort(reverse=True)
    host = list(_clip(trace.host, lo, hi))
    host = [(n, s, e - s) for n, s, e in host]
    named = []
    for dur, g0, g1 in gaps[:top]:
        best, rank = "idle", (0, 0)
        for name, s, d in host:
            cov = min(s + d, g1) - max(s, g0)
            # the innermost (shortest) interval wins a tie of coverage
            if cov > 0 and (cov, -d) > rank:
                best, rank = name, (cov, -d)
        named.append([best, dur / 1e9])
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "modules_s": {k: v / 1e9 for k, v in per_module.items()},
        "families_s": {k: v / 1e9 for k, v in per_family.items()},
        "top_modules": sorted(([k, v / 1e9] for k, v in per_module.items()),
                              key=lambda x: -x[1])[:top],
        "idle_gaps": named,
    }


# ---------------------------------------------------------------------------
# Reading a profiler trace
# ---------------------------------------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def from_xplane(path: str, window: str = "bench.profile",
                host_names: tuple[str, ...] = ("bench.",)) -> Trace:
    """Read a ``.xplane.pb``: device operations from the ``XLA Ops``
    line of each device plane, programs from its ``XLA Modules`` line,
    host intervals whose name starts with one of ``host_names``, and the
    profiled window from the host interval named ``window``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host, devices = [], [], [], 0
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.duration_ns, plane.name)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(host_names)]
    spans = [(s, d) for n, s, d in host if n == window]
    if not spans:
        raise ValueError(f"no {window!r} interval in {path}")
    start, dur = max(spans, key=lambda x: x[1])
    host = [h for h in host if h[0] != window]
    return Trace(ops=ops, modules=modules, host=host, start_ns=int(start),
                 end_ns=int(start + dur), devices=max(devices, 1))

