"""One run of one cell: set-up, warm-up, the measured window, the check.

The entry the window drives is ``repro.api.GraphSession``: its
frontend scheduler thread runs with the session's defaults (micro-
batches of up to 64, no added delay, ``stale="block"``), requests go in
through ``frontend.submit`` / ``submit_sweep``.  Every metric,
end-to-end or per-layer, is read by ``metrics/<name>.py`` from the
``Context`` this module fills.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import threading
import time

import numpy as np

from harness import datagen, drive, spec, traffic
from harness.compile_stats import CompileStats

now = time.perf_counter
# How long answers to the window's requests are awaited after it closes: an
# answer that comes late is late, not wrong.  Two minutes, because one
# program compiled inside the window has taken over 100 s on a v5e.
DRAIN_S = 120.0


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What the metric readers read."""
    cell: spec.Cell
    seconds: float
    setup_s: float
    records: list                 # drive.Record of requests sent in window
    window: tuple[float, float]   # host clock
    reg0: dict | None = None      # registry snapshots around the window
    reg1: dict | None = None
    compiles: dict | None = None  # CompileStats over the window
    spans: list | None = None     # program spans in the window (traced)
    profile: dict | None = None   # reduced device trace (traced)
    profiled: tuple[float, float] | None = None

    def answered(self, lo=None, hi=None) -> list:
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return [r for r in self.records if r.done is not None
                and r.error is None and lo <= r.done < hi]

    def histogram_delta(self, name: str) -> tuple[float, int]:
        """(sum, count) an unlabelled registry histogram gained over the
        window."""
        def get(snap):
            h = (snap or {}).get("histograms", {}).get(name, {}).get("")
            return (h["sum"], h["count"]) if h else (0.0, 0)
        s0, c0 = get(self.reg0)
        s1, c1 = get(self.reg1)
        return s1 - s0, c1 - c0


# ---------------------------------------------------------------------------
# Data and session
# ---------------------------------------------------------------------------


def make_data(cell: spec.Cell, seed: int) -> dict:
    """The configuration's history from the seed: its columns (for the
    reference) and its ops (for the session)."""
    cols = datagen.generate(datagen.Model.from_config(cell.config["data"]),
                            seed)
    from repro.api import Op
    return {"cols": cols, "t_base": int(cols[3].max()),
            "base": [Op(*r) for r in cols.T.tolist()]}


def open_session(cell: spec.Cell, t_base: int):
    """An in-memory session as the configuration states it, with the
    frontend's documented defaults."""
    from repro.api import GraphSession
    from repro.obs.metrics import MetricsRegistry
    s = dict(cell.config["session"])
    if s.pop("durable", False):
        raise ValueError("the harness drives in-memory sessions only")
    policy = s.pop("policy", None)
    if policy is not None:
        from repro.serving.policy import PeriodicMaterializationPolicy
        s["policy"] = PeriodicMaterializationPolicy(
            period=max(1, t_base // policy["period_div"]),
            budget_bytes=policy["budget_bytes"])
    return GraphSession.open(None, metrics=MetricsRegistry(), **s)


def load(cell: spec.Cell, session, base: list, t_base: int) -> None:
    """Ingest the base history in ``load.flushes`` equal spans of time,
    flushing after each: the epochs (and sealed segments) a store that
    had been serving this history would hold."""
    k = int(cell.config.get("load", {}).get("flushes", 1))
    span = max(1, t_base // k)
    i = 0
    for j in range(1, k + 1):
        hi = t_base if j == k else j * span
        n = i
        while n < len(base) and base[n].t <= hi:
            n += 1
        session.ingest(base[i:n])
        session.flush()
        i = n


def warm_shapes(cell: spec.Cell, session, sampler, seed: int) -> int:
    """For every request template, each of its aggregates, and every
    size in the mix's ``warmup.batch_sizes``, ``rounds`` batches of that
    many requests of that template and aggregate, sent together through
    the (not yet started) frontend: each group shape the window's
    micro-batches can form (the aggregate is part of a group's key).
    A batch the program fails is reported and the warm-up goes on; the
    window's check counts any request that fails the same way."""
    w = cell.mix.get("warmup", {})
    rng = np.random.default_rng([seed, 4])
    fe, n = session.frontend, 0
    for e in (dict(t, aggs=[a]) for t in sampler.entries
              for a in t.get("aggs", [""])):
        for b in w.get("batch_sizes", []):
            for _ in range(int(w.get("rounds", 1))):
                futs = [drive.submit(fe, sampler.one(rng, e))
                        for _ in range(b)]
                fe.flush()
                exc = next((f.exception() for f in futs
                            if f.exception() is not None), None)
                if exc is not None:
                    log(f"warm-up batch failed: {b} x {e['kind']} "
                        f"{e['scope']} {e['measure']}: "
                        f"{type(exc).__name__}: {str(exc)[:300]}")
                n += b
    return n


# ---------------------------------------------------------------------------
# Driving a phase
# ---------------------------------------------------------------------------


def _phase(cell, session, sampler, seed: int, phase: int, t0: float,
           seconds: float) -> drive.ClosedLoop:
    """Start the mix's clients until t0 + seconds; returns the load
    generator (``records``; ``join`` waits for the clients).  The order
    of templates depends on ``phase`` alone, the parameters on ``seed``
    too."""
    arrival = cell.mix["arrival"]
    if arrival["kind"] != "closed":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    per = int(arrival.get("stream_per_client", 4096))
    streams = [sampler.stream(np.random.default_rng([seed, phase, c]),
                              per, np.random.default_rng([phase, c]))
               for c in range(arrival["clients"])]
    d = drive.ClosedLoop(session.frontend, streams, t0 + seconds)
    d.start()
    return d


def _await(records, deadline: float) -> None:
    while now() < deadline and any(r.done is None and r.sent is not None
                                   for r in records):
        time.sleep(0.01)


class _Profiler(threading.Thread):
    """Profiles ``seconds`` of the device from ``start`` (host clock)."""

    def __init__(self, log_dir: str, start: float, seconds: float):
        super().__init__(name="bench-profiler", daemon=True)
        self.log_dir, self.start_at, self.seconds = log_dir, start, seconds
        self.bounds: tuple[float, float] | None = None
        self.error: str | None = None

    def run(self):
        import jax
        time.sleep(max(0.0, self.start_at - now()))
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.profile"):
                t0 = now()
                time.sleep(self.seconds)
                t1 = now()
            jax.profiler.stop_trace()
            self.bounds = (t0, t1)
        except Exception as exc:  # noqa: BLE001 — the run reports it
            self.error = f"{type(exc).__name__}: {exc}"


def _tracer_origin(tracer) -> float:
    """The host-clock time of the program tracer's zero."""
    from repro.obs.trace import trace_span
    with trace_span("bench.clock"):
        t = now()
    ev = [e for e in tracer.events() if e["name"] == "bench.clock"][-1]
    return t - ev["ts"] / 1e6


def _reduce_profile(cell, prof, spans, origin) -> dict:
    import glob
    from harness import profile
    files = glob.glob(os.path.join(prof.log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    tr = profile.from_xplane(max(files, key=os.path.getmtime))
    # program spans onto the profiler's clock, by the window annotation
    shift = tr.start_ns - prof.bounds[0] * 1e9
    tr.host += [(e["name"], int((origin + e["ts"] / 1e6) * 1e9 + shift),
                 int(e["dur"] * 1e3)) for e in spans]
    return profile.reduce(tr, cell.mix.get("trace", {}).get("families"))


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        work_dir: str, started: float,
        stats: CompileStats | None = None) -> dict:
    """One run; returns the result line's object.  ``started`` is the
    process start on ``time.time()``'s clock."""
    import jax
    mix = cell.mix
    stats = stats or CompileStats().install()
    t = now()
    data = make_data(cell, seed)
    t_base = data["t_base"]
    log(f"data: {datagen.counts(data['cols'])} t_base={t_base} "
        f"({now() - t:.2f} s)")
    t = now()
    session = open_session(cell, t_base)
    load(cell, session, data["base"], t_base)
    log(f"load: {len(data['base'])} ops, watermark {session.watermark} "
        f"({now() - t:.2f} s)")
    sampler = traffic.Sampler(mix, 1, t_base,
                              cell.config["data"]["n_nodes"])
    t = now()
    c0 = stats.snapshot()
    n = warm_shapes(cell, session, sampler, seed)
    log(f"warm-up shapes: {n} requests, {now() - t:.2f} s, "
        f"{CompileStats.since(c0, stats.snapshot())}")
    # the base history's op objects are no longer needed; what stays is
    # moved out of the collector's reach so that no full collection
    # pauses the window
    data["base"] = None
    gc.collect()
    gc.freeze()
    session.frontend.start()
    # warm-up traffic, then the window; the warm-up clients finish
    # first, so that the window's clients are alone in the queue
    warm_s = float(mix.get("warmup", {}).get("seconds", 0))
    c0 = stats.snapshot()
    warm = None
    if warm_s > 0:
        warm = _phase(cell, session, sampler, seed, 1, now(), warm_s)
        warm.join(warm_s + DRAIN_S)
        _await(warm.records, now() + DRAIN_S)
    c1 = stats.snapshot()
    log(f"warm-up: {len(warm.records) if warm else 0} requests, "
        f"{sum(r.done is None for r in warm.records) if warm else 0} "
        f"still open at the window, {CompileStats.since(c0, c1)} "
        f"{stats.named(c0, c1)}")

    tracer = origin = prof = None
    if trace:
        tracer = session.enable_tracing(capacity=1 << 20)
        origin = _tracer_origin(tracer)
    reg0 = session.metrics()
    w0 = now()
    setup_s = time.time() - started
    if trace:
        p = float(mix.get("trace", {}).get("profile_seconds", seconds / 4))
        p = min(p, seconds)
        prof = _Profiler(os.path.join(work_dir, "profile"),
                         w0 + (seconds - p) / 2, p)
        prof.start()
    window = _phase(cell, session, sampler, seed, 2, w0, seconds)
    w1 = w0 + seconds
    time.sleep(max(0.0, w1 - now()))
    window.join(DRAIN_S)
    _await(window.records, w1 + DRAIN_S)
    c2 = stats.snapshot()
    reg1 = session.metrics()
    if prof is not None:
        prof.join()
    spans = None
    if tracer is not None:
        session.disable_tracing()
        spans = [e for e in tracer.events()
                 if w0 <= origin + e["ts"] / 1e6 < w1]
    records = window.records
    ctx = Context(cell=cell, seconds=seconds, setup_s=setup_s,
                  records=records, window=(w0, w1), reg0=reg0, reg1=reg1,
                  compiles=CompileStats.since(c1, c2), spans=spans)
    if prof is not None:
        if prof.error or prof.bounds is None:
            log(f"profile failed: {prof.error}")
        else:
            ctx.profiled = prof.bounds
            ctx.profile = _reduce_profile(cell, prof, spans or [], origin)
    dev = jax.devices()
    mem = dev[0].memory_stats() or {}
    log(f"device memory, arrays only: bytes_in_use={mem.get('bytes_in_use')}"
        f" peak_bytes_in_use={mem.get('peak_bytes_in_use')}"
        f" bytes_limit={mem.get('bytes_limit')}")
    log(f"window: {len(records)} requests sent, "
        f"{sum(r.done is not None for r in records)} answered, "
        f"compiles {ctx.compiles} {stats.named(c1, c2)}")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(cell, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # --- the check: after the window, with the program's state freed ---
    t = now()
    session.close()
    del session
    gc.collect()
    checks = check(cell, data, records)
    log(f"check: {now() - t:.2f} s")
    failed = sum(r.done is None or r.error is not None for r in records)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    d = dev[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": int(cell.workload["chips"]),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and ctx.profile is not None:
        device["busy_s"] = ctx.profile["busy_s"]
        device["window_s"] = ctx.profile["window_s"]
        result["breakdown"] = {"device_ops": ctx.profile["top_modules"],
                               "idle_gaps": ctx.profile["idle_gaps"]}
    result["checks"] = checks
    return result


# ---------------------------------------------------------------------------
# The check against the plain reference
# ---------------------------------------------------------------------------


def check(cell, data, records) -> dict:
    """Every answered request compared with the reference; every number
    compared, each with its limit."""
    ref_mod = spec.reference(cell)
    ref = ref_mod.Reference(data["cols"], cell.config["session"]["n_cap"])
    answered = [r for r in records if r.done is not None and r.error is None]
    for r in records:
        if r.error is not None:
            log(f"failed request: {r.req} -> {r.error}")
            break
    want = ref.answers([r.req for r in answered])
    wrong = [(r, w) for r, w in zip(answered, want)
             if not ref_mod.same(r.value, w)]
    for r, w in wrong[:10]:
        log(f"MISMATCH {r.req} -> {np.asarray(r.value).tolist()} "
            f"expected {np.asarray(w).tolist()}")
    log(f"compared {len(answered)} answered requests ({len(records)} sent)")
    return {
        "wrong_answers": {"value": len(wrong), "limit": 0},
        "unanswered": {"value": len(records) - len(answered), "limit": 0},
    }
