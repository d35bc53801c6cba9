"""Observability layer (repro/obs): registry, tracing, slow queries.

The acceptance contract (ISSUE 9):

* ``MetricsRegistry`` survives concurrent hammering with exact totals
  (counters monotonic, histograms count-consistent, parent aggregation
  lossless across leaf registries);
* tracing that is OFF costs nothing — ``trace_span`` returns one
  shared no-op singleton (identity-pinned here);
* one routed query through ``GraphSession`` → ``QueryRouter`` →
  ``ReadReplica`` produces a Chrome-trace timeline whose plan /
  dispatch spans nest (by time containment) inside the query span, and
  ``session.metrics()`` carries ``wal_fsync_seconds``,
  ``serving_swap_phase_seconds`` and ``router_replica_lag``;
* the slow-query log attributes slow calls to their engine groups;
* ``WorkloadStats`` is bounded (``max_times``) and its activity level
  decays at rollover instead of growing forever.
"""
import json
import threading

import pytest

from repro.obs.metrics import (COUNT_BUCKETS, MetricsRegistry,
                               NullRegistry, timed)
from repro.obs.trace import (NULL_SPAN, Tracer, active_tracer,
                             install_tracer, trace_span,
                             uninstall_tracer)
from repro.obs.slowlog import SlowQueryLog
from repro.serving.policy import WorkloadStats


@pytest.fixture(autouse=True)
def _no_tracer_leaks():
    """Every test starts and ends with the process-wide tracer slot
    empty (a leaked tracer would silently record other tests)."""
    uninstall_tracer()
    yield
    uninstall_tracer()


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5

    g = reg.gauge("g", "a gauge")
    g.set(7)
    g.inc(3)
    g.dec()
    assert g.value == 9
    g.set_max(4)
    assert g.value == 9          # set_max never lowers
    g.set_max(20)
    assert g.value == 20

    h = reg.histogram("h_seconds", "a histogram")
    for v in (1e-4, 2e-4, 3e-4, 1e-1):
        h.observe(v)
    assert h.count == 4
    assert abs(h.sum - 0.1006) < 1e-9
    assert h.min == 1e-4 and h.max == 1e-1
    assert 0 < h.quantile(0.5) < 1e-2


def test_same_series_is_same_child():
    reg = MetricsRegistry()
    assert reg.counter("x_total") is reg.counter("x_total")
    assert (reg.counter("lbl_total", phase="a")
            is not reg.counter("lbl_total", phase="b"))


def test_snapshot_shape_and_labels():
    reg = MetricsRegistry()
    reg.counter("ops_total", "ops", kind="read").inc(3)
    reg.counter("ops_total", "ops", kind="write").inc(1)
    reg.gauge("depth").set(5)
    reg.histogram("lat_seconds").observe(0.25)
    snap = reg.snapshot()
    assert snap["counters"]["ops_total"] == {"kind=read": 3,
                                             "kind=write": 1}
    assert snap["gauges"]["depth"] == {"": 5}
    st = snap["histograms"]["lat_seconds"][""]
    assert st["count"] == 1 and st["sum"] == 0.25
    # bucket list pairs (upper_bound, count) ending at +Inf
    assert st["buckets"][-1][0] == "+Inf"
    assert sum(n for _, n in st["buckets"]) == 1
    assert json.loads(json.dumps(snap)) == snap    # JSON-able


def test_parent_aggregation_is_lossless_and_leaf_exact():
    parent = MetricsRegistry()
    leaf_a = MetricsRegistry(parent=parent)
    leaf_b = MetricsRegistry(parent=parent)
    leaf_a.counter("served_total").inc(10)
    leaf_b.counter("served_total").inc(32)
    assert leaf_a.counter("served_total").value == 10
    assert leaf_b.counter("served_total").value == 32
    assert parent.counter("served_total").value == 42
    leaf_a.histogram("wait_seconds").observe(0.5)
    leaf_b.histogram("wait_seconds").observe(1.5)
    assert parent.histogram("wait_seconds").count == 2
    assert parent.histogram("wait_seconds").sum == 2.0


def test_null_registry_is_a_noop():
    reg = NullRegistry()
    c = reg.counter("anything_total")
    c.inc(1000)
    assert c.value == 0
    reg.histogram("h").observe(1.0)
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_reset_orphans_held_children():
    reg = MetricsRegistry()
    old = reg.counter("n_total")
    old.inc(5)
    reg.reset()
    old.inc(100)                  # keeps working, lands nowhere
    fresh = reg.counter("n_total")
    assert fresh.value == 0
    fresh.inc(2)
    assert reg.snapshot()["counters"]["n_total"][""] == 2


def test_prometheus_exposition_parses():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", route="a").inc(3)
    reg.gauge("up", "1 when serving").set(1)
    h = reg.histogram("lat_seconds", "latency")
    h.observe(1e-3)
    h.observe(2.0)
    text = reg.render_prometheus()
    typed, samples = {}, []
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            typed[name] = kind
        elif line and not line.startswith("#"):
            name_part, _, value = line.rpartition(" ")
            samples.append((name_part, float(value)))
    assert typed == {"req_total": "counter", "up": "gauge",
                     "lat_seconds": "histogram"}
    as_dict = dict(samples)
    assert as_dict['req_total{route="a"}'] == 3.0
    assert as_dict["up"] == 1.0
    assert as_dict["lat_seconds_count"] == 2.0
    assert as_dict["lat_seconds_sum"] == 2.001
    # cumulative bucket counts are monotone and end at the total
    buckets = [v for k, v in samples if k.startswith("lat_seconds_bucket")]
    assert buckets == sorted(buckets) and buckets[-1] == 2.0


# ---------------------------------------------------------------------------
# concurrency: the hammer tests
# ---------------------------------------------------------------------------

def _hammer(fn, n_threads=4, n_iter=5000):
    errs = []

    def run():
        try:
            for i in range(n_iter):
                fn(i)
        except Exception as exc:              # noqa: BLE001
            errs.append(exc)

    ts = [threading.Thread(target=run) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs


def test_concurrent_counter_and_histogram_exact():
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("obs", buckets=COUNT_BUCKETS)
    g = reg.gauge("hiwater")

    def op(i):
        c.inc()
        h.observe(i % 7)
        g.set_max(i)

    _hammer(op, n_threads=4, n_iter=5000)
    assert c.value == 4 * 5000
    assert h.count == 4 * 5000
    assert sum(i % 7 for i in range(5000)) * 4 == h.sum
    assert g.value == 4999


def test_concurrent_leaf_registries_aggregate_exact():
    parent = MetricsRegistry()
    leaves = [MetricsRegistry(parent=parent) for _ in range(4)]
    counters = [leaf.counter("work_total") for leaf in leaves]
    barrier = threading.Barrier(4)

    def run(k):
        barrier.wait()
        for _ in range(3000):
            counters[k].inc()

    ts = [threading.Thread(target=run, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert [c.value for c in counters] == [3000] * 4
    assert parent.counter("work_total").value == 12000


def test_concurrent_label_family_creation():
    """Racing first-touch of the same labeled series must converge on
    one child (no lost family / duplicate children)."""
    reg = MetricsRegistry()

    def op(i):
        reg.counter("lbl_total", shard=str(i % 3)).inc()

    _hammer(op, n_threads=4, n_iter=3000)
    snap = reg.snapshot()["counters"]["lbl_total"]
    assert sum(snap.values()) == 4 * 3000
    assert set(snap) == {"shard=0", "shard=1", "shard=2"}


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_disabled_tracing_returns_the_null_span_singleton():
    assert active_tracer() is None
    assert trace_span("anything") is NULL_SPAN
    assert trace_span("other", a=1) is NULL_SPAN     # no allocation
    with trace_span("still-off") as sp:
        sp.set(x=2)                                  # all no-ops


def test_tracer_records_spans_with_attrs():
    tr = install_tracer(Tracer())
    with trace_span("outer", a=1) as sp:
        sp.set(b=2)
        with trace_span("inner"):
            pass
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    outer = evs[1]
    assert outer["args"] == {"a": 1, "b": 2}
    assert outer["dur"] >= evs[0]["dur"] >= 0


def test_tracer_ring_is_bounded_and_seq_monotonic():
    tr = install_tracer(Tracer(capacity=4))
    for i in range(10):
        with trace_span(f"s{i}"):
            pass
    evs = tr.events()
    assert len(evs) == 4
    assert tr.seq == 10
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]
    assert [e for e in tr.events_since(8)] == evs[-2:]


def test_span_exception_is_annotated():
    tr = install_tracer(Tracer())
    with pytest.raises(ValueError):
        with trace_span("boom"):
            raise ValueError("x")
    assert tr.events()[-1]["args"]["error"] == "ValueError"


def test_chrome_trace_dump(tmp_path):
    tr = install_tracer(Tracer())
    with trace_span("phase", k="v"):
        pass
    path = tr.dump(str(tmp_path / "trace.json"))
    loaded = json.load(open(path))
    assert loaded["displayTimeUnit"] == "ms"
    ev = loaded["traceEvents"][0]
    for key in ("name", "ph", "pid", "tid", "ts", "dur", "args"):
        assert key in ev
    assert ev["ph"] == "X" and ev["name"] == "phase"


def test_uninstall_only_removes_its_own_tracer():
    a = install_tracer(Tracer())
    b = install_tracer(Tracer())
    uninstall_tracer(a)                  # a is not active: no-op
    assert active_tracer() is b
    uninstall_tracer(b)
    assert active_tracer() is None


def test_timed_feeds_histogram_and_span():
    reg = MetricsRegistry()
    h = reg.histogram("op_seconds")
    tr = install_tracer(Tracer())
    with timed(h, "op", kind="t") as tm:
        pass
    assert h.count == 1 and tm.seconds >= 0.0
    ev = tr.events()[-1]
    assert ev["name"] == "op" and ev["args"] == {"kind": "t"}


# ---------------------------------------------------------------------------
# slow-query log + workload stats bounds
# ---------------------------------------------------------------------------

def test_slow_query_log_threshold_and_bound():
    log = SlowQueryLog(threshold_ms=10.0, capacity=3)
    built = []

    def entry():
        built.append(1)
        return {"n_queries": 1}

    assert not log.record(0.001, entry)      # fast: builder never runs
    assert built == []
    for _ in range(5):
        assert log.record(0.5, entry)
    assert len(log.entries()) == 3           # ring bound
    assert log.recorded == 5
    assert all(e["seconds"] == 0.5 for e in log.entries())


def test_workload_stats_bounded_by_max_times():
    ws = WorkloadStats(max_times=64)
    ws.record(range(1000))
    hist = ws.histogram()
    assert len(hist) <= 64
    # total tracks exactly the surviving mass
    assert abs(ws.total - sum(hist.values())) < 1e-9
    # the heaviest times survive pruning
    ws.record([5] * 50)
    ws.record(range(2000, 3000))
    assert 5 in ws.histogram()


def test_workload_stats_activity_decays_at_rollover():
    ws = WorkloadStats()

    class _Q:
        kind, t_k, t_l = "point", 3, None

    ws.record_queries([_Q(), _Q()])
    assert ws.queries_recorded == 2
    ws.rollover(0.5)
    assert ws.queries_recorded == 1.0
    for _ in range(100):
        ws.rollover(0.5)
    assert ws.queries_recorded < 1e-9        # never grows unbounded


# ---------------------------------------------------------------------------
# end-to-end: session metrics, slow queries, routed-query trace
# ---------------------------------------------------------------------------

def _ops(n_cap, units, t0=1):
    from repro.core import ADD_EDGE, ADD_NODE
    ops = [(ADD_NODE, v, v, t0) for v in range(n_cap)]
    t = t0
    for u in range(units):
        t += 1
        ops.append((ADD_EDGE, u % n_cap, (u + 1) % n_cap, t))
    return ops, t


def test_session_slow_query_log_carries_plan_attribution():
    from repro.api import GraphSession
    from repro.core import Query
    reg = MetricsRegistry()
    with GraphSession(n_cap=8, metrics=reg, slow_query_ms=0.0) as sess:
        ops, t = _ops(8, 12)
        sess.ingest(ops)
        sess.flush()
        sess.query(Query(kind="point", scope="node", measure="degree",
                         t_k=t // 2, v=1))
        entries = sess.slow_queries()
        assert entries, "0ms threshold must record every call"
        e = entries[-1]
        assert e["n_queries"] == 1 and e["seconds"] > 0
        (group,) = e["groups"]
        assert group["measure"] == "degree" and group["batch"] == 1
        assert group["plan"] in ("two_phase", "hybrid", "delta_only")
    # counters moved too
    snap = reg.snapshot()["counters"]
    assert sum(snap["engine_slow_queries_total"].values()) >= 1


def test_acceptance_routed_query_trace_and_session_metrics(tmp_path):
    """ISSUE 9 acceptance: one routed query through GraphSession →
    QueryRouter → replica yields a Chrome trace whose plan/dispatch
    spans nest inside the query span, and the shared registry exposes
    wal_fsync_seconds / serving_swap_phase_seconds /
    router_replica_lag."""
    from repro.api import GraphSession
    from repro.core import Query

    reg = MetricsRegistry()
    sess = GraphSession.open(str(tmp_path / "writer"), n_cap=16,
                             metrics=reg)
    try:
        tracer = sess.enable_tracing()
        ops, t_last = _ops(16, 40)
        sess.ingest(ops)
        sess.flush()
        sess.publish_to(str(tmp_path / "pub"))

        replica = GraphSession.open_replica(str(tmp_path / "pub"),
                                            str(tmp_path / "mirror"),
                                            name="r1", metrics=reg)
        router = GraphSession.open_router({"r1": replica}, metrics=reg)
        router.heartbeat()
        qs = [Query(kind="point", scope="node", measure="degree",
                    t_k=t_last // 2, v=v) for v in range(4)]
        out = router.evaluate_many(qs)
        assert len(out) == 4

        trace_path = str(tmp_path / "trace.json")
        sess.dump_trace(trace_path)
        events = json.load(open(trace_path))["traceEvents"]
        by_name = {}
        for ev in events:
            by_name.setdefault(ev["name"], []).append(ev)
        # the routed call and the replica-side engine work all traced
        assert "route" in by_name and "query" in by_name
        route = by_name["route"][-1]
        assert route["args"]["replica"] == "r1"

        def inside(child, parent):
            return (child["tid"] == parent["tid"]
                    and child["ts"] >= parent["ts"] - 1e-3
                    and child["ts"] + child["dur"]
                        <= parent["ts"] + parent["dur"] + 1e-3)

        queries = by_name["query"]
        for name in ("plan", "dispatch"):
            assert name in by_name, f"missing {name!r} spans"
            assert any(inside(kid, q)
                       for kid in by_name[name] for q in queries), \
                f"{name!r} spans must nest inside a query span"
        # reconstruction work traced under the routed query too
        assert ("reconstruct" in by_name) or ("window_delta" in by_name)
        # swap instrumentation from the writer's flush
        assert "swap" in by_name and "wal.append" in by_name

        snap = sess.metrics()
        fsync = snap["histograms"]["wal_fsync_seconds"]
        assert any(st["count"] > 0 for st in fsync.values())
        phases = snap["histograms"]["serving_swap_phase_seconds"]
        assert {"phase=drain", "phase=flip", "phase=checkpoint"} <= \
            set(phases)
        lag = snap["gauges"]["router_replica_lag"]
        assert lag == {"replica=r1": 0}      # single replica: no lag
        assert sum(snap["counters"]["router_queries_total"]
                   .values()) == 4
        assert sum(snap["counters"]["replica_queries_served_total"]
                   .values()) == 4
        sess.disable_tracing()
        assert active_tracer() is None
        del tracer
    finally:
        sess.close()


def test_frontend_and_replica_stats_are_registry_views(tmp_path):
    """The consolidated stats surfaces read through the registry — the
    same numbers appear under both the old attribute names and the new
    metric names."""
    from repro.api import GraphSession
    from repro.core import Query

    reg = MetricsRegistry()
    with GraphSession(n_cap=8, metrics=reg) as sess:
        ops, t = _ops(8, 10)
        sess.ingest(ops)
        sess.flush()
        q = Query(kind="point", scope="global", measure="num_edges",
                  t_k=t)
        sess.query(q)
        sess.query(q)                         # exact-cache hit
        fe = sess.frontend
        assert fe.stats.submitted == 2
        assert fe.stats.cache_hits == 1
        snap = reg.snapshot()["counters"]
        assert sum(snap["frontend_submitted_total"].values()) == 2
        assert sum(snap["frontend_cache_hits_total"].values()) == 1


# ---------------------------------------------------------------------------
# tracing from inside the device programs (ISSUE 13)
# ---------------------------------------------------------------------------

def test_tracing_off_again_returns_the_null_span():
    """A tracer that came and went leaves tracing free again: the span
    sites get the shared singleton, no annotation, no clock read."""
    install_tracer(Tracer())
    with trace_span("on") as sp:
        assert sp is not NULL_SPAN
    uninstall_tracer()
    assert trace_span("off", a=1) is NULL_SPAN


def test_span_start_matches_its_profiler_annotation(tmp_path):
    """A span's ``ts`` is on the clock the JAX profiler stamps host
    events with, and each span opens a ``TraceAnnotation`` of its name:
    in a CPU profile the two starts agree to within 1 ms."""
    import glob
    import jax
    tr = install_tracer(Tracer())
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with trace_span("obs.aligned"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    origin = None
    starts = []
    for plane in data.planes:
        origin = dict(plane.stats).get("profile_start_time", origin)
        for line in plane.lines:
            starts += [e.start_ns for e in line.events
                       if e.name == "obs.aligned"]
    assert origin is not None and len(starts) == 3
    ring = sorted(e["ts"] * 1e3 for e in tr.events()
                  if e["name"] == "obs.aligned")
    for ann, span in zip(sorted(starts), ring):
        assert abs(span - (origin + ann)) < 1e6


def test_compile_listener_counts_names_and_spans_a_compile():
    """One forced compile: counted and named in every subscribed
    registry, timed in ``jax_compile_seconds``, and recorded as a
    ``compile`` span nested in the span that triggered it; a closed
    session's registry stops counting."""
    import jax
    import jax.numpy as jnp
    from repro.api import GraphSession

    reg = MetricsRegistry()
    sess = GraphSession(n_cap=8, metrics=reg)
    closed = MetricsRegistry()
    GraphSession(n_cap=8, metrics=closed).close()
    assert closed.get("jax_compile_seconds")["count"] == 0
    tr = install_tracer(Tracer())
    try:
        def forced_compile(x):
            return x * 3 + 1
        with trace_span("dispatch"):
            jax.jit(forced_compile)(jnp.ones((7, 13))).block_until_ready()
    finally:
        sess.close()
    counts = reg.snapshot()["counters"]["jax_compiles_total"]
    assert counts.get("program=jit(forced_compile)") == 1
    hist = reg.get("jax_compile_seconds")
    assert hist["count"] == sum(counts.values()) and hist["sum"] > 0
    assert closed.get("jax_compile_seconds")["count"] == 0
    assert "jax_compiles_total" not in closed.snapshot()["counters"]
    evs = tr.events()
    (comp,) = [e for e in evs if e["name"] == "compile"
               and e["args"]["program"] == "jit(forced_compile)"]
    (outer,) = [e for e in evs if e["name"] == "dispatch"]
    assert comp["args"]["seconds"] > 0
    assert comp["dur"] == pytest.approx(comp["args"]["seconds"] * 1e6)
    assert outer["ts"] <= comp["ts"]
    assert comp["ts"] + comp["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_point_program_hlo_carries_replay_and_measure_scopes():
    """The compiled dense point program names its ops by scope:
    ``replay/scatter``, ``replay/decide`` and ``measure`` (JAX writes
    a scope opened under ``vmap`` as ``vmap(measure)``)."""
    import re
    import jax.numpy as jnp
    from repro.core.delta import Delta
    from repro.core.engine import batch_two_phase_point
    from repro.core.graph import DenseGraph

    n, m = 16, 64
    g = DenseGraph(nodes=jnp.zeros((n,), bool),
                   adj=jnp.zeros((n, n), bool))
    z = jnp.zeros((m,), jnp.int32)
    d = Delta(op=z, u=z, v=z, slot=z, t=z, n_ops=jnp.int32(0))
    ts = jnp.zeros((2,), jnp.int32)
    text = batch_two_phase_point.lower(
        g, d, 0, ts, ts, measure="triangles",
        scope="global").compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/replay/scatter/" in n for n in names)
    assert any("/replay/decide/" in n for n in names)
    assert any(re.search(r"/(vmap\()?measure\)?/", n) for n in names)


def test_traced_evaluate_many_emits_fetch_and_replay_fill():
    """A traced call fetches under ``fetch`` (no ``measure`` span);
    two-phase point and diff ``window_delta`` spans carry the replay
    fill, ``dispatch`` spans the padded batch and delta capacity."""
    import numpy as np
    from repro.core import Query
    from repro.core.generate import (EvolutionParams, build_store,
                                     generate_ops)

    params = EvolutionParams(m_attach=3, lam_extra=1.0, lam_remove=1.0)
    store = build_store(60, params, seed=1)
    times = np.sort([o.t for o in generate_ops(60, params, seed=1)])
    t_cur = store.t_cur
    qs = [Query(kind="point", scope="global", measure="num_edges",
                t_k=t_cur // 3),
          Query(kind="diff", scope="global", measure="num_edges",
                t_k=t_cur // 4, t_l=t_cur // 2),
          Query(kind="diff", scope="global", measure="num_edges",
                t_k=t_cur // 5, t_l=t_cur // 3)]
    tr = install_tracer(Tracer())
    store.engine().evaluate_many(qs, plan="two_phase")
    evs = tr.events()
    names = {e["name"] for e in evs}
    assert "fetch" in names and "measure" not in names
    fills = [e["args"] for e in evs if e["name"] == "window_delta"
             and "own_ops" in e["args"]]
    assert sorted(a["replays"] for a in fills) == [1, 2]
    for a in fills:
        assert 0 < a["own_ops"] <= a["cap"] * a["padded"] * a["replays"]
    (point,) = [a for a in fills if a["replays"] == 1]
    # the one point request replays (its time, the current state]
    assert point["own_ops"] == int(np.sum((times > t_cur // 3)
                                          & (times <= t_cur)))
    assert point["padded"] == 1
    (diff,) = [a for a in fills if a["replays"] == 2]
    assert diff["padded"] == 2
    for e in evs:
        if e["name"] == "dispatch":
            assert e["args"]["padded"] >= e["args"]["batch"]
            assert e["args"]["cap"] >= 1
