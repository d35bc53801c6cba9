"""Incremental time-sweep (`evolve`) queries + the merged-delta tree.

Acceptance contracts of the sweep executor (kernels/evolve_sweep):

* ``store.evolve(measure, t_lo, t_hi, stride)`` bit-matches B
  independent point queries over the same sample times — dense AND
  edge layouts, stride ≥ 1, windows crossing segment / anchor / epoch
  boundaries (property test with a seeded fallback).
* tree-covered ``window_delta`` (merged-delta interior nodes) feeds
  reconstructions that bit-match leaf-covered ones, with op counts
  never above the leaf cover.
* the Pallas tiled sweep kernel agrees with the scan executor.
* serving integration: sweeps land in the workload histogram
  (decayed per-sample weights) and coalesce/cache in the frontend.
"""
import numpy as np
import pytest

from repro.core.delta import ADD_EDGE, ADD_NODE, REM_EDGE, REM_NODE
from repro.core.plans import Query
from repro.core.store import Op, TemporalGraphStore

N = 12

SWEEPABLE = [("degree", "node"), ("num_nodes", "global"),
             ("num_edges", "global"), ("density", "global"),
             ("avg_degree", "global"), ("degree_distribution", "global")]


def _item(x):
    return np.asarray(x).item()


def _churn_chunks(rng, n_chunks=4, per_chunk=(6, 18)):
    mix = [ADD_NODE, ADD_NODE, ADD_EDGE, ADD_EDGE, ADD_EDGE, REM_EDGE,
           REM_NODE]
    chunks, t = [], 0
    for _ in range(n_chunks):
        t += 1
        chunk = []
        for _ in range(int(rng.integers(*per_chunk))):
            t += int(rng.integers(0, 2))
            kind = mix[int(rng.integers(0, len(mix)))]
            u = int(rng.integers(0, N))
            v = int(rng.integers(0, N))
            chunk.append(Op(kind, u,
                            v if kind in (ADD_EDGE, REM_EDGE) else u, t))
        chunks.append(chunk)
    return chunks


def _sweep_store(chunks, layout):
    """Freeze between chunks so the log really fragments into sealed
    segments (and the merged tree builds over them): every sweep then
    crosses segment and epoch boundaries."""
    s = TemporalGraphStore(n_cap=N, layout=layout, segment_min_ops=1)
    for chunk in chunks:
        s.ingest(chunk)
        s.advance_to(max(o.t for o in chunk))
        s.freeze_serving_state()
    return s


def _check_evolve_matches_points(s, t_lo, t_hi, stride, measure, scope, v):
    got = np.asarray(s.evolve(measure, t_lo, t_hi, stride=stride, v=v,
                              scope=scope))
    ts = list(range(int(t_lo), int(t_hi) + 1, int(stride)))
    ref = np.asarray(s.evaluate_many(
        [Query("point", scope, measure, t_k=t, v=v) for t in ts]))
    assert got.shape[0] == len(ts)
    assert got.dtype == ref.dtype, (measure, got.dtype, ref.dtype)
    assert np.array_equal(got, ref), (measure, t_lo, t_hi, stride, got,
                                      ref)


def _check_sweep_parity(chunks, layout, probe_seed=0):
    s = _sweep_store(chunks, layout)
    t_cur = s.t_cur
    rng = np.random.default_rng(probe_seed)
    for measure, scope in SWEEPABLE:
        v = int(rng.integers(0, N)) if scope == "node" else None
        # full history, a strided interior window, and a window pinned
        # at t=0 (crosses every seal + the anchor sits past t_hi)
        probes = [(0, t_cur, 1), (1, max(1, t_cur - 1), 3),
                  (0, min(5, t_cur), 2)]
        for t_lo, t_hi, stride in probes:
            _check_evolve_matches_points(s, t_lo, t_hi, stride, measure,
                                         scope, v)


# ---------------------------------------------------------------------------
# Sweep-vs-point bit-parity (property + seeded fallback)
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st

    @st.composite
    def chunk_streams(draw):
        mix = [ADD_NODE, ADD_NODE, ADD_EDGE, ADD_EDGE, ADD_EDGE,
               REM_EDGE, REM_NODE]
        n_chunks = draw(st.integers(min_value=2, max_value=4))
        t, chunks = 0, []
        for _ in range(n_chunks):
            t += draw(st.integers(min_value=1, max_value=2))
            n_ops = draw(st.integers(min_value=2, max_value=12))
            chunk = []
            for _ in range(n_ops):
                t += draw(st.integers(min_value=0, max_value=1))
                kind = draw(st.sampled_from(mix))
                u = draw(st.integers(min_value=0, max_value=N - 1))
                v = draw(st.integers(min_value=0, max_value=N - 1))
                chunk.append(Op(kind, u,
                                v if kind in (ADD_EDGE, REM_EDGE) else u,
                                t))
            chunks.append(chunk)
        return chunks

    @given(chunk_streams(), st.sampled_from(["dense", "edge"]),
           st.sampled_from([1, 2, 3]))
    @settings(max_examples=10, deadline=None)
    def test_property_evolve_bitmatches_point_queries(chunks, layout,
                                                      stride):
        s = _sweep_store(chunks, layout)
        _check_evolve_matches_points(s, 0, s.t_cur, stride, "degree",
                                     "node", 3)
        _check_evolve_matches_points(s, 0, s.t_cur, stride, "num_edges",
                                     "global", None)

except ImportError:
    @pytest.mark.parametrize("layout", ["dense", "edge"])
    def test_property_evolve_bitmatches_point_queries(layout):
        """Seeded-random stand-in when hypothesis is unavailable."""
        for seed in range(4):
            rng = np.random.default_rng(seed)
            s = _sweep_store(_churn_chunks(rng, n_chunks=3), layout)
            stride = 1 + seed % 3
            _check_evolve_matches_points(s, 0, s.t_cur, stride, "degree",
                                         "node", 3)
            _check_evolve_matches_points(s, 0, s.t_cur, stride,
                                         "num_edges", "global", None)


@pytest.mark.parametrize("layout", ["dense", "edge"])
def test_evolve_all_measures_seeded(layout):
    """Deterministic instance over every sweepable measure (always
    runs, with or without hypothesis)."""
    rng = np.random.default_rng(42)
    _check_sweep_parity(_churn_chunks(rng, n_chunks=4), layout,
                        probe_seed=7)


def test_evolve_fallback_measure_matches_points():
    """A measure outside SWEEP_MEASURES transparently degrades to B
    point queries — same values, no sweep program."""
    rng = np.random.default_rng(5)
    s = _sweep_store(_churn_chunks(rng, n_chunks=3), "dense")
    got = np.asarray(s.evolve("triangles", 1, min(6, s.t_cur)))
    ref = np.asarray(s.evaluate_many(
        [Query("point", "global", "triangles", t_k=t)
         for t in range(1, min(6, s.t_cur) + 1)]))
    assert np.array_equal(got, ref)


def test_evolve_groups_share_one_program():
    """Sweeps sharing (measure, stride, anchor) coalesce into one
    engine group; mixed stride or measure splits them."""
    rng = np.random.default_rng(8)
    s = _sweep_store(_churn_chunks(rng, n_chunks=3), "dense")
    eng = s.engine()
    qs = [Query("evolve", "global", "num_edges", t_k=1, t_l=s.t_cur,
                stride=1),
          Query("evolve", "global", "num_edges", t_k=2, t_l=s.t_cur,
                stride=1),
          Query("evolve", "global", "num_edges", t_k=1, t_l=s.t_cur,
                stride=2)]
    res = eng.evaluate_many(qs)
    evolve_groups = [row for row in eng.last_group_stats
                     if row[0].kind == "evolve"]
    assert len(evolve_groups) == 2            # stride splits, times don't
    assert sorted(r[1] for r in evolve_groups) == [1, 2]
    for q, r in zip(qs, res):
        ts = list(range(q.t_k, q.t_l + 1, q.stride))
        assert np.asarray(r).shape[0] == len(ts)


# ---------------------------------------------------------------------------
# Merged-delta tree: tree-covered windows bit-match leaf-covered ones
# ---------------------------------------------------------------------------


def _long_store(layout="dense", n_chunks=12):
    rng = np.random.default_rng(13)
    return _sweep_store(_churn_chunks(rng, n_chunks=n_chunks,
                                      per_chunk=(8, 16)), layout)


def test_merged_tree_cover_is_never_larger():
    s = _long_store()
    view = s.delta_view()
    assert view.merged, "long sealed history must build interior nodes"
    t_cur = s.t_cur
    for t_lo, t_hi in [(0, t_cur), (0, t_cur // 2), (t_cur // 4, t_cur),
                       (3, t_cur - 3)]:
        leaf = view.window_cover(t_lo, t_hi)
        tree = view.window_cover(t_lo, t_hi, merged=True)
        assert sum(p.n_ops for p in tree) <= sum(p.n_ops for p in leaf)
        assert len(tree) <= len(leaf)
    # on the full history the collapse must strictly win (the churn mix
    # guarantees superseded ops)
    full_leaf = view.window_cover(0, t_cur)
    full_tree = view.window_cover(0, t_cur, merged=True)
    assert sum(p.n_ops for p in full_tree) < sum(p.n_ops
                                                 for p in full_leaf)


@pytest.mark.parametrize("layout", ["dense", "edge"])
def test_merged_window_reconstruction_bitmatches_leaf(layout):
    """Reconstructing through a tree-covered window delta gives the
    same bits as through the leaf-covered one, forward and backward."""
    from repro.core.reconstruct import reconstruct_dense, reconstruct_edge
    s = _long_store(layout)
    view = s.delta_view()
    t_cur = s.t_cur
    anchor = s.current if layout == "dense" else s.current_edge_snapshot()
    rec = reconstruct_dense if layout == "dense" else reconstruct_edge
    for t in range(0, t_cur + 1, max(1, t_cur // 9)):
        d_leaf = view.window_delta(min(t, t_cur), t_cur)
        d_tree = view.window_delta(min(t, t_cur), t_cur, merged=True)
        a = rec(anchor, d_leaf, t_cur, t)
        b = rec(anchor, d_tree, t_cur, t)
        if layout == "edge":
            a, b = a.to_dense(), b.to_dense()
        assert np.array_equal(np.asarray(a.adj), np.asarray(b.adj)), t
        assert np.array_equal(np.asarray(a.nodes), np.asarray(b.nodes)), t


def test_merged_nodes_participate_in_residency():
    """Interior nodes count against (and are restored by) the same
    device-residency budget as leaf segments."""
    s = _long_store()
    view = s.delta_view()
    # touch every merged node so each holds a device array
    view.window_delta(0, s.t_cur, merged=True)
    for node in view.merged.values():
        node.delta  # noqa: B018 — property access builds the device log
    total = view.device_bytes()
    assert any(n.is_resident for n in view.merged.values())
    # a zero budget spills everything except the pinned hot tail —
    # merged nodes are LRU citizens, none may survive
    view.ensure_device(0)
    hot = sum(seg.device_bytes() for seg in view.segments[-2:])
    assert view.device_bytes() == hot < total
    assert not any(n.is_resident for n in view.merged.values())
    # queries after the spill transparently rebuild what they need
    view.window_delta(0, s.t_cur, merged=True)
    assert view.device_bytes() > hot


# ---------------------------------------------------------------------------
# Pallas tiled sweep kernel vs the scan executor
# ---------------------------------------------------------------------------


def test_pallas_sweep_kernel_matches_scan():
    from repro.core.reconstruct import reconstruct_dense
    from repro.kernels.evolve_sweep import sweep_degree_series
    s = _long_store(n_chunks=6)
    view = s.delta_view()
    t_cur = s.t_cur
    d = view.window_delta(0, t_cur)
    t_lo, stride, nb = 1, 2, 8
    g0 = reconstruct_dense(s.current, d, t_cur, t_lo)
    series, overflow = sweep_degree_series(
        g0.degrees(), d, t_lo, t_lo + (nb - 1) * stride, stride, nb,
        tile=4, cap=1024, interpret=True)
    assert not bool(overflow)
    for b in range(nb):
        t = min(t_lo + b * stride, t_cur)
        ref = reconstruct_dense(s.current, d, t_cur, t).degrees()
        assert np.array_equal(np.asarray(series[b]), np.asarray(ref)), b


# ---------------------------------------------------------------------------
# Serving integration: workload histogram + frontend coalescing
# ---------------------------------------------------------------------------


def test_workload_records_swept_times():
    from repro.serving.policy import WorkloadStats
    stats = WorkloadStats()
    stats.record_queries([Query("evolve", "global", "num_edges", t_k=4,
                                t_l=11, stride=2)])
    hist = stats.histogram()
    assert set(hist) == {4, 6, 8, 10}
    # one sweep carries one query's mass, spread over its samples
    assert all(abs(w - 0.25) < 1e-9 for w in hist.values())
    assert abs(stats.total - 1.0) < 1e-9
    stats.record_queries([Query("point", "global", "num_edges", t_k=6)])
    assert abs(stats.histogram()[6] - 1.25) < 1e-9


def test_frontend_sweep_coalesce_and_cache():
    from repro.serving import LiveGraphStore
    from repro.serving.frontend import MicroBatchFrontend
    rng = np.random.default_rng(21)
    chunks = _churn_chunks(rng, n_chunks=3)
    live = LiveGraphStore(n_cap=N)
    for chunk in chunks:
        live.append(chunk)
        live.swap()
    fe = MicroBatchFrontend(live, max_batch=8)
    t_hi = live.t_served
    f1 = fe.submit_sweep("num_edges", 0, t_hi, stride=1)
    f2 = fe.submit_sweep("num_edges", 0, t_hi, stride=1)   # dupe
    f3 = fe.submit_sweep("num_edges", 0, t_hi, stride=2)   # distinct
    fe.flush()
    r1, r2, r3 = f1.result(), f2.result(), f3.result()
    assert np.array_equal(r1, r2)
    assert fe.stats.coalesced_dupes == 1
    assert len(r3) == t_hi // 2 + 1
    ref = np.asarray(live.evaluate_many(
        [Query("point", "global", "num_edges", t_k=t)
         for t in range(0, t_hi + 1)]))
    assert np.array_equal(np.asarray(r1), ref)
    # second submit of the same sweep inside the epoch: exact-cache hit
    before = fe.stats.cache_hits
    f4 = fe.submit_sweep("num_edges", 0, t_hi, stride=1)
    assert fe.stats.cache_hits == before + 1
    assert np.array_equal(f4.result(), r1)
