"""End-to-end behaviour tests for the paper's system: ingest an
evolving social graph, serve every query class of Table 1 against the
brute-force oracle, with materialization + Algorithm 3 incremental
updates in the loop."""
import jax.numpy as jnp
import numpy as np

from repro.core import MaterializationPolicy, Op, TemporalGraphStore
from repro.core.delta import ADD_EDGE, ADD_NODE, REM_EDGE
from repro.core.generate import EvolutionParams, generate_ops
from repro.core.plans import Query
from reference import BruteForce


def test_incremental_update_loop_algorithm3():
    """Ingest in per-time-unit batches (Algorithm 3), materializing via
    the op-count policy; every historical degree query stays correct."""
    params = EvolutionParams(m_attach=2, lam_extra=0.5, lam_remove=0.8,
                             events_per_unit=4)
    ops = generate_ops(50, params, seed=21)
    t_max = max(o.t for o in ops)
    store = TemporalGraphStore(
        n_cap=64, policy=MaterializationPolicy(kind="opcount",
                                               op_budget=40))
    # feed ops one time unit at a time
    by_t = {}
    for o in ops:
        by_t.setdefault(o.t, []).append(o)
    for t in range(1, t_max + 1):
        store.ingest(by_t.get(t, []))
        store.advance_to(t)
    assert store.t_cur == t_max
    assert len(store.materialized.times) >= 2  # policy fired

    acc = [Op(int(o), int(u), int(v), int(tt)) for o, u, v, tt in
           zip(store._op, store._u, store._v, store._t)]
    bf = BruteForce(acc, 64, t_max)
    for t in range(0, t_max + 1, max(t_max // 9, 1)):
        g = store.snapshot_at(t)
        assert np.array_equal(np.asarray(g.adj), bf.adj(t)), t
        g2 = store.snapshot_at(t, use_materialized=False)
        assert np.array_equal(np.asarray(g2.adj), bf.adj(t)), t


def test_full_query_matrix_end_to_end(small_history):
    store, bf = small_history
    tc = store.t_cur
    checks = 0
    for v in (0, 7, 23):
        for (tk, tl) in ((tc // 4, tc // 2), (tc // 2, 3 * tc // 4)):
            q = Query("point", "node", "degree", t_k=tk, v=v)
            for plan in ("two_phase", "hybrid"):
                assert int(store.query(q, plan=plan)) == bf.degree(v, tk)
                checks += 1
            q = Query("diff", "node", "degree", t_k=tk, t_l=tl, v=v)
            for plan in ("two_phase", "delta_only", "hybrid"):
                assert int(store.query(q, plan=plan)) == \
                    abs(bf.degree(v, tl) - bf.degree(v, tk))
                checks += 1
            q = Query("agg", "node", "degree", t_k=tk,
                      t_l=min(tk + 5, tc), v=v, agg="max")
            expect = max(bf.degree_series(v, tk, min(tk + 5, tc)))
            for plan in ("two_phase", "hybrid"):
                assert int(store.query(q, plan=plan)) == expect
                checks += 1
    assert checks >= 42


def test_global_measures_on_reconstruction(small_history):
    from repro.core import queries as Q
    store, bf = small_history
    t = store.t_cur // 2
    g = store.snapshot_at(t)
    nodes, edges = bf.snapshots[t]
    assert int(Q.num_nodes(g)) == len(nodes)
    assert int(Q.num_edges(g)) == len(edges)
    # component count vs union-find reference
    parent = {n: n for n in nodes}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for (u, v) in edges:
        parent[find(u)] = find(v)
    n_comp = len({find(n) for n in nodes})
    assert int(Q.num_components(g)) == n_comp
    # triangles vs brute force
    adj = bf.adj(t)
    tri = 0
    n = adj.shape[0]
    for i in range(n):
        for j in range(i + 1, n):
            if adj[i, j]:
                tri += int((adj[i] & adj[j])[j + 1:].sum())
    assert int(Q.triangle_count(g)) == tri


def test_triangle_count_exact_past_f32_integers():
    """6·triangles above 2^24 and path counts above 256: an f32
    trace(A³) rounds here, and a bf16 second product would on a TPU."""
    from repro.core import queries as Q
    from repro.core.graph import DenseGraph
    rng = np.random.default_rng(0)
    adj = np.triu(rng.random((1024, 1024)) < 0.5, 1)
    adj = adj | adj.T
    a = adj.astype(np.float64)
    exact = int(np.trace(a @ a @ a)) // 6
    assert 6 * exact > 2 ** 24
    g = DenseGraph(nodes=jnp.ones(1024, bool), adj=jnp.asarray(adj))
    assert int(Q.triangle_count(g)) == exact


def test_degree_distribution_and_pagerank(small_history):
    from repro.core import queries as Q
    store, bf = small_history
    t = store.t_cur // 2
    g = store.snapshot_at(t)
    adj = bf.adj(t)
    hist = np.bincount(adj.sum(1)[bf.node_mask(t)], minlength=21)[:21]
    got = np.asarray(Q.degree_distribution(g, 20))
    assert np.array_equal(got, hist)
    pr = np.asarray(Q.pagerank(g))
    assert abs(float(pr.sum()) - 1.0) < 1e-3  # stochastic vector
    # higher-degree nodes should not have lower rank than isolated ones
    assert pr[np.argmax(adj.sum(1))] > pr[~bf.node_mask(t)].max() \
        if (~bf.node_mask(t)).any() else True


def test_diameter_bfs(small_history):
    from repro.core import queries as Q
    store, bf = small_history
    t = store.t_cur
    g = store.current
    adj = bf.adj(t)
    mask = bf.node_mask(t)
    # reference BFS diameter (largest finite eccentricity)
    import collections
    best = 0
    nodes = np.nonzero(mask)[0]
    for s in nodes:
        dist = {int(s): 0}
        dq = collections.deque([int(s)])
        while dq:
            u = dq.popleft()
            for w in np.nonzero(adj[u])[0]:
                if int(w) not in dist:
                    dist[int(w)] = dist[u] + 1
                    dq.append(int(w))
        best = max(best, max(dist.values()))
    assert int(Q.diameter(g)) == best
