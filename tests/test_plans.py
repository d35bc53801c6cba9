"""Plan equivalence (paper Table 2): every applicable plan returns the
same answer, with and without indexes, against the brute-force oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plans import Query


def _ts(store, frac):
    return max(1, int(store.t_cur * frac))


@pytest.mark.parametrize("v", [0, 3, 17, 40])
@pytest.mark.parametrize("frac", [0.2, 0.5, 0.9])
def test_point_degree_all_plans(small_history, v, frac):
    store, bf = small_history
    t = _ts(store, frac)
    q = Query("point", "node", "degree", t_k=t, v=v)
    expect = bf.degree(v, t)
    assert int(store.query(q, plan="two_phase")) == expect
    assert int(store.query(q, plan="two_phase", partial_rows=True)) == \
        expect
    assert int(store.query(q, plan="hybrid")) == expect
    assert int(store.query(q, plan="hybrid", indexed=True)) == expect


@pytest.mark.parametrize("v", [1, 9, 33])
def test_diff_degree_all_plans(small_history, v):
    store, bf = small_history
    t_k, t_l = _ts(store, 0.3), _ts(store, 0.8)
    q = Query("diff", "node", "degree", t_k=t_k, t_l=t_l, v=v)
    expect = abs(bf.degree(v, t_l) - bf.degree(v, t_k))
    assert int(store.query(q, plan="two_phase")) == expect
    assert int(store.query(q, plan="delta_only")) == expect
    assert int(store.query(q, plan="delta_only", indexed=True)) == expect
    assert int(store.query(q, plan="hybrid")) == expect


@pytest.mark.parametrize("v", [2, 21])
@pytest.mark.parametrize("agg", ["mean", "min", "max"])
def test_agg_degree_all_plans(small_history, v, agg):
    store, bf = small_history
    t_k = _ts(store, 0.4)
    t_l = min(t_k + 7, store.t_cur)
    q = Query("agg", "node", "degree", t_k=t_k, t_l=t_l, v=v, agg=agg)
    series = bf.degree_series(v, t_k, t_l)
    expect = {"mean": np.mean, "min": np.min, "max": np.max}[agg](series)
    got_two = float(store.query(q, plan="two_phase"))
    got_hyb = float(store.query(q, plan="hybrid"))
    assert abs(got_two - expect) < 1e-5
    assert abs(got_hyb - expect) < 1e-5


def test_global_queries_two_phase(small_history):
    store, bf = small_history
    t = _ts(store, 0.6)
    q_edges = Query("point", "global", "num_edges", t_k=t)
    assert int(store.query(q_edges)) == bf.num_edges(t)
    q_nodes = Query("point", "global", "num_nodes", t_k=t)
    assert int(store.query(q_nodes)) == bf.num_nodes(t)
    # differential global
    t2 = _ts(store, 0.9)
    q_d = Query("diff", "global", "num_edges", t_k=t, t_l=t2)
    assert int(store.query(q_d)) == abs(bf.num_edges(t2) - bf.num_edges(t))


def test_plan_applicability_matrix(small_history):
    store, _ = small_history
    q = Query("point", "global", "num_edges", t_k=1)
    with pytest.raises(ValueError):
        store.query(q, plan="delta_only")


def test_materialized_selection(small_history):
    store, bf = small_history
    # materialize a few snapshots by hand
    for frac in (0.25, 0.5, 0.75):
        t = _ts(store, frac)
        g = store.snapshot_at(t, use_materialized=False)
        store.materialized.add(t, g)
    for frac in (0.3, 0.6, 0.95):
        t = _ts(store, frac)
        for sel in ("time", "ops"):
            g = store.snapshot_at(t, use_materialized=True, selection=sel)
            assert np.array_equal(np.asarray(g.adj), bf.adj(t)), (t, sel)


def test_sequential_two_phase(small_history):
    store, bf = small_history
    t = _ts(store, 0.5)
    q = Query("point", "node", "degree", t_k=t, v=5)
    assert int(store.query(q, plan="two_phase", sequential=True)) == \
        bf.degree(5, t)


def test_windowed_snapshot_matches(small_history):
    """Temporal-index windowed reconstruction == full-log masked
    reconstruction (the §Perf windowed-materialization path)."""
    import numpy as np
    store, bf = small_history
    g = store.snapshot_at(store.t_cur // 2, use_materialized=False)
    store.materialized.add(store.t_cur // 2, g)
    for frac in (0.2, 0.55, 0.8):
        t = max(1, int(store.t_cur * frac))
        a = store.snapshot_at(t, windowed=False)
        b = store.snapshot_at(t, windowed=True)
        assert np.array_equal(np.asarray(a.adj), np.asarray(b.adj)), t
        assert np.array_equal(np.asarray(a.adj), bf.adj(t)), t


def test_div_rn_matches_ieee_division():
    """The float measures' divide is the IEEE quotient on every backend
    (a TPU's own f32 divide is only faithful)."""
    import jax
    from repro.core.queries import div_rn
    rng = np.random.default_rng(0)
    counts = rng.integers(0, 1 << 24, 20000).astype(np.float32)
    dens = rng.integers(1, 1 << 24, 20000).astype(np.float32)
    x = rng.uniform(1e-3, 1e6, 20000).astype(np.float32)
    y = rng.uniform(1e-3, 1e6, 20000).astype(np.float32)
    for a, b in ((counts, dens), (x, y), (dens, dens), (2 * dens, dens)):
        got = np.asarray(jax.jit(div_rn)(jnp.asarray(a), jnp.asarray(b)))
        assert np.array_equal(got.view(np.int32), (a / b).view(np.int32))
    assert float(div_rn(jnp.float32(0), jnp.float32(3))) == 0.0
