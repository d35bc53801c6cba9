"""The benchmark's copy of the evolving scale-free model."""
import numpy as np

import benchkit  # noqa: F401
from harness import datagen

TABLE3 = datagen.Model(n_nodes=5063, m_attach=6, lam_extra=2.2,
                       lam_remove=3.61, events_per_unit=8)
# arXiv:1302.5549, Table 3
PUBLISHED = {"nodes_inserted": 5063, "edges_inserted": 41067,
             "edges_removed": 18280, "ops": 64410}


def test_table3_counts_beside_published():
    got = datagen.counts(datagen.generate(TABLE3, seed=7))
    print({k: (got[k], PUBLISHED[k]) for k in PUBLISHED})
    assert got["nodes_inserted"] == PUBLISHED["nodes_inserted"]
    for k in ("edges_inserted", "edges_removed", "ops"):
        assert abs(got[k] - PUBLISHED[k]) <= 0.02 * PUBLISHED[k], k


def test_same_seed_same_history():
    a = datagen.generate(TABLE3, seed=3)
    b = datagen.generate(TABLE3, seed=3)
    c = datagen.generate(TABLE3, seed=4)
    assert np.array_equal(a, b)
    assert a.shape != c.shape or not np.array_equal(a, c)


def test_large_seed_accepted():
    m = datagen.Model(n_nodes=50, m_attach=2, lam_extra=1.0,
                      lam_remove=1.0, events_per_unit=4)
    assert datagen.generate(m, seed=2 ** 33 + 5).shape[0] == 4


def test_stream_is_legal():
    """Time-ordered; no duplicate live edge; removals only of live
    edges; endpoints exist — so a store accepts every op."""
    cols = datagen.generate(TABLE3, seed=11)
    op, u, v, t = cols
    assert (np.diff(t) >= 0).all()
    nodes, live = set(), set()
    for o, a, b in zip(op.tolist(), u.tolist(), v.tolist()):
        if o == datagen.ADD_NODE:
            nodes.add(a)
        elif o == datagen.ADD_EDGE:
            assert a < b and a in nodes and b in nodes
            assert (a, b) not in live
            live.add((a, b))
        else:
            assert o == datagen.REM_EDGE and (a, b) in live
            live.remove((a, b))
