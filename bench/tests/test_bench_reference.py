"""The plain reference on a hand-built history; its triangle series
against the bitset formula it replaced."""
import numpy as np
import pytest

import benchkit  # noqa: F401  (puts the benchmark on sys.path)
from harness import datagen
from harness.traffic import Req

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_reference_replay",
    os.path.join(benchkit.BENCH, "references", "replay.py"))
replay = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(replay)

A_N, R_N, A_E, R_E = 0, 1, 2, 3
# t=1: nodes 0..3, edges 0-1 0-2 1-2 (a triangle) | t=2: edge 2-3
# t=3: remove 0-1 | t=4: re-add 0-1, edge 0-3 | t=5: remove node 3's
# edges 2-3 and 0-3
HISTORY = [(A_N, 0, 0, 1), (A_N, 1, 1, 1), (A_N, 2, 2, 1), (A_N, 3, 3, 1),
           (A_E, 0, 1, 1), (A_E, 0, 2, 1), (A_E, 1, 2, 1),
           (A_E, 2, 3, 2),
           (R_E, 0, 1, 3),
           (A_E, 0, 1, 4), (A_E, 0, 3, 4),
           (R_E, 2, 3, 5), (R_E, 0, 3, 5)]


def ref():
    return replay.Reference(np.asarray(HISTORY, np.int64).T, 8)


def q(kind, measure, t_k, t_l=None, v=None, agg="", stride=1):
    return Req(kind, "node" if v is not None else "global", measure, t_k,
               t_l, v, agg, stride)


def test_point_measures():
    r = ref()
    got = r.answers([q("point", "num_edges", t) for t in range(1, 6)])
    assert got == [3, 4, 3, 5, 3]
    got = r.answers([q("point", "degree", t, v=0) for t in range(1, 6)])
    assert got == [2, 2, 1, 3, 2]
    assert r.answers([q("point", "degree", 4, v=3)]) == [2]
    assert r.answers([q("point", "num_nodes", 5)]) == [4]
    avg = r.answers([q("point", "avg_degree", 4)])[0]
    assert avg == np.float32(2.5) and avg.dtype == np.float32


def test_triangles_and_degree_distribution():
    r = ref()
    # t=1 and t=5: the triangle 0-1-2; t=3: 0-1 gone; t=4: 0-2-3? no 2-3
    # edge at t=4 (added t=2, so yes): triangles 0-1-2 and 0-2-3
    got = r.answers([q("point", "triangles", t) for t in (1, 3, 4, 5)])
    assert got == [1, 0, 2, 1]
    hist = r.answers([q("point", "degree_distribution", 4)])[0]
    assert hist.shape == (replay.DEGREE_BINS + 1,)
    # degrees at t=4: 0:3 1:2 2:3 3:2
    assert hist[2] == 2 and hist[3] == 2 and hist.sum() == 4


def test_ranges():
    r = ref()
    assert r.answers([q("diff", "num_edges", 2, 3)]) == [1]
    assert r.answers([q("agg", "num_edges", 1, 5, agg="min"),
                      q("agg", "num_edges", 1, 5, agg="max")]) == [3, 5]
    mean = r.answers([q("agg", "degree", 1, 4, v=0, agg="mean")])[0]
    assert mean == np.float32(8) / np.float32(4)
    sweep = r.answers([q("evolve", "num_edges", 1, 5, stride=2)])[0]
    assert sweep.tolist() == [3, 3, 3]


def test_stale_control_reads_one_unit_early():
    r = ref()
    qs = [q("point", "num_edges", t) for t in range(2, 6)]
    assert r.answers(qs, shift=1) == r.answers(
        [q("point", "num_edges", t - 1) for t in range(2, 6)])
    assert r.answers(qs, shift=1) != r.answers(qs)


def test_same_is_exact():
    assert replay.same(np.float32(2.5), np.float32(2.5))
    assert not replay.same(np.float32(2.5), np.nextafter(np.float32(2.5),
                                                         np.float32(3)))
    assert not replay.same(np.arange(3), np.arange(4))


# --- the triangle series against the bitset formula --------------------


def triangles_by_matrix(cols, n_cap: int, t: int) -> int:
    """Triangles of the live-edge graph at ``t`` (an edge is live iff the
    last op on its key at or before ``t`` is an insert), counted by the
    bitset formula: common neighbours of both ends of every live edge,
    over three."""
    op, u, v, tt = cols
    m = (tt <= t) & (op >= A_E)
    key = (np.minimum(u, v) * n_cap + np.maximum(u, v))[m][::-1]
    keys, last = np.unique(key, return_index=True)
    live = keys[op[m][::-1][last] == A_E]
    ku, kv = live // n_cap, live % n_cap
    adj = np.zeros((n_cap, n_cap), bool)
    adj[ku, kv] = adj[kv, ku] = True
    rows = np.packbits(adj, axis=1)
    common = np.unpackbits(rows[ku] & rows[kv], axis=1).sum()
    return int(common) // 3


def random_history(seed: int, n: int = 12, steps: int = 400) -> np.ndarray:
    """Edge inserts and removals on a few nodes, the same keys removed and
    re-added many times, several ops per time, either end order, and some
    ops that change nothing (an insert of a live key, a removal of a dead
    one)."""
    rng = np.random.default_rng(seed)
    rows = [(A_N, i, i, 0) for i in range(n)]
    for s in range(steps):
        a, b = rng.choice(n, 2, replace=False)
        rows.append((int(rng.choice([A_E, A_E, R_E])), int(a), int(b),
                     1 + s // 3))
    return np.asarray(rows, np.int64).T


def _points(ts):
    return [q("point", "triangles", int(t)) for t in ts]


def test_triangle_series_matches_matrix_on_history():
    cols = np.asarray(HISTORY, np.int64).T
    ts = range(0, 7)
    want = [triangles_by_matrix(cols, 8, t) for t in ts]
    assert want == [0, 1, 1, 0, 2, 1, 1]
    assert ref().answers(_points(ts)) == want


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_triangle_series_matches_matrix_on_random_histories(seed):
    cols = random_history(seed)
    ts = range(0, int(cols[3].max()) + 2)
    r = replay.Reference(cols, 16)
    want = [triangles_by_matrix(cols, 16, t) for t in ts]
    assert max(want) > 0 and r.answers(_points(ts)) == want


@pytest.fixture(scope="module")
def table3():
    """The configuration's history at seed 7, and its ``n_cap``."""
    cfg = benchkit.load_json("bench/configs/table3-dense-n5063.json")
    cols = datagen.generate(datagen.Model.from_config(cfg["data"]), seed=7)
    return cols, cfg["session"]["n_cap"]


def test_triangle_series_matches_matrix_on_table3(table3):
    cols, n_cap = table3
    ts = np.random.default_rng(7).integers(0, cols[3].max() + 1, 16)
    got = replay.Reference(cols, n_cap).answers(_points(ts))
    assert got == [triangles_by_matrix(cols, n_cap, t) for t in ts]


def test_triangles_at_every_unit_of_table3(table3):
    """One ``answers`` call over every unit of the history: the check's
    cost must not grow with the number of times asked."""
    cols, n_cap = table3
    t_max = int(cols[3].max())
    got = replay.Reference(cols, n_cap).answers(_points(range(t_max + 1)))
    assert len(got) == t_max + 1
    for t in np.random.default_rng(8).integers(0, t_max + 1, 16):
        assert got[t] == triangles_by_matrix(cols, n_cap, t)


def test_self_loop_key_refused():
    cols = np.asarray(HISTORY + [(A_E, 2, 2, 6)], np.int64).T
    with pytest.raises(ValueError, match="self-loop"):
        replay.Reference(cols, 8).answers(_points([6]))
