"""The plain reference on a hand-built history."""
import numpy as np

import benchkit  # noqa: F401  (puts the benchmark on sys.path)
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
