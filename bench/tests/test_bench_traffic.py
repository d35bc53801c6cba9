"""The traffic generator: the same work for every seed."""
import collections

import numpy as np

import benchkit  # noqa: F401 — puts the harness on the path
from harness import traffic

# node and global templates, weights summing to 61: of 130 requests, 2
# are sweeps and 17 global num_edges points
MIX = {"queries": [
    {"weight": 16, "kind": "point", "scope": "node", "measure": "degree",
     "hubs": 64, "hub_share": 0.5},
    {"weight": 8, "kind": "diff", "scope": "node", "measure": "degree",
     "span_max": 64, "hubs": 64, "hub_share": 0.5},
    {"weight": 8, "kind": "agg", "scope": "node", "measure": "degree",
     "span_max": 16, "span_min": 15, "aggs": ["mean", "min", "max"],
     "hubs": 64, "hub_share": 0.5},
    {"weight": 8, "kind": "point", "scope": "global", "measure": "num_edges"},
    {"weight": 4, "kind": "diff", "scope": "global", "measure": "num_edges",
     "span_max": 64},
    {"weight": 8, "kind": "point", "scope": "global",
     "measure": "avg_degree"},
    {"weight": 8, "kind": "agg", "scope": "global", "measure": "num_edges",
     "span_max": 8, "span_min": 7, "aggs": ["min", "max"]},
    {"weight": 1, "kind": "evolve", "scope": "global", "measure": "num_edges",
     "stride_div": 16},
]}


def test_allocate_sums_and_follows_weights():
    assert traffic.allocate([16, 8, 1], 25) == [16, 8, 1]
    c = traffic.allocate([1, 1, 1], 10)
    assert sum(c) == 10 and max(c) - min(c) <= 1


def test_stream_kind_counts_fixed_per_seed():
    s = traffic.Sampler(MIX, 1, 3700, 262144)

    def kinds(seed):
        return [(r.kind, r.scope, r.measure) for r in s.stream(
            np.random.default_rng(seed), 130, np.random.default_rng(0))]
    assert kinds(1) == kinds(2)
    assert collections.Counter(kinds(1))[("evolve", "global",
                                          "num_edges")] == 2
    reqs = s.stream(np.random.default_rng(3), 500, np.random.default_rng(0))
    for r in reqs:
        assert 1 <= r.t_k <= 3700
        if r.t_l is not None:
            assert r.t_k <= r.t_l <= 3700
        if r.kind == "evolve":
            assert (r.t_k, r.t_l, r.stride) == (1, 3700, 231)
    hubs = sum(r.v < 64 for r in reqs if r.v is not None)
    nodes = sum(r.v is not None for r in reqs)
    assert 0.4 < hubs / nodes < 0.65


def test_stream_times_one_per_stratum_for_every_seed():
    s = traffic.Sampler(MIX, 1, 3700, 262144)

    def times(seed):
        reqs = s.stream(np.random.default_rng(seed), 130,
                        np.random.default_rng(0))
        return [r.t_k for r in reqs
                if (r.kind, r.measure) == ("point", "num_edges")]
    a, b = times(1), times(2)
    assert len(a) == len(b) == 17
    # the same stratum at each position, a seed-drawn offset inside it
    width = 3700 / len(a)
    assert max(abs(x - y) for x, y in zip(a, b)) < width + 1
    assert a != b
    assert sorted(int((t - 1) // width) for t in a) == list(range(len(a)))
