"""Plain reference of the temporal graph store: a numpy replay of the log.

The graph at time t is decided, per node and per edge key, by the last
op at or before t.  The answers follow the query semantics of the
store's documentation: a point query is the measure at ``t_k``; a diff
is ``|m(t_l) - m(t_k)|``; an agg is min, max or mean of the measure at
every unit of ``[t_k, t_l]``; a sweep (``evolve``) is the measure at
``t_k, t_k + stride, ... <= t_l``.  Float measures are f32, as the
store serves them, and every division is IEEE division.

Shares no code with the program under test: it reads only the op
columns the benchmark generated.
"""
from __future__ import annotations

import numpy as np

ADD_NODE, REM_NODE, ADD_EDGE, REM_EDGE = 0, 1, 2, 3
# Bins of the degree_distribution measure: degrees 0..64, the last bin
# collecting everything above.
DEGREE_BINS = 64


class Reference:
    """Replays ``cols`` = int64 columns (op, u, v, t), time-ordered."""

    def __init__(self, cols: np.ndarray, n_cap: int):
        op, u, v, t = cols
        self.t, self.n_cap = t, n_cap
        edge = op >= ADD_EDGE
        key = np.minimum(u, v) * n_cap + np.maximum(u, v)
        keys, kid = np.unique(key[edge], return_inverse=True)
        self.ku, self.kv = keys // n_cap, keys % n_cap
        self.k = len(keys)
        # item per op: its edge key, or K + node id for node ops
        self.item = np.empty(len(op), np.int64)
        self.item[edge] = kid
        self.item[~edge] = self.k + u[~edge]
        self.adds = (op == ADD_NODE) | (op == ADD_EDGE)
        # incident edge keys per node, as one CSR over both endpoints
        ends = np.concatenate([self.ku, self.kv])
        order = np.argsort(ends, kind="stable")
        self._inc_keys = np.concatenate([np.arange(self.k)] * 2)[order]
        self._inc_ptr = np.searchsorted(ends[order], np.arange(n_cap + 1))
        # largest degree seen by a degree_distribution answer
        self.max_degree: int | None = None

    def incident(self, v: int) -> np.ndarray:
        return self._inc_keys[self._inc_ptr[v]:self._inc_ptr[v + 1]]

    def measures(self, needs: dict) -> dict:
        """``needs`` maps time -> set of (measure, v); returns
        (t, measure, v) -> value, replaying the log once in time order."""
        last = np.full(self.k + self.n_cap, -1, np.int64)
        lo, out = 0, {}
        for t in sorted(needs):
            hi = int(np.searchsorted(self.t, t, side="right"))
            np.maximum.at(last, self.item[lo:hi], np.arange(lo, hi))
            lo = hi
            alive = (last >= 0) & self.adds[np.maximum(last, 0)]
            edges, nodes = alive[:self.k], alive[self.k:]
            for measure, v in needs[t]:
                out[t, measure, v] = self._measure(measure, v, edges, nodes)
        return out

    def _measure(self, measure, v, edges, nodes):
        if measure == "degree":
            return int(edges[self.incident(v)].sum())
        n_e, n_n = int(edges.sum()), int(nodes.sum())
        if measure == "num_edges":
            return n_e
        if measure == "num_nodes":
            return n_n
        if measure == "avg_degree":
            return np.float32(2.0) * np.float32(n_e) / np.float32(max(n_n, 1))
        ku, kv = self.ku[edges], self.kv[edges]
        if measure == "degree_distribution":
            deg = (np.bincount(ku, minlength=self.n_cap)
                   + np.bincount(kv, minlength=self.n_cap))
            self.max_degree = max(self.max_degree or 0, int(deg.max()))
            return np.bincount(np.minimum(deg, DEGREE_BINS), weights=nodes,
                               minlength=DEGREE_BINS + 1).astype(np.int64)
        if measure == "triangles":
            adj = np.zeros((self.n_cap, self.n_cap), bool)
            adj[ku, kv] = adj[kv, ku] = True
            rows = np.packbits(adj, axis=1)
            common = np.unpackbits(rows[ku] & rows[kv], axis=1).sum()
            return int(common) // 3
        raise ValueError(f"no reference for {measure!r}")

    def answers(self, queries, shift: int = 0) -> list:
        """Reference answer per query (anything with the fields of the
        store's ``Query``).  ``shift`` reads every time that many units
        earlier: the control, a store serving a stale snapshot."""
        needs: dict = {}
        for q in queries:
            for t in times(q):
                needs.setdefault(max(int(t) - shift, 0), set()).add(
                    (q.measure, q.v))
        vals = self.measures(needs)
        out = []
        for q in queries:
            series = [vals[max(int(t) - shift, 0), q.measure, q.v]
                      for t in times(q)]
            if q.kind == "point":
                out.append(series[0])
            elif q.kind == "diff":
                out.append(abs(series[-1] - series[0]))
            elif q.kind == "evolve":
                out.append(np.asarray(series))
            elif q.agg == "mean":
                # the measure's exact f32 sum of integers over the width
                out.append(np.float32(sum(series)) / np.float32(len(series)))
            else:
                out.append(min(series) if q.agg == "min" else max(series))
        return out


def times(q) -> list[int]:
    """The times a query reads."""
    if q.kind == "point":
        return [q.t_k]
    if q.kind == "diff":
        return [q.t_k, q.t_l]
    stride = q.stride if q.kind == "evolve" else 1
    return list(range(q.t_k, q.t_l + 1, stride))


def same(got, want) -> bool:
    """Exact equality of one served answer with the reference's."""
    g, w = np.asarray(got), np.asarray(want)
    return g.shape == w.shape and np.array_equal(g.astype(np.float64),
                                                 w.astype(np.float64))
