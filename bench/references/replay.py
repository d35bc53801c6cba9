"""Plain reference of the temporal graph store: a numpy replay of the log.

The graph at time t is decided, per node and per edge key, by the last
op at or before t.  The answers follow the query semantics of the
store's documentation: a point query is the measure at ``t_k``; a diff
is ``|m(t_l) - m(t_k)|``; an agg is min, max or mean of the measure at
every unit of ``[t_k, t_l]``; a sweep (``evolve``) is the measure at
``t_k, t_k + stride, ... <= t_l``.  Float measures are f32, as the
store serves them, and every division is IEEE division.

Triangles are counted once over the whole history, op by op (see
``tri_after``), so the check costs one pass however many times it asks.

Shares no code with the program under test: it reads only the op
columns the benchmark generated.
"""
from __future__ import annotations

from functools import cached_property

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

    @cached_property
    def tri_after(self) -> np.ndarray:
        """int64 per op: triangles of the live-edge graph after it.

        An edge is live iff the last op on its key is an insert; node ops
        do not matter.  Inserting a dead key adds the common neighbours
        of its ends before the insert; removing a live key takes away
        those after the delete.  Built on the first need, in one pass."""
        loops = self.ku == self.kv
        if loops.any():
            raise ValueError(f"self-loop edge key on node "
                             f"{int(self.ku[loops][0])}: no triangle count")
        ku, kv = self.ku.tolist(), self.kv.tolist()
        nbr = [set() for _ in range(self.n_cap)]
        live = [False] * self.k
        count, out = 0, []
        for key, add in zip(self.item.tolist(), self.adds.tolist()):
            if key < self.k and add != live[key]:
                a, b = ku[key], kv[key]
                if add:
                    count += len(nbr[a] & nbr[b])
                    nbr[a].add(b)
                    nbr[b].add(a)
                else:
                    nbr[a].discard(b)
                    nbr[b].discard(a)
                    count -= len(nbr[a] & nbr[b])
                live[key] = add
            out.append(count)
        return np.asarray(out, np.int64)

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
                if measure == "triangles":
                    out[t, measure, v] = (int(self.tri_after[hi - 1])
                                          if hi else 0)
                else:
                    out[t, measure, v] = self._measure(measure, v, edges,
                                                       nodes)
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
        if measure == "degree_distribution":
            ku, kv = self.ku[edges], self.kv[edges]
            deg = (np.bincount(ku, minlength=self.n_cap)
                   + np.bincount(kv, minlength=self.n_cap))
            self.max_degree = max(self.max_degree or 0, int(deg.max()))
            return np.bincount(np.minimum(deg, DEGREE_BINS), weights=nodes,
                               minlength=DEGREE_BINS + 1).astype(np.int64)
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
