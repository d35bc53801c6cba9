"""Evolving scale-free op streams: the model the paper's evaluation used.

arXiv:1302.5549 (Table 3) made its dataset with Ren et al.'s evolving
scale-free model: Barabási–Albert arrivals with extra preferential edges
and random edge removals between versions.  Per arrival: one node with
``m_attach`` preferential edges, Poisson(``lam_extra``) extra edges
between preferentially chosen nodes, Poisson(``lam_remove``) removals of
uniformly chosen live edges.  Every arrival, extra edge and removal is
one event; ``events_per_unit`` events make one time unit.

A preferential pick draws from the endpoint pool (every endpoint an
added edge ever had, so a node's weight is the number of edges it ever
gained) with probability 0.9, else a uniform node among those that
exist; a pick equal to the excluded node is retried up to 8 times.

This is the benchmark's own copy of the model, kept apart from the
program so that no change to the program can change the data.  It
draws its random numbers in bulk and emits only legal ops (no duplicate
edge, removals of live edges only), so a store accepts every op and the
reference can replay the stream as it is.  Node removals are not part
of Table 3's dataset and are not modelled.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Op codes of the log (the store's wire format).
ADD_NODE, REM_NODE, ADD_EDGE, REM_EDGE = 0, 1, 2, 3

_P_POOL = 0.9      # share of preferential picks drawn from the pool
_TRIES = 8         # retries of a pick that hit the excluded node
_CHUNK = 1 << 20   # uniforms drawn per refill


@dataclasses.dataclass(frozen=True)
class Model:
    n_nodes: int
    m_attach: int
    lam_extra: float
    lam_remove: float
    events_per_unit: int
    n_seed: int = 4

    @classmethod
    def from_config(cls, data: dict) -> "Model":
        return cls(**{f.name: data[f.name] for f in dataclasses.fields(cls)
                      if f.name in data})


def _uniforms(rng):
    while True:
        yield from rng.random(_CHUNK).tolist()


def generate(model: Model, seed: int) -> np.ndarray:
    """The op stream as int64 columns ``(op, u, v, t)``, time-ordered."""
    rng = np.random.default_rng(seed)
    n, epu = model.n_nodes, model.events_per_unit
    arrivals = n - model.n_seed
    extra = rng.poisson(model.lam_extra, arrivals).tolist()
    remove = rng.poisson(model.lam_remove, arrivals).tolist()
    u = _uniforms(rng).__next__
    out: list[int] = []
    emit = out.extend
    endpoints: list[int] = []
    edges: list[int] = []                  # live edge keys a * n + b
    pos: dict[int, int] = {}               # key -> index in ``edges``

    def add_edge(a: int, b: int, t: int, pos=pos, edges=edges,
                 pool=endpoints.append, emit=emit) -> None:
        if a == b:
            return
        if a > b:
            a, b = b, a
        key = a * n + b
        if key in pos:
            return
        pos[key] = len(edges)
        edges.append(key)
        pool(a)
        pool(b)
        emit((ADD_EDGE, a, b, t))

    def pick(excl: int, upper: int, u=u, endpoints=endpoints) -> int:
        for _ in range(_TRIES):
            if endpoints and u() < _P_POOL:
                c = endpoints[int(u() * len(endpoints))]
            else:
                c = int(u() * upper)
            if c != excl:
                return c
        return excl                        # degenerate: add_edge rejects

    t = 1
    for i in range(model.n_seed):
        emit((ADD_NODE, i, i, t))
    for i in range(model.n_seed):
        for k in range(i + 1, model.n_seed):
            add_edge(i, k, t)
    ev = 1
    t += ev % epu == 0
    for k, nid in enumerate(range(model.n_seed, n)):
        emit((ADD_NODE, nid, nid, t))
        for _ in range(model.m_attach):
            add_edge(nid, pick(nid, nid), t)
        ev += 1
        t += ev % epu == 0
        for _ in range(extra[k]):
            a = pick(-1, nid + 1)
            add_edge(a, pick(a, nid + 1), t)
            ev += 1
            t += ev % epu == 0
        for _ in range(remove[k]):
            if not edges:
                break
            idx = int(u() * len(edges))
            key = edges[idx]
            last = edges.pop()
            if idx < len(edges):
                edges[idx] = last
                pos[last] = idx
            del pos[key]
            emit((REM_EDGE, key // n, key % n, t))
            ev += 1
            t += ev % epu == 0
    return np.asarray(out, np.int64).reshape(-1, 4).T.copy()


def counts(cols: np.ndarray) -> dict:
    """Inserted nodes, inserted and removed edges, ops, time units."""
    op = cols[0]
    return {"nodes_inserted": int((op == ADD_NODE).sum()),
            "edges_inserted": int((op == ADD_EDGE).sum()),
            "edges_removed": int((op == REM_EDGE).sum()),
            "ops": int(op.size), "t_max": int(cols[3].max())}
