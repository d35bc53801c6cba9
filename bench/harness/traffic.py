"""The one generator of traffic: requests and arrivals from a mix file.

A mix (``mixes/<name>.json``) holds:

* ``arrival``: ``{"kind": "closed", "clients": C, "stream_per_client":
  S}`` — C callers that each wait for their answer before the next,
  each from a stream of S requests;
* ``queries``: weighted request templates.  Each has ``weight``,
  ``kind`` (point, diff, agg, evolve), ``scope`` and ``measure``, and as
  the kind needs: ``span_max`` (units) or ``span_div`` (history
  divisor) bounding ``t_l - t_k`` of a diff or agg from above
  (exclusive) and ``span_min`` from below, ``aggs`` to choose from,
  ``stride_div`` for a sweep over the whole history, and for node scope
  ``hubs`` / ``hub_share`` (that share of requests asks for a node below
  ``hubs``, the rest for a uniform node);
* ``warmup``: first, for every template and every size in
  ``batch_sizes``, ``rounds`` batches of that many requests of that
  template sent together (each group shape the window can form), then
  ``seconds`` of the cell's own traffic;
* ``trace``: ``profile_seconds`` of the window the traced run profiles.

Every seed gets the same work in the same order: the count of each
template is fixed by the weights, a template's times one stratum of
the history each, and both are shuffled by a generator that does not
depend on the seed (on the chip, a request order drawn per seed moved
a run's median latency by a factor of four, and times drawn freely by
a factor of three).  The offset of the times inside their strata,
nodes, widths, aggregates and the data are drawn from the seed.
"""
from __future__ import annotations

from collections import namedtuple

import numpy as np

Req = namedtuple("Req", "kind scope measure t_k t_l v agg stride")


def allocate(weights, n: int) -> list[int]:
    """Counts per weight summing to n (largest remainders)."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    rest = np.argsort(-(exact - counts), kind="stable")
    counts[rest[:n - counts.sum()]] += 1
    return counts.tolist()


class Sampler:
    """Requests of one mix over the served history [t_lo, t_hi]."""

    def __init__(self, mix: dict, t_lo: int, t_hi: int, n_nodes: int):
        self.entries = mix["queries"]
        self.t_lo, self.t_hi, self.n_nodes = int(t_lo), int(t_hi), n_nodes

    def _span(self, e: dict) -> int:
        if "span_div" in e:
            return max(1, (self.t_hi - self.t_lo) // e["span_div"])
        return int(e["span_max"])

    def one(self, rng, e: dict, q: float | None = None,
            hub: bool | None = None) -> Req:
        """One request of template ``e``.  ``q`` in [0, 1) places its
        time in the history and ``hub`` says whether it asks for a hub;
        left out, both are drawn from ``rng``."""
        lo, hi = self.t_lo, self.t_hi
        q = rng.random() if q is None else q
        v = None
        if e["scope"] == "node":
            if hub is None:
                hub = rng.random() < e.get("hub_share", 0.0)
            v = int(rng.integers(0, e["hubs"] if hub else self.n_nodes))
        kind, agg, stride = e["kind"], "", 1
        if kind == "evolve":
            t_k, t_l = lo, hi
            stride = max(1, (hi - lo) // e["stride_div"])
        elif kind == "point":
            t_k, t_l = lo + int(q * (hi - lo + 1)), None
        else:
            span = min(self._span(e), hi - lo + 1)
            w = int(rng.integers(min(e.get("span_min", 0), span - 1), span))
            t_k = lo + int(q * (hi - w - lo + 1))
            t_l = t_k + w
            if kind == "agg":
                aggs = e["aggs"]
                agg = aggs[int(rng.integers(len(aggs)))]
        return Req(kind, e["scope"], e["measure"], t_k, t_l, v, agg, stride)

    def stream(self, rng, n: int, order) -> list[Req]:
        """n requests: each template its weight's share, in the order
        ``order`` shuffles them.  A template's c requests take the times
        of c equal strata of the history, one each, and the hub share
        of them asks for hubs; ``order`` decides which request gets which
        stratum and which ask for hubs, ``rng`` one offset inside the
        strata per template and every other parameter."""
        counts = allocate([e["weight"] for e in self.entries], n)
        kinds = order.permutation(np.repeat(np.arange(len(counts)), counts))
        plans = []
        for e, c in zip(self.entries, counts):
            strata = order.permutation(c)
            hubs = order.permutation(c) < round(e.get("hub_share", 0.0) * c)
            plans.append(iter(zip((strata + rng.random()) / max(c, 1),
                                  hubs.tolist())))
        return [self.one(rng, self.entries[i], *next(plans[i]))
                for i in kinds]


def to_query(r: Req):
    """The program's ``Query`` for one request."""
    from repro.api import Query
    if r.kind == "point":
        return Query("point", r.scope, r.measure, t_k=r.t_k, v=r.v)
    if r.kind == "agg":
        return Query("agg", r.scope, r.measure, t_k=r.t_k, t_l=r.t_l, v=r.v,
                     agg=r.agg)
    return Query(r.kind, r.scope, r.measure, t_k=r.t_k, t_l=r.t_l, v=r.v)
