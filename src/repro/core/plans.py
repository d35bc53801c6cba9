"""Historical-query plans (paper §3.2, Table 2).

Query taxonomy: {point, range-differential, range-aggregate} ×
{node-centric, global}.  Plans:

* two-phase  — reconstruct snapshot(s), then measure (all query types)
* delta-only — range-differential node-centric, straight off the log
* hybrid     — point / range-aggregate node-centric: one measure on
  SG_tcur + a corrective pass over the window's ops

Each plan comes in an unindexed variant (mask the whole log) and an
indexed variant (temporal index → windowed slice; node-centric index →
per-node op list) — the four curves of the paper's Figure 1.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.delta import ADD_EDGE, REM_EDGE, Delta
from repro.core.graph import DenseGraph, EdgeGraph
from repro.core.index import NodeIndex, gather_node_ops, gather_window
from repro.core.partial import partial_reconstruct, seed_mask
from repro.core.queries import (EDGE_GLOBAL_MEASURES, EDGE_NODE_MEASURES,
                                GLOBAL_MEASURES, NODE_MEASURES, div_rn)
from repro.core.reconstruct import (node_degree_series, reconstruct_dense,
                                    reconstruct_edge,
                                    reconstruct_sequential)

Aggregate = Literal["mean", "min", "max"]


_KINDS = ("point", "diff", "agg", "evolve")
_RANGE_KINDS = ("diff", "agg", "evolve")
_AGGS = ("mean", "min", "max")


@dataclasses.dataclass(frozen=True)
class Query:
    """A historical query (paper Table 1).

    This dataclass is THE validated construction path for every query
    in the system — the engine, the serving frontend, and the
    ``GraphSession`` facade all consume it as-is, so a malformed query
    fails here with a clear ``ValueError`` instead of deep inside a
    jitted kernel.  ``scope`` may be omitted: it is inferred from ``v``
    (node-centric iff a node is given).  Time-vs-watermark violations
    are intentionally NOT checked here (a Query is store-independent);
    they surface as ``WatermarkError`` — a ``ValueError`` subclass —
    at evaluation time.
    """

    kind: Literal["point", "diff", "agg", "evolve"] = "point"
    scope: Literal["node", "global"] | None = None
    measure: str = ""             # key into NODE_MEASURES / GLOBAL_MEASURES
    t_k: int = 0                  # point time, or range start
    t_l: int | None = None        # range end (diff/agg/evolve)
    v: int | None = None          # node (node-centric)
    agg: Aggregate = "mean"
    stride: int = 1               # evolve: sample every ``stride`` units

    def __post_init__(self):
        from repro.core.queries import edge_supported
        if self.kind not in _KINDS:
            raise ValueError(f"unknown query kind {self.kind!r} "
                             f"(one of {_KINDS})")
        if self.scope is None:
            object.__setattr__(self, "scope",
                               "node" if self.v is not None else "global")
        if self.scope not in ("node", "global"):
            raise ValueError(f"unknown scope {self.scope!r} "
                             "(node | global)")
        known = (NODE_MEASURES if self.scope == "node"
                 else GLOBAL_MEASURES)
        if self.measure not in known and not edge_supported(self.measure,
                                                            self.scope):
            raise ValueError(
                f"unknown {self.scope}-scope measure {self.measure!r} "
                f"(known: {', '.join(sorted(known))})")
        if self.scope == "node" and self.v is None:
            raise ValueError(f"node-scope query {self.measure!r} needs "
                             "v=<node id>")
        if self.kind in _RANGE_KINDS:
            if self.t_l is None:
                raise ValueError(f"{self.kind!r} query needs a time range"
                                 " — pass t_l (range end) as well as t_k")
            if self.t_l < self.t_k:
                raise ValueError(f"empty time range: t_l={self.t_l} < "
                                 f"t_k={self.t_k}")
        if self.kind == "evolve":
            if self.stride <= 0:
                raise ValueError(f"evolve stride must be >= 1, got "
                                 f"{self.stride}")
        elif self.stride != 1:
            raise ValueError(f"stride is an evolve parameter "
                             f"({self.kind!r} query got stride="
                             f"{self.stride})")
        if self.kind == "agg" and self.agg not in _AGGS:
            raise ValueError(f"unknown aggregate {self.agg!r} "
                             f"(one of {_AGGS})")


def _measure(g, q: Query):
    if isinstance(g, EdgeGraph):
        if q.scope == "node":
            return EDGE_NODE_MEASURES[q.measure](g, q.v)
        return EDGE_GLOBAL_MEASURES[q.measure](g)
    if q.scope == "node":
        return NODE_MEASURES[q.measure](g, q.v)
    return GLOBAL_MEASURES[q.measure](g)


def _aggregate(vals: jax.Array, agg: Aggregate):
    if agg == "mean":
        # Explicit sum/width (not jnp.mean, which lowers to a
        # reciprocal-multiply), correctly rounded: keeps the scalar path
        # bit-identical to the engine's masked batched aggregation.
        v = vals.astype(jnp.float32)
        return div_rn(jnp.sum(v), jnp.float32(v.shape[0]))
    return jnp.min(vals) if agg == "min" else jnp.max(vals)


# ---------------------------------------------------------------------------
# Two-phase plan (paper §3.2.1) — reconstruct, then evaluate
# ---------------------------------------------------------------------------


def two_phase(current, delta: Delta, t_cur, q: Query, *,
              partial_rows: bool = False, sequential: bool = False,
              passes: int = 2):
    """General plan, all query types, both snapshot layouts.

    ``sequential=True`` replays the paper's Algorithm 2 op-by-op (the
    faithful baseline); otherwise the vectorized LWW reconstruction.
    ``partial_rows=True`` enables partial reconstruction (§3.3.1) for
    node-centric queries.  An ``EdgeGraph`` ``current`` runs the O(E)
    slot-scatter reconstruction instead of the dense N² one
    (sequential / partial variants are dense-layout concepts).
    """
    is_edge = isinstance(current, EdgeGraph)
    if is_edge and (sequential or partial_rows):
        raise ValueError("sequential / partial variants need the dense "
                         "layout")

    def recon_from(g, t_base, t):
        if is_edge:
            return reconstruct_edge(g, delta, t_base, t)
        if sequential:
            return reconstruct_sequential(g, delta, t_base, t)
        return reconstruct_dense(g, delta, t_base, t)

    def recon(t):
        if not is_edge and not sequential and partial_rows \
                and q.scope == "node":
            return partial_reconstruct(current, delta, t_cur, t,
                                       seed_mask(current.n_cap, q.v),
                                       passes=passes)
        return recon_from(current, t_cur, t)

    if q.kind == "point":
        return _measure(recon(q.t_k), q)

    if q.kind == "diff":
        # Reconstruct SG_tl backward from current, then SG_tk backward
        # from SG_tl — reusing the nearer snapshot exactly as the paper's
        # point-range plan does (§3.2.1), so the shared part of the delta
        # is applied once.
        g_l = recon(q.t_l)
        g_k = recon_from(g_l, q.t_l, q.t_k)
        return jnp.abs(_measure(g_l, q) - _measure(g_k, q))

    # aggregate: one snapshot per time unit in [t_k, t_l]
    ts = jnp.arange(q.t_k, q.t_l + 1, dtype=jnp.int32)
    vals = jax.lax.map(lambda t: _measure(recon(t), q), ts)
    return _aggregate(vals, q.agg)


# ---------------------------------------------------------------------------
# Delta-only plan (paper §3.2.2) — range-differential node-centric
# ---------------------------------------------------------------------------


@jax.jit
def delta_only_degree_diff(delta: Delta, v, t_k, t_l):
    """|Δdegree(v)| over [t_k, t_l] by counting add/rem edge ops that
    touch v — no snapshot access at all."""
    win = delta.window_mask(t_k, t_l) & delta.valid_mask()
    touch = win & ((delta.u == v) | (delta.v == v))
    sign = jnp.where(delta.op == ADD_EDGE, 1,
                     jnp.where(delta.op == REM_EDGE, -1, 0))
    return jnp.abs(jnp.sum(sign * touch.astype(jnp.int32)))


@partial(jax.jit, static_argnames=("cap",))
def delta_only_degree_diff_indexed(delta: Delta, index: NodeIndex, v,
                                   t_k, t_l, cap: int):
    """Same, via the node-centric index: O(deg_ops) gathers."""
    sub = gather_node_ops(delta, index, v, cap)
    return delta_only_degree_diff(sub, v, t_k, t_l)


# ---------------------------------------------------------------------------
# Hybrid plan (paper §3.2.3) — point / aggregate node-centric
# ---------------------------------------------------------------------------


@jax.jit
def hybrid_point_degree(current: DenseGraph, delta: Delta, v, t_k, t_cur):
    """degree(v) at t_k = degree on SG_tcur − net additions in (t_k, t_cur]."""
    deg_cur = current.degree(v)
    win = delta.window_mask(t_k, t_cur) & delta.valid_mask()
    touch = win & ((delta.u == v) | (delta.v == v))
    sign = jnp.where(delta.op == ADD_EDGE, 1,
                     jnp.where(delta.op == REM_EDGE, -1, 0))
    return deg_cur - jnp.sum(sign * touch.astype(jnp.int32))


@partial(jax.jit, static_argnames=("cap",))
def hybrid_point_degree_indexed(current: DenseGraph, delta: Delta,
                                index: NodeIndex, v, t_k, t_cur, cap: int):
    sub = gather_node_ops(delta, index, v, cap)
    return hybrid_point_degree(current, sub, v, t_k, t_cur)


def masked_aggregate(vals: jax.Array, width, num_buckets: int,
                     agg: Aggregate):
    """Aggregate the first ``width`` of ``num_buckets`` bucketed values
    (the tail is padding).  Shared by the scalar hybrid plan and the
    engine's batched executors: one definition keeps the bit-identity
    guarantee between the scalar and batched paths (exact f32 sum of
    integer values, correctly rounded division by the width — not
    ``jnp.mean``, which lowers to a reciprocal-multiply)."""
    keep = jnp.arange(num_buckets, dtype=jnp.int32) < width
    if agg == "mean":
        return div_rn(jnp.sum(jnp.where(keep, vals, 0).astype(jnp.float32)),
                      jnp.asarray(width, jnp.float32))
    big = jnp.asarray(1 << 30, vals.dtype)
    if agg == "min":
        return jnp.min(jnp.where(keep, vals, big))
    return jnp.max(jnp.where(keep, vals, -big))


@partial(jax.jit, static_argnames=("num_buckets", "agg"))
def hybrid_agg_degree(current: DenseGraph, delta: Delta, v, t_k, t_l,
                      num_buckets: int, agg: Aggregate = "mean"):
    """Aggregate of degree(v) over [t_k, t_l]: measure once on SG_tcur,
    reverse-cumulative correction per time unit (one delta pass)."""
    series = node_degree_series(current.degree(v), delta, v, t_k,
                                num_buckets)
    return masked_aggregate(series, t_l - t_k + 1, num_buckets, agg)


def hybrid_agg_degree_windowed(current: DenseGraph, delta: Delta, v, t_k,
                               t_l, t_cur, num_buckets: int,
                               window_cap: int, agg: Aggregate = "mean"):
    """Temporal-index variant: slice (t_k, t_cur] once, then correct.

    Note the correction window must extend to t_cur (the anchor measure
    is on the *current* snapshot), so the slice is (t_k, t_cur].
    """
    sub = gather_window(delta, t_k, t_cur, window_cap)
    return hybrid_agg_degree(current, sub, v, t_k, t_l, num_buckets, agg)


# ---------------------------------------------------------------------------
# Plan selection (paper Table 2)
# ---------------------------------------------------------------------------

APPLICABLE = {
    ("point", "node"): ("two_phase", "hybrid"),
    ("point", "global"): ("two_phase",),
    ("diff", "node"): ("two_phase", "delta_only", "hybrid"),
    ("diff", "global"): ("two_phase",),
    ("agg", "node"): ("two_phase", "hybrid"),
    ("agg", "global"): ("two_phase",),
    # evolve executes on its own incremental sweep kernel; the planner
    # only chooses the anchor, so two_phase is the (sole) cost model.
    ("evolve", "node"): ("two_phase",),
    ("evolve", "global"): ("two_phase",),
}


def applicable_plans(q: Query) -> tuple[str, ...]:
    return APPLICABLE[(q.kind, q.scope)]


def evaluate(current: DenseGraph, delta: Delta, t_cur, q: Query,
             index: NodeIndex | None = None, plan: str = "auto",
             node_cap: int = 1024, **kw):
    """Evaluate a query with the cheapest applicable plan (or a forced
    one).  Degree queries get the specialised delta-only/hybrid paths;
    everything else falls back to two-phase, as in Table 2.

    Thin wrapper kept for compatibility: plan *choice* is delegated to
    the engine's cost-based ``Planner`` (``core.engine``); the kernels
    below remain the single-query execution path.  Deprecated as an
    entry point — new code should go through ``repro.api.GraphSession``
    (or ``store.evaluate_many`` when holding a bare store).
    """
    plans = applicable_plans(q)
    if plan == "auto":
        import numpy as np
        from repro.core.engine import AnchorSelector, Planner
        # one host copy of the timestamps keeps plan costing free of
        # per-candidate blocking device syncs
        selector = AnchorSelector((), (), t_cur=t_cur, current=current,
                                  t_host=np.asarray(delta.t))
        planner = Planner(selector, n_cap=current.n_cap, index=index,
                          node_cap=node_cap)
        plan = planner.choose(q, delta, t_cur).plan
    if plan not in plans:
        raise ValueError(f"plan {plan} not applicable to {q}")

    if plan == "two_phase" or q.measure != "degree":
        return two_phase(current, delta, t_cur, q, **kw)
    if plan == "delta_only":
        if index is not None:
            return delta_only_degree_diff_indexed(delta, index, q.v, q.t_k,
                                                  q.t_l, node_cap)
        return delta_only_degree_diff(delta, q.v, q.t_k, q.t_l)
    # hybrid
    if q.kind == "point":
        if index is not None:
            return hybrid_point_degree_indexed(current, delta, index, q.v,
                                               q.t_k, t_cur, node_cap)
        return hybrid_point_degree(current, delta, q.v, q.t_k, t_cur)
    if q.kind == "diff":
        d_l = hybrid_point_degree(current, delta, q.v, q.t_l, t_cur)
        d_k = hybrid_point_degree(current, delta, q.v, q.t_k, t_cur)
        return jnp.abs(d_l - d_k)
    num_buckets = int(q.t_l - q.t_k + 1)
    return hybrid_agg_degree(current, delta, q.v, q.t_k, q.t_l,
                             num_buckets, q.agg)
