"""Unified historical-query engine: anchor planner + batched executor.

This module centralizes the choice logic that used to be spread across
``store.snapshot_at`` (inline anchor costing), ``plans.evaluate``
(hard-coded auto plan rule) and ``partial.py`` (caller-built seed
masks), mirroring how DeltaGraph centralizes snapshot-retrieval
planning.  Components map to the paper as follows:

* ``AnchorSelector`` — §2.2 (materialized snapshots + Theorem 1): the
  anchor candidates are SG_tcur plus every materialized snapshot,
  costed either by time distance or by #ops in the connecting delta
  window (``count_window_ops``, O(log M) via the temporal index).

* ``Planner`` — §3.2 (Table 2 plans) × §3.3 (partial reconstruction,
  delta indexes): picks {two-phase, delta-only, hybrid} and the
  {indexed, windowed, partial} variant per query from delta/index
  statistics, producing an explicit ``PlanChoice``.

* ``evaluate_many`` — the batched multi-query executor (beyond-paper;
  the successor system "Storing and Analyzing Historical Graph Data at
  Scale" batches multi-snapshot retrieval the same way): B queries are
  grouped by (plan choice, anchor), their times/nodes padded into
  device arrays, and each group runs as ONE ``vmap``'d reconstruction +
  measurement program — one LWW scatter pass amortized over all the
  queries sharing an anchor window — instead of B separate host-side
  dispatches.

The executor reuses the exact kernels from ``plans.py`` under ``vmap``,
so batched results bit-match the single-query path (integer measures
are exact; see tests/test_engine.py).  ``core/distributed.py`` will
shard these groups next: the (anchor, plan) group is precisely the unit
that is device-parallel.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delta import Delta, pow2_capacity as _pow2
from repro.core.graph import DenseGraph, EdgeGraph, dense_to_edge
from repro.core.index import (NodeIndex, count_window_ops, gather_node_ops,
                              gather_window)
from repro.core.partial import partial_reconstruct, seed_mask
from repro.core.plans import (Query, applicable_plans,
                              delta_only_degree_diff, hybrid_point_degree,
                              masked_aggregate)
from repro.core.queries import (EDGE_GLOBAL_MEASURES, EDGE_NODE_MEASURES,
                                GLOBAL_MEASURES, NODE_MEASURES,
                                edge_supported)
from repro.core.reconstruct import (degree_series, reconstruct_dense,
                                    reconstruct_edge)
# window_ops_count: #ops with t in (t_lo, t_hi] on a host timestamp
# copy or a SegmentedDeltaView — keeps the planning loop free of
# device round-trips (costing B queries is binary searches, not 2B
# syncs); one definition shared with serving.policy.
from repro.core.segments import (SegmentedDeltaView,
                                 window_ops_count as _window_ops_host)
from repro.obs import clock as _clock
from repro.obs.metrics import COUNT_BUCKETS, default_registry
from repro.obs.trace import NULL_SPAN, trace_span


class WatermarkError(ValueError, RuntimeError):
    """A query's time lies beyond the engine's serving watermark
    ``t_served``: ops at that time may still sit in a pending ingest
    buffer, so the frozen state cannot answer it exactly.  Raised by
    watermarked engines (``repro.serving``); callers choose between
    surfacing it and blocking on an epoch swap.  Subclasses
    ``ValueError`` (a t-past-watermark query is an invalid argument at
    this instant, and the validated-``Query`` API contract promises
    ``ValueError`` for every malformed request) and keeps the historic
    ``RuntimeError`` base for existing handlers."""




# ---------------------------------------------------------------------------
# Anchor selection (paper §2.2, Theorem 1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AnchorCandidate:
    """One reconstruction anchor: the current snapshot (id == -1) or a
    materialized snapshot (id == index into the materialized store)."""

    anchor_id: int
    t: int
    cost: int


class AnchorSelector:
    """Picks the cheapest anchor snapshot for reconstructing SG_t.

    Candidates are SG_tcur (when given) plus every materialized
    snapshot; the "current snapshot competes with the materialized
    ones" rule that used to be inlined in ``store.snapshot_at`` lives
    here now.  ``method='ops'`` prices a candidate by #ops in the
    window between it and the query time (operation-based selection,
    exact cost proxy, O(log M) each via the temporal index);
    ``'time'`` by |t_candidate - t_query| (the paper's cheap variant,
    wrong under bursty logs).
    """

    def __init__(self, times: Sequence[int], snapshots: Sequence[DenseGraph],
                 *, t_cur: int | None = None,
                 current: DenseGraph | None = None,
                 t_host=None):
        assert len(times) == len(snapshots)
        self.times = [int(t) for t in times]
        self.snapshots = list(snapshots)
        self.t_cur = t_cur
        self.current = current
        # host copy of delta.t — or a SegmentedDeltaView — for
        # sync-free window costing (see _window_ops_host)
        self.t_host = t_host

    def candidates(self, t_query: int, delta: Delta,
                   method: Literal["time", "ops"] = "ops"
                   ) -> list[AnchorCandidate]:
        cands = []

        def cost(t_a: int) -> int:
            if method == "time":
                return abs(int(t_a) - int(t_query))
            if self.t_host is not None:
                return _window_ops_host(self.t_host, min(t_a, t_query),
                                        max(t_a, t_query))
            return int(count_window_ops(delta, min(t_a, t_query),
                                        max(t_a, t_query)))

        if self.current is not None and self.t_cur is not None:
            cands.append(AnchorCandidate(-1, int(self.t_cur),
                                         cost(self.t_cur)))
        for i, t_a in enumerate(self.times):
            cands.append(AnchorCandidate(i, t_a, cost(t_a)))
        if not cands:
            raise ValueError("no anchor candidates (no current snapshot "
                             "and no materialized snapshots)")
        return cands

    def select(self, t_query: int, delta: Delta,
               method: Literal["time", "ops"] = "ops") -> AnchorCandidate:
        cands = self.candidates(t_query, delta, method)
        # Stable tie-break: earliest candidate wins (current first), so
        # selection is deterministic and batch grouping reproducible.
        return min(cands, key=lambda c: c.cost)

    def get(self, anchor_id: int) -> tuple[int, DenseGraph]:
        if anchor_id == -1:
            if self.current is None:
                raise ValueError("no current snapshot registered")
            return int(self.t_cur), self.current
        return self.times[anchor_id], self.snapshots[anchor_id]


# ---------------------------------------------------------------------------
# Plan choice (paper §3.2 Table 2 × §3.3 variants)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanChoice:
    """A fully resolved execution recipe for one query."""

    plan: str                 # two_phase | delta_only | hybrid
    anchor_id: int = -1       # -1 = current snapshot
    t_anchor: int = 0
    indexed: bool = False     # node-centric index (§3.3.2)
    windowed: bool = False    # temporal-index window slice (§3.3.2)
    partial: bool = False     # partial reconstruction (§3.3.1)
    layout: str = "dense"     # dense (N² adjacency) | edge (E slots)
    cost: int = 0             # planner's op-count estimate


# Fixed per-program surcharge (op-count equivalents) of launching one
# multi-device dispatch: collective setup + per-device launch latency.
# A group is only sharded when the work it *removes* from the critical
# path exceeds this, so tiny groups stay single-device.
DISPATCH_OVERHEAD_OPS = 4096


class Planner:
    """Cost-based plan selection from delta / index statistics.

    Costs are op counts (the paper's unit): a plan pays for the delta
    window it must traverse, plus a layout surcharge for dense
    reconstruction (the N² LWW scatter) that the measure-only plans
    avoid.  Degree queries admit all of Table 2; other measures fall
    back to two-phase, as in the paper.

    The planner also owns the *cross-device dispatch* cost term
    (``shard_mode``): given a (plan, anchor) group and a mesh size it
    decides whether the group is worth sharding at all, and along
    which axis (query batch vs adjacency rows).
    """

    def __init__(self, selector: AnchorSelector, *, n_cap: int,
                 index: NodeIndex | None = None, node_cap: int = 1024,
                 selection: Literal["time", "ops"] = "ops",
                 dispatch_overhead: int = DISPATCH_OVERHEAD_OPS,
                 e_cap: int = 0, dense_available: bool = True,
                 edge_available: bool = False, seg_view=None):
        self.selector = selector
        self.n_cap = int(n_cap)
        self.index = index
        self.node_cap = int(node_cap)
        self.selection = selection
        self.dispatch_overhead = int(dispatch_overhead)
        # edge-slot layout statistics (0 / False when the engine has no
        # slot registry — e.g. engines built from bare arrays)
        self.e_cap = int(e_cap)
        self.dense_available = bool(dense_available)
        self.edge_available = bool(edge_available)
        # Segmented log (core.segments): per-segment node-count
        # statistics stand in for the node-centric index's row extents
        # when no index was built.
        self.seg_view = seg_view
        self._row_ptr_host: np.ndarray | None = None

    def _window_ops(self, delta: Delta, t_lo, t_hi) -> int:
        if self.selector.t_host is not None:
            return _window_ops_host(self.selector.t_host, t_lo, t_hi)
        return int(count_window_ops(delta, t_lo, t_hi))

    def _node_ops(self, v: int) -> int | None:
        """#ops touching node v: node-centric index row extent when an
        index was built, else the segmented log's per-segment node
        counts (same counting rule), else unknown."""
        if v is None:
            return None
        if self.index is not None:
            if self._row_ptr_host is None:
                self._row_ptr_host = np.asarray(self.index.row_ptr)
            ptr = self._row_ptr_host
            return int(ptr[v + 1] - ptr[v])
        if self.seg_view is not None:
            return self.seg_view.node_ops(v)
        return None

    def layout_for(self, q: Query, plan: str) -> str:
        """{dense, edge} execution layout for one query.

        Edge-slot layout is eligible when the engine carries a slot
        registry and the measure has an edge implementation; among
        eligible queries the N²-vs-E cost term decides: a two-phase
        reconstruction pays the dense LWW scatter (O(N²), or O(N) with
        partial reconstruction) vs the slot scatter (O(E)).  The
        measure-only plans (hybrid / delta-only) never materialize N²,
        so they keep the dense row read unless the dense snapshot is
        absent entirely (large-graph edge-only serving).
        """
        if not self.edge_available or not edge_supported(q.measure,
                                                         q.scope):
            return "dense"
        if not self.dense_available:
            return "edge"
        if plan != "two_phase":
            return "dense"
        dense_scatter = (self.n_cap if q.scope == "node"
                         and q.measure == "degree" and q.kind != "diff"
                         else self.n_cap ** 2 // 64)
        return "edge" if self.e_cap // 64 < dense_scatter else "dense"

    def choose(self, q: Query, delta: Delta, t_cur: int) -> PlanChoice:
        plans = applicable_plans(q)
        anchor = self.selector.select(q.t_k, delta, self.selection)
        if q.kind == "evolve":
            # The sweep executor reconstructs ONCE at t_lo and scans the
            # window incrementally — the planner's only real choices are
            # the anchor (nearest to t_lo, same Theorem-1 costing as any
            # two-phase query) and the layout.  Partial / windowed /
            # indexed are point-plan concepts and stay off.
            return PlanChoice(plan="two_phase", anchor_id=anchor.anchor_id,
                              t_anchor=anchor.t,
                              layout=self.layout_for(q, "two_phase"),
                              cost=anchor.cost)
        # two-phase traverses the anchor→query window and pays the dense
        # scatter; partial reconstruction (node scope) reduces the
        # scatter to the closure rows.
        scatter = self.n_cap if q.scope == "node" else self.n_cap ** 2 // 64
        cost_two = anchor.cost + scatter
        # Partial reconstruction is only auto-enabled where its closure
        # provably covers the query: single-window reconstructions of a
        # degree measure.  diff composes a second reconstruction from
        # the first's (already truncated) partial snapshot — stale rows
        # outside the first closure would leak — and non-degree
        # measures keep the scalar auto path's dense behavior.
        use_partial = (q.scope == "node" and q.measure == "degree"
                       and q.kind != "diff")

        best_plan, best_cost = "two_phase", cost_two
        if q.measure == "degree" and q.scope == "node":
            n_ops = self._node_ops(q.v)
            if "hybrid" in plans:
                # one corrective pass over (t_k, t_cur]
                c = self._window_ops(delta, q.t_k, t_cur)
                if n_ops is not None:
                    c = min(c, n_ops)
                if c < best_cost:
                    best_plan, best_cost = "hybrid", c
            if "delta_only" in plans:
                c = self._window_ops(delta, q.t_k, q.t_l)
                if n_ops is not None:
                    c = min(c, n_ops)
                if c < best_cost:
                    best_plan, best_cost = "delta_only", c

        indexed = (self.index is not None and q.scope == "node"
                   and best_plan in ("delta_only", "hybrid")
                   and (self._node_ops(q.v) or 0) <= self.node_cap)
        # windowed pays off when the anchor window is much smaller than
        # the full log (pow2 capacities bound recompiles).
        windowed = (best_plan == "two_phase"
                    and _pow2(anchor.cost, 64) * 2 <= delta.capacity)
        layout = self.layout_for(q, best_plan)
        return PlanChoice(plan=best_plan, anchor_id=anchor.anchor_id,
                          t_anchor=anchor.t, indexed=indexed,
                          windowed=windowed,
                          partial=(use_partial and best_plan == "two_phase"
                                   and layout == "dense"),
                          layout=layout, cost=best_cost)

    # ------------------------------------------------- cross-device dispatch

    def shard_mode(self, key, b: int, n_dev: int, delta_cap: int,
                   *, force: bool = False) -> str | None:
        """How to shard one (plan, anchor) group of ``b`` queries over
        ``n_dev`` devices: ``"rows"`` (dense two-phase row-sharded
        scatter + psum measures), ``"slots"`` (edge two-phase
        slot-sharded scatter + psum measures), ``"batch"`` (replicate
        graph, split the query axis), or ``None`` (stay single-device).

        The decision is a cost term: a multi-device program pays a
        fixed ``dispatch_overhead`` (collective setup + launch), so it
        only wins when the work moved *off* the critical path —
        ``group_work · (1 − 1/D)`` — exceeds that overhead.  ``force``
        skips the threshold (tests, benchmarks) but never makes an
        unshardable group shardable.
        """
        from repro.core.distributed import ROW_MEASURES, SLOT_MEASURES
        if n_dev <= 1:
            return None
        evolve = getattr(key, "kind", "") == "evolve"
        if key.plan == "two_phase" and getattr(key, "layout",
                                               "dense") == "edge":
            # Slot-sharding: the LWW slot scatter splits over the slot
            # axis; measures combine as psum'd integer partials exactly
            # like row-sharding (slots partition the edge set, so
            # per-shard popcounts/degree counts sum to the global
            # value — same exactness argument, 1-D instead of 2-D).
            # evolve additionally admits degree_distribution: the sweep
            # carries full psum'd degree counts, so the histogram is a
            # replicated finalization, not a partial.
            slot_ok = (key.measure in SLOT_MEASURES
                       or (evolve and key.measure == "degree_distribution"))
            if slot_ok and self.e_cap and self.e_cap % n_dev == 0:
                # per query: one masked log scan + one slot scatter
                work = b * max(delta_cap, self.e_cap)
                if force or work - work // n_dev > self.dispatch_overhead:
                    return "slots"
        elif key.plan == "two_phase":
            # Row-sharding needs a row-decomposable measure, an even
            # row split, and no partial reconstruction (the closure
            # mask is a full-graph object).  Evolve's dense path has no
            # row-sharded sweep — it batch-shards instead (the sharded
            # sweep is the slot path above).
            if (key.measure in ROW_MEASURES and not key.partial
                    and not evolve and self.n_cap % n_dev == 0):
                # one dense LWW scatter per query (agg kinds do one per
                # bucket — strictly more, so the bound is conservative)
                work = b * (self.n_cap ** 2 // 64)
                if force or work - work // n_dev > self.dispatch_overhead:
                    return "rows"
            # fall through: a two-phase group is still batch-shardable
            # (each device reconstructs dense, but only for its own
            # queries).
        if b < n_dev and not force:
            return None
        # per-query kernel work is dominated by the masked log scan
        work = b * max(delta_cap, self.n_cap)
        if force or work - work // n_dev > self.dispatch_overhead:
            return "batch"
        return None


# ---------------------------------------------------------------------------
# Batched kernels (vmap over the plans.py kernels)
# ---------------------------------------------------------------------------


def _snapshot_bytes(g) -> int:
    """Approximate device footprint of a cached snapshot (bool N² for
    dense, (4+4+1)·E + N for edge) — drives the reconstruction LRU's
    byte budget."""
    if isinstance(g, EdgeGraph):
        return 9 * g.e_cap + g.n_cap
    return g.n_cap * g.n_cap + g.n_cap


def _measure_named(g, measure: str, scope: str, v):
    """Measure dispatch over both snapshot layouts: the edge-layout
    measures are segment reductions with the exact same integer counts
    and f32 finalizations as the dense ones, so layout never changes a
    result bit (tests/test_engine.py edge-parity).  Runs under the
    ``measure`` name scope (metadata only), beside the reconstruction's
    ``replay``."""
    with jax.named_scope("measure"):
        if isinstance(g, EdgeGraph):
            if scope == "node":
                return EDGE_NODE_MEASURES[measure](g, v)
            return EDGE_GLOBAL_MEASURES[measure](g)
        if scope == "node":
            return NODE_MEASURES[measure](g, v)
        return GLOBAL_MEASURES[measure](g)


@partial(jax.jit, static_argnames=("measure", "scope"))
def batch_measure(g, vs, *, measure: str, scope: str):
    """Measure one (already reconstructed) snapshot at B nodes — the
    execution half of the per-anchor reconstruction cache: a cache hit
    skips the LWW delta replay and runs only this."""
    return jax.vmap(lambda v: _measure_named(g, measure, scope, v))(vs)


@partial(jax.jit, static_argnames=("measure", "scope", "use_partial",
                                   "passes"))
def batch_two_phase_point(anchor: DenseGraph, delta: Delta, t_anchor,
                          ts, vs, *, measure: str, scope: str,
                          use_partial: bool = False, passes: int = 2):
    """B point queries against one anchor: one vmapped LWW pass."""

    def one(t, v):
        if use_partial and scope == "node":
            g = partial_reconstruct(anchor, delta, t_anchor, t,
                                    seed_mask(anchor.n_cap, v),
                                    passes=passes)
        else:
            g = reconstruct_dense(anchor, delta, t_anchor, t)
        return _measure_named(g, measure, scope, v)

    return jax.vmap(one)(ts, vs)


@partial(jax.jit, static_argnames=("measure", "scope", "use_partial",
                                   "passes"))
def batch_two_phase_diff(anchor: DenseGraph, delta: Delta, t_anchor,
                         tks, tls, vs, *, measure: str, scope: str,
                         use_partial: bool = False, passes: int = 2):
    """B range-differential queries: reconstruct SG_tl from the anchor,
    then SG_tk from SG_tl (reusing the nearer snapshot exactly as the
    single-query plan does, so bitwise parity holds)."""

    def one(tk, tl, v):
        if use_partial and scope == "node":
            g_l = partial_reconstruct(anchor, delta, t_anchor, tl,
                                      seed_mask(anchor.n_cap, v),
                                      passes=passes)
        else:
            g_l = reconstruct_dense(anchor, delta, t_anchor, tl)
        g_k = reconstruct_dense(g_l, delta, tl, tk)
        a = _measure_named(g_l, measure, scope, v)
        b = _measure_named(g_k, measure, scope, v)
        return jnp.abs(a - b)

    return jax.vmap(one)(tks, tls, vs)


@partial(jax.jit, static_argnames=("measure", "scope", "num_buckets",
                                   "agg", "use_partial", "passes"))
def batch_two_phase_agg(anchor: DenseGraph, delta: Delta, t_anchor,
                        tks, tls, vs, *, measure: str, scope: str,
                        num_buckets: int, agg: str,
                        use_partial: bool = False, passes: int = 2):
    """B range-aggregate queries, each over ≤ num_buckets time units:
    a vmapped scan of reconstructions (buckets past t_l are masked)."""

    def one(tk, tl, v):
        ts = tk + jnp.arange(num_buckets, dtype=jnp.int32)

        def m_at(t):
            if use_partial and scope == "node":
                g = partial_reconstruct(anchor, delta, t_anchor, t,
                                        seed_mask(anchor.n_cap, v),
                                        passes=passes)
            else:
                g = reconstruct_dense(anchor, delta, t_anchor, t)
            return _measure_named(g, measure, scope, v)

        vals = jax.lax.map(m_at, ts)
        return masked_aggregate(vals, tl - tk + 1, num_buckets, agg)

    return jax.vmap(one)(tks, tls, vs)


# ---- edge-slot-layout two-phase kernels (O(E) per query, no N²) ----
#
# Same shape as the dense batch_two_phase_* kernels with the LWW slot
# scatter (reconstruct_edge) in place of the dense cell scatter; the
# hybrid / delta-only kernels below are layout-polymorphic already
# (they only touch the snapshot through degree()/degrees(), which both
# layouts implement with identical integer results), so edge-layout
# groups of those plans reuse them with an EdgeGraph operand.


@partial(jax.jit, static_argnames=("measure", "scope"))
def batch_edge_two_phase_point(anchor: EdgeGraph, delta: Delta, t_anchor,
                               ts, vs, *, measure: str, scope: str):
    """B point queries against one edge-layout anchor: one vmapped
    1-D LWW slot scatter per query — O(B·(M + E)) instead of
    O(B·(M + N²))."""

    def one(t, v):
        g = reconstruct_edge(anchor, delta, t_anchor, t)
        return _measure_named(g, measure, scope, v)

    return jax.vmap(one)(ts, vs)


@partial(jax.jit, static_argnames=("measure", "scope"))
def batch_edge_two_phase_diff(anchor: EdgeGraph, delta: Delta, t_anchor,
                              tks, tls, vs, *, measure: str, scope: str):
    """B range-differential queries, nearer-snapshot reuse exactly like
    the dense diff kernel (SG_tl from the anchor, SG_tk from SG_tl)."""

    def one(tk, tl, v):
        g_l = reconstruct_edge(anchor, delta, t_anchor, tl)
        g_k = reconstruct_edge(g_l, delta, tl, tk)
        a = _measure_named(g_l, measure, scope, v)
        b = _measure_named(g_k, measure, scope, v)
        return jnp.abs(a - b)

    return jax.vmap(one)(tks, tls, vs)


@partial(jax.jit, static_argnames=("measure", "scope", "num_buckets",
                                   "agg"))
def batch_edge_two_phase_agg(anchor: EdgeGraph, delta: Delta, t_anchor,
                             tks, tls, vs, *, measure: str, scope: str,
                             num_buckets: int, agg: str):
    """B range-aggregate queries: a vmapped scan of slot
    reconstructions (buckets past t_l are masked, identically to the
    dense agg kernel)."""

    def one(tk, tl, v):
        ts = tk + jnp.arange(num_buckets, dtype=jnp.int32)

        def m_at(t):
            g = reconstruct_edge(anchor, delta, t_anchor, t)
            return _measure_named(g, measure, scope, v)

        vals = jax.lax.map(m_at, ts)
        return masked_aggregate(vals, tl - tk + 1, num_buckets, agg)

    return jax.vmap(one)(tks, tls, vs)


@jax.jit
def batch_hybrid_point(current: DenseGraph, delta: Delta, vs, tks, t_cur):
    return jax.vmap(hybrid_point_degree,
                    in_axes=(None, None, 0, 0, None))(current, delta, vs,
                                                      tks, t_cur)


@partial(jax.jit, static_argnames=("cap",))
def batch_hybrid_point_indexed(current: DenseGraph, delta: Delta,
                               index: NodeIndex, vs, tks, t_cur, cap: int):
    def one(v, tk):
        sub = gather_node_ops(delta, index, v, cap)
        return hybrid_point_degree(current, sub, v, tk, t_cur)

    return jax.vmap(one)(vs, tks)


@jax.jit
def batch_hybrid_diff(current: DenseGraph, delta: Delta, vs, tks, tls,
                      t_cur):
    def one(v, tk, tl):
        d_l = hybrid_point_degree(current, delta, v, tl, t_cur)
        d_k = hybrid_point_degree(current, delta, v, tk, t_cur)
        return jnp.abs(d_l - d_k)

    return jax.vmap(one)(vs, tks, tls)


@partial(jax.jit, static_argnames=("cap",))
def batch_hybrid_diff_indexed(current: DenseGraph, delta: Delta,
                              index: NodeIndex, vs, tks, tls, t_cur,
                              cap: int):
    def one(v, tk, tl):
        sub = gather_node_ops(delta, index, v, cap)
        d_l = hybrid_point_degree(current, sub, v, tl, t_cur)
        d_k = hybrid_point_degree(current, sub, v, tk, t_cur)
        return jnp.abs(d_l - d_k)

    return jax.vmap(one)(vs, tks, tls)


@partial(jax.jit, static_argnames=("w_q", "agg"))
def batch_hybrid_agg_per_node(current: DenseGraph, delta: Delta, vs, tks,
                              tls, w_q: int, agg: str):
    """Fallback for groups whose union window is too wide to
    materialize as an all-nodes series: one O(w_q) per-node series per
    query (B scatter passes, O(B·w_q) memory — no n_cap factor)."""
    from repro.core.reconstruct import node_degree_series

    def one(v, tk, tl):
        series = node_degree_series(current.degree(v), delta, v, tk, w_q)
        return masked_aggregate(series, tl - tk + 1, w_q, agg)

    return jax.vmap(one)(vs, tks, tls)


@partial(jax.jit, static_argnames=("w_total", "w_q", "agg"))
def batch_hybrid_agg(current: DenseGraph, delta: Delta, vs, tks, tls, t0,
                     t_cur, w_total: int, w_q: int, agg: str):
    """B range-aggregate degree queries off ONE shared all-nodes degree
    time-series: a single un-vmapped scatter pass over the delta
    (``degree_series``) covering the union window [t0, t0 + w_total),
    then per-query gathers + masked aggregation.  This is the "one
    delta pass amortized over all queries sharing a window" form —
    vmapping the per-node kernel instead costs B scatter passes.

    Bitwise-identical to the scalar ``hybrid_agg_degree``: both compute
    degree(v, τ) = deg_cur(v) − suffix-net(τ) in exact int32 and divide
    the exact f32 sum by the width.
    """
    series = degree_series(current, delta, t0, t0 + w_total - 1, w_total,
                           t_cur)                       # i32[w_total, N]

    def one(v, tk, tl):
        idx = (tk - t0) + jnp.arange(w_q, dtype=jnp.int32)
        vals = series[jnp.clip(idx, 0, w_total - 1), v]
        return masked_aggregate(vals, tl - tk + 1, w_q, agg)

    return jax.vmap(one)(vs, tks, tls)


@jax.jit
def batch_delta_only_diff(delta: Delta, vs, tks, tls):
    return jax.vmap(delta_only_degree_diff,
                    in_axes=(None, 0, 0, 0))(delta, vs, tks, tls)


@partial(jax.jit, static_argnames=("cap",))
def batch_delta_only_diff_indexed(delta: Delta, index: NodeIndex, vs, tks,
                                  tls, cap: int):
    def one(v, tk, tl):
        sub = gather_node_ops(delta, index, v, cap)
        return delta_only_degree_diff(sub, v, tk, tl)

    return jax.vmap(one)(vs, tks, tls)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _GroupKey:
    """Everything that must be equal for two queries to share one
    device program (static shapes / static jit args / anchor)."""

    plan: str
    kind: str
    scope: str
    measure: str
    agg: str            # "" unless kind == "agg"
    anchor_id: int
    indexed: bool
    windowed: bool
    partial: bool
    layout: str = "dense"
    stride: int = 0     # 0 unless kind == "evolve" (sweep sample step)


class GroupStats(list):
    """``last_group_stats``: the per-call list of (group key, batch,
    shard mode) rows, plus the reconstruction-cache counters for the
    call (hits skip the LWW delta replay entirely)."""

    def __init__(self, *a):
        super().__init__(*a)
        self.cache_hits = 0
        self.cache_misses = 0


class HistoricalQueryEngine:
    """Planner + batched executor over one store state.

    Construct via ``HistoricalQueryEngine.from_store(store)`` (or let
    ``TemporalGraphStore.engine()`` cache one).  The engine is a pure
    view: it never mutates the store; re-create it (or let the store's
    cache invalidate) after ingesting new ops.
    """

    def __init__(self, current: DenseGraph | None, delta,
                 t_cur: int, *,
                 mat_times: Sequence[int] = (),
                 mat_snapshots: Sequence[DenseGraph] = (),
                 index: NodeIndex | None = None, node_cap: int = 1024,
                 selection: Literal["time", "ops"] = "ops",
                 passes: int = 2, series_budget: int = 1 << 24,
                 mesh=None, current_edge: EdgeGraph | None = None,
                 snap_cache_cap: int = 16, t_host=None):
        if current is None and current_edge is None:
            raise ValueError("need a current snapshot in at least one "
                             "layout")
        self.current = current
        self.current_edge = current_edge
        # ``delta`` is the full device log (monolithic stores) OR a
        # ``SegmentedDeltaView`` (segmented stores): planning reads
        # only .capacity / window counts from it, and every executor
        # path materializes its per-group window through _plan_delta /
        # _group_delta, so the full log never hits the device when the
        # view is segmented.
        self.delta = delta
        self.view = delta if isinstance(delta, SegmentedDeltaView) else None
        self.t_cur = int(t_cur)
        self.index = index
        self.node_cap = int(node_cap)
        self.passes = int(passes)
        # max elements of the shared all-nodes degree series a single
        # agg group may materialize (i32; 1<<24 ≈ 64 MB)
        self.series_budget = int(series_budget)
        # Serving mesh (None → single-device).  Snapshot/delta arrays
        # are placed on it lazily per role (replicated for batch-axis
        # groups, row/slot-sharded per anchor for two-phase groups) and
        # cached, so steady-state serving does no host→device copies.
        self.mesh = mesh
        self._placed_rep: dict = {}     # (mesh, role) -> replicated tree
        self._placed_rows: dict = {}    # (mesh, anchor_id) -> row-sharded
        self._placed_slots: dict = {}   # (mesh, anchor_id) -> slot-sharded
        # Per-anchor reconstruction LRU: (anchor_id, t, layout) ->
        # reconstructed snapshot.  Hot timestamps skip the delta replay
        # (point groups + store.snapshot_at); hit/miss counters land in
        # last_group_stats per call and on the engine cumulatively.
        # Eviction is bounded by entry count AND by device bytes
        # (``snap_cache_bytes``) — dense N² snapshots are big, so large
        # graphs keep only as many as fit the budget (edge-layout
        # entries are E-sized and effectively always fit).
        self.snap_cache_cap = int(snap_cache_cap)
        self.snap_cache_bytes = 256 << 20
        self._snap_cache_total = 0
        from collections import OrderedDict
        self._snap_cache: "OrderedDict" = OrderedDict()
        self.cache_hits = 0
        self.cache_misses = 0
        # Per-call instrumentation: [(group key, batch, shard mode)].
        # The cache counters on it are only live inside evaluate_many —
        # direct reconstruct_cached calls (store.snapshot_at) must not
        # retroactively mutate a previous call's saved stats.
        self.last_group_stats: GroupStats = GroupStats()
        self._stats_active = False
        # Observability: registry-backed counters/histograms (the
        # serving layer rebinds to the session registry at each freeze
        # via ``bind_metrics``) and an optional slow-query log.  The
        # engine-local ``cache_hits``/``cache_misses`` ints above stay
        # as per-engine-lifetime compatibility views — every epoch swap
        # builds a fresh engine, so they reset per epoch by
        # construction, while the registry counters are monotonic for
        # the registry's lifetime.
        self.slow_log = None
        self.bind_metrics(default_registry())
        # Serving-mode plumbing (repro.serving).  ``t_served`` is the
        # live watermark: when set, evaluate_many refuses queries past
        # it (WatermarkError) instead of silently serving a state that
        # may be missing pending ops.  ``workload`` is an optional
        # recorder (``serving.policy.WorkloadStats``): every served
        # query's times land in its histogram, which drives
        # workload-driven materialization at the next epoch swap.
        self.t_served: int | None = None
        self.workload = None
        # Minimum padded group size (1 = tightest pow2).  A serving
        # layer sets this to its micro-batch size so every group runs
        # the same program shape regardless of how a batch fragments
        # across (plan, anchor, measure) groups — bounding compiles to
        # one per group key instead of one per (key, pow2(b)).
        self.group_pad_min = 1
        # Edge-layout anchors are derived lazily from the dense ones
        # through the slot registry (dense_to_edge) and cached.
        self._edge_anchors: dict = {}
        # One host copy of the sorted timestamps — or the segment
        # view's per-segment statistics: all per-query costing (anchor
        # selection + plan choice) runs sync-free on it.  Callers that
        # already hold a host copy (the store caches one) pass it in,
        # skipping the O(M) device sync.
        if self.view is not None:
            self.t_host = self.view
        elif t_host is not None:
            self.t_host = t_host
        else:
            self.t_host = np.asarray(delta.t)  # graphlint: ignore[host-sync] one-time planning copy at engine build, off the hot path
        n_cap = (current.n_cap if current is not None
                 else current_edge.n_cap)
        # edge-only engines register the edge current as the -1 anchor
        # (the planner never routes dense groups without a dense
        # current, so get(-1) always returns the right layout)
        self.selector = AnchorSelector(
            mat_times, mat_snapshots, t_cur=self.t_cur,
            current=current if current is not None else current_edge,
            t_host=self.t_host)
        self.planner = Planner(
            self.selector, n_cap=n_cap, index=index, node_cap=node_cap,
            selection=selection,
            e_cap=current_edge.e_cap if current_edge is not None else 0,
            dense_available=current is not None,
            edge_available=current_edge is not None,
            seg_view=self.view)

    @classmethod
    def from_store(cls, store, *, indexed: bool = False,
                   node_cap: int = 1024,
                   selection: Literal["time", "ops"] = "ops",
                   mesh=None):
        current = store.current
        if not isinstance(current, DenseGraph):
            current = None  # edge-layout store: no N² state anywhere
        get_edge = getattr(store, "current_edge_snapshot", None)
        if getattr(store, "segmented", False):
            # the engine runs over the segment view: no full-log device
            # conversion, no O(M) host timestamp sync — epoch swaps
            # stay O(ops since the last swap)
            dref, t_host = store.delta_view(), None
        else:
            dref, t_host = store.delta(), store.op_times_host()
        return cls(current, dref, store.t_cur,
                   mat_times=store.materialized.times,
                   mat_snapshots=store.materialized.snapshots,
                   index=store.node_index() if indexed else None,
                   node_cap=node_cap, selection=selection, mesh=mesh,
                   current_edge=get_edge() if get_edge else None,
                   t_host=t_host)

    # --------------------------------------------------- device placement

    def _replicated(self, mesh, role: str, tree):
        """Cache a fully-replicated placement of ``tree`` on ``mesh``
        (graph/delta/index operands of batch-axis-sharded groups)."""
        key = (mesh, role)
        if key not in self._placed_rep:
            from repro.sharding.graph import replicate
            self._placed_rep[key] = replicate(tree, mesh)
        return self._placed_rep[key]

    def _row_sharded_anchor(self, mesh, anchor_id: int):
        """Cache the row-sharded placement of one anchor snapshot."""
        key = (mesh, anchor_id)
        if key not in self._placed_rows:
            from repro.core.distributed import shard_graph
            _, g = self.selector.get(anchor_id)
            self._placed_rows[key] = shard_graph(g, mesh)
        return self._placed_rows[key]

    def _slot_sharded_anchor(self, mesh, anchor_id: int):
        """Cache the slot-sharded placement of one edge-layout anchor."""
        key = (mesh, anchor_id)
        if key not in self._placed_slots:
            from repro.sharding.graph import shard_slots
            _, g = self.edge_anchor(anchor_id)
            self._placed_slots[key] = shard_slots(g, mesh)
        return self._placed_slots[key]

    # ------------------------------------------------------ edge anchors

    def edge_anchor(self, anchor_id: int) -> tuple[int, EdgeGraph]:
        """(t, snapshot) of an anchor in edge-slot layout.

        The current snapshot comes straight from the store's registry;
        materialized (dense) anchors are converted once through
        ``dense_to_edge`` over that same registry and cached — an O(E)
        gather, conversion is exact for any snapshot because slots are
        append-only."""
        if self.current_edge is None:
            raise ValueError("engine has no edge-slot registry")
        if anchor_id == -1:
            return self.t_cur, self.current_edge
        cached = self._edge_anchors.get(anchor_id)
        if cached is None:
            t_a, g = self.selector.get(anchor_id)
            if not isinstance(g, EdgeGraph):
                g = dense_to_edge(g, self.current_edge)
            cached = (t_a, g)
            self._edge_anchors[anchor_id] = cached
        return cached

    # -------------------------------------------------------- observability

    def bind_metrics(self, registry) -> None:
        """Resolve this engine's metric children against ``registry``
        (``repro.obs.metrics``).  Called with the process-global
        default at construction; the serving layer rebinds every frozen
        epoch's engine to its session registry."""
        self.metrics = registry
        self._m_queries = registry.counter(
            "engine_queries_total", "queries evaluated (batched path)")
        self._m_calls = registry.counter(
            "engine_calls_total", "evaluate_many invocations")
        self._m_eval_seconds = registry.histogram(
            "engine_evaluate_seconds",
            "wall seconds per evaluate_many call")
        self._m_group_batch = registry.histogram(
            "engine_group_batch", "queries per dispatched group",
            buckets=COUNT_BUCKETS)
        self._m_cache_hits = registry.counter(
            "engine_snap_cache_hits_total",
            "reconstruction-LRU hits (LWW replay skipped)")
        self._m_cache_misses = registry.counter(
            "engine_snap_cache_misses_total",
            "reconstruction-LRU misses (full LWW replay)")
        self._m_slow = registry.counter(
            "engine_slow_queries_total",
            "evaluate_many calls past the slow-query threshold")

    def _slow_entry(self, queries, seconds: float, trace_seq) -> dict:
        """Full plan attribution for one slow call (lazy — only built
        when the threshold triggered)."""
        from repro.obs.trace import active_tracer
        entry = {
            "n_queries": len(queries),
            "cache_hits": self.last_group_stats.cache_hits,
            "cache_misses": self.last_group_stats.cache_misses,
            "groups": [
                {"plan": k.plan, "kind": k.kind, "measure": k.measure,
                 "layout": k.layout, "anchor_id": k.anchor_id,
                 "indexed": k.indexed, "windowed": k.windowed,
                 "partial": k.partial, "batch": b, "shard_mode": mode}
                for k, b, mode in self.last_group_stats],
        }
        tracer = active_tracer()
        if tracer is not None and trace_seq is not None:
            entry["spans"] = tracer.events_since(trace_seq)
        return entry

    # ------------------------------------------- reconstruction cache

    def reconstruct_cached(self, anchor_id: int, t: int,
                           layout: str = "dense"):
        """LWW reconstruction of SG_t from one anchor, through the
        per-anchor LRU: repeated queries at hot timestamps skip the
        delta replay and only pay the measure."""
        key = (int(anchor_id), int(t), layout)
        g = self._snap_cache.get(key)
        if g is not None:
            self._snap_cache.move_to_end(key)
            self.cache_hits += 1
            self._m_cache_hits.inc()
            if self._stats_active:
                self.last_group_stats.cache_hits += 1
            return g
        self.cache_misses += 1
        self._m_cache_misses.inc()
        if self._stats_active:
            self.last_group_stats.cache_misses += 1
        with trace_span("reconstruct", anchor=int(anchor_id), t=int(t),
                        layout=layout):
            if layout == "edge":
                t_a, g_a = self.edge_anchor(anchor_id)
            else:
                t_a, g_a = self.selector.get(anchor_id)
            # single-window LWW reconstruction masks exactly at the
            # window bounds, so the merged-delta tree may cover the
            # whole window
            d = (self.view.window_delta(min(t_a, t), max(t_a, t),
                                        merged=True)
                 if self.view is not None else self.delta)
            if layout == "edge":
                g = reconstruct_edge(g_a, d, t_a, t)
            else:
                g = reconstruct_dense(g_a, d, t_a, t)
        if self.snap_cache_cap > 0:
            self._snap_cache[key] = g
            self._snap_cache_total += _snapshot_bytes(g)
            while self._snap_cache and (
                    len(self._snap_cache) > self.snap_cache_cap
                    or self._snap_cache_total > self.snap_cache_bytes):
                _, old = self._snap_cache.popitem(last=False)
                self._snap_cache_total -= _snapshot_bytes(old)
        return g

    # ------------------------------------------------------------- planning

    def plan(self, q: Query) -> PlanChoice:
        return self.planner.choose(q, self.delta, self.t_cur)

    def _resolve(self, q: Query, plan: str, indexed: bool | None,
                 partial_rows: bool | None, windowed: bool | None,
                 layout: str | None = None) -> PlanChoice:
        """Forced-plan / forced-variant resolution (test compatibility:
        mirrors the ``plans.evaluate`` kwargs).  ``layout`` forces the
        execution layout: ``"edge"`` falls back to dense per query when
        the measure has no edge implementation (mirroring how forced
        plans fall back for non-degree measures); ``"dense"`` /
        ``"edge"`` raise when the engine lacks that layout entirely."""
        if plan == "auto":
            c = self.plan(q)
        else:
            if plan not in applicable_plans(q):
                raise ValueError(f"plan {plan} not applicable to {q}")
            anchor = (self.selector.select(q.t_k, self.delta)
                      if plan == "two_phase"
                      else AnchorCandidate(-1, self.t_cur, 0))
            c = PlanChoice(plan=plan, anchor_id=anchor.anchor_id,
                           t_anchor=anchor.t,
                           layout=self.planner.layout_for(q, plan))
        if indexed is not None:
            c = dataclasses.replace(
                c, indexed=indexed and self.index is not None)
        if partial_rows is not None:
            c = dataclasses.replace(c, partial=partial_rows)
        if windowed is not None:
            c = dataclasses.replace(c, windowed=windowed)
        if c.plan != "two_phase" and q.measure != "degree":
            # The delta-only/hybrid kernels are degree-specialised;
            # mirror plans.evaluate's fallback to two-phase for every
            # other measure instead of running the wrong kernel.
            anchor = self.selector.select(q.t_k, self.delta)
            c = dataclasses.replace(
                c, plan="two_phase", anchor_id=anchor.anchor_id,
                t_anchor=anchor.t, indexed=False,
                layout=self.planner.layout_for(q, "two_phase"))
        if c.plan != "two_phase":
            c = dataclasses.replace(c, partial=False, windowed=False,
                                    anchor_id=-1, t_anchor=self.t_cur)
        if layout is not None and layout != "auto":
            if layout == "edge":
                ok = (self.current_edge is not None
                      and edge_supported(q.measure, q.scope))
                if not ok and self.current is None:
                    raise ValueError(f"measure {q.measure} has no "
                                     "edge-layout implementation and "
                                     "the engine has no dense state")
                c = dataclasses.replace(c,
                                        layout="edge" if ok else "dense")
            elif layout == "dense":
                if self.current is None:
                    raise ValueError("engine has no dense snapshot")
                c = dataclasses.replace(c, layout="dense")
            else:
                raise ValueError(f"unknown layout {layout!r}")
        if c.layout == "edge":
            # partial reconstruction is a dense-rows concept
            c = dataclasses.replace(c, partial=False)
        if q.kind == "evolve":
            # the sweep executor does its own (full) reconstruction and
            # windowing — forced point-plan variants must not leak in
            c = dataclasses.replace(c, indexed=False, windowed=False,
                                    partial=False)
        return c

    def _group_key(self, q: Query, c: PlanChoice) -> _GroupKey:
        return _GroupKey(plan=c.plan, kind=q.kind, scope=q.scope,
                         measure=q.measure, agg=q.agg if q.kind == "agg"
                         else "", anchor_id=c.anchor_id,
                         indexed=c.indexed, windowed=c.windowed,
                         partial=c.partial, layout=c.layout,
                         stride=q.stride if q.kind == "evolve" else 0)

    # ------------------------------------------------------------ execution

    def _group_delta(self, key: _GroupKey, t_anchor: int,
                     ts: np.ndarray) -> Delta:
        """The delta operand of one two-phase group: the union window
        covering every query in the group (pow2 capacity).  A
        segmented engine always materializes just the overlapping
        segments; a monolithic one slices via the temporal index when
        the planner marked the group windowed.  Reconstruction only
        reads in-window ops, so results are identical to the full
        log."""
        t_lo = int(min(ts.min(), t_anchor))
        t_hi = int(max(ts.max(), t_anchor))
        if self.view is not None:
            # Merged-tree nodes are only safe where every reconstruction
            # window in the group fully contains them (the LWW collapse
            # dropped superseded ops, so a window that *straddles* a
            # node would read a torn state).  Every window runs between
            # the anchor and one query time, so the common fully-covered
            # subrange is (t_anchor, min ts] going forward / (max ts,
            # t_anchor] going backward; a mixed-direction group keeps
            # leaves everywhere.
            ts_min, ts_max = int(ts.min()), int(ts.max())
            if ts_min >= t_anchor:
                return self.view.window_delta(t_lo, t_hi, merged=True,
                                              merged_lo=t_anchor,
                                              merged_hi=ts_min)
            if ts_max <= t_anchor:
                return self.view.window_delta(t_lo, t_hi, merged=True,
                                              merged_lo=ts_max,
                                              merged_hi=t_anchor)
            return self.view.window_delta(t_lo, t_hi)
        if not key.windowed:
            return self.delta
        n_win = _window_ops_host(self.t_host, t_lo, t_hi)
        cap = _pow2(n_win, 64)
        if cap >= self.delta.capacity:
            return self.delta
        return gather_window(self.delta, t_lo, t_hi, cap)

    def _own_ops(self, kind: str, t_anchor: int, tks: np.ndarray,
                 tls: np.ndarray) -> int:
        """Logged ops a two-phase point or diff group's requests need:
        the sum over requests of the ops in each of its own replay
        windows (anchor → t for a point; anchor → t_l and t_l → t_k
        for a diff), counted on the host, no device sync."""
        def ops(a, b):
            return _window_ops_host(self.t_host, min(a, b), max(a, b))
        if kind == "point":
            return sum(ops(t_anchor, int(t)) for t in tks)
        return sum(ops(t_anchor, int(tl)) + ops(int(tl), int(tk))
                   for tk, tl in zip(tks, tls))

    def _plan_delta(self, key: _GroupKey, tks: np.ndarray,
                    tls: np.ndarray, b: int) -> Delta:
        """The delta operand of one delta-only / hybrid group.  The
        monolithic path hands every group the full log (their kernels
        window-mask internally); the segmented path materializes the
        union window — (min t_k, max t_l] for delta-only, the
        (min t_k, log end] suffix for hybrid (its corrective pass runs
        against SG_tcur, and matching the monolithic operand exactly —
        including any future-dated ops — keeps bit-parity
        unconditional).  Indexed groups gather by log position, so
        they use the full (position-stable) materialization."""
        if self.view is None:
            return self.delta
        if key.indexed:
            return self.view.full_delta()
        if key.plan == "delta_only":
            return self.view.window_delta(int(tks[:b].min()),
                                          int(tls[:b].max()))
        return self.view.window_delta(int(tks[:b].min()), None)

    def _maybe_replicated_delta(self, mesh, d: Delta) -> Delta:
        """Replicate a group's delta operand on the mesh: only the
        monolithic full log is worth caching under a stable role.
        Window materializations — segmented OR monolithic
        gather_window slices — pass through and shard_map places them
        on the fly, exactly like the pre-segmented windowed path (an
        identity-keyed cache would both leak replicated copies and
        risk serving a stale window after id reuse)."""
        if self.view is None and d is self.delta:
            return self._replicated(mesh, "delta", d)
        return d

    def _shard_mode(self, key: _GroupKey, b: int, mesh,
                    shard: str) -> str | None:
        """Group-level sharding decision (host fallback on 1 device)."""
        if mesh is None or shard == "never":
            return None
        from repro.sharding.graph import mesh_size, single_device
        if single_device(mesh):
            return None
        return self.planner.shard_mode(key, b, mesh_size(mesh),
                                       self.delta.capacity,
                                       force=(shard == "force"))

    def _run_group(self, key: _GroupKey, qs: list[Query], mesh=None,
                   shard: str = "auto", span=NULL_SPAN):
        """Dispatch one group as a single device program; returns the
        (padded) device array — callers slice to len(qs) after one
        batch-wide ``device_get``, so group dispatches overlap.

        With a multi-device ``mesh``, the group may run as one sharded
        program (``core.distributed``): the planner's dispatch cost
        term picks the axis — query batch for hybrid/delta-only (and
        non-decomposable two-phase), adjacency rows for two-phase with
        psum-combinable measures.  Either way the padded device array
        that comes back holds bit-identical per-query values.

        ``span`` is the caller's ``dispatch`` span: a real one gains
        ``padded`` (the padded batch) and ``cap`` (the delta operand's
        capacity), so a compile inside it names its shape.
        """
        b = len(qs)
        mode = self._shard_mode(key, b, mesh, shard)
        b_floor = max(b, self.group_pad_min)
        if mode is not None:
            from repro.sharding.graph import batch_pad, mesh_size
            padded = (batch_pad(b_floor, mesh_size(mesh))
                      if mode == "batch" else _pow2(b_floor))
        else:
            padded = _pow2(b_floor)
        traced = span is not NULL_SPAN
        if traced:
            span.set(padded=padded)
        self.last_group_stats.append((key, b, mode))
        # per-group accounting: plan/layout/shard-mode labels come from
        # closed vocabularies (bounded label cardinality); batch size
        # goes to a histogram, not a label
        self.metrics.counter(
            "engine_groups_total", "device programs dispatched",
            plan=key.plan, layout=key.layout,
            shard=mode or "none").inc()
        self._m_group_batch.observe(b)
        pad = padded - b
        tks = np.asarray([q.t_k for q in qs] + [qs[-1].t_k] * pad,
                         np.int32)
        last_tl = qs[-1].t_l if qs[-1].t_l is not None else qs[-1].t_k
        tls = np.asarray([q.t_l if q.t_l is not None else q.t_k
                          for q in qs] + [last_tl] * pad, np.int32)
        last_v = qs[-1].v if qs[-1].v is not None else 0
        vs = np.asarray([q.v if q.v is not None else 0 for q in qs]
                        + [last_v] * pad, np.int32)
        tks_d, tls_d, vs_d = map(jnp.asarray, (tks, tls, vs))

        # Per-anchor reconstruction cache: a point group whose times
        # repeat (or already sit in the LRU) reconstructs each unique
        # time once — cache hits skip even that — and pays only the
        # measures.  Same reconstruct + measure functions as the batch
        # kernel, so results are bit-identical.
        if (key.plan == "two_phase" and key.kind == "point"
                and mode is None and not key.partial
                and self.snap_cache_cap > 0):
            uts = np.unique(tks[:b])
            hits = sum((key.anchor_id, int(t), key.layout)
                       in self._snap_cache for t in uts)
            # worth it only when dedup at least halves the replays or
            # the LRU already covers every time in the group — a stray
            # single hit must not demote a large distinct-time batch to
            # the sequential per-time loop
            if 2 * len(uts) <= b or hits == len(uts):
                return self._run_point_group_cached(key, b, tks, vs)

        # Replicated operand placement for batch-axis sharded groups
        # (cached on the engine; plain single-device arrays otherwise).
        # The delta operand of a delta-only / hybrid group is its union
        # window (segmented engines materialize only the overlapping
        # segments); two-phase groups window separately below.
        base_cur = (self.current_edge if key.layout == "edge"
                    else self.current)
        if key.plan in ("delta_only", "hybrid"):
            with trace_span("window_delta", plan=key.plan):
                dlt = self._plan_delta(key, tks, tls, b)
            if traced:
                span.set(cap=dlt.capacity)
        else:
            dlt = None
        if mode == "batch":
            cur_role = ("current_edge" if key.layout == "edge"
                        else "current")
            cur = self._replicated(mesh, cur_role, base_cur)
            if dlt is not None:
                dlt = self._maybe_replicated_delta(mesh, dlt)
            idx = (self._replicated(mesh, "index", self.index)
                   if self.index is not None else None)
        else:
            cur, idx = base_cur, self.index

        # Build one dispatch descriptor: (kernel, static kwargs,
        # positional args, query-axis mask).  The same descriptor runs
        # locally or under shard_map — the kernel body is identical.
        if key.plan == "delta_only":
            if key.indexed:
                desc = (batch_delta_only_diff_indexed,
                        (("cap", self.node_cap),),
                        (dlt, idx, vs_d, tks_d, tls_d),
                        (0, 0, 1, 1, 1))
            else:
                desc = (batch_delta_only_diff, (),
                        (dlt, vs_d, tks_d, tls_d), (0, 1, 1, 1))
        elif key.plan == "hybrid":
            if key.kind == "point":
                if key.indexed:
                    desc = (batch_hybrid_point_indexed,
                            (("cap", self.node_cap),),
                            (cur, dlt, idx, vs_d, tks_d, self.t_cur),
                            (0, 0, 0, 1, 1, 0))
                else:
                    desc = (batch_hybrid_point, (),
                            (cur, dlt, vs_d, tks_d, self.t_cur),
                            (0, 0, 1, 1, 0))
            elif key.kind == "diff":
                if key.indexed:
                    desc = (batch_hybrid_diff_indexed,
                            (("cap", self.node_cap),),
                            (cur, dlt, idx, vs_d, tks_d, tls_d,
                             self.t_cur),
                            (0, 0, 0, 1, 1, 1, 0))
                else:
                    desc = (batch_hybrid_diff, (),
                            (cur, dlt, vs_d, tks_d, tls_d, self.t_cur),
                            (0, 0, 1, 1, 1, 0))
            else:  # agg
                # Shared series covers the union window [t0, max t_l];
                # per-query values past each query's own t_l are masked
                # inside the kernel, so results are bit-identical for
                # any capacity ≥ width (pow2 bounds recompiles).
                t0 = int(tks[:b].min())
                w_total = _pow2(int(tls[:b].max()) - t0 + 1)
                w_q = _pow2(max(int(tl - tk) + 1
                                for tk, tl in zip(tks[:b], tls[:b])))
                if w_total * base_cur.n_cap > self.series_budget:
                    # one temporally-distant query would inflate the
                    # shared series to O(w_total · n_cap); fall back to
                    # per-node series (identical values, no n_cap term)
                    desc = (batch_hybrid_agg_per_node,
                            (("w_q", w_q), ("agg", key.agg)),
                            (cur, dlt, vs_d, tks_d, tls_d),
                            (0, 0, 1, 1, 1))
                else:
                    desc = (batch_hybrid_agg,
                            (("w_total", w_total), ("w_q", w_q),
                             ("agg", key.agg)),
                            (cur, dlt, vs_d, tks_d, tls_d, t0,
                             self.t_cur),
                            (0, 0, 1, 1, 1, 0, 0))
        else:  # two_phase
            with trace_span("anchor_select", anchor=key.anchor_id,
                            layout=key.layout):
                if key.layout == "edge":
                    t_anchor, g_anchor = self.edge_anchor(key.anchor_id)
                else:
                    t_anchor, g_anchor = self.selector.get(key.anchor_id)
            if key.kind == "evolve":
                return self._run_evolve_group(key, b, mode, mesh, t_anchor,
                                              g_anchor, tks, tls, vs_d,
                                              span)
            # replay fill, counted before the span so that it does not
            # lengthen it: each of the ``padded`` requests replays all
            # ``cap`` slots, ``replays`` times (a diff replays anchor →
            # t_l, then t_l → t_k), and needs only ``own_ops`` of them
            own_ops = (self._own_ops(key.kind, t_anchor, tks[:b], tls[:b])
                       if traced and key.kind in ("point", "diff")
                       else None)
            with trace_span("window_delta", plan="two_phase",
                            anchor=key.anchor_id) as wd:
                d = self._group_delta(
                    key, t_anchor,
                    np.concatenate([tks, tls])
                    if key.kind != "point" else tks)
                if own_ops is not None:
                    wd.set(cap=d.capacity, padded=padded,
                           replays=1 if key.kind == "point" else 2,
                           own_ops=own_ops)
            if traced:
                span.set(cap=d.capacity)
            nb = 0
            if key.kind == "agg":
                nb = _pow2(max(int(tl - tk) + 1
                               for tk, tl in zip(tks[:b], tls[:b])))
            if mode == "rows":
                from repro.core import distributed as D
                anchor_rows = self._row_sharded_anchor(mesh, key.anchor_id)
                d = self._maybe_replicated_delta(mesh, d)
                return D.two_phase_rows(
                    mesh, anchor_rows, d, t_anchor, tks_d, tls_d, vs_d,
                    kind=key.kind, measure=key.measure, agg=key.agg,
                    num_buckets=nb)
            if mode == "slots":
                from repro.core import distributed as D
                anchor_slots = self._slot_sharded_anchor(mesh,
                                                         key.anchor_id)
                d = self._maybe_replicated_delta(mesh, d)
                return D.two_phase_slots(
                    mesh, anchor_slots, d, t_anchor, tks_d, tls_d, vs_d,
                    kind=key.kind, measure=key.measure, agg=key.agg,
                    num_buckets=nb)
            if mode == "batch":
                # anchor -1 IS the current snapshot — share its cached
                # placement instead of replicating the array twice
                if key.layout == "edge":
                    role = ("current_edge" if key.anchor_id == -1
                            else ("edge_anchor", key.anchor_id))
                else:
                    role = ("current" if key.anchor_id == -1
                            else ("anchor", key.anchor_id))
                g_anchor = self._replicated(mesh, role, g_anchor)
                d = self._maybe_replicated_delta(mesh, d)
            if key.layout == "edge":
                if key.kind == "point":
                    desc = (batch_edge_two_phase_point,
                            (("measure", key.measure),
                             ("scope", key.scope)),
                            (g_anchor, d, t_anchor, tks_d, vs_d),
                            (0, 0, 0, 1, 1))
                elif key.kind == "diff":
                    desc = (batch_edge_two_phase_diff,
                            (("measure", key.measure),
                             ("scope", key.scope)),
                            (g_anchor, d, t_anchor, tks_d, tls_d, vs_d),
                            (0, 0, 0, 1, 1, 1))
                else:
                    desc = (batch_edge_two_phase_agg,
                            (("measure", key.measure),
                             ("scope", key.scope),
                             ("num_buckets", nb), ("agg", key.agg)),
                            (g_anchor, d, t_anchor, tks_d, tls_d, vs_d),
                            (0, 0, 0, 1, 1, 1))
            elif key.kind == "point":
                desc = (batch_two_phase_point,
                        (("measure", key.measure), ("scope", key.scope),
                         ("use_partial", key.partial),
                         ("passes", self.passes)),
                        (g_anchor, d, t_anchor, tks_d, vs_d),
                        (0, 0, 0, 1, 1))
            elif key.kind == "diff":
                desc = (batch_two_phase_diff,
                        (("measure", key.measure), ("scope", key.scope),
                         ("use_partial", key.partial),
                         ("passes", self.passes)),
                        (g_anchor, d, t_anchor, tks_d, tls_d, vs_d),
                        (0, 0, 0, 1, 1, 1))
            else:
                desc = (batch_two_phase_agg,
                        (("measure", key.measure), ("scope", key.scope),
                         ("num_buckets", nb), ("agg", key.agg),
                         ("use_partial", key.partial),
                         ("passes", self.passes)),
                        (g_anchor, d, t_anchor, tks_d, tls_d, vs_d),
                        (0, 0, 0, 1, 1, 1))

        kernel, statics, args, qmask = desc
        if mode == "batch":
            from repro.core import distributed as D
            return D.batch_sharded(mesh, kernel, statics, args, qmask)
        return kernel(*args, **dict(statics))

    def _run_evolve_group(self, key: _GroupKey, b: int, mode, mesh,
                          t_anchor: int, g_anchor, tks: np.ndarray,
                          tls: np.ndarray, vs_d, span=NULL_SPAN):
        """Dispatch one sweep group as ONE device program
        (``kernels.evolve_sweep.batch_evolve``): reconstruct each
        query's start state from the shared anchor, then an incremental
        apply-net / measure scan over the sweep window.

        Two delta operands with different coverage contracts:

        * ``d_rec`` (anchor ↔ every t_lo) feeds pure LWW
          reconstructions, so the merged-delta tree may cover its
          anchor-side common subrange;
        * ``d_net`` (every sweep window) feeds the signed NET-count
          scatter, which needs EVERY logged op — leaf segments only
          (the LWW collapse would corrupt the counts).
        """
        from repro.kernels.evolve_sweep.ops import (SWEEP_MEASURES,
                                                    batch_evolve)
        if key.measure not in SWEEP_MEASURES:
            raise ValueError(
                f"measure {key.measure!r} has no incremental sweep; "
                "store.evolve falls back to point queries for it")
        stride = max(int(key.stride), 1)
        widths = ((tls - tks) // stride + 1).astype(np.int32)
        nb = _pow2(int(widths.max()))
        ts_last = tks + (widths - 1) * stride
        lo_all, hi_all = int(tks.min()), int(tks.max())
        if self.view is not None:
            w_lo = min(lo_all, t_anchor)
            w_hi = max(hi_all, t_anchor)
            if lo_all >= t_anchor:
                d_rec = self.view.window_delta(w_lo, w_hi, merged=True,
                                               merged_lo=t_anchor,
                                               merged_hi=lo_all)
            elif hi_all <= t_anchor:
                d_rec = self.view.window_delta(w_lo, w_hi, merged=True,
                                               merged_lo=hi_all,
                                               merged_hi=t_anchor)
            else:
                d_rec = self.view.window_delta(w_lo, w_hi)
            d_net = self.view.window_delta(lo_all, int(ts_last.max()))
        else:
            d_rec = d_net = self.delta
        if span is not NULL_SPAN:
            span.set(cap=d_rec.capacity)
        tlos_d = jnp.asarray(tks)
        widths_d = jnp.asarray(widths)
        if mode == "slots":
            from repro.core import distributed as D
            anchor_slots = self._slot_sharded_anchor(mesh, key.anchor_id)
            d_rec = self._maybe_replicated_delta(mesh, d_rec)
            d_net = self._maybe_replicated_delta(mesh, d_net)
            return D.evolve_slots(mesh, anchor_slots, d_rec, d_net,
                                  t_anchor, tlos_d, widths_d, vs_d,
                                  measure=key.measure, scope=key.scope,
                                  stride=stride, num_buckets=nb)
        if mode == "batch":
            if key.layout == "edge":
                role = ("current_edge" if key.anchor_id == -1
                        else ("edge_anchor", key.anchor_id))
            else:
                role = ("current" if key.anchor_id == -1
                        else ("anchor", key.anchor_id))
            g_anchor = self._replicated(mesh, role, g_anchor)
            d_rec = self._maybe_replicated_delta(mesh, d_rec)
            d_net = self._maybe_replicated_delta(mesh, d_net)
        statics = (("measure", key.measure), ("scope", key.scope),
                   ("stride", stride), ("num_buckets", nb))
        args = (g_anchor, d_rec, d_net, t_anchor, tlos_d, widths_d, vs_d)
        if mode == "batch":
            from repro.core import distributed as D
            return D.batch_sharded(mesh, batch_evolve, statics, args,
                                   (0, 0, 0, 0, 1, 1, 1))
        return batch_evolve(*args, **dict(statics))

    def _run_point_group_cached(self, key: _GroupKey, b: int,
                                tks: np.ndarray, vs: np.ndarray):
        """Serve one two-phase point group through the per-anchor
        reconstruction LRU: one LWW replay per *unique* query time
        (cache hits skip even that), then one vmapped measure pass per
        time.  Uses the same reconstruct/measure functions as the
        batch kernel, so per-query values are bit-identical."""
        uts, inv = np.unique(tks[:b], return_inverse=True)
        out = None
        for k, t in enumerate(uts):
            sel = np.nonzero(inv == k)[0]
            g = self.reconstruct_cached(key.anchor_id, int(t), key.layout)
            m = batch_measure(g, jnp.asarray(vs[sel]),
                              measure=key.measure, scope=key.scope)
            if out is None:
                # trailing dims carry vector measures
                # (degree_distribution) through unchanged
                out = jnp.zeros((b,) + m.shape[1:], m.dtype)
            out = out.at[jnp.asarray(sel)].set(m)
        return out

    def evaluate_many(self, queries: Sequence[Query], plan: str = "auto",
                      *, indexed: bool | None = None,
                      partial_rows: bool | None = None,
                      windowed: bool | None = None,
                      layout: str | None = None,
                      return_choices: bool = False,
                      mesh=None, shard: str = "auto",
                      enforce_watermark: bool = True):
        """Evaluate B historical queries, grouped by (plan, anchor) and
        executed as one device program per group.

        ``plan``/``indexed``/``partial_rows``/``windowed``/``layout``
        force the planner's choice uniformly (same semantics as
        ``plans.evaluate``); the default lets the cost model decide per
        query — ``layout`` picks between the dense N² adjacency and the
        O(E) edge-slot registry (``"edge"`` falls back to dense per
        query for measures without an edge implementation).  Returns a
        list of scalars in query order (and the per-query
        ``PlanChoice`` list when ``return_choices``).

        ``mesh`` (default: the engine's construction-time mesh) turns
        each large-enough group into one multi-device program —
        ``shard`` is ``"auto"`` (planner cost term decides per group),
        ``"force"`` (shard every shardable group) or ``"never"``.
        Sharded and single-device execution return bit-identical
        results; with one visible device the mesh is ignored (host
        fallback).

        A watermarked engine (``t_served`` set by the serving layer)
        refuses queries past the watermark with ``WatermarkError``;
        ``enforce_watermark=False`` bypasses the check for a caller
        that already applied its own staleness policy
        (``serving.LiveGraphStore`` with ``stale="serve"``).
        """
        mesh = mesh if mesh is not None else self.mesh
        if self.t_served is not None and enforce_watermark:
            for q in queries:
                t_hi = q.t_k if q.t_l is None else max(q.t_k, q.t_l)
                if t_hi > self.t_served:
                    raise WatermarkError(
                        f"query time {t_hi} is past the serving "
                        f"watermark t_served={self.t_served}; swap the "
                        "ingest epoch (or pass stale='block' at the "
                        "serving layer) to advance it")
        if self.workload is not None:
            self.workload.record_queries(queries)
        from repro.obs.trace import active_tracer
        tracer = active_tracer()
        trace_seq = tracer.seq if tracer is not None else None
        t_call = _clock.now()
        with trace_span("query", n=len(queries)) as top:
            with trace_span("plan", n=len(queries)):
                choices = [self._resolve(q, plan, indexed, partial_rows,
                                         windowed, layout)
                           for q in queries]
                groups: dict[_GroupKey, list[int]] = {}
                for i, (q, c) in enumerate(zip(queries, choices)):
                    groups.setdefault(self._group_key(q, c), []).append(i)
            top.set(groups=len(groups))
            # Dispatch every group first (async), then fetch everything
            # with one device_get so transfers don't serialize the
            # group programs.
            self.last_group_stats = GroupStats()
            self._stats_active = True
            try:
                outs = []
                for key, idxs in groups.items():
                    with trace_span("dispatch", plan=key.plan,
                                    layout=key.layout,
                                    measure=key.measure,
                                    batch=len(idxs)) as sp:
                        outs.append(
                            (idxs,
                             self._run_group(key,
                                             [queries[i] for i in idxs],
                                             mesh=mesh, shard=shard,
                                             span=sp)))
            finally:
                self._stats_active = False
            with trace_span("fetch", groups=len(outs)):
                fetched = jax.device_get([o for _, o in outs])
            results: list = [None] * len(queries)
            for (idxs, _), host in zip(outs, fetched):
                arr = np.asarray(host)
                for j, i in enumerate(idxs):
                    q = queries[i]
                    if q.kind == "evolve":
                        # sweep rows past a query's own width repeat
                        # its last sample (group padding) — slice off
                        t_l = q.t_k if q.t_l is None else q.t_l
                        bq = (int(t_l) - q.t_k) // max(int(q.stride),
                                                       1) + 1
                        results[i] = arr[j][:bq]
                    else:
                        results[i] = arr[j]
        seconds = _clock.now() - t_call
        self._m_calls.inc()
        self._m_queries.inc(len(queries))
        self._m_eval_seconds.observe(seconds)
        if self.slow_log is not None and self.slow_log.record(
                seconds,
                lambda: self._slow_entry(queries, seconds, trace_seq)):
            self._m_slow.inc()
        if return_choices:
            return results, choices
        return results

    def evaluate(self, q: Query, plan: str = "auto", **kw):
        """Single-query entry point: ``evaluate_many([q])[0]``."""
        return self.evaluate_many([q], plan, **kw)[0]
