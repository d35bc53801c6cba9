"""Distributed temporal-graph engine (DESIGN.md §2.4).

The paper names parallel snapshot reconstruction (à la Pregel/GBASE) as
future work; here it is, in two layers:

**Primitives** (bottom half of this file): adjacency rows + node mask
sharded over a 1-D ``rows`` mesh axis, the delta log replicated (it is
tiny next to N²), reconstruction row-parallel with zero communication,
global measures psum partial aggregates.

**Sharded group execution** (top half): the engine's batched executor
(``core.engine.evaluate_many``) groups queries by (plan choice,
anchor); a group is exactly the unit that is device-parallel, and this
module turns one group dispatch into one multi-device program:

* hybrid / delta-only groups → ``batch_sharded``: graph + delta
  replicated, the padded query batch axis split over the mesh.  Each
  device runs the identical vmapped kernel on its query slice, so
  results are bit-identical to the single-device path by construction.
* two-phase groups → ``two_phase_rows``: queries replicated, adjacency
  rows split; every device runs the LWW delta-apply scatter on its row
  block only (O(N²/D) work) and contributes integer partial sums that
  are ``psum``'d into the global measure.  Integer partials make the
  combination exact, so these also bit-match the single-device path.

All functions are shard_map programs over an existing mesh; they make
no assumption about the device count (tests run them on 8 forced host
devices, the production mesh on 512).  With a 1-device mesh the engine
never routes here — the host-process fallback is the ordinary path.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.delta import ADD_EDGE, ADD_NODE, Delta
from repro.core.graph import DenseGraph, EdgeGraph
from repro.core.plans import masked_aggregate
from repro.core.queries import avg_degree_of, density_of
from repro.core.reconstruct import _lww_decide, _lww_key
from repro.sharding.graph import (AXIS, batch_specs,  # noqa: F401
                                  graph_mesh, replicate, shard_rows,
                                  shard_slots)
# graph_mesh / replicate are re-exported: callers historically import
# them from here.


def shard_graph(g: DenseGraph, mesh: Mesh) -> DenseGraph:
    """Place adjacency rows / node mask row-sharded on the mesh."""
    return shard_rows(g, mesh)


def shard_edge_graph(g: EdgeGraph, mesh: Mesh) -> EdgeGraph:
    """Place an edge-layout snapshot slot-sharded on the mesh."""
    return shard_slots(g, mesh)


# ---------------------------------------------------------------------------
# Sharded group execution: batch-axis sharding (hybrid / delta-only)
# ---------------------------------------------------------------------------

# (mesh, kernel, statics, qmask) -> jitted shard_map program.  Kernels
# are module-level jitted functions, statics are hashable (name, value)
# pairs, so the cache key is stable across calls and each program
# compiles once per padded shape.
_BATCH_CACHE: dict = {}


def batch_sharded(mesh: Mesh, kernel, statics: tuple, args: tuple,
                  qmask: tuple):
    """Run ``kernel(*args, **dict(statics))`` with the query-batch axis
    of the ``qmask``-flagged args split over the mesh.

    Every other arg (graph, delta, scalars) is replicated.  The kernel
    body is the *same* vmapped program the single-device executor runs,
    applied to a contiguous slice of the batch, so per-query results
    are bit-identical; out axis ``P(AXIS)`` re-concatenates slices in
    order.  Batch length must be a multiple of the device count
    (``sharding.graph.batch_pad``).
    """
    key = (mesh, kernel, statics, qmask)
    fn = _BATCH_CACHE.get(key)
    if fn is None:
        bound = functools.partial(kernel, **dict(statics))
        fn = jax.jit(shard_map(lambda *a: bound(*a), mesh=mesh,
                               in_specs=batch_specs(qmask),
                               out_specs=P(AXIS)))
        _BATCH_CACHE[key] = fn
    return fn(*args)


# ---------------------------------------------------------------------------
# Sharded group execution: row-sharded two-phase with psum measures
# ---------------------------------------------------------------------------

# Measures whose value decomposes into a sum of per-row-block integer
# partials (finalized identically to the single-device formula after
# the psum).  Everything else routes through batch_sharded.
ROW_MEASURES = ("degree", "num_nodes", "num_edges", "density",
                "avg_degree")


def _row_parts(nodes_l, adj_l, v, row0, measure: str):
    """Integer partial sums of this shard's row block: i32[2] =
    (node-ish partial, edge partial).  Edge rows count each edge twice
    across the full mesh — finalization divides by 2, exactly like
    ``DenseGraph.num_edges``."""
    i32 = jnp.int32
    n_loc = adj_l.shape[0]
    if measure == "degree":
        lv = v - row0
        ok = (lv >= 0) & (lv < n_loc)
        row = adj_l[jnp.clip(lv, 0, n_loc - 1)]
        deg = jnp.where(ok, jnp.sum(row.astype(i32)), 0)
        return jnp.stack([deg, jnp.zeros((), i32)])
    nn = jnp.sum(nodes_l.astype(i32))
    ee = jnp.sum(adj_l.astype(i32))
    return jnp.stack([nn, ee])


def _row_finalize(tot, measure: str):
    """Global measure from psum'd partials — the same arithmetic as the
    single-device measures in ``core.queries`` (exact for integers,
    identical f32 expression for density/avg_degree)."""
    if measure == "degree":
        return tot[..., 0]
    if measure == "num_nodes":
        return tot[..., 0]
    if measure == "num_edges":
        return tot[..., 1] // 2
    n = tot[..., 0]
    e = tot[..., 1] // 2
    if measure == "density":
        return density_of(n, e)
    if measure == "avg_degree":
        return avg_degree_of(n, e)
    raise ValueError(f"measure {measure} is not row-decomposable")


_ROW_CACHE: dict = {}


def two_phase_rows(mesh: Mesh, anchor: DenseGraph, delta: Delta, t_anchor,
                   tks, tls, vs, *, kind: str, measure: str, agg: str = "",
                   num_buckets: int = 0):
    """One two-phase (plan, anchor) group as a row-parallel program.

    The anchor's rows are split over the mesh (``shard_graph`` layout);
    the delta and the query arrays are replicated.  Each device
    LWW-reconstructs only its row block per query time (the row-sharded
    delta-apply scatter — O(B · N²/D) instead of O(B · N²)) and emits
    integer partial sums; one psum per group combines them, then the
    measure is finalized with the single-device formula, so results
    bit-match ``core.engine.batch_two_phase_*``.

    Supported: kind ∈ {point, diff, agg} × measure ∈ ROW_MEASURES.
    """
    key = (mesh, kind, measure, agg, num_buckets)
    fn = _ROW_CACHE.get(key)
    if fn is None:
        fn = jax.jit(shard_map(
            functools.partial(_two_phase_rows_local, kind=kind,
                              measure=measure, agg=agg,
                              num_buckets=num_buckets),
            mesh=mesh,
            in_specs=(P(AXIS), P(AXIS, None), P(), P(), P(), P(), P()),
            out_specs=P()))
        _ROW_CACHE[key] = fn
    return fn(anchor.nodes, anchor.adj, delta, t_anchor, tks, tls, vs)


def _two_phase_rows_local(nodes_l, adj_l, delta, t_anchor, tks, tls, vs,
                          *, kind, measure, agg, num_buckets):
    row0 = jax.lax.axis_index(AXIS) * adj_l.shape[0]

    def parts_at(base_nodes, base_adj, t_base, t, v):
        nl, al = _local_lww(base_nodes, base_adj, delta, t_base, t)
        return _row_parts(nl, al, v, row0, measure), (nl, al)

    if kind == "point":
        def one(t, v):
            return parts_at(nodes_l, adj_l, t_anchor, t, v)[0]

        parts = jax.vmap(one)(tks, vs)                       # [B, 2]
        return _row_finalize(jax.lax.psum(parts, AXIS), measure)

    if kind == "diff":
        # SG_tl from the anchor, then SG_tk from SG_tl — the same
        # nearer-snapshot reuse as the single-device diff kernel.
        def one(tk, tl, v):
            p_l, (nl, al) = parts_at(nodes_l, adj_l, t_anchor, tl, v)
            p_k, _ = parts_at(nl, al, tl, tk, v)
            return p_l, p_k

        p_l, p_k = jax.vmap(one)(tks, tls, vs)               # [B, 2] each
        a = _row_finalize(jax.lax.psum(p_l, AXIS), measure)
        b = _row_finalize(jax.lax.psum(p_k, AXIS), measure)
        return jnp.abs(a - b)

    # agg: one reconstruction per bucket (times past each query's t_l
    # are computed but masked by masked_aggregate, exactly as in
    # batch_two_phase_agg).
    def one(tk, tl, v):
        ts = tk + jnp.arange(num_buckets, dtype=jnp.int32)
        return jax.lax.map(
            lambda t: parts_at(nodes_l, adj_l, t_anchor, t, v)[0], ts)

    parts = jax.vmap(one)(tks, tls, vs)                      # [B, nb, 2]
    vals = _row_finalize(jax.lax.psum(parts, AXIS), measure)  # [B, nb]
    return jax.vmap(
        lambda row, tk, tl: masked_aggregate(row, tl - tk + 1,
                                             num_buckets, agg))(
        vals, tks, tls)


# ---------------------------------------------------------------------------
# Sharded group execution: slot-sharded edge-layout two-phase
# ---------------------------------------------------------------------------

# Measures combinable from per-slot-shard integer partials.  Slots
# partition the edge set (each undirected edge lives in exactly one
# slot), so per-shard popcounts / incident-slot counts sum to the
# global count — the same exactness argument as row-sharding, with the
# simplification that no edge is ever double-counted (rows see each
# edge twice, slots once).
SLOT_MEASURES = ROW_MEASURES


def _slot_parts(nodes_cur, live_l, eu_l, ev_l, v, measure: str):
    """Integer partial sums of this shard's slot block: i32[2] =
    (node-ish partial, edge partial).  The node mask is replicated
    (N-sized), so only shard 0 contributes its count."""
    i32 = jnp.int32
    if measure == "degree":
        touch = live_l & ((eu_l == v) | (ev_l == v))
        return jnp.stack([jnp.sum(touch.astype(i32)),
                          jnp.zeros((), i32)])
    on_zero = jax.lax.axis_index(AXIS) == 0
    nn = jnp.where(on_zero, jnp.sum(nodes_cur.astype(i32)), 0)
    ee = jnp.sum(live_l.astype(i32))
    return jnp.stack([nn, ee])


def _slot_finalize(tot, measure: str):
    """Global measure from psum'd slot partials — identical arithmetic
    to the single-device edge measures (``core.queries``): slots count
    each edge once, so no halving (unlike ``_row_finalize``)."""
    if measure in ("degree", "num_nodes"):
        return tot[..., 0]
    if measure == "num_edges":
        return tot[..., 1]
    n = tot[..., 0]
    e = tot[..., 1]
    if measure == "density":
        return density_of(n, e)
    if measure == "avg_degree":
        return avg_degree_of(n, e)
    raise ValueError(f"measure {measure} is not slot-decomposable")


_SLOT_CACHE: dict = {}


def two_phase_slots(mesh: Mesh, anchor: EdgeGraph, delta: Delta, t_anchor,
                    tks, tls, vs, *, kind: str, measure: str,
                    agg: str = "", num_buckets: int = 0):
    """One edge-layout two-phase (plan, anchor) group as a
    slot-parallel program.

    The anchor's slot registry (eu/ev/emask) is split over the mesh
    (``shard_slots`` layout); the node mask, the delta and the query
    arrays are replicated.  Each device LWW-reconstructs only its slot
    block per query time (O(B · E/D) scatter work) and emits integer
    partial sums; one psum per group combines them and the measure is
    finalized with the single-device edge formula, so results
    bit-match ``core.engine.batch_edge_two_phase_*`` — and hence the
    dense path too (tests/test_distributed.py).

    Supported: kind ∈ {point, diff, agg} × measure ∈ SLOT_MEASURES.
    """
    key = (mesh, kind, measure, agg, num_buckets)
    fn = _SLOT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(shard_map(
            functools.partial(_two_phase_slots_local, kind=kind,
                              measure=measure, agg=agg,
                              num_buckets=num_buckets),
            mesh=mesh,
            in_specs=(P(), P(AXIS), P(AXIS), P(AXIS), P(), P(), P(),
                      P(), P(), P()),
            out_specs=P()))
        _SLOT_CACHE[key] = fn
    return fn(anchor.nodes, anchor.eu, anchor.ev, anchor.emask,
              anchor.n_edges_reg, delta, t_anchor, tks, tls, vs)


def _slot_lww(emask_l, delta: Delta, t_anchor, t_query, slot0):
    """Shard-local last-writer-wins over the local slot block (ops are
    pre-resolved to slot ids host-side, so this is a 1-D scatter)."""
    e_loc = emask_l.shape[0]
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()

    ls = delta.slot - slot0
    ok = in_win & delta.is_edge_op() & (ls >= 0) & (ls < e_loc)
    ls = jnp.clip(ls, 0, e_loc - 1)
    key = jnp.full((e_loc,), -1, jnp.int32).at[ls].max(
        _lww_key(ok, delta.op, forward, ADD_EDGE))
    return _lww_decide(key, emask_l)


def _node_lww(nodes, delta: Delta, t_anchor, t_query):
    """Full-N node-mask LWW (the node mask is replicated on every
    shard — it is N-sized, negligible next to the slot scatter)."""
    n = nodes.shape[0]
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    keyn = jnp.full((n,), -1, jnp.int32).at[delta.u].max(
        _lww_key(in_win & delta.is_node_op(), delta.op, forward,
                 ADD_NODE))
    return _lww_decide(keyn, nodes)


def _two_phase_slots_local(nodes, eu_l, ev_l, emask_l, n_reg, delta,
                           t_anchor, tks, tls, vs, *, kind, measure, agg,
                           num_buckets):
    e_loc = emask_l.shape[0]
    slot0 = jax.lax.axis_index(AXIS) * e_loc
    reg_l = (slot0 + jnp.arange(e_loc, dtype=jnp.int32)) < n_reg

    def parts_at(emask_base, nodes_base, t_base, t, v):
        em = _slot_lww(emask_base, delta, t_base, t, slot0)
        nd = _node_lww(nodes_base, delta, t_base, t)
        p = _slot_parts(nd, em & reg_l, eu_l, ev_l, v, measure)
        return p, (em, nd)

    if kind == "point":
        def one(t, v):
            return parts_at(emask_l, nodes, t_anchor, t, v)[0]

        parts = jax.vmap(one)(tks, vs)                       # [B, 2]
        return _slot_finalize(jax.lax.psum(parts, AXIS), measure)

    if kind == "diff":
        # SG_tl from the anchor, then SG_tk from SG_tl — the same
        # nearer-snapshot reuse as the single-device diff kernel.
        def one(tk, tl, v):
            p_l, (em, nd) = parts_at(emask_l, nodes, t_anchor, tl, v)
            p_k, _ = parts_at(em, nd, tl, tk, v)
            return p_l, p_k

        p_l, p_k = jax.vmap(one)(tks, tls, vs)               # [B, 2] each
        a = _slot_finalize(jax.lax.psum(p_l, AXIS), measure)
        b = _slot_finalize(jax.lax.psum(p_k, AXIS), measure)
        return jnp.abs(a - b)

    # agg: one reconstruction per bucket (times past each query's t_l
    # are computed but masked by masked_aggregate, exactly as in
    # batch_edge_two_phase_agg).
    def one(tk, tl, v):
        ts = tk + jnp.arange(num_buckets, dtype=jnp.int32)
        return jax.lax.map(
            lambda t: parts_at(emask_l, nodes, t_anchor, t, v)[0], ts)

    parts = jax.vmap(one)(tks, tls, vs)                      # [B, nb, 2]
    vals = _slot_finalize(jax.lax.psum(parts, AXIS), measure)  # [B, nb]
    return jax.vmap(
        lambda row, tk, tl: masked_aggregate(row, tl - tk + 1,
                                             num_buckets, agg))(
        vals, tks, tls)


# ---------------------------------------------------------------------------
# Sharded group execution: slot-sharded incremental time sweeps (evolve)
# ---------------------------------------------------------------------------

_EVOLVE_SLOT_CACHE: dict = {}


def evolve_slots(mesh: Mesh, anchor: EdgeGraph, d_rec: Delta, d_net: Delta,
                 t_anchor, t_los, widths, vs, *, measure: str, scope: str,
                 stride: int, num_buckets: int):
    """One evolve (sweep) group as a slot-parallel program.

    The expensive half of a sweep is the one LWW reconstruction at each
    query's t_lo — so that is what shards: each device reconstructs only
    its slot block (O(E/D) scatter) and emits integer partials of the
    start state (per-node degree counts from local edges, local live-
    edge count; the replicated node mask contributes from shard 0 only,
    exactly the ``_slot_parts`` convention).  ONE psum of those integer
    partials rebuilds the exact start state on every device — the same
    exactness argument as ``two_phase_slots`` — and the cheap half (the
    per-sample net scatter + measure scan over the replicated ``d_net``)
    runs replicated, so every device holds the identical result and the
    outputs bit-match the single-device ``batch_evolve``.
    """
    key = (mesh, measure, scope, stride, num_buckets)
    fn = _EVOLVE_SLOT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(shard_map(
            functools.partial(_evolve_slots_local, measure=measure,
                              scope=scope, stride=stride,
                              num_buckets=num_buckets),
            mesh=mesh,
            in_specs=(P(), P(AXIS), P(AXIS), P(AXIS), P(), P(), P(), P(),
                      P(), P(), P()),
            out_specs=P()))
        _EVOLVE_SLOT_CACHE[key] = fn
    return fn(anchor.nodes, anchor.eu, anchor.ev, anchor.emask,
              anchor.n_edges_reg, d_rec, d_net, t_anchor, t_los, widths,
              vs)


def _evolve_slots_local(nodes, eu_l, ev_l, emask_l, n_reg, d_rec, d_net,
                        t_anchor, t_los, widths, vs, *, measure, scope,
                        stride, num_buckets):
    from repro.kernels.evolve_sweep.ops import sweep_nets, sweep_scan
    e_loc = emask_l.shape[0]
    n = nodes.shape[0]
    slot0 = jax.lax.axis_index(AXIS) * e_loc
    reg_l = (slot0 + jnp.arange(e_loc, dtype=jnp.int32)) < n_reg
    on_zero = jax.lax.axis_index(AXIS) == 0

    def one(t_lo, width, v):
        em = _slot_lww(emask_l, d_rec, t_anchor, t_lo, slot0)
        nd = _node_lww(nodes, d_rec, t_anchor, t_lo)
        live = (em & reg_l).astype(jnp.int32)
        deg_p = (jnp.zeros((n,), jnp.int32).at[eu_l].add(live)
                 .at[ev_l].add(live))
        ne_p = jnp.sum(live)
        nn_p = jnp.where(on_zero, jnp.sum(nd.astype(jnp.int32)),
                         jnp.int32(0))
        nodes_p = jnp.where(on_zero, nd.astype(jnp.int32),
                            jnp.zeros((n,), jnp.int32))
        deg0, nodes0, nn0, ne0 = jax.lax.psum(
            (deg_p, nodes_p, nn_p, ne_p), AXIS)
        nets = sweep_nets(d_net, t_lo, t_lo + (width - 1) * stride,
                          stride, num_buckets, n)
        return sweep_scan(measure, scope, v, deg0, nodes0, nn0, ne0, nets)

    return jax.vmap(one)(t_los, widths, vs)


# ---------------------------------------------------------------------------
# Row-parallel reconstruction
# ---------------------------------------------------------------------------


def _local_lww(nodes_l, adj_l, delta: Delta, t_anchor, t_query):
    """Shard-local last-writer-wins over the local row block."""
    n_loc = adj_l.shape[0]
    row0 = jax.lax.axis_index(AXIS) * n_loc
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()

    # Edge op (u, v) lands in local row u (col v) and local row v (col u).
    e_win = in_win & delta.is_edge_op()
    key = jnp.full((n_loc, adj_l.shape[1]), -1, jnp.int32)
    for (r, c) in ((delta.u, delta.v), (delta.v, delta.u)):
        lr = r - row0
        ok = e_win & (lr >= 0) & (lr < n_loc)
        lr = jnp.clip(lr, 0, n_loc - 1)
        key = key.at[lr, c].max(_lww_key(ok, delta.op, forward, ADD_EDGE))
    adj_l = _lww_decide(key, adj_l)

    ln = delta.u - row0
    ok = in_win & delta.is_node_op() & (ln >= 0) & (ln < n_loc)
    ln = jnp.clip(ln, 0, n_loc - 1)
    keyn = jnp.full((n_loc,), -1, jnp.int32).at[ln].max(
        _lww_key(ok, delta.op, forward, ADD_NODE))
    nodes_l = _lww_decide(keyn, nodes_l)
    return nodes_l, adj_l


def dist_reconstruct(mesh: Mesh, current: DenseGraph, delta: Delta,
                     t_anchor, t_query) -> DenseGraph:
    """SG_{t_query} with rows reconstructed in parallel, no comms."""
    fn = shard_map(
        _local_lww, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS, None), P(), P(), P()),
        out_specs=(P(AXIS), P(AXIS, None)))
    nodes, adj = jax.jit(fn)(current.nodes, current.adj, delta,
                             t_anchor, t_query)
    return DenseGraph(nodes=nodes, adj=adj)


# ---------------------------------------------------------------------------
# Global measures with psum combination
# ---------------------------------------------------------------------------


def dist_num_edges(mesh: Mesh, g: DenseGraph):
    def f(adj_l):
        local = jnp.sum(adj_l.astype(jnp.int32))
        return jax.lax.psum(local, AXIS)[None] // 2

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(AXIS, None),),
                             out_specs=P(AXIS)))(g.adj)[0]


def dist_degrees(mesh: Mesh, g: DenseGraph) -> jax.Array:
    def f(adj_l):
        return jnp.sum(adj_l, axis=1).astype(jnp.int32)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(AXIS, None),),
                             out_specs=P(AXIS)))(g.adj)


def dist_degree_distribution(mesh: Mesh, g: DenseGraph, max_deg: int):
    @partial(shard_map, mesh=mesh,
             in_specs=(P(AXIS), P(AXIS, None)), out_specs=P(AXIS))
    def f(nodes_l, adj_l):
        deg = jnp.clip(jnp.sum(adj_l, axis=1).astype(jnp.int32), 0, max_deg)
        hist = jnp.zeros((max_deg + 1,), jnp.int32).at[deg].add(
            nodes_l.astype(jnp.int32))
        total = jax.lax.psum(hist, AXIS)
        # every shard holds the full histogram; emit only shard 0's copy
        keep = jax.lax.axis_index(AXIS) == 0
        return jnp.where(keep, total, 0)

    parts = jax.jit(f)(g.nodes, g.adj)
    return parts.reshape(len(mesh.devices), -1).sum(axis=0)


def dist_triangles(mesh: Mesh, g: DenseGraph):
    """trace(A³)/6 with row-sharded A: local A_l @ A_full (MXU), then
    elementwise with A_l, psum."""
    @partial(shard_map, mesh=mesh, in_specs=(P(AXIS, None),),
             out_specs=P(AXIS))
    def f(adj_l):
        a_l = adj_l.astype(jnp.float32)
        a_full = jax.lax.all_gather(a_l, AXIS, tiled=True)
        m = a_l @ a_full
        contrib = jnp.sum(m * a_l)
        return jax.lax.psum(contrib, AXIS)[None]

    return (jax.jit(f)(g.adj)[0] / 6.0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Batched historical query serving (hybrid plan, DESIGN.md §2.3)
# ---------------------------------------------------------------------------


def dist_batch_point_degree(mesh: Mesh, current: DenseGraph, delta: Delta,
                            vs: jax.Array, ts: jax.Array, t_cur):
    """Serve a batch of point node-centric degree queries:
    degree(vs[i]) at time ts[i].  Current-degree partials come from the
    owning shard (psum); the delta correction is computed redundantly on
    every shard (the log is replicated and the correction is O(B·M) int
    math)."""
    @partial(shard_map, mesh=mesh,
             in_specs=(P(AXIS, None), P(), P(), P(), P()),
             out_specs=P())
    def f(adj_l, delta, vs, ts, t_cur):
        n_loc = adj_l.shape[0]
        row0 = jax.lax.axis_index(AXIS) * n_loc
        lv = vs - row0
        ok = (lv >= 0) & (lv < n_loc)
        lv = jnp.clip(lv, 0, n_loc - 1)
        deg_local = jnp.where(ok, jnp.sum(adj_l[lv], axis=1), 0)
        deg_cur = jax.lax.psum(deg_local.astype(jnp.int32), AXIS)

        win = (delta.t[None, :] > ts[:, None]) & \
              (delta.t[None, :] <= t_cur) & delta.valid_mask()[None, :]
        touch = (delta.u[None, :] == vs[:, None]) | \
                (delta.v[None, :] == vs[:, None])
        sign = jnp.where(delta.op == ADD_EDGE, 1,
                         jnp.where(delta.is_edge_op(), -1, 0))[None, :]
        corr = jnp.sum(sign * (win & touch).astype(jnp.int32), axis=1)
        return deg_cur - corr

    return jax.jit(f)(current.adj, delta, vs, ts, t_cur)
