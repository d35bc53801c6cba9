"""One-program incremental time-sweep (`evolve`) executor.

A sweep query asks for a measure at every sample time

    t_lo, t_lo + stride, ..., t_lo + (B-1)·stride  (≤ t_hi)

Point-query serving pays B reconstructions whose delta windows overlap
almost entirely; DeltaGraph (arXiv 1207.5777) observes the shared path
should be paid once.  Here the whole sweep is ONE device program:

1. reconstruct SG_{t_lo} from the group anchor (the only LWW pass),
2. scatter every in-sweep op into per-sample integer NET counts
   (``sweep_nets``) — op at time t lands in sample ceil((t-t_lo)/stride),
   the first sample that observes it,
3. a ``lax.scan`` alternates apply-net / measure: carry is the exact
   integer state (degrees, node validity, node count, edge count), each
   step emits the registered measure.

Bit-exactness vs B point queries is *not* approximate: the store's
transition log is legal (``GraphStore._apply_host`` refuses double-adds
and ghost-removes), so signed per-sample net counts reproduce the true
integer state at every sample, and every SWEEP measure is a fixed f32
expression of those integers — copied verbatim from ``core.queries``,
so the floats are bit-identical too.

The NET scatter is why the sweep-window delta operand must be LEAF
segments, never merged-tree nodes: the LWW collapse drops superseded
ops, which leaves LWW reconstruction invariant but corrupts signed
counts.  (The anchor→t_lo operand ``d_rec`` is a pure LWW input and
may be tree-covered.)  See ``core.segments``.

``SWEEP_MEASURES`` is the registry: measures expressible as a pure
function of the swept integer state.  Everything else falls back to B
independent point queries in ``store.evolve``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.delta import ADD_EDGE, ADD_NODE, Delta
from repro.core.graph import EdgeGraph
from repro.core.queries import (DEGREE_DIST_BINS, _degree_histogram,
                                avg_degree_of, density_of)
from repro.core.reconstruct import reconstruct_dense, reconstruct_edge

# Measures the incremental executor supports on BOTH layouts: pure
# functions of (degrees, node validity, num_nodes, num_edges).
SWEEP_MEASURES = ("degree", "num_nodes", "num_edges", "density",
                  "avg_degree", "degree_distribution")


def sweep_nets(delta: Delta, t_lo, t_last, stride: int, num_buckets: int,
               n_cap: int):
    """Per-sample signed NET counts from the sweep-window ops.

    An op at time t is first observed by sample k = ceil((t-t_lo)/stride)
    (samples sit at t_lo + k·stride; windows are half-open (·, ·]).
    Sample 0 *is* t_lo, so k ≥ 1 for every in-window op and row 0 is
    always zero — the scan's init carry is the state at t_lo.

    Returns (deg_net i32[B,N], node_net i32[B,N], ne_net i32[B],
    nn_net i32[B]).
    """
    win = delta.valid_mask() & delta.window_mask(t_lo, t_last)
    # guard the bucket arithmetic against T_PAD overflow: padding rows
    # carry weight 0 anyway, so pin them to sample 1
    t = jnp.where(win, delta.t, t_lo + 1)
    k = jnp.clip((t - t_lo + stride - 1) // stride, 0, num_buckets - 1)
    sign = jnp.where((delta.op == ADD_EDGE) | (delta.op == ADD_NODE), 1, -1)
    is_e = delta.is_edge_op()
    we = jnp.where(win & is_e, sign, 0).astype(jnp.int32)
    wn = jnp.where(win & ~is_e, sign, 0).astype(jnp.int32)
    deg_net = (jnp.zeros((num_buckets, n_cap), jnp.int32)
               .at[k, delta.u].add(we).at[k, delta.v].add(we))
    node_net = jnp.zeros((num_buckets, n_cap), jnp.int32).at[k, delta.u].add(wn)
    ne_net = jnp.zeros((num_buckets,), jnp.int32).at[k].add(we)
    nn_net = jnp.zeros((num_buckets,), jnp.int32).at[k].add(wn)
    return deg_net, node_net, ne_net, nn_net


def measure_from_state(measure: str, scope: str, v, deg, nodes_i, nn, ne):
    """The registered measure as a function of the swept integer state.

    The f32 finalizations are ``core.queries``'s own (both layouts share
    them there too) — this is what makes sweep samples bit-equal to
    point queries, f32 measures included.
    """
    if scope == "node":
        if measure == "degree":
            return deg[v]
        raise ValueError(f"measure {measure!r} is not sweepable")
    if measure == "num_nodes":
        return nn
    if measure == "num_edges":
        return ne
    if measure == "density":
        return density_of(nn, ne)
    if measure == "avg_degree":
        return avg_degree_of(nn, ne)
    if measure == "degree_distribution":
        return _degree_histogram(deg, nodes_i.astype(bool), DEGREE_DIST_BINS)
    raise ValueError(f"measure {measure!r} is not sweepable")


def sweep_scan(measure: str, scope: str, v, deg0, nodes0, nn0, ne0, nets):
    """apply-net / measure alternation: one scan step per sample."""

    def step(carry, net):
        deg, nod, nn, ne = carry
        deg_net, node_net, ne_net, nn_net = net
        with jax.named_scope("replay"):
            carry = (deg + deg_net, nod + node_net, nn + nn_net,
                     ne + ne_net)
        with jax.named_scope("measure"):
            out = measure_from_state(measure, scope, v, *carry)
        return carry, out

    _, outs = jax.lax.scan(step, (deg0, nodes0.astype(jnp.int32),
                                  nn0, ne0), nets)
    return outs


@functools.partial(jax.jit, static_argnames=("measure", "scope", "stride",
                                             "num_buckets"))
def batch_evolve(anchor, d_rec: Delta, d_net: Delta, t_anchor,
                 t_los, widths, vs, *, measure: str, scope: str,
                 stride: int, num_buckets: int):
    """The engine's sweep-group entry point: one program for Q sweeps.

    ``anchor``/``d_rec``/``t_anchor`` reconstruct each query's start
    state (``d_rec`` may be merged-tree-covered — LWW only);
    ``d_net`` is the LEAF delta covering every query's sweep window.
    ``t_los``/``widths``/``vs`` are i32[Q]; all queries in the group
    share (measure, scope, stride) by the planner's group key.

    Output: [Q, num_buckets] (i32 or f32 per measure), or
    [Q, num_buckets, bins] for degree_distribution.  Samples past a
    query's width repeat its last state — callers slice ``[:width]``.

    Name scopes (metadata only): the start-state reconstruction, the
    NET scatter and the scan's apply run under ``replay``, the scan's
    measure under ``measure``.
    """
    n_cap = anchor.n_cap
    edge_layout = isinstance(anchor, EdgeGraph)

    def one(t_lo, width, v):
        if edge_layout:
            g = reconstruct_edge(anchor, d_rec, t_anchor, t_lo)
        else:
            g = reconstruct_dense(anchor, d_rec, t_anchor, t_lo)
        t_last = t_lo + (width - 1) * stride
        with jax.named_scope("replay"):
            nets = sweep_nets(d_net, t_lo, t_last, stride, num_buckets,
                              n_cap)
        return sweep_scan(measure, scope, v, g.degrees(), g.nodes,
                          g.num_nodes(), g.num_edges(), nets)

    return jax.vmap(one)(t_los, widths, vs)
