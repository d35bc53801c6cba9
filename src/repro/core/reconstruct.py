"""Snapshot reconstruction from deltas.

Three implementations of the paper's ForRec/BackRec (Algorithms 1 & 2):

1. ``reconstruct_sequential`` — the *paper-faithful* baseline: a
   ``lax.scan`` that replays one operation per step, exactly Algorithm 1
   (forward) / Algorithm 2 (backward, via the inverted delta of
   Definition 5).

2. ``reconstruct_at`` — the TPU-native *last-writer-wins* reduction
   (DESIGN.md §2.2).  Validity of a key at t′ is decided by the last op
   with t ≤ t′ (forward from an anchor) or the first op with t > t′
   (backward): a scatter-argmin/argmax over op indices, fully parallel
   over ops — no sequential dependence.  This is the beyond-paper
   optimization measured against (1) in EXPERIMENTS.md §Perf.

3. ``validity_series`` — all-times reconstruction for range queries:
   per-time-bucket net counts + a cumulative correction, one pass over
   the window instead of one reconstruction per bucket.

Both directions (Theorem 1) are supported; the direction is chosen from
``t_query`` vs ``t_anchor``.  Windows are half-open: SG_t contains the
effect of every op with time ≤ t.

The LWW reconstructions run under the ``replay`` name scope, with
``scatter`` (the first/last op-index scatters) and ``decide``
(``_lww_decide`` and the select) inside it: metadata only, which lets a
device trace split a program's time between replay and measure.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.delta import (ADD_EDGE, ADD_NODE, NOP, REM_EDGE, REM_NODE,
                              Delta)
from repro.core.graph import DenseGraph, EdgeGraph

# --------------------------------------------------------------------------
# Vectorized last-writer-wins reconstruction
# --------------------------------------------------------------------------


def _lww_decide(first_idx, last_idx, op, forward, sentinel_hi, add_code):
    """Shared decision rule.

    forward:  decided by LAST in-window op; new value = (op == ADD).
    backward: decided by FIRST in-window op; new value = (op == REM),
              i.e. if the first later op re-adds the key it was absent
              at t′, if it removes the key it was present.
    Returns (decided_mask, new_value).
    """
    dec_f = last_idx >= 0
    val_f = op[jnp.clip(last_idx, 0)] == add_code
    dec_b = first_idx < sentinel_hi
    val_b = op[jnp.clip(first_idx, None, sentinel_hi - 1)] != add_code
    decided = jnp.where(forward, dec_f, dec_b)
    value = jnp.where(forward, val_f, val_b)
    return decided, value


@partial(jax.jit, static_argnames=("restrict_rows",))
@jax.named_scope("replay")
def reconstruct_dense(anchor: DenseGraph, delta: Delta, t_anchor, t_query,
                      row_mask: jax.Array | None = None,
                      restrict_rows: bool = False) -> DenseGraph:
    """Last-writer-wins reconstruction of SG_{t_query} from an anchor
    snapshot at ``t_anchor`` (forward or backward chosen automatically).

    ``row_mask``/``restrict_rows`` implement *partial reconstruction*
    (paper §3.3.1): only keys touching masked nodes are reconstructed;
    everything else keeps its anchor value (callers must only read the
    reconstructed subgraph).
    """
    n = anchor.n_cap
    m = delta.capacity
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    if restrict_rows:
        assert row_mask is not None
        touch = row_mask[delta.u] | row_mask[delta.v]
        in_win = in_win & touch

    idx = jnp.arange(m, dtype=jnp.int32)

    # ---- edges: scatter first/last op index per (u, v) cell ----
    e_win = in_win & delta.is_edge_op()
    e_first = jnp.where(e_win, idx, m)
    e_last = jnp.where(e_win, idx, -1)
    with jax.named_scope("scatter"):
        first = jnp.full((n, n), m, jnp.int32)
        last = jnp.full((n, n), -1, jnp.int32)
        first = first.at[delta.u, delta.v].min(e_first)
        first = first.at[delta.v, delta.u].min(e_first)
        last = last.at[delta.u, delta.v].max(e_last)
        last = last.at[delta.v, delta.u].max(e_last)
    with jax.named_scope("decide"):
        decided, value = _lww_decide(first, last, delta.op, forward, m,
                                     ADD_EDGE)
        adj = jnp.where(decided, value, anchor.adj)

    # ---- nodes ----
    n_win = in_win & delta.is_node_op()
    n_first = jnp.where(n_win, idx, m)
    n_last = jnp.where(n_win, idx, -1)
    with jax.named_scope("scatter"):
        firstn = jnp.full((n,), m, jnp.int32).at[delta.u].min(n_first)
        lastn = jnp.full((n,), -1, jnp.int32).at[delta.u].max(n_last)
    with jax.named_scope("decide"):
        decided_n, value_n = _lww_decide(firstn, lastn, delta.op, forward,
                                         m, ADD_NODE)
        nodes = jnp.where(decided_n, value_n, anchor.nodes)
    return DenseGraph(nodes=nodes, adj=adj)


@jax.jit
@jax.named_scope("replay")
def reconstruct_edge(anchor: EdgeGraph, delta: Delta, t_anchor,
                     t_query) -> EdgeGraph:
    """Last-writer-wins reconstruction on the edge-slot layout.

    Scatters over 1-D persistent slots (DESIGN.md §2.1) — O(M) work and
    O(E+N) state, independent of N²; this is the layout the distributed
    engine shards.
    """
    m = delta.capacity
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    idx = jnp.arange(m, dtype=jnp.int32)

    e_win = in_win & delta.is_edge_op()
    with jax.named_scope("scatter"):
        first = jnp.full((anchor.e_cap,), m, jnp.int32)
        last = jnp.full((anchor.e_cap,), -1, jnp.int32)
        first = first.at[delta.slot].min(jnp.where(e_win, idx, m))
        last = last.at[delta.slot].max(jnp.where(e_win, idx, -1))
    with jax.named_scope("decide"):
        decided, value = _lww_decide(first, last, delta.op, forward, m,
                                     ADD_EDGE)
        emask = jnp.where(decided, value, anchor.emask)

    n_win = in_win & delta.is_node_op()
    with jax.named_scope("scatter"):
        firstn = jnp.full((anchor.n_cap,), m, jnp.int32)
        lastn = jnp.full((anchor.n_cap,), -1, jnp.int32)
        firstn = firstn.at[delta.slot].min(jnp.where(n_win, idx, m))
        lastn = lastn.at[delta.slot].max(jnp.where(n_win, idx, -1))
    with jax.named_scope("decide"):
        decided_n, value_n = _lww_decide(firstn, lastn, delta.op, forward,
                                         m, ADD_NODE)
        nodes = jnp.where(decided_n, value_n, anchor.nodes)
    return dataclasses.replace(anchor, nodes=nodes, emask=emask)


def reconstruct_at(anchor, delta: Delta, t_anchor, t_query, **kw):
    """Dispatch on snapshot layout."""
    if isinstance(anchor, DenseGraph):
        return reconstruct_dense(anchor, delta, t_anchor, t_query, **kw)
    return reconstruct_edge(anchor, delta, t_anchor, t_query)


# --------------------------------------------------------------------------
# Paper-faithful sequential replay (Algorithms 1 & 2)
# --------------------------------------------------------------------------


@jax.jit
def reconstruct_sequential(anchor: DenseGraph, delta: Delta, t_anchor,
                           t_query) -> DenseGraph:
    """One-op-at-a-time replay, exactly the paper's ForRec/BackRec.

    Forward: scan ops in log order, apply those with t_anchor < t ≤ t_query.
    Backward: scan in reverse order, apply the *inverse* op (Definition 5)
    for those with t_query < t ≤ t_anchor.
    """
    forward = t_query >= t_anchor

    def body(carry, x):
        nodes, adj = carry
        op, u, v, t = x
        apply_f = forward & (t > t_anchor) & (t <= t_query)
        apply_b = (~forward) & (t > t_query) & (t <= t_anchor)
        op = jnp.where(apply_b & (op != NOP), op ^ 1, op)  # invert (Def. 5)
        app = (apply_f | apply_b) & (op != NOP)

        is_edge = (op == ADD_EDGE) | (op == REM_EDGE)
        bit = op == ADD_EDGE
        cur_uv = adj[u, v]
        new_uv = jnp.where(app & is_edge, bit, cur_uv)
        adj = adj.at[u, v].set(new_uv)
        adj = adj.at[v, u].set(new_uv)

        is_node = (op == ADD_NODE) | (op == REM_NODE)
        nbit = op == ADD_NODE
        cur_n = nodes[u]
        nodes = nodes.at[u].set(jnp.where(app & is_node, nbit, cur_n))
        return (nodes, adj), None

    xs = (delta.op, delta.u, delta.v, delta.t)
    xs_ordered = jax.tree.map(
        lambda a: jnp.where(forward, a, a[::-1]), xs)
    (nodes, adj), _ = jax.lax.scan(body, (anchor.nodes, anchor.adj),
                                   xs_ordered)
    return DenseGraph(nodes=nodes, adj=adj)


# --------------------------------------------------------------------------
# All-times validity series (for range queries / hybrid plans)
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_buckets",))
def degree_series(current, delta: Delta, t_k, t_l,
                  num_buckets: int, t_cur) -> jax.Array:
    """Degree of every node at each time unit in [t_k, t_l].

    Hybrid-plan primitive (paper §3.2.3): measure once on SG_tcur, then
    correct backwards with per-bucket net edge counts — one pass over the
    delta.  Bucket b corresponds to time t_k + b; ``num_buckets`` must be
    ≥ t_l - t_k + 1 (extra buckets are computed but ignorable).

    ``current`` is layout-polymorphic: only ``degrees()``/``n_cap`` are
    read, so an ``EdgeGraph`` works too (its segment-sum degrees are
    the same integers, keeping edge-layout hybrid results bit-identical
    to dense ones) — the delta correction below never touches N² state.

    Returns i32[num_buckets, N]: row b = degrees at time t_k + b.
    """
    n = current.n_cap
    valid = delta.valid_mask() & delta.is_edge_op()
    sign = jnp.where(delta.op == ADD_EDGE, 1, -1) * valid.astype(jnp.int32)

    # Net degree change per (bucket, node) for ops with t in (t_k, t_cur].
    # Ops later than t_l all fold into the correction of the last bucket,
    # so clip bucket index to num_buckets - 1... they must correct every
    # bucket; handled via suffix-cumsum below, ops in (t_l, t_cur] land in
    # bucket num_buckets (a virtual tail row).
    b = jnp.clip(delta.t - t_k, 0, num_buckets)  # bucket per op (0 => ≤ t_k)
    in_suffix = (delta.t > t_k) & valid
    sign = sign * in_suffix.astype(jnp.int32)

    net = jnp.zeros((num_buckets + 1, n), jnp.int32)
    net = net.at[b, delta.u].add(sign)
    net = net.at[b, delta.v].add(sign)

    # degree at bucket time τ_b = deg_cur − Σ_{t > τ_b} net
    # suffix sums over buckets strictly greater than b:
    suffix = jnp.cumsum(net[::-1], axis=0)[::-1]          # Σ_{b' ≥ b}
    suffix_after = jnp.concatenate([suffix[1:], jnp.zeros((1, n), jnp.int32)])
    deg_cur = current.degrees()[None, :]
    return (deg_cur - suffix_after[:num_buckets]).astype(jnp.int32)


@partial(jax.jit, static_argnames=("num_buckets",))
def node_degree_series(current_degree, delta: Delta, v, t_k, num_buckets: int):
    """Degree time-series for a single node (hybrid plan, no N² state).

    Returns i32[num_buckets]: entry b = degree(v) at time t_k + b.
    """
    valid = delta.valid_mask() & delta.is_edge_op()
    touch = (delta.u == v) | (delta.v == v)
    sign = jnp.where(delta.op == ADD_EDGE, 1, -1)
    in_suffix = (delta.t > t_k) & valid & touch
    sign = sign * in_suffix.astype(jnp.int32)
    b = jnp.clip(delta.t - t_k, 0, num_buckets)
    net = jnp.zeros((num_buckets + 1,), jnp.int32).at[b].add(sign)
    suffix = jnp.cumsum(net[::-1])[::-1]
    suffix_after = jnp.concatenate([suffix[1:], jnp.zeros((1,), jnp.int32)])
    return current_degree - suffix_after[:num_buckets]
