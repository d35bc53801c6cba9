"""Snapshot reconstruction from deltas.

Three implementations of the paper's ForRec/BackRec (Algorithms 1 & 2):

1. ``reconstruct_sequential`` — the *paper-faithful* baseline: a
   ``lax.scan`` that replays one operation per step, exactly Algorithm 1
   (forward) / Algorithm 2 (backward, via the inverted delta of
   Definition 5).

2. ``reconstruct_at`` — the TPU-native *last-writer-wins* reduction
   (DESIGN.md §2.2).  Validity of a key at t′ is decided by the last op
   with t ≤ t′ (forward from an anchor) or the first op with t > t′
   (backward): one scatter-max of a per-op key that carries the op's
   log position and the key's new bit (``_lww_key``), fully parallel
   over ops — no sequential dependence, and the op column is never
   read back per key.  This is the beyond-paper optimization measured
   against (1) in EXPERIMENTS.md §Perf.

3. ``validity_series`` — all-times reconstruction for range queries:
   per-time-bucket net counts + a cumulative correction, one pass over
   the window instead of one reconstruction per bucket.

Both directions (Theorem 1) are supported; the direction is chosen from
``t_query`` vs ``t_anchor``.  Windows are half-open: SG_t contains the
effect of every op with time ≤ t.

The LWW reconstructions run under the ``replay`` name scope, with
``scatter`` (the key scatter-max) and ``decide`` (``_lww_decide``, the
read of the scattered key and the select) inside it: metadata only,
which lets a device trace split a program's time between replay and
measure.
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


def _lww_key(win, op, forward, add_code):
    """Per-op last-writer-wins key, −1 where ``win`` is False.

    forward:  ``2·idx + (op == add_code)`` — the scatter-max per key
              picks the LAST in-window op, and bit 0 is the new value;
    backward: ``2·(m − 1 − idx) + (op != add_code)`` — the max picks
              the FIRST in-window op: if it re-adds the key, the key
              was absent at t′; if it removes the key, it was present.
    The largest key is 2·m − 1, so int32 holds it for any m ≤ 2^30.
    """
    m = op.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    is_add = op == add_code
    pos = jnp.where(forward, idx, m - 1 - idx)
    bit = jnp.where(forward, is_add, ~is_add).astype(jnp.int32)
    return jnp.where(win, 2 * pos + bit, -1)


def _lww_decide(key, old):
    """Shared decision rule on the scatter-max of ``_lww_key`` (−1
    where no in-window op touched the key): a decided key takes bit 0
    of its key, an undecided one keeps ``old``."""
    return jnp.where(key >= 0, (key & 1) == 1, old)


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
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    if restrict_rows:
        assert row_mask is not None
        touch = row_mask[delta.u] | row_mask[delta.v]
        in_win = in_win & touch

    # ---- edges: scatter-max one key per (u, v) cell ----
    e_key = _lww_key(in_win & delta.is_edge_op(), delta.op, forward,
                     ADD_EDGE)
    with jax.named_scope("scatter"):
        key = jnp.full((n, n), -1, jnp.int32)
        key = key.at[delta.u, delta.v].max(e_key)
        key = key.at[delta.v, delta.u].max(e_key)
    with jax.named_scope("decide"):
        adj = _lww_decide(key, anchor.adj)

    # ---- nodes ----
    n_key = _lww_key(in_win & delta.is_node_op(), delta.op, forward,
                     ADD_NODE)
    with jax.named_scope("scatter"):
        keyn = jnp.full((n,), -1, jnp.int32).at[delta.u].max(n_key)
    with jax.named_scope("decide"):
        nodes = _lww_decide(keyn, anchor.nodes)
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
    forward = t_query >= t_anchor
    t_lo = jnp.minimum(t_anchor, t_query)
    t_hi = jnp.maximum(t_anchor, t_query)
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()

    e_key = _lww_key(in_win & delta.is_edge_op(), delta.op, forward,
                     ADD_EDGE)
    with jax.named_scope("scatter"):
        key = jnp.full((anchor.e_cap,), -1, jnp.int32).at[
            delta.slot].max(e_key)
    with jax.named_scope("decide"):
        emask = _lww_decide(key, anchor.emask)

    n_key = _lww_key(in_win & delta.is_node_op(), delta.op, forward,
                     ADD_NODE)
    with jax.named_scope("scatter"):
        keyn = jnp.full((anchor.n_cap,), -1, jnp.int32).at[
            delta.slot].max(n_key)
    with jax.named_scope("decide"):
        nodes = _lww_decide(keyn, anchor.nodes)
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
