"""The keyed last-writer-wins rule against the gather rule it replaced.

Every LWW replay decides a key from one scatter-max of a per-op key
``2·position + bit`` (``reconstruct._lww_key`` / ``_lww_decide``).  The
rule it replaced scattered the first and last in-window op index per
key and gathered the op column at them; that formula is copied below
as the oracle, and every caller of the keyed rule must give the same
bits on every case, forward and backward.
"""
import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.delta import (ADD_EDGE, ADD_NODE, NOP, REM_EDGE, REM_NODE,
                              Delta, delta_from_numpy)
from repro.core.distributed import _local_lww, _node_lww, _slot_lww
from repro.core.graph import DenseGraph, EdgeGraph
from repro.core.partial import closure_mask, partial_reconstruct, seed_mask
from repro.core.reconstruct import reconstruct_dense, reconstruct_edge
from repro.core.store import Op, TemporalGraphStore
from repro.kernels.delta_apply.ops import _node_mask_lww
from repro.sharding.graph import AXIS

N = 8
CAP = 64

# ---------------------------------------------------------------------------
# Oracle: the gather rule, as the replay computed it before the keyed rule
# ---------------------------------------------------------------------------


def _gather_decide(first_idx, last_idx, op, forward, sentinel_hi, add_code):
    dec_f = last_idx >= 0
    val_f = op[jnp.clip(last_idx, 0)] == add_code
    dec_b = first_idx < sentinel_hi
    val_b = op[jnp.clip(first_idx, None, sentinel_hi - 1)] != add_code
    decided = jnp.where(forward, dec_f, dec_b)
    value = jnp.where(forward, val_f, val_b)
    return decided, value


def _gather_rule(old, targets, win, op, forward, add_code):
    """First/last op index per key by scatter-min/max over ``targets``
    (index tuples into ``old``), then the op column gathered there."""
    m = op.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    first = jnp.full(old.shape, m, jnp.int32)
    last = jnp.full(old.shape, -1, jnp.int32)
    for tgt in targets:
        first = first.at[tgt].min(jnp.where(win, idx, m))
        last = last.at[tgt].max(jnp.where(win, idx, -1))
    dec, val = _gather_decide(first, last, op, forward, m, add_code)
    return jnp.where(dec, val, old)


def _window(d: Delta, t_anchor, t_query):
    forward = t_query >= t_anchor
    win = (d.window_mask(jnp.minimum(t_anchor, t_query),
                         jnp.maximum(t_anchor, t_query)) & d.valid_mask())
    return forward, win


@partial(jax.jit, static_argnames=("restrict_rows",))
def _oracle_dense(g: DenseGraph, d: Delta, t_anchor, t_query,
                  row_mask=None, restrict_rows=False):
    forward, win = _window(d, t_anchor, t_query)
    if restrict_rows:
        win = win & (row_mask[d.u] | row_mask[d.v])
    adj = _gather_rule(g.adj, [(d.u, d.v), (d.v, d.u)],
                       win & d.is_edge_op(), d.op, forward, ADD_EDGE)
    nodes = _gather_rule(g.nodes, [(d.u,)], win & d.is_node_op(), d.op,
                         forward, ADD_NODE)
    return DenseGraph(nodes=nodes, adj=adj)


@jax.jit
def _oracle_edge(g: EdgeGraph, d: Delta, t_anchor, t_query):
    forward, win = _window(d, t_anchor, t_query)
    emask = _gather_rule(g.emask, [(d.slot,)], win & d.is_edge_op(), d.op,
                         forward, ADD_EDGE)
    nodes = _gather_rule(g.nodes, [(d.slot,)], win & d.is_node_op(), d.op,
                         forward, ADD_NODE)
    return dataclasses.replace(g, nodes=nodes, emask=emask)


@partial(jax.jit, static_argnames=("slot0",))
def _oracle_slot_block(emask_l, d: Delta, t_anchor, t_query, slot0):
    forward, win = _window(d, t_anchor, t_query)
    e_loc = emask_l.shape[0]
    ls = d.slot - slot0
    ok = win & d.is_edge_op() & (ls >= 0) & (ls < e_loc)
    return _gather_rule(emask_l, [(jnp.clip(ls, 0, e_loc - 1),)], ok, d.op,
                        forward, ADD_EDGE)


# ---------------------------------------------------------------------------
# Cases: hand-made windows on N nodes, and a merged-delta window of a store
# ---------------------------------------------------------------------------

_PAIRS = [(a, b) for a in range(N) for b in range(a + 1, N)]
_SLOT = {p: i for i, p in enumerate(_PAIRS)}
E_CAP = 32


def _delta(ops, capacity=CAP):
    """``ops`` as (code, u, v, t); edge ops get their pair's slot, node
    ops their node id, NOPs slot 0."""
    op, u, v, slot, t = [], [], [], [], []
    for c, a, b, tt in ops:
        op.append(c)
        u.append(a)
        v.append(b)
        t.append(tt)
        if c in (ADD_EDGE, REM_EDGE):
            slot.append(_SLOT[(min(a, b), max(a, b))])
        else:
            slot.append(a if c != NOP else 0)
    return delta_from_numpy(op, u, v, slot, t, capacity=capacity)


def _anchors(rng):
    up = np.triu(rng.random((N, N)) < 0.4, 1)
    adj = up | up.T
    nodes = rng.random(N) < 0.7
    dense = DenseGraph(nodes=jnp.asarray(nodes), adj=jnp.asarray(adj))
    eu = np.zeros(E_CAP, np.int32)
    ev = np.zeros(E_CAP, np.int32)
    emask = np.zeros(E_CAP, bool)
    for (a, b), s in _SLOT.items():
        eu[s], ev[s], emask[s] = a, b, adj[a, b]
    edge = EdgeGraph(nodes=jnp.asarray(nodes), eu=jnp.asarray(eu),
                     ev=jnp.asarray(ev), emask=jnp.asarray(emask),
                     n_edges_reg=jnp.int32(len(_PAIRS)))
    return dense, edge


def _case_empty():
    # ops on both sides of the window (4, 6], none inside it
    ops = [(ADD_EDGE, 0, 1, 1), (REM_EDGE, 2, 3, 2), (ADD_NODE, 5, 5, 4),
           (ADD_EDGE, 1, 2, 7), (REM_NODE, 6, 6, 9)]
    return _delta(ops), 4, 6


def _case_nop_padding():
    # NOPs inside the window and among the counted ops, then the
    # capacity's own NOP padding
    ops = [(NOP, 0, 0, 2), (ADD_EDGE, 0, 1, 2), (NOP, 3, 4, 3),
           (REM_EDGE, 0, 1, 3), (NOP, 5, 5, 4), (ADD_NODE, 4, 4, 4),
           (NOP, 1, 2, 5)]
    return _delta(ops), 1, 6


def _case_readd_reremove():
    ops = [(ADD_EDGE, 0, 1, 2), (REM_EDGE, 2, 3, 2), (REM_NODE, 5, 5, 2),
           (REM_EDGE, 0, 1, 3), (ADD_EDGE, 2, 3, 3), (ADD_NODE, 5, 5, 3),
           (ADD_EDGE, 1, 0, 4),                      # re-added, reversed
           (REM_EDGE, 3, 2, 5),                      # re-removed, reversed
           (ADD_NODE, 6, 6, 4), (REM_NODE, 6, 6, 5),
           (ADD_EDGE, 4, 7, 5), (ADD_EDGE, 4, 7, 5),  # twice at one time
           (REM_EDGE, 4, 7, 6)]
    return _delta(ops), 1, 6


def _case_outside_only():
    # (1, 2) and node 3 have their only op at t_lo, which the half-open
    # window (2, 6] leaves out; (4, 5) and node 7 only after t_hi
    ops = [(REM_EDGE, 1, 2, 2), (ADD_NODE, 3, 3, 2), (ADD_EDGE, 0, 6, 3),
           (REM_EDGE, 0, 6, 6), (ADD_EDGE, 4, 5, 7), (REM_NODE, 7, 7, 8)]
    return _delta(ops), 2, 6


def _case_random():
    rng = np.random.default_rng(5)
    codes = [ADD_NODE, REM_NODE, ADD_EDGE, ADD_EDGE, REM_EDGE, REM_EDGE, NOP]
    ops, t = [], 0
    for _ in range(48):
        t += int(rng.integers(0, 2))
        c = codes[int(rng.integers(0, len(codes)))]
        a = int(rng.integers(0, N))
        b = int(rng.integers(0, N - 1))
        b = b + (b >= a)                       # no self-loops
        ops.append((c, a, b if c in (ADD_EDGE, REM_EDGE) else a, t))
    return _delta(ops), 3, max(t - 3, 4)


@lru_cache(maxsize=1)
def _merged_store():
    rng = np.random.default_rng(13)
    mix = [ADD_NODE, ADD_NODE, ADD_EDGE, ADD_EDGE, ADD_EDGE, REM_EDGE,
           REM_NODE]
    s = TemporalGraphStore(n_cap=16, layout="edge", segment_min_ops=1)
    t = 0
    for _ in range(12):
        t += 1
        chunk = []
        for _ in range(int(rng.integers(8, 16))):
            t += int(rng.integers(0, 2))
            c = mix[int(rng.integers(0, len(mix)))]
            a, b = int(rng.integers(0, 16)), int(rng.integers(0, 16))
            chunk.append(Op(c, a, b if c in (ADD_EDGE, REM_EDGE) else a, t))
        s.ingest(chunk)
        s.advance_to(t)
        s.freeze_serving_state()
    return s


_CASES = {"empty": _case_empty, "nop_padding": _case_nop_padding,
          "readd_reremove": _case_readd_reremove,
          "outside_only": _case_outside_only, "random": _case_random}


def _setup(case, direction):
    """(dense anchor, edge anchor, delta, t_anchor, t_query)."""
    if case == "merged":
        s = _merged_store()
        view = s.delta_view()
        lo, hi = 2, s.t_cur
        d = view.window_delta(lo, hi, merged=True)
        assert int(d.n_ops) < int(view.window_delta(lo, hi).n_ops), \
            "the window must be served by collapsed tree nodes"
        edge = s.current_edge_snapshot()
        dense = edge.to_dense()
    else:
        d, lo, hi = _CASES[case]()
        dense, edge = _anchors(np.random.default_rng(len(case)))
    t_anchor, t_query = (lo, hi) if direction == "forward" else (hi, lo)
    return dense, edge, d, t_anchor, t_query


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Callers of the keyed rule
# ---------------------------------------------------------------------------


def _check_dense(dense, edge, d, ta, tq):
    got = reconstruct_dense(dense, d, ta, tq)
    want = _oracle_dense(dense, d, ta, tq)
    _eq(got.adj, want.adj)
    _eq(got.nodes, want.nodes)


def _check_edge(dense, edge, d, ta, tq):
    got = reconstruct_edge(edge, d, ta, tq)
    want = _oracle_edge(edge, d, ta, tq)
    _eq(got.emask, want.emask)
    _eq(got.nodes, want.nodes)


def _check_slot(dense, edge, d, ta, tq):
    # the two halves of the slot axis, as two shards hold them
    half = edge.e_cap // 2
    for slot0 in (0, half):
        block = edge.emask[slot0:slot0 + half]
        got = jax.jit(_slot_lww, static_argnums=4)(block, d, ta, tq, slot0)
        _eq(got, _oracle_slot_block(block, d, ta, tq, slot0))


def _check_node(dense, edge, d, ta, tq):
    got = jax.jit(_node_lww)(dense.nodes, d, ta, tq)
    _eq(got, _oracle_dense(dense, d, ta, tq).nodes)


def _check_rows(dense, edge, d, ta, tq):
    # two row blocks under a named axis stand in for two shards
    n = dense.n_cap
    f = jax.jit(jax.vmap(_local_lww, in_axes=(0, 0, None, None, None),
                         axis_name=AXIS))
    nodes, adj = f(dense.nodes.reshape(2, n // 2),
                   dense.adj.reshape(2, n // 2, n), d, ta, tq)
    want = _oracle_dense(dense, d, ta, tq)
    _eq(adj.reshape(n, n), want.adj)
    _eq(nodes.reshape(n), want.nodes)


def _check_kernel_node(dense, edge, d, ta, tq):
    forward = tq >= ta
    lo, hi = min(ta, tq), max(ta, tq)
    n = dense.n_cap
    want = _oracle_dense(dense, d, ta, tq).nodes
    for row0 in (0, n // 2):
        got = _node_mask_lww(dense.nodes[row0:row0 + n // 2], d, lo, hi,
                             forward, row0)
        _eq(got, want[row0:row0 + n // 2])


def _check_partial(dense, edge, d, ta, tq):
    seed = seed_mask(dense.n_cap, 1)
    got = partial_reconstruct(dense, d, ta, tq, seed)
    mask = closure_mask(dense, d, seed, min(ta, tq), max(ta, tq))
    want = _oracle_dense(dense, d, ta, tq, row_mask=mask,
                         restrict_rows=True)
    _eq(got.adj, want.adj & mask[:, None] & mask[None, :])
    _eq(got.nodes, want.nodes & mask)


_CALLERS = {"dense": _check_dense, "edge": _check_edge,
            "slot": _check_slot, "node": _check_node, "rows": _check_rows,
            "kernel_node": _check_kernel_node, "partial": _check_partial}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("case", [*_CASES, "merged"])
@pytest.mark.parametrize("caller", list(_CALLERS))
def test_keyed_rule_matches_gather_rule(caller, case, direction):
    _CALLERS[caller](*_setup(case, direction))


def test_key_carries_position_and_bit():
    """Forward keys order ops by log position, backward keys by reverse
    position; bit 0 is the value the key takes; -1 off the window."""
    from repro.core.reconstruct import _lww_key
    op = jnp.asarray([ADD_EDGE, REM_EDGE, ADD_EDGE, REM_EDGE], jnp.int32)
    win = jnp.asarray([True, True, True, False])
    _eq(_lww_key(win, op, True, ADD_EDGE), [1, 2, 5, -1])
    _eq(_lww_key(win, op, False, ADD_EDGE), [6, 5, 2, -1])
