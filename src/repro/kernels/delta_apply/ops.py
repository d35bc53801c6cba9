"""jit'd wrapper for the delta_apply kernel: window filtering, tile
bucketing, ordering, and the node-mask update (nodes are N-sized and
cheap — they stay on the XLA path)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.delta import ADD_EDGE, ADD_NODE, REM_EDGE, Delta
from repro.core.graph import DenseGraph
from repro.core.reconstruct import _lww_decide, _lww_key
from repro.kernels.delta_apply.delta_apply import delta_apply_tiles


@functools.partial(jax.jit, static_argnames=("n", "tile", "cap", "forward",
                                             "n_rows", "row0",
                                             "n_valid_rows"))
def bucket_ops(delta: Delta, n: int, t_lo, t_hi, tile: int, cap: int,
               forward: bool, n_rows: int | None = None, row0: int = 0,
               n_valid_rows: int | None = None):
    """Build the dense per-tile op blocks i32[Tr, Tc, 4, cap],
    field-major: each entry is the column [lu, lv, value, valid].

    Every in-window edge op contributes two entries ((u,v) and (v,u)).
    Entries are ordered so sequential overwrite == last-writer-wins:
    ascending time for forward, descending for backward.  Per-tile
    overflow beyond ``cap`` is detected and returned as a flag.

    ``n_rows``/``row0`` make the bucketing *shard-safe*: a device that
    owns only adjacency rows [row0, row0 + n_rows) buckets exactly the
    entries landing in its row block (columns stay global), with its
    own tile padding — so per-shard blocks concatenate to the full
    grid and the kernel runs unchanged on one row shard.
    ``n_valid_rows`` (default ``n_rows``) caps the *kept* rows below
    the tile-padded count, so ops owned by the next shard never leak
    into this shard's pad band (they would waste cap slots and trip a
    spurious overflow).
    """
    m = delta.capacity
    n_rows = n if n_rows is None else n_rows
    n_valid_rows = n_rows if n_valid_rows is None else n_valid_rows
    tr = n_rows // tile
    tc = n // tile
    nt = tr * tc
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    e = in_win & delta.is_edge_op()
    val = (delta.op == (ADD_EDGE if forward else REM_EDGE)).astype(jnp.int32)

    us = jnp.concatenate([delta.u, delta.v])
    vs = jnp.concatenate([delta.v, delta.u])
    ee = jnp.concatenate([e, e])
    vals = jnp.concatenate([val, val])
    order_rank = jnp.concatenate([jnp.arange(m), jnp.arange(m)])
    if not forward:
        order_rank = (m - 1) - order_rank  # descending time

    lr = us - row0                       # row local to this shard
    ee = ee & (lr >= 0) & (lr < n_valid_rows)
    lr = jnp.clip(lr, 0, max(n_rows - 1, 0))
    tile_id = jnp.where(ee, (lr // tile) * tc + (vs // tile), nt)
    # sort by (tile, rank): stable two-pass — first by rank, then by tile
    o1 = jnp.argsort(order_rank, stable=True)
    t1 = tile_id[o1]
    o2 = jnp.argsort(t1, stable=True)
    perm = o1[o2]
    tid_s = tile_id[perm]
    # position of each entry within its tile bucket
    seg_start = jnp.searchsorted(tid_s, jnp.arange(nt + 1))
    pos = jnp.arange(2 * m) - seg_start[tid_s]
    overflow = jnp.any((pos >= cap) & (tid_s < nt))

    dst_t = jnp.where(tid_s < nt, tid_s, nt)
    dst_p = jnp.clip(pos, 0, cap - 1)
    entries = jnp.stack([lr[perm] % tile, vs[perm] % tile, vals[perm],
                         jnp.ones_like(dst_p)], axis=1)
    blocks = jnp.zeros((nt + 1, 4, cap), jnp.int32)
    keep = (tid_s < nt) & (pos < cap)
    blocks = blocks.at[jnp.where(keep, dst_t, nt), :,
                       dst_p].set(jnp.where(keep[:, None], entries, 0))
    return blocks[:nt].reshape(tr, tc, 4, cap), overflow


def _node_mask_lww(nodes, delta: Delta, t_lo, t_hi, forward: bool,
                   row0: int = 0):
    """LWW node-mask update for rows [row0, row0 + len(nodes)) — the
    XLA path (N-sized, negligible next to the N² edge part)."""
    n_rows = nodes.shape[0]
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    lu = delta.u - row0
    nwin = in_win & delta.is_node_op() & (lu >= 0) & (lu < n_rows)
    lu = jnp.clip(lu, 0, n_rows - 1)
    key = jnp.full((n_rows,), -1, jnp.int32).at[lu].max(
        _lww_key(nwin, delta.op, forward, ADD_NODE))
    return _lww_decide(key, nodes)


def delta_apply_row_block(nodes_block: jnp.ndarray, adj_block: jnp.ndarray,
                          delta: Delta, t_anchor: int, t_query: int,
                          row0: int, tile: int = 256, cap: int = 1024,
                          interpret: bool = False):
    """Kernel-backed LWW reconstruction of one adjacency *row block*
    (shard-safe: this is what each device of a row-sharded mesh runs).

    ``adj_block`` is bool[R, N] — rows [row0, row0 + R) of the global
    adjacency, columns global.  Row/column padding to the tile size is
    applied per block, so any shard width that divides into tiles (or
    pads up to one) works without touching other shards' rows.
    """
    n_rows, n_cols = adj_block.shape
    pad_r = (-n_rows) % tile
    pad_c = (-n_cols) % tile
    forward = bool(t_query >= t_anchor)
    t_lo, t_hi = min(t_anchor, t_query), max(t_anchor, t_query)

    adj = adj_block.astype(jnp.int32)
    if pad_r or pad_c:
        adj = jnp.pad(adj, ((0, pad_r), (0, pad_c)))
    blocks, overflow = bucket_ops(delta, n_cols + pad_c, t_lo, t_hi, tile,
                                  cap, forward, n_rows=n_rows + pad_r,
                                  row0=row0, n_valid_rows=n_rows)
    out = delta_apply_tiles(adj, blocks, tile=tile, cap=cap,
                            interpret=interpret)
    adj_new = out[:n_rows, :n_cols].astype(bool)
    nodes = _node_mask_lww(nodes_block, delta, t_lo, t_hi, forward, row0)
    return nodes, adj_new, overflow


def delta_apply(anchor: DenseGraph, delta: Delta, t_anchor: int,
                t_query: int, tile: int = 256, cap: int = 1024,
                interpret: bool = False) -> DenseGraph:
    """Kernel-backed reconstruct_at for DenseGraph (edge part on the
    Pallas kernel, node mask via XLA scatter)."""
    nodes, adj_new, overflow = delta_apply_row_block(
        anchor.nodes, anchor.adj, delta, t_anchor, t_query, 0,
        tile=tile, cap=cap, interpret=interpret)
    return DenseGraph(nodes=nodes, adj=adj_new), overflow
