"""jit'd wrapper for the edge_delta_apply kernel: window filtering,
slot-tile bucketing, ordering, and the node-mask update (nodes are
N-sized and cheap — they stay on the XLA path, exactly like
``kernels/delta_apply``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.delta import ADD_EDGE, REM_EDGE, Delta
from repro.core.graph import EdgeGraph
from repro.kernels.delta_apply.ops import _node_mask_lww
from repro.kernels.edge_delta_apply.edge_delta_apply import (
    edge_delta_apply_tiles)


@functools.partial(jax.jit, static_argnames=("e", "tile", "cap", "forward",
                                             "slot0", "n_valid_slots"))
def bucket_slot_ops(delta: Delta, e: int, t_lo, t_hi, tile: int, cap: int,
                    forward: bool, slot0: int = 0,
                    n_valid_slots: int | None = None):
    """Build the dense per-slot-tile op blocks i32[T, 4, cap],
    field-major: each entry is the column [local_slot, value, valid, 0].

    Every in-window edge op contributes ONE entry under its
    pre-resolved slot id (``delta.slot``, assigned host-side by the
    store) — the 1-D analogue of ``delta_apply.bucket_ops``'s (u,v)
    mirrors.  Entries are ordered so sequential overwrite ==
    last-writer-wins: ascending time for forward, descending for
    backward.  Per-tile overflow beyond ``cap`` is detected and
    returned as a flag.

    ``slot0``/``n_valid_slots`` make the bucketing *shard-safe*: a
    device that owns only slots [slot0, slot0 + n_valid_slots) buckets
    exactly the ops landing in its slot block, with its own tile
    padding — per-shard blocks concatenate to the full grid and the
    kernel runs unchanged on one slot shard.  ``n_valid_slots``
    (default ``e``) caps the kept slots below the tile-padded count so
    the next shard's ops never leak into this shard's pad band.
    """
    m = delta.capacity
    n_valid_slots = e if n_valid_slots is None else n_valid_slots
    tcount = e // tile
    in_win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()
    ee = in_win & delta.is_edge_op()
    val = (delta.op == (ADD_EDGE if forward else REM_EDGE)).astype(jnp.int32)

    order_rank = jnp.arange(m)
    if not forward:
        order_rank = (m - 1) - order_rank  # descending time

    ls = delta.slot - slot0              # slot local to this shard
    ee = ee & (ls >= 0) & (ls < n_valid_slots)
    ls = jnp.clip(ls, 0, max(e - 1, 0))
    tile_id = jnp.where(ee, ls // tile, tcount)
    # sort by (tile, rank): stable two-pass — first by rank, then by tile
    o1 = jnp.argsort(order_rank, stable=True)
    t1 = tile_id[o1]
    o2 = jnp.argsort(t1, stable=True)
    perm = o1[o2]
    tid_s = tile_id[perm]
    # position of each entry within its tile bucket
    seg_start = jnp.searchsorted(tid_s, jnp.arange(tcount + 1))
    pos = jnp.arange(m) - seg_start[tid_s]
    overflow = jnp.any((pos >= cap) & (tid_s < tcount))

    dst_p = jnp.clip(pos, 0, cap - 1)
    entries = jnp.stack([ls[perm] % tile, val[perm],
                         jnp.ones_like(dst_p), jnp.zeros_like(dst_p)],
                        axis=1)
    blocks = jnp.zeros((tcount + 1, 4, cap), jnp.int32)
    keep = (tid_s < tcount) & (pos < cap)
    blocks = blocks.at[jnp.where(keep, tid_s, tcount), :,
                       dst_p].set(jnp.where(keep[:, None], entries, 0))
    return blocks[:tcount], overflow


def edge_delta_apply_slot_block(nodes: jnp.ndarray, emask_block: jnp.ndarray,
                                delta: Delta, t_anchor: int, t_query: int,
                                slot0: int, tile: int = 512,
                                cap: int = 1024, interpret: bool = False):
    """Kernel-backed LWW reconstruction of one edge-mask *slot block*
    (shard-safe: this is what each device of a slot-sharded mesh runs).

    ``emask_block`` is bool[S] — slots [slot0, slot0 + S) of the global
    registry.  Slot padding to the tile size is applied per block, so
    any shard width that divides into tiles (or pads up to one) works
    without touching other shards' slots.  ``nodes`` is the (full,
    replicated) node mask — N-sized, updated on the XLA path.
    """
    s = emask_block.shape[0]
    pad = (-s) % tile
    forward = bool(t_query >= t_anchor)
    t_lo, t_hi = min(t_anchor, t_query), max(t_anchor, t_query)

    mask = emask_block.astype(jnp.int32)
    if pad:
        mask = jnp.pad(mask, (0, pad))
    blocks, overflow = bucket_slot_ops(delta, s + pad, t_lo, t_hi, tile,
                                       cap, forward, slot0=slot0,
                                       n_valid_slots=s)
    out = edge_delta_apply_tiles(mask, blocks, tile=tile, cap=cap,
                                 interpret=interpret)
    emask_new = out[:s].astype(bool)
    nodes_new = _node_mask_lww(nodes, delta, t_lo, t_hi, forward, 0)
    return nodes_new, emask_new, overflow


def edge_delta_apply(anchor: EdgeGraph, delta: Delta, t_anchor: int,
                     t_query: int, tile: int = 512, cap: int = 1024,
                     interpret: bool = False):
    """Kernel-backed reconstruct_at for EdgeGraph (edge mask on the
    Pallas slot kernel, node mask via XLA scatter).  Returns
    (EdgeGraph, overflow flag)."""
    import dataclasses
    nodes, emask, overflow = edge_delta_apply_slot_block(
        anchor.nodes, anchor.emask, delta, t_anchor, t_query, 0,
        tile=tile, cap=cap, interpret=interpret)
    return dataclasses.replace(anchor, nodes=nodes, emask=emask), overflow
