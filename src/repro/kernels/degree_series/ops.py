"""jit'd wrapper: bucket edge-op endpoint events by node tile, run the
degree_series kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.delta import ADD_EDGE, Delta
from repro.core.graph import DenseGraph
from repro.kernels.degree_series.degree_series import degree_series_tiles


@functools.partial(jax.jit,
                   static_argnames=("n", "tile", "cap", "num_buckets",
                                    "row0", "n_valid"))
def bucket_node_events(delta: Delta, n: int, t_k, num_buckets: int,
                       tile: int, cap: int, row0: int = 0,
                       n_valid: int | None = None):
    """Dense per-node-tile event blocks i32[T, 4, cap], field-major:
    each entry is the column [local_node, bucket, sign, valid].  Each
    in-suffix edge op (t > t_k) yields one event per endpoint;
    bucket = clip(t - t_k, 0, B).

    ``row0`` makes the bucketing shard-safe: with ``n`` the *local*
    (tile-padded) node count, only events touching nodes
    [row0, row0 + n_valid) are kept (``n_valid`` defaults to ``n``;
    pass the unpadded count so the next shard's events never leak into
    this shard's pad band) and node ids are shifted to shard-local, so
    each device of a node-sharded mesh builds its own tile blocks and
    the kernel runs unchanged on the shard."""
    m = delta.capacity
    n_valid = n if n_valid is None else n_valid
    tcount = n // tile
    e = delta.valid_mask() & delta.is_edge_op() & (delta.t > t_k)
    sign = jnp.where(delta.op == ADD_EDGE, 1, -1)
    b = jnp.clip(delta.t - t_k, 0, num_buckets)

    nodes = jnp.concatenate([delta.u, delta.v]) - row0
    ee = jnp.concatenate([e, e]) & (nodes >= 0) & (nodes < n_valid)
    nodes = jnp.clip(nodes, 0, max(n - 1, 0))
    signs = jnp.concatenate([sign, sign])
    bs = jnp.concatenate([b, b])

    tile_id = jnp.where(ee, nodes // tile, tcount)
    order = jnp.argsort(tile_id, stable=True)
    tid_s = tile_id[order]
    seg_start = jnp.searchsorted(tid_s, jnp.arange(tcount + 1))
    pos = jnp.arange(2 * m) - seg_start[tid_s]
    overflow = jnp.any((pos >= cap) & (tid_s < tcount))
    keep = (tid_s < tcount) & (pos < cap)
    entries = jnp.stack([nodes[order] % tile, bs[order], signs[order],
                         jnp.ones_like(pos)], axis=1)
    blocks = jnp.zeros((tcount + 1, 4, cap), jnp.int32)
    blocks = blocks.at[jnp.where(keep, tid_s, tcount), :,
                       jnp.clip(pos, 0, cap - 1)].set(
        jnp.where(keep[:, None], entries, 0))
    return blocks[:tcount], overflow


def degree_series_rows(deg_block: jnp.ndarray, delta: Delta, t_k: int,
                       num_buckets: int, row0: int = 0, tile: int = 256,
                       cap: int = 1024, interpret: bool = False):
    """Shard-safe variant: the series for one node block only.

    ``deg_block`` is i32[R] — current degrees of nodes
    [row0, row0 + R); per-block tile padding, so concatenating shard
    outputs along nodes equals the full-kernel output."""
    n = deg_block.shape[0]
    pad = (-n) % tile
    deg = jnp.pad(deg_block, (0, pad)) if pad else deg_block
    blocks, overflow = bucket_node_events(delta, n + pad, t_k, num_buckets,
                                          tile, cap, row0=row0, n_valid=n)
    out = degree_series_tiles(deg, blocks, tile=tile, cap=cap,
                              num_buckets=num_buckets, interpret=interpret)
    return out[:, :n], overflow


def degree_series_kernel(current: DenseGraph, delta: Delta, t_k: int,
                         num_buckets: int, tile: int = 256,
                         cap: int = 1024, interpret: bool = False):
    """i32[num_buckets, N]: degrees of every node at t_k + b."""
    return degree_series_rows(current.degrees(), delta, t_k, num_buckets,
                              row0=0, tile=tile, cap=cap,
                              interpret=interpret)
