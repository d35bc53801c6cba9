"""Pallas TPU kernel: forward degree sweep over node tiles.

The forward twin of ``kernels.degree_series``: that kernel walks BACK
from the frontier degrees (hybrid plan); a sweep walks FORWARD from the
reconstructed degrees at t_lo, with samples every ``stride`` time units
instead of every unit:

  deg(v, t_lo + b·stride) = deg0(v) + Σ_{b' ≤ b} net[b', v]

Grid: 1-D over node tiles.  ``bucket_sweep_events`` builds the same
field-major per-tile event blocks i32[T, 4, cap] (rows [local_node,
sample, sign, valid]) as ``degree_series.ops.bucket_node_events``, but
buckets by first-observing sample ceil((t − t_lo)/stride).  Kernel:
scatter the per-(sample, node) nets into VMEM, then a forward running
sum.

This is the tiled specialization of the sweep executor for the
node-degree measure; ``ops.batch_evolve`` is the general (all-measure,
both-layout, vmappable) path and the two are asserted bit-equal in
``tests/test_evolve.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.delta import ADD_EDGE, Delta
from repro.kernels.cell import padded_rows, update_cell


@functools.partial(jax.jit,
                   static_argnames=("n", "stride", "num_buckets", "tile",
                                    "cap"))
def bucket_sweep_events(delta: Delta, n: int, t_lo, t_last, stride: int,
                        num_buckets: int, tile: int, cap: int):
    """Dense per-node-tile sweep event blocks i32[T, 4, cap].

    Each in-window edge op (t in (t_lo, t_last]) yields one event per
    endpoint at sample ceil((t − t_lo)/stride); an entry is the column
    [local_node, sample, sign, valid]."""
    m = delta.capacity
    tcount = n // tile
    e = (delta.valid_mask() & delta.is_edge_op()
         & (delta.t > t_lo) & (delta.t <= t_last))
    sign = jnp.where(delta.op == ADD_EDGE, 1, -1)
    t = jnp.where(e, delta.t, t_lo + 1)          # T_PAD overflow guard
    b = jnp.clip((t - t_lo + stride - 1) // stride, 0, num_buckets - 1)

    nodes = jnp.concatenate([delta.u, delta.v])
    ee = jnp.concatenate([e, e]) & (nodes < n)
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


def _kernel(ops_ref, deg_ref, out_ref, net_ref, *, cap: int,
            num_buckets: int):
    net_ref[...] = jnp.zeros_like(net_ref)

    def scatter(j, _):
        @pl.when(ops_ref[0, 3, j] > 0)
        def _():
            sign = ops_ref[0, 2, j]
            update_cell(net_ref, ops_ref[0, 1, j], ops_ref[0, 0, j],
                        lambda w: w + sign)
        return 0

    jax.lax.fori_loop(0, cap, scatter, 0)

    # static row indices: Mosaic refuses dynamic single-row slices
    acc = jnp.zeros_like(net_ref[0, :])
    for b in range(num_buckets):
        acc = acc + net_ref[b, :]
        out_ref[b, :] = deg_ref[0, :] + acc


@functools.partial(jax.jit,
                   static_argnames=("tile", "cap", "num_buckets",
                                    "interpret"))
def sweep_series_tiles(deg0: jax.Array, tile_ops: jax.Array,
                       tile: int = 256, cap: int = 1024,
                       num_buckets: int = 64,
                       interpret: bool = False) -> jax.Array:
    """deg0: i32[N]; tile_ops: i32[T, 4, cap] → i32[num_buckets, N]."""
    n = deg0.shape[0]
    assert n % tile == 0
    grid = (n // tile,)
    return pl.pallas_call(
        functools.partial(_kernel, cap=cap, num_buckets=num_buckets),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 4, cap), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((num_buckets, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((num_buckets, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((padded_rows(num_buckets + 1), tile),
                                   jnp.int32)],
        interpret=interpret,
    )(tile_ops, deg0.reshape(1, n))


def sweep_degree_series(deg0: jax.Array, delta: Delta, t_lo, t_last,
                        stride: int, num_buckets: int, tile: int = 256,
                        cap: int = 1024, interpret: bool = False):
    """i32[num_buckets, N]: every node's degree at each sweep sample.

    Row b holds deg(·, t_lo + b·stride); rows past the last real sample
    repeat it (no later events scatter there)."""
    n = deg0.shape[0]
    pad = (-n) % tile
    deg = jnp.pad(deg0, (0, pad)) if pad else deg0
    blocks, overflow = bucket_sweep_events(delta, n + pad, t_lo, t_last,
                                           stride, num_buckets, tile, cap)
    out = sweep_series_tiles(deg, blocks, tile=tile, cap=cap,
                             num_buckets=num_buckets, interpret=interpret)
    return out[:, :n], overflow
