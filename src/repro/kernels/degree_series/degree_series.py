"""Pallas TPU kernel: hybrid-plan degree time series.

Computes degree(v, τ) for every node v in a tile and every time unit τ
in [t_k, t_l] (B buckets) from the current degrees plus the window's
edge ops — the hot loop of the paper's hybrid plan (§3.2.3) evaluated
for *all* nodes at once (batched query serving).

Grid: 1-D over node tiles.  ops.py buckets edge-op endpoint events by
node tile: entry [local_node, bucket, sign, valid]; bucket B is a
virtual tail for ops in (t_l, t_cur].  Kernel: scatter-accumulate the
per-(bucket, node) net counts in VMEM (one aligned-window
read-modify-write per event, ``kernels.cell``; the event block is
field-major (4, cap) in SMEM), then a reverse running sum turns them
into the series:

  degree(v, t_k + b) = deg_cur(v) − Σ_{b' > b} net[b', v]

Per instance: (B+2)·TN·4 bytes of VMEM scratch (rows rounded up to a
multiple of 8) and a 4·cap·4-byte SMEM event block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cell import padded_rows, update_cell


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
    for b in range(num_buckets - 1, -1, -1):
        acc = acc + net_ref[b + 1, :]
        out_ref[b, :] = deg_ref[0, :] - acc


@functools.partial(jax.jit,
                   static_argnames=("tile", "cap", "num_buckets",
                                    "interpret"))
def degree_series_tiles(deg_cur: jax.Array, tile_ops: jax.Array,
                        tile: int = 256, cap: int = 1024,
                        num_buckets: int = 64,
                        interpret: bool = False) -> jax.Array:
    """deg_cur: i32[N]; tile_ops: i32[T, 4, cap] → i32[num_buckets, N]."""
    n = deg_cur.shape[0]
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
        scratch_shapes=[pltpu.VMEM((padded_rows(num_buckets + 2), tile),
                                   jnp.int32)],
        interpret=interpret,
    )(tile_ops, deg_cur.reshape(1, n))
