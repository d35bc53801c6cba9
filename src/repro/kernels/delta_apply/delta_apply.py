"""Pallas TPU kernel: tiled delta application to a dense adjacency.

The TPU-native reconstruction (DESIGN.md §2.2): the adjacency bitmask is
tiled (TN × TN) over a 2-D grid; ops.py pre-buckets the window's edge
ops *by destination tile* (both (u,v) and (v,u) mirrors) and pre-orders
them so that a plain sequential overwrite inside each tile realizes
last-writer-wins for either reconstruction direction:

  forward  — ops ascending in time, write value = (op == addEdge)
  backward — ops descending in time, write value = (op == remEdge)
             (the "first op after t′ decides" rule, Definition 5)

Each grid instance owns one VMEM tile and replays only its own op
segment (a field-major (4, CAP) int32 block in SMEM: rows [local_u,
local_v, value, valid], so the op loop reads scalars), so total work is
O(window ops + tiles·pad) with zero cross-tile dependencies — the
parallel reconstruction the paper leaves as future work.  Each op is
one aligned-window read-modify-write (``kernels.cell``).

Memory per instance: TN·TN·4 bytes of VMEM (adjacency tile, int32) and
4·CAP·4 bytes of SMEM (op block).  Defaults TN=256, CAP=1024 → 256 KiB
VMEM and 16 KiB SMEM per buffer; TN is kept a multiple of 128 to stay
lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cell import update_cell


def _kernel(ops_ref, anchor_ref, out_ref, *, cap: int):
    out_ref[...] = anchor_ref[...]

    def body(j, _):
        @pl.when(ops_ref[0, 0, 3, j] > 0)
        def _():
            val = ops_ref[0, 0, 2, j]
            update_cell(out_ref, ops_ref[0, 0, 0, j], ops_ref[0, 0, 1, j],
                        lambda w: jnp.full_like(w, val))
        return 0

    jax.lax.fori_loop(0, cap, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("tile", "cap", "interpret"))
def delta_apply_tiles(anchor_adj: jax.Array, tile_ops: jax.Array,
                      tile: int = 256, cap: int = 1024,
                      interpret: bool = False) -> jax.Array:
    """Apply pre-bucketed tile op lists to the adjacency.

    anchor_adj: i32[R, C] (0/1) — both dims multiples of ``tile``.
    R == C for a full snapshot; R < C for one row shard of a
    row-sharded mesh (ops.bucket_ops builds the matching blocks).
    tile_ops:   i32[Tr, Tc, 4, cap] — per-tile rows [lu, lv, value, valid]
    returns:    i32[R, C]
    """
    r, c = anchor_adj.shape
    assert r % tile == 0 and c % tile == 0, (r, c, tile)
    grid = (r // tile, c // tile)
    return pl.pallas_call(
        functools.partial(_kernel, cap=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 4, cap), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int32),
        interpret=interpret,
    )(tile_ops, anchor_adj)
