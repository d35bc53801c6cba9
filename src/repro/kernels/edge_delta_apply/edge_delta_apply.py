"""Pallas TPU kernel: slot-block LWW delta application to an edge
registry.

The edge-slot analogue of ``kernels/delta_apply`` (DESIGN.md §2.1): the
persistent edge-slot validity mask ``emask[E]`` is tiled 1-D over the
slot axis; ops.py pre-resolves the window's edge ops to slot ids,
buckets them *by destination slot tile* and pre-orders them so that a
plain sequential overwrite inside each tile realizes last-writer-wins
for either reconstruction direction:

  forward  — ops ascending in time, write value = (op == addEdge)
  backward — ops descending in time, write value = (op == remEdge)
             (the "first op after t′ decides" rule, Definition 5)

Each grid instance owns one VMEM slot tile and replays only its own op
segment (a field-major (4, CAP) int32 block in SMEM: rows [local_slot,
value, valid, 0]; each op is one aligned-window read-modify-write,
``kernels.cell``), so total work is O(window ops + tiles·pad) and state
is O(E) — no N² anywhere.  Unlike the dense kernel an edge op contributes ONE entry
(its slot), not two (u,v)/(v,u) mirrors.

Memory per instance: TILE·4 bytes of VMEM (mask tile, int32) and
4·CAP·4 bytes of SMEM (op block).  Defaults TILE=512, CAP=1024 → 2 KiB
VMEM and 16 KiB SMEM per buffer; TILE is kept a multiple of 128 to stay
lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cell import update_cell


def _kernel(ops_ref, mask_ref, out_ref, *, cap: int):
    out_ref[...] = mask_ref[...]

    def body(j, _):
        @pl.when(ops_ref[0, 2, j] > 0)
        def _():
            val = ops_ref[0, 1, j]
            update_cell(out_ref, 0, ops_ref[0, 0, j],
                        lambda w: jnp.full_like(w, val))
        return 0

    jax.lax.fori_loop(0, cap, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("tile", "cap", "interpret"))
def edge_delta_apply_tiles(emask: jax.Array, tile_ops: jax.Array,
                           tile: int = 512, cap: int = 1024,
                           interpret: bool = False) -> jax.Array:
    """Apply pre-bucketed slot-tile op lists to the edge mask.

    emask:    i32[E] (0/1) — E a multiple of ``tile``.  A full registry
              for a single-device snapshot; one slot shard of a
              slot-sharded mesh (ops.bucket_slot_ops builds matching
              blocks via ``slot0``).
    tile_ops: i32[T, 4, cap] — per-tile rows [local_slot, value, valid, 0]
    returns:  i32[E]
    """
    e = emask.shape[0]
    assert e % tile == 0, (e, tile)
    grid = (e // tile,)
    out = pl.pallas_call(
        functools.partial(_kernel, cap=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 4, cap), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        interpret=interpret,
    )(tile_ops, emask.reshape(1, e))
    return out.reshape(e)
