"""Single-cell read-modify-write inside a Pallas TPU kernel.

Every graph kernel replays an op list by updating ONE element of a VMEM
tile per op.  Mosaic only lowers dynamic slices whose offsets it can
prove tile-aligned (8 sublanes × 128 lanes for 32-bit types), so a
1 × 1 dynamic slice is refused.  ``update_cell`` reads the aligned
window that holds the cell, rewrites the one element selected by an
iota mask, and stores the window back: one vreg of traffic per op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8
LANES = 128


def padded_rows(rows: int) -> int:
    """``rows`` rounded up to whole sublane groups — the row count a
    scratch buffer needs so that every cell has a full aligned window."""
    return -(-rows // SUBLANES) * SUBLANES


def _aligned(i, w: int):
    """Start of the width-``w`` window holding index ``i``, with the
    alignment hint Mosaic needs (a static ``i`` stays static)."""
    start = (i // w) * w
    return pl.multiple_of(start, w) if isinstance(start, jax.Array) else start


def update_cell(ref, r, c, fn) -> None:
    """``ref[r, c] = fn(ref[r, c])`` for dynamic scalar ``r``, ``c``.

    The window is ``min(8, R) × min(128, C)`` of the 2-D ref
    ``(R, C)``; both dims must be whole multiples of it (tiles smaller
    than a vreg only occur in interpret mode, where the window shrinks
    with them).  ``fn`` maps the window to its new values elementwise;
    only the selected cell is written."""
    rows, cols = ref.shape
    wr, wc = min(SUBLANES, rows), min(LANES, cols)
    if rows % wr or cols % wc:
        raise ValueError(f"ref {ref.shape} is not a whole number of "
                         f"({wr}, {wc}) windows")
    r0 = _aligned(r, wr)
    c0 = _aligned(c, wc)
    win = ref[pl.ds(r0, wr), pl.ds(c0, wc)]
    hit = ((jax.lax.broadcasted_iota(jnp.int32, (wr, wc), 0) == r - r0)
           & (jax.lax.broadcasted_iota(jnp.int32, (wr, wc), 1) == c - c0))
    ref[pl.ds(r0, wr), pl.ds(c0, wc)] = jnp.where(hit, fn(win), win)
