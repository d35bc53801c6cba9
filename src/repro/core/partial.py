"""Partial snapshot reconstruction (paper §3.3.1).

Node-centric queries touch a subgraph G' = (V', E'); instead of
reconstructing all of SG_t we reconstruct only the rows of V'.  The
paper notes that *multiple passes* over the delta may be needed: ops in
the window can attach new neighbors whose own edges then matter (e.g.
for induced-subgraph measures).  We implement the closure as a bounded
fixpoint over "nodes touched by ops touching the current set".
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.delta import Delta
from repro.core.graph import DenseGraph
from repro.core.reconstruct import reconstruct_dense


def seed_mask(n_cap: int, v) -> jax.Array:
    """Single-node seed set for a node-centric query — the V' of the
    paper's partial reconstruction.  Shared by ``plans.two_phase`` and
    the engine's batched executor so both build bit-identical seeds."""
    return jnp.zeros((n_cap,), bool).at[v].set(True)


@partial(jax.jit, static_argnames=("passes",))
def closure_mask(current: DenseGraph, delta: Delta, seed_mask: jax.Array,
                 t_lo, t_hi, passes: int = 2) -> jax.Array:
    """Expand a seed node set to every node whose state can influence the
    queried subgraph: current neighbors plus endpoints of window ops that
    touch the set.  ``passes`` bounds the paper's multi-pass loop; one
    pass suffices for degree, two for induced-subgraph measures.
    """
    win = delta.window_mask(t_lo, t_hi) & delta.valid_mask()

    def one_pass(_, mask):
        # neighbors in the current snapshot
        nbr = (mask.astype(jnp.float32) @ current.adj.astype(jnp.float32)) > 0
        # endpoints of ops touching the set inside the window
        touch = win & (mask[delta.u] | mask[delta.v])
        scat = jnp.zeros_like(mask).at[delta.u].max(touch)
        scat = scat.at[delta.v].max(touch)
        return mask | nbr | scat

    return jax.lax.fori_loop(0, passes, one_pass, seed_mask)


@partial(jax.jit, static_argnames=("passes",))
def partial_reconstruct(current: DenseGraph, delta: Delta, t_cur, t_query,
                        seed_mask: jax.Array, passes: int = 2) -> DenseGraph:
    """Reconstruct SG_{t_query} restricted to the closure of
    ``seed_mask``.  The returned snapshot is only meaningful on the
    closure (other rows keep current values) — exactly the paper's
    contract: "it suffices to reconstruct the corresponding snapshots of
    the subgraph G'"."""
    # the closure and the final masking are replay work too
    # (reconstruct_dense carries its own ``replay`` scope)
    with jax.named_scope("replay"):
        t_lo = jnp.minimum(t_cur, t_query)
        t_hi = jnp.maximum(t_cur, t_query)
        mask = closure_mask(current, delta, seed_mask, t_lo, t_hi,
                            passes=passes)
    g = reconstruct_dense(current, delta, t_cur, t_query,
                          row_mask=mask, restrict_rows=True)
    with jax.named_scope("replay"):
        # Zero out rows outside the closure so accidental reads are loud.
        adj = g.adj & mask[:, None] & mask[None, :]
        nodes = g.nodes & mask
    return DenseGraph(nodes=nodes, adj=adj)
