"""Graph measures used by historical queries (paper Table 1).

Node-centric measures: degree, neighborhood, induced-subgraph stats,
k-core membership.  Global measures: diameter, connected components,
degree distribution, PageRank, triangle count, density.

On the dense layout, global measures are deliberately formulated as
(boolean) matrix products so that on TPU they run on the MXU
(DESIGN.md §2.2): BFS by frontier expansion, components by label
propagation, triangles by trace(A³).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.graph import DenseGraph, EdgeGraph

INF = jnp.int32(0x3FFFFFFF)


# ---------------------------------------------------------------------------
# Exact f32 finalization
# ---------------------------------------------------------------------------


def div_rn(num: jax.Array, den: jax.Array) -> jax.Array:
    """``num / den`` in f32, correctly rounded on every backend.

    XLA's f32 divide on a TPU is faithful (within one ulp), not
    correctly rounded, so a float measure would differ in its last bit
    between a CPU and a TPU and from any IEEE reference.  Here the
    significands are divided exactly by long division in int32 and the
    quotient rounded to nearest even.  Operands are positive normal f32
    or ``num == 0`` (counts, sums of counts and their products); a zero
    ``den`` gives an unspecified value, so callers mask it."""
    a = jax.lax.bitcast_convert_type(num.astype(jnp.float32), jnp.int32)
    b = jax.lax.bitcast_convert_type(den.astype(jnp.float32), jnp.int32)
    ma = (a & 0x7FFFFF) | 0x800000
    mb = (b & 0x7FFFFF) | 0x800000
    below = ma < mb                      # quotient significand < 1
    r = jnp.where(below, ma << 1, ma)
    q = jnp.zeros_like(r)
    for _ in range(25):                  # 24 significand bits + guard
        bit = (r >= mb).astype(jnp.int32)
        q = (q << 1) | bit
        r = (r - bit * mb) << 1
    up = (q & 1) & ((r != 0) | ((q >> 1) & 1)).astype(jnp.int32)
    q = (q >> 1) + up                    # round to nearest, ties even
    carry = q >> 24                      # rounding overflowed to 2.0
    exp = ((a >> 23) - (b >> 23) + 127 - below.astype(jnp.int32) + carry)
    bits = (exp << 23) | ((q >> carry) & 0x7FFFFF)
    out = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where(a == 0, jnp.float32(0.0), out)


def avg_degree_of(n_nodes: jax.Array, n_edges: jax.Array) -> jax.Array:
    """2·|E| / max(|V|, 1) from the integer counts — the one
    finalization every layout, shard mode and sweep uses."""
    n = jnp.maximum(n_nodes, 1).astype(jnp.float32)
    return div_rn(2.0 * n_edges.astype(jnp.float32), n)


def density_of(n_nodes: jax.Array, n_edges: jax.Array) -> jax.Array:
    """2·|E| / (|V|·(|V| − 1)), 0 below two nodes."""
    n = n_nodes.astype(jnp.float32)
    e = n_edges.astype(jnp.float32)
    return jnp.where(n > 1, div_rn(2.0 * e, n * (n - 1.0)), 0.0)


# ---------------------------------------------------------------------------
# Node-centric measures
# ---------------------------------------------------------------------------


def degree(g: DenseGraph, v) -> jax.Array:
    return g.degree(v)


def neighborhood_size(g: DenseGraph, v, hops: int = 2) -> jax.Array:
    """|{u : dist(v, u) ≤ hops}| − 1, via frontier matmuls."""
    reached = jnp.zeros((g.n_cap,), bool).at[v].set(True)
    frontier = reached
    for _ in range(hops):
        nxt = (frontier.astype(jnp.float32) @ g.adj.astype(jnp.float32)) > 0
        frontier = nxt & ~reached
        reached = reached | nxt
    return jnp.sum(reached.astype(jnp.int32)) - 1


def induced_subgraph_mask(g: DenseGraph, v) -> jax.Array:
    """v plus its neighbors (the paper's induced-subgraph example)."""
    return g.adj[v] | jnp.zeros((g.n_cap,), bool).at[v].set(g.nodes[v])


def induced_avg_degree(g: DenseGraph, v) -> jax.Array:
    """Average degree of the subgraph induced by v and its neighbors —
    the paper's §3.2.3 multi-pass hybrid example."""
    m = induced_subgraph_mask(g, v)
    sub = g.induced(m)
    return avg_degree_of(sub.num_nodes(), sub.num_edges())


def in_k_core(g: DenseGraph, v, k: int) -> jax.Array:
    """Whether v survives k-core peeling."""
    def cond(state):
        keep, changed = state
        return changed

    def body(state):
        keep, _ = state
        deg = jnp.sum(g.adj & keep[None, :], axis=1)
        new = keep & (deg >= k) & g.nodes
        return new, jnp.any(new != keep)

    keep0 = g.nodes
    keep, _ = jax.lax.while_loop(cond, body, (keep0, jnp.bool_(True)))
    return keep[v]


# ---------------------------------------------------------------------------
# Global measures
# ---------------------------------------------------------------------------


def num_nodes(g: DenseGraph):
    return g.num_nodes()


def num_edges(g: DenseGraph):
    return g.num_edges()


def density(g: DenseGraph) -> jax.Array:
    return density_of(g.num_nodes(), g.num_edges())


def avg_degree(g: DenseGraph) -> jax.Array:
    return avg_degree_of(g.num_nodes(), g.num_edges())


# Registered degree-distribution bin count: degrees past the last bin
# clip into it, so the histogram shape is static (one jit program per
# measure) at any graph size.
DEGREE_DIST_BINS = 64


def _degree_histogram(deg: jax.Array, nodes: jax.Array,
                      max_deg: int) -> jax.Array:
    """Validity-weighted degree bincount, bins [0, max_deg] with
    overflow clipped into the last bin.  Shared by BOTH layouts: the
    dense/edge parity contract is exactly 'same degrees in, same bits
    out', so the histogram arithmetic must live in one place."""
    deg = jnp.clip(deg, 0, max_deg)
    w = nodes.astype(jnp.int32)
    return jnp.zeros((max_deg + 1,), jnp.int32).at[deg].add(w)


def degree_distribution(g: DenseGraph,
                        max_deg: int = DEGREE_DIST_BINS) -> jax.Array:
    """Histogram of degrees over valid nodes, bins [0, max_deg]."""
    return _degree_histogram(g.degrees(), g.nodes, max_deg)


@partial(jax.jit, static_argnames=("max_iters",))
def connected_components(g: DenseGraph, max_iters: int = 64) -> jax.Array:
    """Component labels via min-label propagation (MXU-friendly)."""
    n = g.n_cap
    labels0 = jnp.where(g.nodes, jnp.arange(n, dtype=jnp.int32), INF)

    def body(state):
        labels, _, it = state
        neigh = jnp.where(g.adj, labels[None, :], INF)
        new = jnp.minimum(labels, jnp.min(neigh, axis=1))
        new = jnp.where(g.nodes, new, INF)
        return new, jnp.any(new != labels), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), jnp.int32(0)))
    return labels


def num_components(g: DenseGraph) -> jax.Array:
    labels = connected_components(g)
    own = labels == jnp.arange(g.n_cap, dtype=jnp.int32)
    return jnp.sum((own & g.nodes).astype(jnp.int32))


@partial(jax.jit, static_argnames=("num_sources", "max_iters"))
def diameter(g: DenseGraph, num_sources: int = 0, max_iters: int = 64):
    """(Estimated) diameter via multi-source BFS frontier matmuls.

    ``num_sources == 0`` → exact: BFS from every node.  Unreachable pairs
    are ignored (per-component eccentricity).
    """
    n = g.n_cap
    if num_sources and num_sources < n:
        src = jnp.linspace(0, n - 1, num_sources).astype(jnp.int32)
    else:
        src = jnp.arange(n, dtype=jnp.int32)
    s = src.shape[0]
    reached = jnp.zeros((s, n), bool).at[jnp.arange(s), src].set(
        g.nodes[src])
    dist = jnp.where(reached, 0, INF)
    adj_f = g.adj.astype(jnp.float32)

    def body(state):
        reached, dist, d, _ = state
        nxt = (reached.astype(jnp.float32) @ adj_f) > 0
        new = nxt & ~reached
        dist = jnp.where(new, d + 1, dist)
        return reached | new, dist, d + 1, jnp.any(new)

    def cond(state):
        _, _, d, changed = state
        return changed & (d < max_iters)

    _, dist, _, _ = jax.lax.while_loop(
        cond, body, (reached, dist, jnp.int32(0), jnp.bool_(True)))
    dist = jnp.where(dist >= INF, -1, dist)  # unreachable
    ecc = jnp.max(dist, axis=1)
    ecc = jnp.where(g.nodes[src], ecc, -1)
    return jnp.max(ecc)


def triangle_count(g: DenseGraph) -> jax.Array:
    """trace(A³)/6 as sum((A @ A) ⊙ A)/6 for the symmetric A.  The one
    product multiplies 0/1 entries, exact at any matmul precision (the
    TPU's default rounds f32 inputs to bf16, so a second product over
    path counts above 256 would not be); the rest is int32."""
    a = g.adj.astype(jnp.float32)
    paths2 = (a @ a).astype(jnp.int32)
    return jnp.sum(jnp.where(g.adj, paths2, 0)) // 6


@partial(jax.jit, static_argnames=("iters",))
def pagerank(g: DenseGraph, iters: int = 20, damp: float = 0.85):
    """Power iteration on the degree-normalized adjacency."""
    n_valid = jnp.maximum(g.num_nodes(), 1).astype(jnp.float32)
    deg = jnp.maximum(g.degrees().astype(jnp.float32), 1.0)
    a = g.adj.astype(jnp.float32) / deg[:, None]
    r = jnp.where(g.nodes, 1.0 / n_valid, 0.0)

    def body(_, r):
        r2 = damp * (r @ a) + (1.0 - damp) / n_valid
        return jnp.where(g.nodes, r2, 0.0)

    return jax.lax.fori_loop(0, iters, body, r)


# Registry: name -> (fn, scope). Node-centric fns take (g, v).
NODE_MEASURES = {
    "degree": degree,
    "neighborhood2": neighborhood_size,
    "induced_avg_degree": induced_avg_degree,
}
GLOBAL_MEASURES = {
    "num_nodes": num_nodes,
    "num_edges": num_edges,
    "density": density,
    "avg_degree": avg_degree,
    "num_components": num_components,
    "diameter": diameter,
    "triangles": triangle_count,
    "degree_distribution": degree_distribution,
}


# ---------------------------------------------------------------------------
# Edge-slot-layout measures (segment reductions — O(E + N), no N² state)
# ---------------------------------------------------------------------------
#
# Each mirrors the dense measure's arithmetic exactly: the integer
# counts are the same values, and the float finalizations are the same
# f32 expressions of those integers, so edge-layout results bit-match
# the dense layout (tests/test_engine.py, tests/test_property.py).


def edge_degree(g: EdgeGraph, v) -> jax.Array:
    return g.degree(v)


def edge_num_nodes(g: EdgeGraph) -> jax.Array:
    return g.num_nodes()


def edge_num_edges(g: EdgeGraph) -> jax.Array:
    # slots hold each undirected edge once — the popcount equals the
    # dense sum(adj) // 2 exactly
    return g.num_edges()


def edge_density(g: EdgeGraph) -> jax.Array:
    return density_of(g.num_nodes(), g.num_edges())


def edge_avg_degree(g: EdgeGraph) -> jax.Array:
    return avg_degree_of(g.num_nodes(), g.num_edges())


def edge_degree_distribution(g: EdgeGraph,
                             max_deg: int = DEGREE_DIST_BINS) -> jax.Array:
    """Degree histogram without the N² adjacency: the shared bincount
    over the slot-registry degrees (``EdgeGraph.degrees`` is the
    validity-masked segment-sum over ``eu``/``ev``).  The integer
    counts equal the dense row-sum degrees exactly, so the histogram
    bit-matches ``degree_distribution``."""
    return _degree_histogram(g.degrees(), g.nodes, max_deg)


EDGE_NODE_MEASURES = {
    "degree": edge_degree,
}
EDGE_GLOBAL_MEASURES = {
    "num_nodes": edge_num_nodes,
    "num_edges": edge_num_edges,
    "density": edge_density,
    "avg_degree": edge_avg_degree,
    "degree_distribution": edge_degree_distribution,
}


def edge_supported(measure: str, scope: str) -> bool:
    """True iff the measure has an edge-slot-layout implementation."""
    table = EDGE_NODE_MEASURES if scope == "node" else EDGE_GLOBAL_MEASURES
    return measure in table
