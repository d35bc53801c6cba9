"""End-to-end driver (the paper's workload): serve batched historical
queries against a sharded temporal graph store.

Builds a Table-3-scale evolving social graph, row-shards the current
snapshot over all available devices, then serves:
  1. a batch of point-degree queries via the distributed hybrid plan,
  2. a mixed-plan query stream through the unified engine's *batched*
     executor (core/engine.py: cost-based per-query plan choice, one
     vmapped device program per (plan, anchor) group), compared
     against the sequential single-query loop,
  3. a degree *time-series* for every node at once (the hybrid
     aggregate plan vectorized over the whole graph).

This example deliberately drives the internal layers the facade wraps;
application code should use ``repro.api.GraphSession`` instead (see
``examples/quickstart.py``), which adds live ingest, watermark
semantics, result caching, and durability over the same engine.

  PYTHONPATH=src python examples/serve_historical.py [--nodes 2000]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as D
from repro.core.generate import EvolutionParams, build_store, paper_table3
from repro.core.plans import Query
from repro.core.reconstruct import degree_series


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1500)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--table3", action="store_true",
                    help="use the paper's full Table-3 dataset")
    args = ap.parse_args()

    t0 = time.time()
    if args.table3:
        store = paper_table3()
    else:
        store = build_store(args.nodes, EvolutionParams(
            m_attach=4, lam_extra=1.0, lam_remove=1.0), seed=0)
    print(f"[build {time.time()-t0:.1f}s]", store.stats())

    mesh = D.graph_mesh()
    g = D.shard_graph(store.current, mesh)
    d = store.delta()
    print(f"[mesh] {len(jax.devices())} device(s), adjacency "
          f"row-sharded")

    # 1 — batched point-degree queries, distributed hybrid plan
    rng = np.random.default_rng(1)
    vs = jnp.asarray(rng.integers(0, store.n_cap, args.queries)
                     .astype(np.int32))
    ts = jnp.asarray(rng.integers(1, store.t_cur, args.queries)
                     .astype(np.int32))
    t0 = time.time()
    deg = D.dist_batch_point_degree(mesh, g, d, vs, ts, store.t_cur)
    deg.block_until_ready()
    t0 = time.time()  # second call = steady state
    deg = D.dist_batch_point_degree(mesh, g, d, vs, ts, store.t_cur)
    deg.block_until_ready()
    dt = time.time() - t0
    print(f"[serve] {args.queries} point-degree queries in "
          f"{dt*1e3:.1f} ms ({dt/args.queries*1e6:.0f} µs/query)")
    # spot-check one against single-device two-phase
    q0 = Query("point", "node", "degree", t_k=int(ts[0]), v=int(vs[0]))
    assert int(store.query(q0, plan="two_phase")) == int(deg[0])

    # 2 — mixed-plan stream through the unified engine (auto-planned,
    # batched by (plan, anchor) group) vs the single-query loop
    tc = store.t_cur
    mixed = [
        Query("point", "node", "degree", t_k=tc // 3, v=int(vs[1])),
        Query("diff", "node", "degree", t_k=tc // 4, t_l=3 * tc // 4,
              v=int(vs[2])),
        Query("agg", "node", "degree", t_k=tc // 2, t_l=tc // 2 + 10,
              v=int(vs[3]), agg="mean"),
        Query("point", "global", "num_edges", t_k=tc // 2),
        Query("diff", "global", "avg_degree", t_k=tc // 4, t_l=3 * tc // 4),
    ]
    stream = [mixed[i % len(mixed)] for i in range(args.queries)]
    engine = store.engine()
    engine.evaluate_many(stream)  # warm-up / compile
    t0 = time.time()
    res, choices = engine.evaluate_many(stream, return_choices=True)
    dt_batch = time.time() - t0
    t0 = time.time()
    seq = [engine.evaluate_many([q])[0] for q in stream]
    dt_loop = time.time() - t0
    for q, c, r in zip(stream[:len(mixed)], choices, res):
        print(f"[query] {q.kind}/{q.scope}/{q.measure:12s} "
              f"plan={c.plan:10s} -> {np.round(float(r), 3)}")
    assert all(float(a) == float(b) for a, b in zip(res, seq))
    print(f"[engine] {len(stream)} mixed queries: batched "
          f"{dt_batch*1e3:.1f} ms vs loop {dt_loop*1e3:.1f} ms "
          f"({dt_loop/max(dt_batch, 1e-9):.1f}x)")

    # 3 — all-node degree time series (one pass over the delta)
    t_k = 2 * tc // 3
    B = 32
    t0 = time.time()
    series = degree_series(store.current, d, t_k, min(t_k + B - 1, tc),
                           B, tc)
    series.block_until_ready()
    print(f"[series] degree(v, τ) for ALL {store.n_cap} nodes × {B} "
          f"time units in {(time.time()-t0)*1e3:.1f} ms "
          f"(shape {series.shape})")
    print("done.")


if __name__ == "__main__":
    main()
