"""Batched multi-query serving throughput (engine.evaluate_many).

The paper's successor system batches multi-snapshot retrieval into
single scans; our analogue is the engine's batched executor: B
historical queries grouped by (plan, anchor) and run as one vmapped
device program per group, instead of B separate host dispatches.

Workload: a synthetic evolving graph and a mixed stream of node-centric
degree queries (point / range-differential / range-aggregate — the
serving mix of examples/serve_historical.py), auto-planned.  We measure
queries/sec for the single-query loop (B=1) and for batched execution
at B ∈ {8, 64, 256}, and write the rows to
``benchmarks/BENCH_engine_batch.json`` next to the other BENCH
artifacts.

  PYTHONPATH=src python benchmarks/bench_engine_batch.py [--fast]
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.core.generate import EvolutionParams, build_store
from repro.core.plans import Query

HERE = os.path.dirname(__file__)
OUT_JSON = os.path.join(HERE, "BENCH_engine_batch.json")


def make_workload(store, n_queries: int, seed: int = 0) -> list[Query]:
    rng = np.random.default_rng(seed)
    tc = store.t_cur
    qs: list[Query] = []
    for i in range(n_queries):
        v = int(rng.integers(0, store.n_cap))
        t1 = int(rng.integers(1, max(2, tc)))
        t2 = min(tc, t1 + int(rng.integers(0, 8)))
        kind = ("point", "diff", "agg")[i % 3]
        if kind == "point":
            qs.append(Query("point", "node", "degree", t_k=t1, v=v))
        elif kind == "diff":
            qs.append(Query("diff", "node", "degree", t_k=t1, t_l=t2, v=v))
        else:
            qs.append(Query("agg", "node", "degree", t_k=t1, t_l=t2, v=v,
                            agg="mean"))
    return qs


def _serve(engine, queries: list[Query], batch: int) -> None:
    for i in range(0, len(queries), batch):
        engine.evaluate_many(queries[i:i + batch])


def run(n_nodes: int = 300, n_queries: int = 256,
        batch_sizes: tuple[int, ...] = (1, 8, 64, 256), reps: int = 3,
        seed: int = 0):
    """Returns (rows, result_dict); rows are (name, value, note) like
    the other bench modules."""
    store = build_store(n_nodes, EvolutionParams(
        m_attach=3, lam_extra=1.0, lam_remove=1.0), seed=seed)
    engine = store.engine()
    queries = make_workload(store, n_queries, seed)
    # B > n_queries would silently re-measure the full batch under a
    # mislabeled row
    batch_sizes = tuple(b for b in batch_sizes if b <= n_queries)

    qps: dict[int, float] = {}
    rows = []
    for b in batch_sizes:
        _serve(engine, queries, b)         # warm-up / compile
        t0 = time.perf_counter()
        for _ in range(reps):
            _serve(engine, queries, b)
        dt = (time.perf_counter() - t0) / reps
        qps[b] = n_queries / dt
        rows.append((f"engine_batch/qps@B={b}", f"{qps[b]:.1f}",
                     f"{dt / n_queries * 1e6:.0f} us/query"))

    base = qps[min(batch_sizes)]
    speedups = {b: qps[b] / base for b in batch_sizes}
    for b in batch_sizes[1:]:
        rows.append((f"engine_batch/speedup@B={b}",
                     f"{speedups[b]:.1f}x", ""))

    result = {
        "n_nodes": n_nodes,
        "n_queries": n_queries,
        "t_cur": int(store.t_cur),
        "total_ops": int(store.stats()["total_ops"]),
        "reps": reps,
        "qps": {str(b): qps[b] for b in batch_sizes},
        "speedup_vs_b1": {str(b): speedups[b] for b in batch_sizes},
    }
    return rows, result


def write_json(result: dict) -> None:
    """Refresh BENCH_engine_batch.json with the shared artifact schema
    (benchmarks/artifacts.py)."""
    import sys
    root = os.path.dirname(HERE)
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.artifacts import make_artifact, write_artifact
    write_artifact(OUT_JSON, make_artifact("engine_batch", result))


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    rows, result = run(n_nodes=150 if args.fast else 300,
                       n_queries=64 if args.fast else 256,
                       reps=2 if args.fast else 3)
    for name, val, note in rows:
        print(f"{name},{val},{note}")
    if args.fast:
        # --fast is a sanity tier: don't clobber the committed
        # default-config artifact with incomparable numbers
        print(f"--fast: skipping {OUT_JSON} refresh")
    else:
        write_json(result)
        print(f"wrote {OUT_JSON}")
    s64 = result["speedup_vs_b1"].get("64")
    if s64 is not None and s64 < 5.0:
        print(f"WARNING: B=64 speedup {s64:.1f}x below the 5x target")


if __name__ == "__main__":
    main()
