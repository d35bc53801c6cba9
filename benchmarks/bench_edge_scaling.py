"""Edge-slot vs dense execution at scale: qps and peak bytes vs N.

The tentpole claim of the O(E) path: per-query serving cost on the
dense layout is O(N²) (the LWW cell scatter materializes two i32[N, N]
index planes per reconstruction), on the edge layout O(E + M).  This
bench sweeps N ∈ {4k, 16k, 64k} at fixed E/N (≈ m_attach·2) and runs
the same forced-two-phase degree/num_edges workload through both
layouts, recording queries/sec and peak memory:

* ``est_peak_bytes`` — analytic per-program scatter footprint
  (dense: 2·4·N²·B_group + N²; edge: (2·4·e_cap + 5·4·M)·B_group),
* ``max_rss_bytes``  — measured ru_maxrss of the worker process.

A dense config whose estimate exceeds ``--mem-budget`` is recorded as
**infeasible** and skipped — at N=64k the dense scatter alone wants
~32 GB/query, which is the point: the edge path runs the same workload
in a few hundred MB.  Each (layout, N) config runs in its own
subprocess so RSS is per-config and device arrays are truly freed.

  PYTHONPATH=src python benchmarks/bench_edge_scaling.py [--fast|--smoke]

``--smoke`` is the CI sanity tier: one small edge config, no artifact
refresh.  Results land in ``benchmarks/BENCH_edge_scaling.json``
(schema: benchmarks/artifacts.py).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_JSON = os.path.join(HERE, "BENCH_edge_scaling.json")

SIZES = (4096, 16384, 65536)
E_OVER_N = 8  # m_attach=4 → ~8 live edge slots per node


def _est_peak_bytes(layout: str, n: int, e_cap: int, delta_cap: int,
                    b_group: int) -> int:
    """Analytic scatter footprint of one two-phase group program."""
    if layout == "dense":
        # first/last i32[N, N] per vmapped query + the bool adjacency
        return 2 * 4 * n * n * b_group + n * n
    # first/last i32[E] per query + the masked log columns (5 × i32[M])
    return (2 * 4 * e_cap + 5 * 4 * delta_cap) * b_group + e_cap


def _workload(t_cur: int, n_nodes: int, b: int, seed: int = 0):
    """Forced-two-phase degree/num_edges mix with *distinct* times, so
    the engine's reconstruction cache cannot shortcut the replay."""
    import numpy as np

    from repro.core.plans import Query
    rng = np.random.default_rng(seed)
    ts = rng.choice(np.arange(1, max(t_cur, b + 1)), size=b,
                    replace=False)
    qs = []
    for i, t in enumerate(sorted(int(t) for t in ts)):
        v = int(rng.integers(0, n_nodes))
        if i % 4 == 3:
            qs.append(Query("point", "global", "num_edges", t_k=t))
        else:
            qs.append(Query("point", "node", "degree", t_k=t, v=v))
    return qs


def worker(layout: str, n_nodes: int, b: int, reps: int) -> dict:
    import resource

    from repro.core.generate import EvolutionParams, build_store

    t0 = time.perf_counter()
    store = build_store(
        n_nodes,
        EvolutionParams(m_attach=E_OVER_N // 2, lam_extra=0.5,
                        lam_remove=0.5, events_per_unit=max(
                            8, n_nodes // 256)),
        seed=7, layout=layout)
    build_s = time.perf_counter() - t0
    eng = store.engine()
    delta_cap = store.delta().capacity
    e_cap = eng.current_edge.e_cap if eng.current_edge is not None else 0
    queries = _workload(store.t_cur, n_nodes, b)

    kw = dict(plan="two_phase", layout=layout)
    eng.evaluate_many(queries, **kw)              # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        eng.evaluate_many(queries, **kw)
    dt = (time.perf_counter() - t0) / reps
    # the executor groups by (kind, scope, measure): batch per program
    b_group = max(len(idx) for idx in (
        [q for q in queries if q.scope == "node"],
        [q for q in queries if q.scope == "global"]))
    return {
        "layout": layout,
        "n_nodes": n_nodes,
        "qps": b / dt,
        "us_per_query": dt / b * 1e6,
        "n_queries": b,
        "reps": reps,
        "t_cur": int(store.t_cur),
        "total_ops": int(store.stats()["total_ops"]),
        "e_cap": int(e_cap),
        "delta_cap": int(delta_cap),
        "build_s": build_s,
        "est_peak_bytes": _est_peak_bytes(layout, store.n_cap, e_cap,
                                          delta_cap, b_group),
        "max_rss_bytes": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024,
    }


def spawn(layout: str, n_nodes: int, args) -> dict:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.artifacts import merge_xla_flags
    env = dict(os.environ)
    # single-device workload; append to (don't clobber) pre-set flags
    env["XLA_FLAGS"] = merge_xla_flags(
        env.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=1")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    b = args.dense_queries if layout == "dense" else args.edge_queries
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--layout", layout, "--n-nodes", str(n_nodes),
           "--n-queries", str(b), "--reps", str(args.reps)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"worker {layout}@{n_nodes} failed:\n"
                           f"{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.splitlines()[-1])


def run(args) -> tuple[list, dict]:
    rows, configs = [], []
    for n in args.sizes:
        for layout in ("dense", "edge"):
            # rough dense estimate before paying the subprocess: the
            # group batch is ~3/4 of the query count (node-degree share)
            b = (args.dense_queries if layout == "dense"
                 else args.edge_queries)
            est = _est_peak_bytes(layout, n, 16 * n, 16 * n,
                                  max(1, 3 * b // 4))
            if est > args.mem_budget:
                configs.append({"layout": layout, "n_nodes": n,
                                "infeasible": True,
                                "est_peak_bytes": est})
                rows.append((f"edge_scaling/{layout}@N={n}", "infeasible",
                             f"est {est / 1e9:.1f} GB > budget "
                             f"{args.mem_budget / 1e9:.1f} GB"))
                continue
            res = spawn(layout, n, args)
            configs.append(res)
            rows.append((f"edge_scaling/{layout}@N={n}",
                         f"{res['qps']:.2f} qps",
                         f"{res['us_per_query']:.0f} us/query, "
                         f"rss {res['max_rss_bytes'] / 1e9:.2f} GB"))
    speedups = {}
    by = {(c["layout"], c["n_nodes"]): c for c in configs}
    for n in args.sizes:
        d, e = by.get(("dense", n)), by.get(("edge", n))
        if d and e and not d.get("infeasible") and not e.get("infeasible"):
            s = d["us_per_query"] / e["us_per_query"]
            speedups[str(n)] = s
            rows.append((f"edge_scaling/speedup@N={n}", f"{s:.1f}x",
                         "dense us/query ÷ edge us/query"))
        elif (d and d.get("infeasible") and e
                and not e.get("infeasible")):
            speedups[str(n)] = None
            rows.append((f"edge_scaling/speedup@N={n}", "inf",
                         "dense infeasible, edge "
                         f"{e['us_per_query']:.0f} us/query"))
    results = {"configs": configs, "speedup_per_query": speedups,
               "e_over_n": E_OVER_N, "mem_budget": args.mem_budget,
               "sizes": list(args.sizes)}
    return rows, results


def write_json(results: dict) -> None:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.artifacts import make_artifact, write_artifact
    write_artifact(OUT_JSON, make_artifact("edge_scaling", results,
                                           device_count=1))


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sizes / fewer reps, no artifact")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sanity: ONE small edge config, no artifact")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--layout", default="edge")
    ap.add_argument("--n-nodes", type=int, default=None)
    ap.add_argument("--n-queries", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--mem-budget", type=int, default=8 << 30,
                    help="skip configs whose est. scatter bytes exceed "
                         "this (records them as infeasible)")
    args = ap.parse_args()

    if args.worker:
        print(json.dumps(worker(args.layout, args.n_nodes,
                                args.n_queries, args.reps or 2)))
        return

    if args.smoke:
        args.sizes = (2048,)
        args.dense_queries, args.edge_queries, args.reps = 4, 8, 1
        # smoke covers exactly one config: the edge path
        res = spawn("edge", args.sizes[0], args)
        assert res["qps"] > 0 and res["layout"] == "edge", res
        print(f"edge_scaling/smoke@N={args.sizes[0]},"
              f"{res['qps']:.2f} qps,"
              f"rss {res['max_rss_bytes'] / 1e9:.2f} GB")
        print("edge_scaling smoke OK")
        return

    args.sizes = (1024, 4096) if args.fast else SIZES
    args.dense_queries = 4
    args.edge_queries = 8 if args.fast else 16
    args.reps = args.reps or (1 if args.fast else 2)

    rows, results = run(args)
    for name, val, note in rows:
        print(f"{name},{val},{note}")
    if args.fast:
        print(f"--fast: skipping {OUT_JSON} refresh")
    else:
        write_json(results)
        print(f"wrote {OUT_JSON}")


if __name__ == "__main__":
    main()
