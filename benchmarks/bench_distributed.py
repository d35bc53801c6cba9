"""Multi-device serving throughput (sharded evaluate_many).

Measures queries/sec of the engine's batched executor at device_count
∈ {1, 8} on the same workload as bench_engine_batch (mixed node-centric
point / diff / agg stream plus a two-phase global slice, auto-planned).
The device count is locked at first jax init, so the driver re-execs
itself once per device count with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and collects one
JSON line per worker; results land in ``benchmarks/BENCH_distributed.json``
(schema: benchmarks/artifacts.py).

On a CPU host the 8 forced devices share the machine's cores, so the
measured speedup depends on how many cores are free (anywhere from
< 1x under load to a few x on an idle multi-core host) — the artifact
records it honestly; what matters for real parts is that the
per-device work drops to 1/D.

  PYTHONPATH=src python benchmarks/bench_distributed.py [--fast]
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
OUT_JSON = os.path.join(HERE, "BENCH_distributed.json")
DEVICE_COUNTS = (1, 8)


def _workload(store, n_queries: int, seed: int = 0):
    import numpy as np

    from repro.core.plans import Query
    rng = np.random.default_rng(seed)
    tc = store.t_cur
    qs = []
    for i in range(n_queries):
        v = int(rng.integers(0, store.n_cap))
        t1 = int(rng.integers(1, max(2, tc)))
        t2 = min(tc, t1 + int(rng.integers(0, 8)))
        kind = ("point", "diff", "agg", "global")[i % 4]
        if kind == "point":
            qs.append(Query("point", "node", "degree", t_k=t1, v=v))
        elif kind == "diff":
            qs.append(Query("diff", "node", "degree", t_k=t1, t_l=t2, v=v))
        elif kind == "agg":
            qs.append(Query("agg", "node", "degree", t_k=t1, t_l=t2, v=v,
                            agg="mean"))
        else:
            qs.append(Query("point", "global", "num_edges", t_k=t1))
    return qs


def worker(n_nodes: int, n_queries: int, reps: int, seed: int) -> dict:
    """Runs inside one fixed-device-count process; prints a JSON dict."""
    import jax

    from repro.core.generate import EvolutionParams, build_store
    from repro.sharding.graph import graph_mesh, single_device

    n_dev = len(jax.devices())
    # n_cap must split evenly for the row-sharded two-phase groups
    n_cap = -(-n_nodes // 8) * 8
    store = build_store(n_nodes, EvolutionParams(
        m_attach=3, lam_extra=1.0, lam_remove=1.0), seed=seed, n_cap=n_cap)
    queries = _workload(store, n_queries, seed)
    mesh = graph_mesh()
    eng = (store.engine() if single_device(mesh)
           else store.place_on_mesh(mesh))

    kw = {} if single_device(mesh) else dict(mesh=mesh)
    eng.evaluate_many(queries, **kw)              # warm-up / compile
    sharded_groups = sum(m is not None
                         for *_, m in eng.last_group_stats)
    t0 = time.perf_counter()
    for _ in range(reps):
        eng.evaluate_many(queries, **kw)
    dt = (time.perf_counter() - t0) / reps
    return {
        "device_count": n_dev,
        "qps": n_queries / dt,
        "us_per_query": dt / n_queries * 1e6,
        "n_queries": n_queries,
        "groups": len(eng.last_group_stats),
        "sharded_groups": sharded_groups,
        "t_cur": int(store.t_cur),
        "total_ops": int(store.stats()["total_ops"]),
    }


def spawn(n_dev: int, args) -> dict:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.artifacts import merge_xla_flags
    env = dict(os.environ)
    # append to (don't clobber) a pre-set XLA_FLAGS — only the device
    # count is forced, everything else the caller exported is kept
    env["XLA_FLAGS"] = merge_xla_flags(
        env.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={n_dev}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--n-nodes", str(args.n_nodes), "--n-queries",
           str(args.n_queries), "--reps", str(args.reps)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"worker D={n_dev} failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    return json.loads(r.stdout.splitlines()[-1])


def run(args) -> tuple[list, dict]:
    """(rows, results) like the other bench modules."""
    per_dev = {}
    rows = []
    for n_dev in DEVICE_COUNTS:
        res = spawn(n_dev, args)
        assert res["device_count"] == n_dev, res
        per_dev[str(n_dev)] = res
        rows.append((f"distributed/qps@D={n_dev}", f"{res['qps']:.1f}",
                     f"{res['us_per_query']:.0f} us/query, "
                     f"{res['sharded_groups']}/{res['groups']} groups "
                     "sharded"))
    speedup = per_dev["8"]["qps"] / max(per_dev["1"]["qps"], 1e-9)
    rows.append(("distributed/speedup@D=8", f"{speedup:.2f}x",
                 "host-CPU devices share cores; see module docstring"))
    results = {"qps": {d: r["qps"] for d, r in per_dev.items()},
               "speedup_8_vs_1": speedup,
               "per_device_count": per_dev,
               "n_nodes": args.n_nodes, "n_queries": args.n_queries,
               "reps": args.reps}
    return rows, results


def write_json(results: dict) -> None:
    """Refresh BENCH_distributed.json (shared schema, one writer for
    both the standalone bench and benchmarks/run.py)."""
    if ROOT not in sys.path:  # direct `python benchmarks/...` invocation
        sys.path.insert(0, ROOT)
    from benchmarks.artifacts import make_artifact, write_artifact
    # the orchestrating process has 1 device; record the max measured
    write_artifact(OUT_JSON, make_artifact(
        "distributed", results, device_count=max(DEVICE_COUNTS)))


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--n-nodes", type=int, default=None)
    ap.add_argument("--n-queries", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args()
    args.n_nodes = args.n_nodes or (150 if args.fast else 300)
    args.n_queries = args.n_queries or (64 if args.fast else 256)
    args.reps = args.reps or (2 if args.fast else 3)

    if args.worker:
        print(json.dumps(worker(args.n_nodes, args.n_queries, args.reps,
                                seed=0)))
        return

    rows, results = run(args)
    for name, val, note in rows:
        print(f"{name},{val},{note}")
    if args.fast:
        # --fast is a sanity tier: don't clobber the committed
        # default-config artifact with incomparable numbers
        print(f"--fast: skipping {OUT_JSON} refresh")
    else:
        write_json(results)
        print(f"wrote {OUT_JSON}")


if __name__ == "__main__":
    main()
