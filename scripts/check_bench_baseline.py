"""Bench regression guard: fresh --smoke qps vs the committed artifact.

Benchmarks commit their results as BENCH_*.json (schema in
benchmarks/artifacts.py) and every supported bench records a
``smoke``-scale measurement even in full runs, so a fresh ``--smoke``
run is directly comparable to the committed number.  This script runs
the smoke config, extracts the qps metric, and fails only when the
fresh number falls below ``committed / slack`` — the default 3x slack
absorbs CI-runner noise (shared cores, cold caches) while still
catching order-of-magnitude regressions (an accidentally-serialized
dispatch loop, a recompile per request, ...).

Usage:
  PYTHONPATH=src python scripts/check_bench_baseline.py \
      [--bench serving] [--slack 3.0] [--keep PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# bench name -> (script, committed artifact, path of the qps metric
# inside results{}, both for the committed and the fresh artifact)
BENCHES = {
    "serving": ("benchmarks/bench_serving.py",
                "benchmarks/BENCH_serving.json",
                ("smoke", "qps")),
    # epoch-swap throughput of the segmented delta log at the largest
    # smoke history — the O(epoch-ops) swap contract (a regression to
    # O(history) conversion tanks this number first)
    "segments": ("benchmarks/bench_segments.py",
                 "benchmarks/BENCH_segments.json",
                 ("smoke", "swaps_per_sec")),
    # whole-sweep (evolve) dispatch throughput on the dense layout — a
    # regression to per-sample dispatch (B programs instead of one
    # scan) tanks this number first
    "sweep": ("benchmarks/bench_sweep.py",
              "benchmarks/BENCH_sweep.json",
              ("smoke", "sweeps_per_sec")),
    # WAL-on ingest drain throughput — a regression to per-op fsyncs,
    # per-swap segment rewrites, or checkpoint work that scales with
    # history (instead of with the epoch) tanks this number first
    "persistence": ("benchmarks/bench_persistence.py",
                    "benchmarks/BENCH_persistence.json",
                    ("smoke", "wal_drain_ops_per_sec")),
    # routed read throughput through the replica stack (sync + router
    # + replica engine dispatch) — a regression to per-query engine
    # rebuilds or per-call sync work tanks this number first
    "replica": ("benchmarks/bench_replica.py",
                "benchmarks/BENCH_replica.json",
                ("smoke", "routed_qps")),
    # whole-repo static-analysis throughput — the lint gate runs on
    # every push, so a pass that goes accidentally quadratic (AST
    # re-walks per rule, call-closure fixpoint blowup) shows up here
    # before it shows up as a slow CI lane
    "graphlint": ("benchmarks/bench_graphlint.py",
                  "benchmarks/BENCH_graphlint.json",
                  ("smoke", "files_per_sec")),
}


def _metric(artifact: dict, path: tuple[str, ...]) -> float:
    node = artifact["results"]
    for key in path:
        node = node[key]
    return float(node)


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--bench", default="serving", choices=sorted(BENCHES))
    ap.add_argument("--slack", type=float, default=3.0,
                    help="fail when fresh qps < committed / slack")
    ap.add_argument("--keep", default=None,
                    help="also save the fresh artifact here")
    args = ap.parse_args()

    script, committed_path, metric_path = BENCHES[args.bench]
    committed_file = os.path.join(ROOT, committed_path)
    if not os.path.exists(committed_file):
        print(f"no committed artifact at {committed_path} — nothing to "
              "compare (commit one with a full bench run)")
        return 1
    with open(committed_file) as fh:
        committed = _metric(json.load(fh), metric_path)

    out = args.keep or os.path.join(tempfile.mkdtemp(), "fresh.json")
    env = dict(os.environ)
    src = os.path.join(ROOT, "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, os.path.join(ROOT, script), "--smoke",
           "--out", out]
    print("+", " ".join(cmd), flush=True)
    r = subprocess.run(cmd, cwd=ROOT, env=env)
    if r.returncode != 0:
        print(f"FAIL: bench exited {r.returncode}")
        return r.returncode
    with open(out) as fh:
        fresh = _metric(json.load(fh), metric_path)

    floor = committed / args.slack
    verdict = "OK" if fresh >= floor else "REGRESSION"
    print(f"{args.bench}: fresh {fresh:.1f} qps vs committed "
          f"{committed:.1f} qps (floor {floor:.1f} at {args.slack:.1f}x "
          f"slack) — {verdict}")
    return 0 if fresh >= floor else 1


if __name__ == "__main__":
    sys.exit(main())
