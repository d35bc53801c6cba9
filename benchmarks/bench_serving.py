"""Closed-loop live-serving benchmark: mixed read/write, hot-tail reads.

Drives the full serving stack — ``LiveGraphStore`` (double-buffered
ingest + epoch swaps) behind a ``MicroBatchFrontend`` (coalescing +
exact result cache) — with a closed-loop client:

* **Mix**: ≥80/20 read/write.  Writes are the continuation of the
  scale-free evolution stream, appended to the pending buffer in small
  batches; an epoch swap runs every ``swap_every`` read bursts.
* **Read times**: hot-tail — a heavy band around a fixed historical
  time (default t_serv/3, the "everyone analyses the incident window"
  shape) plus an exponential tail decaying back from the watermark.
* **Measured**: sustained qps, p50/p99 request latency
  (submit→future-done through the frontend), ingest lag at each swap
  (pending ops + time units behind), frontend cache hit rate.

The same closed loop runs twice under the same device-byte budget:
once with ``WorkloadMaterializationPolicy`` (query histogram places
the anchors) and once with the static ``PeriodicMaterializationPolicy``
cadence — the artifact records both, and the workload-driven policy
must win on p99 for this distribution (two-phase queries in the hot
band reconstruct through a short window instead of the whole suffix).

``--smoke`` runs a down-scaled config only (CI fast lane; the
committed artifact keeps a ``smoke`` section from the full run so
``scripts/check_bench_baseline.py`` can compare apples to apples).

  PYTHONPATH=src python benchmarks/bench_serving.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

OUT_JSON = os.path.join(HERE, "BENCH_serving.json")

# The interesting serving regime is the paper's: a long churning op
# log over a bounded node set, so reconstruction cost is dominated by
# the delta window an anchor choice implies (ops >> N²) — that is
# where materialization placement moves the latency needle.
# Request mix: each burst issues ``burst`` read requests and
# ``writes_per_burst`` write requests (one append call of one complete
# time unit each) — burst=8 / writes=2 is the 80/20 read/write point.
FULL = dict(n_cap=64, prime_units=360, per_unit=48, n_bursts=150,
            burst=8, writes_per_burst=2, swap_every=25, warm_bursts=50,
            budget_snapshots=2, min_gap_ops=1500, seed=7)
SMOKE = dict(n_cap=64, prime_units=80, per_unit=16, n_bursts=40,
             burst=4, writes_per_burst=1, swap_every=10, warm_bursts=10,
             budget_snapshots=2, min_gap_ops=300, seed=7)


def _percentile(xs, p):
    xs = sorted(xs)
    if not xs:
        return 0.0
    k = min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))
    return xs[k]


def _hot_tail_time(rng, t_served, hot_center, hot_width):
    """60% hot historical band, 40% exponential tail off the watermark."""
    if rng.random() < 0.6:
        t = hot_center + int(rng.integers(-hot_width, hot_width + 1))
    else:
        t = t_served - int(rng.exponential(max(t_served / 8.0, 1.0)))
    return int(min(max(t, 1), t_served))


def churn_ops(n_cap, units, per_unit, rng, t0=1):
    """A churning op stream over a fixed node set: ``per_unit`` random
    add/remove-edge proposals per time unit (the store rejects illegal
    transitions, so proposals are admissible input).  This is the
    paper's serving regime — a log much larger than the graph — where
    reconstruction cost is the delta window, i.e. where anchors live.
    """
    from repro.core.store import Op
    from repro.core.delta import ADD_EDGE, ADD_NODE, REM_EDGE
    ops = [Op(ADD_NODE, v, v, t0) for v in range(n_cap)]
    for t in range(t0 + 1, t0 + 1 + units):
        for _ in range(per_unit):
            u, v = int(rng.integers(0, n_cap)), int(rng.integers(0, n_cap))
            if u == v:
                continue
            kind = ADD_EDGE if rng.random() < 0.55 else REM_EDGE
            ops.append(Op(kind, u, v, t))
    return ops


def closed_loop(policy_kind: str, cfg: dict) -> dict:
    """One closed-loop run; returns the measured stats dict."""
    import numpy as np

    from repro.core.engine import _snapshot_bytes
    from repro.core.plans import Query
    from repro.core.store import TemporalGraphStore
    from repro.serving import (LiveGraphStore, MicroBatchFrontend,
                               PeriodicMaterializationPolicy,
                               WorkloadMaterializationPolicy)

    rng = np.random.default_rng(cfg["seed"])
    # prime history + the continuation the write stream will append
    # (one complete time unit per write request — a swap closes units)
    write_units = cfg["n_bursts"] * cfg["writes_per_burst"] + 2
    ops = churn_ops(cfg["n_cap"], cfg["prime_units"] + write_units,
                    cfg["per_unit"], rng)
    t_prime = cfg["prime_units"] + 1
    prime = next(i for i, o in enumerate(ops) if o.t > t_prime)

    # the byte budget buys the same #snapshots for either policy
    probe = TemporalGraphStore(n_cap=cfg["n_cap"])
    budget = cfg["budget_snapshots"] * _snapshot_bytes(probe.current) + 1
    if policy_kind == "workload":
        policy = WorkloadMaterializationPolicy(
            budget_bytes=budget, min_gap_ops=cfg["min_gap_ops"],
            decay=0.5)
    else:
        # the static cadence: snapshots on a uniform time grid
        policy = PeriodicMaterializationPolicy(
            period=max(t_prime // (cfg["budget_snapshots"] + 1), 4),
            budget_bytes=budget)

    # pre-size the device log and pad every group to the burst size:
    # swaps and batch fragmentation then never change a kernel shape,
    # so steady-state latency has no recompiles
    live = LiveGraphStore(n_cap=cfg["n_cap"], policy=policy,
                          delta_cap_hint=2 * len(ops),
                          group_pad_min=cfg["burst"])
    live.append(ops[:prime])
    live.swap()
    fe = MicroBatchFrontend(live, max_batch=cfg["burst"])
    hot_center = max(live.t_served // 3, 2)
    hot_width = 6

    def burst_queries():
        qs = []
        for _ in range(cfg["burst"]):
            t = _hot_tail_time(rng, live.t_served, hot_center, hot_width)
            if rng.random() < 0.5:
                qs.append(Query("point", "node", "degree", t_k=t,
                                v=int(rng.integers(0, cfg["n_cap"]))))
            else:
                qs.append(Query("point", "global", "num_edges", t_k=t))
        return qs

    lat, lags = [], []
    write_ptr = prime
    n_reads = n_write_reqs = n_write_ops = 0
    measuring = False
    t0 = time.perf_counter()
    for i in range(cfg["n_bursts"]):
        if i == cfg["warm_bursts"]:
            # measurement starts once the policy has converged and the
            # program shapes are compiled; writes/swaps keep flowing
            # through the measured phase — this is the steady state
            lat, measuring = [], True
            n_reads = n_write_reqs = n_write_ops = 0
            t0 = time.perf_counter()
        for _ in range(cfg["writes_per_burst"]):
            if write_ptr >= len(ops):
                break
            # one write request = one append of one complete time unit
            # (a swap closes every pending unit; a mid-unit cut would
            # make the stream continuation un-appendable, by design)
            end = write_ptr + 1
            while end < len(ops) and ops[end].t == ops[write_ptr].t:
                end += 1
            batch = ops[write_ptr:end]
            write_ptr = end
            live.append(batch)
            n_write_reqs += 1
            n_write_ops += len(batch)
        qs = burst_queries()
        t_sub = time.perf_counter()
        futs = [fe.submit(q) for q in qs]
        fe.flush()
        done = time.perf_counter()
        for f in futs:
            f.result()
            lat.append(done - t_sub)
        n_reads += len(qs)
        if (i + 1) % cfg["swap_every"] == 0:
            if measuring:
                lags.append(live.ingest_lag())
            live.swap()
    elapsed = time.perf_counter() - t0

    return {
        "policy": policy_kind,
        "reads": n_reads,
        "write_requests": n_write_reqs,
        "write_ops": n_write_ops,
        "read_fraction": n_reads / max(n_reads + n_write_reqs, 1),
        "qps": n_reads / elapsed,
        "p50_ms": _percentile(lat, 50) * 1e3,
        "p99_ms": _percentile(lat, 99) * 1e3,
        "epochs": live.epoch,
        "max_pending_ops": max((g["pending_ops"] for g in lags),
                               default=0),
        "max_t_behind": max((g["t_behind"] for g in lags), default=0),
        "mean_swap_seconds": (sum(r.seconds for r in live.swap_history)
                              / max(len(live.swap_history), 1)),
        "anchors": list(live.store.materialized.times),
        "cache_hit_rate": fe.stats.cache_hits / max(fe.stats.submitted, 1),
        "coalesced_dupes": fe.stats.coalesced_dupes,
    }


def run_config(cfg_name: str) -> dict:
    """Run each policy's closed loop in its OWN subprocess: a shared
    process would hand whichever runs second a warm jit cache, skewing
    the comparison (the house rule — see bench_edge_scaling)."""
    import json
    import subprocess
    out = {}
    for kind in ("workload", "static"):
        cmd = [sys.executable, os.path.abspath(__file__), "--worker",
               kind, "--config", cfg_name]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           cwd=ROOT, timeout=1800)
        if r.returncode != 0:
            raise RuntimeError(f"worker {kind} failed:\n{r.stdout}\n"
                               f"{r.stderr}")
        out[kind] = json.loads(r.stdout.splitlines()[-1])
    cfg = dict(FULL if cfg_name == "full" else SMOKE)
    return {
        "config": cfg,
        "workload": out["workload"],
        "static": out["static"],
        "p99_speedup_workload_vs_static":
            out["static"]["p99_ms"] / max(out["workload"]["p99_ms"], 1e-9),
        "qps": out["workload"]["qps"],
    }


def main() -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="down-scaled run only (CI fast lane)")
    ap.add_argument("--out", default=OUT_JSON)
    ap.add_argument("--worker", default=None,
                    choices=("workload", "static"),
                    help="internal: run one closed loop, print JSON")
    ap.add_argument("--config", default="smoke",
                    choices=("smoke", "full"))
    args = ap.parse_args()

    if args.worker:
        import json
        cfg = FULL if args.config == "full" else SMOKE
        print(json.dumps(closed_loop(args.worker, cfg)))
        return 0

    from artifacts import make_artifact, write_artifact

    results = {"smoke": run_config("smoke")}
    print("smoke:", {k: round(v, 3) if isinstance(v, float) else v
                     for k, v in results["smoke"]["workload"].items()
                     if k in ("qps", "p50_ms", "p99_ms",
                              "cache_hit_rate")})
    if not args.smoke:
        results["closed_loop"] = run_config("full")
        for kind in ("workload", "static"):
            r = results["closed_loop"][kind]
            print(f"{kind:9s} qps={r['qps']:9.1f}  p50={r['p50_ms']:7.2f}ms"
                  f"  p99={r['p99_ms']:7.2f}ms  lag≤{r['max_pending_ops']}"
                  f" ops/{r['max_t_behind']}tu  anchors={r['anchors']}")
        print("p99 speedup (workload vs static): "
              f"{results['closed_loop']['p99_speedup_workload_vs_static']:.2f}x")
    write_artifact(args.out, make_artifact("serving", results))
    print("wrote", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
