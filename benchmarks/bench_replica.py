"""Replicated serving cost: routed read qps, replica lag, failover time.

Three questions the replication layer (``repro/replica``) must answer
with numbers:

1. **What does routed serving cost?**  A fleet of D read replicas
   (D in {1, 2, 4}; smoke {1, 2}) is synced from one published root and
   a ``QueryRouter`` drives a fixed batched read load through the
   whole stack — watermark check, candidate ordering, replica engine
   dispatch.  Recorded per D: routed queries/second.  (All replicas
   share one process and device here, so this measures the serving
   path's overhead, not horizontal scale-out.)
2. **How far behind does a polling replica run under write churn?**
   The writer streams epoch after epoch; after every checkpoint the
   replica's pre-sync staleness (time units behind the writer) and its
   catch-up sync time are recorded.  The incremental paths (WAL growth
   / rotation suffix) keep the catch-up cost bounded by the epoch, not
   the history.
3. **What does failover cost?**  Two replicas behind a router; the one
   currently serving is killed (its transport and serving surface both
   go dark) and the next routed call must come back from the survivor.
   Recorded: median/max seconds for that first post-death answer —
   detection + failover + retry, measured at the client.

``--smoke`` runs the down-scaled sweep only; the CI fast lane guards
its ``routed_qps`` via ``scripts/check_bench_baseline.py --bench
replica``.

  PYTHONPATH=src python benchmarks/bench_replica.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

OUT_JSON = os.path.join(HERE, "BENCH_replica.json")

FULL = dict(n_cap=128, per_unit=256, epoch_units=4, warm_epochs=6,
            churn_epochs=12, replica_counts=(1, 2, 4), batch_q=32,
            n_batches=120, warmup_batches=10, failover_trials=5)
SMOKE = dict(n_cap=64, per_unit=128, epoch_units=4, warm_epochs=3,
             churn_epochs=6, replica_counts=(1, 2), batch_q=32,
             n_batches=30, warmup_batches=5, failover_trials=3)


def _churn_unit(rng, n_cap, t, per_unit):
    from repro.core.delta import ADD_EDGE, REM_EDGE
    from repro.core.store import Op
    ops = []
    for _ in range(per_unit):
        u, v = int(rng.integers(0, n_cap)), int(rng.integers(0, n_cap))
        if u == v:
            continue
        ops.append(Op(ADD_EDGE if rng.random() < 0.55 else REM_EDGE,
                      u, v, t))
    return ops


def _seed_writer(cfg, tmp):
    """A durable writer with ``warm_epochs`` of published history."""
    import numpy as np

    from repro.api import GraphSession
    from repro.core.delta import ADD_NODE
    from repro.core.store import Op

    rng = np.random.default_rng(3)
    s = GraphSession.open(os.path.join(tmp, "writer"), n_cap=cfg["n_cap"])
    pub = s.publish_to(os.path.join(tmp, "pub"))
    s.ingest([Op(ADD_NODE, v, v, 1) for v in range(cfg["n_cap"])])
    t = 1
    for _ in range(cfg["warm_epochs"]):
        batch = []
        for _ in range(cfg["epoch_units"]):
            t += 1
            batch += _churn_unit(rng, cfg["n_cap"], t, cfg["per_unit"])
        s.ingest(batch)
        s.flush()
    return s, pub, rng, t


def _query_batches(cfg, watermark):
    from repro.core import Query
    qs = []
    for i in range(cfg["batch_q"]):
        t = 1 + (i * 7) % watermark
        if i % 4 == 0:
            qs.append(Query("point", "global", "num_edges", t_k=t))
        else:
            qs.append(Query("point", "node", "degree", t_k=t,
                            v=i % cfg["n_cap"]))
    return qs


def measure_routed_qps(cfg: dict) -> dict:
    """Routed read throughput vs fleet size over identical state."""
    from repro.api import GraphSession
    from repro.replica import ReadReplica

    out = {}
    tmp = tempfile.mkdtemp(prefix="bench_replica_qps_")
    try:
        s, pub, _rng, _t = _seed_writer(cfg, tmp)
        qs = _query_batches(cfg, s.watermark)
        for d in cfg["replica_counts"]:
            replicas = {}
            for i in range(d):
                r = ReadReplica(pub.transport(),
                                os.path.join(tmp, f"rep{d}_{i}"),
                                name=f"r{i}")
                r.sync()
                replicas[r.name] = r
            router = GraphSession.open_router(replicas)
            for _ in range(cfg["warmup_batches"]):
                router.evaluate_many(qs)
            t0 = time.perf_counter()
            for _ in range(cfg["n_batches"]):
                router.evaluate_many(qs)
            wall = time.perf_counter() - t0
            qps = cfg["n_batches"] * len(qs) / wall
            out[str(d)] = qps
            print(f"routed qps  D={d}: {qps:9.0f}", flush=True)
        s.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def measure_lag_under_churn(cfg: dict) -> dict:
    """Per-epoch staleness and catch-up time of a polling replica."""
    from repro.replica import ReadReplica

    tmp = tempfile.mkdtemp(prefix="bench_replica_lag_")
    try:
        s, pub, rng, t = _seed_writer(cfg, tmp)
        replica = ReadReplica(pub.transport(), os.path.join(tmp, "rep"))
        replica.sync()
        lags, sync_s, applied = [], [], []
        for _ in range(cfg["churn_epochs"]):
            batch = []
            for _ in range(cfg["epoch_units"]):
                t += 1
                batch += _churn_unit(rng, cfg["n_cap"], t,
                                     cfg["per_unit"])
            s.ingest(batch)
            s.flush()
            lags.append(s.watermark - replica.watermark)
            rec = replica.sync()
            sync_s.append(rec["seconds"])
            applied.append(rec["records_applied"])
            assert replica.watermark == s.watermark
        s.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    cell = {
        "pre_sync_lag_units_median": statistics.median(lags),
        "pre_sync_lag_units_max": max(lags),
        "catchup_s_median": statistics.median(sync_s),
        "catchup_s_max": max(sync_s),
        "records_per_catchup_median": statistics.median(applied),
        "epochs": cfg["churn_epochs"],
    }
    print(f"lag under churn: pre-sync p50 "
          f"{cell['pre_sync_lag_units_median']:.0f} units, catch-up p50 "
          f"{cell['catchup_s_median'] * 1e3:.1f} ms", flush=True)
    return cell


class _Killable:
    """Serving proxy whose death is a switch — the router sees the
    same surface a remote replica process would expose."""

    def __init__(self, replica):
        self.replica = replica
        self.dead = False

    def status(self):
        if self.dead:
            raise ConnectionError("replica down")
        return self.replica.status()

    def evaluate_many(self, queries, plan="auto", **kw):
        if self.dead:
            raise ConnectionError("replica down")
        return self.replica.evaluate_many(queries, plan, **kw)


def measure_failover(cfg: dict) -> dict:
    """Client-observed seconds for the first answer after the serving
    replica dies (detection + mark-down + retry on the survivor)."""
    from repro.api import GraphSession
    from repro.replica import ReadReplica

    tmp = tempfile.mkdtemp(prefix="bench_replica_fo_")
    try:
        s, pub, _rng, _t = _seed_writer(cfg, tmp)
        qs = _query_batches(cfg, s.watermark)
        proxies = {}
        for i in range(2):
            r = ReadReplica(pub.transport(), os.path.join(tmp, f"rep{i}"),
                            name=f"r{i}")
            r.sync()
            proxies[r.name] = _Killable(r)
        router = GraphSession.open_router(proxies)
        for _ in range(cfg["warmup_batches"]):
            router.evaluate_many(qs)
        trials = []
        for _ in range(cfg["failover_trials"]):
            # kill whichever replica is about to be picked
            victim = max(proxies.values(),
                         key=lambda p: p.replica.stats.queries_served)
            victim.dead = True
            t0 = time.perf_counter()
            router.evaluate_many(qs)      # must answer from the survivor
            trials.append(time.perf_counter() - t0)
            victim.dead = False
            router.heartbeat()            # readmit before the next trial
        s.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    cell = {
        "first_answer_s_median": statistics.median(trials),
        "first_answer_s_max": max(trials),
        "trials": len(trials),
    }
    print(f"failover: first post-death answer p50 "
          f"{cell['first_answer_s_median'] * 1e3:.1f} ms "
          f"(max {cell['first_answer_s_max'] * 1e3:.1f} ms)", flush=True)
    return cell


def run_sweep(cfg: dict) -> dict:
    out: dict = {"config": dict(cfg)}
    out["qps_by_replicas"] = measure_routed_qps(cfg)
    out["routed_qps"] = out["qps_by_replicas"][
        str(min(cfg["replica_counts"]))]
    out["lag"] = measure_lag_under_churn(cfg)
    out["failover"] = measure_failover(cfg)
    return out


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="down-scaled sweep only (CI fast lane)")
    ap.add_argument("--out", default=OUT_JSON)
    args = ap.parse_args()

    from artifacts import make_artifact, write_artifact

    results = {"smoke": run_sweep(SMOKE)}
    if not args.smoke:
        results["full"] = run_sweep(FULL)
    write_artifact(args.out, make_artifact("replica", results))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
