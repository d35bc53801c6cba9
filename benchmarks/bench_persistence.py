"""Durability cost: WAL'd ingest overhead + crash-recovery time.

Two questions the persistence layer (``repro/persist``) must answer
with numbers:

1. **What does durability cost on the write path?**  The same
   closed-loop append/swap workload is driven through ``GraphSession``
   in three modes — in-memory, durable with per-record fsync (the
   default contract: an acknowledged op survives kill -9), and durable
   without fsync (page-cache durability; survives process death, not
   power loss).  Recorded per mode: ingest drain throughput (ops
   absorbed into served epochs per second) and swap latency.  The
   acceptance bar (ISSUE 7): WAL-on drain stays within **1.5x** of
   in-memory (``overhead_ratio`` in the artifact).

2. **What does recovery cost as history grows?**  For each history
   length H: open a checkpointed root (manifest + mmap'd segments +
   base-record-only WAL — the fast path ``close()`` buys) and a
   crashed root (same history, ~one epoch of WAL tail to replay).
   Recorded: open seconds for both paths vs H.

``--smoke`` runs the down-scaled sweep only; the CI fast lane guards
its ``wal_drain_ops_per_sec`` via
``scripts/check_bench_baseline.py --bench persistence``.

  PYTHONPATH=src python benchmarks/bench_persistence.py [--smoke] [--out PATH]
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

OUT_JSON = os.path.join(HERE, "BENCH_persistence.json")

FULL = dict(n_cap=128, per_unit=512, epoch_units=8, n_epochs=10,
            warmup_epochs=2, hist_units=(64, 256, 1024),
            replay_units=8)
SMOKE = dict(n_cap=128, per_unit=512, epoch_units=8, n_epochs=4,
             warmup_epochs=1, hist_units=(16, 64), replay_units=8)


def _churn_unit(rng, n_cap, t, per_unit):
    from repro.core.delta import ADD_EDGE, REM_EDGE
    from repro.core.store import Op
    ops = []
    for _ in range(per_unit):
        u, v = int(rng.integers(0, n_cap)), int(rng.integers(0, n_cap))
        if u == v:
            continue
        kind = ADD_EDGE if rng.random() < 0.55 else REM_EDGE
        ops.append(Op(kind, u, v, t))
    return ops


def _open_session(mode: str, cfg: dict, root: str | None):
    from repro.api import GraphSession
    if mode == "memory":
        return GraphSession(n_cap=cfg["n_cap"])
    return GraphSession.open(root, n_cap=cfg["n_cap"],
                             fsync=(mode == "wal"))


def measure_ingest(mode: str, cfg: dict) -> dict:
    """Closed-loop append/swap drain throughput for one mode."""
    import numpy as np

    from repro.core.delta import ADD_NODE
    from repro.core.store import Op

    rng = np.random.default_rng(7)
    n_cap, per_unit = cfg["n_cap"], cfg["per_unit"]
    root = tempfile.mkdtemp(prefix=f"bench_persist_{mode}_") \
        if mode != "memory" else None
    try:
        session = _open_session(mode, cfg, root)
        session.ingest([Op(ADD_NODE, v, v, 1) for v in range(n_cap)])
        session.flush()
        t = 1

        def one_epoch():
            nonlocal t
            batch = []
            for _ in range(cfg["epoch_units"]):
                t += 1
                batch += _churn_unit(rng, n_cap, t, per_unit)
            # one append per epoch: clients batch writes (the serving
            # frontend already coalesces), so the WAL pays one fsync'd
            # record per batch, not one per op
            n = session.ingest(batch)
            rec = session.flush()
            return n, rec.seconds

        for _ in range(cfg["warmup_epochs"]):
            one_epoch()
        t0 = time.perf_counter()
        results = [one_epoch() for _ in range(cfg["n_epochs"])]
        wall = time.perf_counter() - t0
        session.close()
    finally:
        if root is not None:
            shutil.rmtree(root, ignore_errors=True)
    absorbed = sum(n for n, _ in results)
    return {
        "drain_ops_per_sec": absorbed / wall,
        "swap_median_s": statistics.median(s for _, s in results),
        "ops_absorbed": absorbed,
    }


def measure_recovery(hist_units: int, cfg: dict) -> dict:
    """Open-time for a checkpointed vs a crashed (replaying) root."""
    import numpy as np

    from repro.api import GraphSession
    from repro.core.delta import ADD_NODE
    from repro.core.store import Op

    rng = np.random.default_rng(11)
    n_cap, per_unit = cfg["n_cap"], cfg["per_unit"]
    root = tempfile.mkdtemp(prefix="bench_persist_rec_")
    try:
        with GraphSession.open(root, n_cap=n_cap) as s:
            s.ingest([Op(ADD_NODE, v, v, 1) for v in range(n_cap)])
            t = 1
            batch = []
            for i in range(hist_units):
                t += 1
                batch += _churn_unit(rng, n_cap, t, per_unit)
                if (i + 1) % cfg["epoch_units"] == 0:
                    s.ingest(batch)
                    batch = []
                    s.flush()
            if batch:
                s.ingest(batch)
            s.flush()
            history_ops = s.store.stats()["total_ops"]
        GraphSession.open(root).close()   # warm the open path's jits
        # clean, checkpointed open: manifest + mmap + base-record WAL
        t0 = time.perf_counter()
        s2 = GraphSession.open(root)
        open_ckpt = time.perf_counter() - t0
        # now crash it mid-epoch: durable WAL tail, no checkpoint
        for _ in range(cfg["replay_units"]):
            t += 1
            s2.ingest(_churn_unit(rng, n_cap, t, per_unit))
        s2.live.swap()                    # seals + checkpoints
        for _ in range(cfg["replay_units"]):
            t += 1
            s2.ingest(_churn_unit(rng, n_cap, t, per_unit))
        del s2                            # kill -9 stand-in: no close()
        t0 = time.perf_counter()
        s3 = GraphSession.open(root)
        open_replay = time.perf_counter() - t0
        s3.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return {
        "history_ops": int(history_ops),
        "open_checkpointed_s": open_ckpt,
        "open_with_replay_s": open_replay,
    }


def run_sweep(cfg: dict) -> dict:
    out: dict = {"config": dict(cfg)}
    # all modes run the identical workload, so one throwaway pass
    # warms every jit shape the measured passes will hit — without it
    # the first mode pays all the compiles and the comparison is noise
    measure_ingest("memory", cfg)
    for mode in ("memory", "wal", "wal_nofsync"):
        out[mode] = measure_ingest(mode, cfg)
        print(f"{mode:11s}: drain "
              f"{out[mode]['drain_ops_per_sec']:9.0f} ops/s, swap p50 "
              f"{out[mode]['swap_median_s'] * 1e3:7.2f} ms", flush=True)
    out["overhead_ratio"] = (out["memory"]["drain_ops_per_sec"]
                             / out["wal"]["drain_ops_per_sec"])
    out["wal_drain_ops_per_sec"] = out["wal"]["drain_ops_per_sec"]
    recovery = {}
    for hu in cfg["hist_units"]:
        cell = measure_recovery(hu, cfg)
        recovery[str(cell["history_ops"])] = cell
        print(f"recovery hist={cell['history_ops']:>6d} ops: "
              f"checkpointed {cell['open_checkpointed_s'] * 1e3:7.1f} ms, "
              f"with replay {cell['open_with_replay_s'] * 1e3:7.1f} ms",
              flush=True)
    out["recovery"] = recovery
    print(f"WAL ingest overhead: {out['overhead_ratio']:.2f}x over "
          "in-memory (acceptance bar 1.5x)", flush=True)
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
    write_artifact(args.out, make_artifact("persistence", results))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
