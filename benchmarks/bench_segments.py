"""Epoch-swap cost: segmented vs monolithic delta log.

The ISSUE this benchmark guards: a serving epoch swap used to rebuild
the whole device log from the full host history — O(total history)
conversion per swap — so swap latency (and therefore ingest lag) grew
with the age of the deployment.  The segmented log
(``core/segments.py``) seals + converts only the ops since the last
swap, so swap latency must stay flat while history grows.

Protocol: for each history length H (a churning op stream over a
bounded node set, the paper's ops ≫ N² regime) and each mode
(``segmented=True`` / ``False``), prime a ``LiveGraphStore`` with H
ops, then measure K epoch swaps each absorbing the same number of
pending ops.  Recorded per (mode, H): median/mean swap seconds and the
ingest drain rate (ops absorbed per second).  The artifact also
records the *flatness ratio* — median swap latency at the largest
history over the smallest (≥16x apart): the acceptance criterion is
segmented ≤ 2x while monolithic grows with H.

``--smoke`` runs the down-scaled sweep only (CI fast lane;
``scripts/check_bench_baseline.py --bench segments`` compares its
swaps/sec against the committed artifact).

  PYTHONPATH=src python benchmarks/bench_segments.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

OUT_JSON = os.path.join(HERE, "BENCH_segments.json")

# history sweep (ops ≈ units × per_unit); largest/smallest = 16x
FULL = dict(n_cap=64, per_unit=32, hist_units=(256, 1024, 4096),
            epoch_units=4, n_swaps=8, warmup_swaps=2)
SMOKE = dict(n_cap=64, per_unit=16, hist_units=(32, 128, 512),
             epoch_units=4, n_swaps=5, warmup_swaps=1)


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


def measure_mode(segmented: bool, hist_units: int, cfg: dict) -> dict:
    """One (mode, history length) cell: prime, warm up, measure."""
    import numpy as np

    from repro.core.delta import ADD_NODE
    from repro.core.store import Op, TemporalGraphStore
    from repro.serving import LiveGraphStore

    rng = np.random.default_rng(7)
    n_cap, per_unit = cfg["n_cap"], cfg["per_unit"]
    store = TemporalGraphStore(n_cap=n_cap, segmented=segmented)
    live = LiveGraphStore(store=store)
    prime = [Op(ADD_NODE, v, v, 1) for v in range(n_cap)]
    t = 1
    for _ in range(hist_units):
        t += 1
        prime += _churn_unit(rng, n_cap, t, per_unit)
    live.append(prime)
    live.swap()

    def one_swap():
        nonlocal t
        batch = []
        for _ in range(cfg["epoch_units"]):
            t += 1
            batch += _churn_unit(rng, n_cap, t, per_unit)
        live.append(batch)
        return live.swap()

    for _ in range(cfg["warmup_swaps"]):
        one_swap()
    recs = [one_swap() for _ in range(cfg["n_swaps"])]
    secs = [r.seconds for r in recs]
    absorbed = [r.ops_absorbed for r in recs]
    med = statistics.median(secs)
    return {
        "history_ops": store.stats()["total_ops"] - sum(absorbed),
        "epoch_ops": int(statistics.median(absorbed)),
        "swap_median_s": med,
        "swap_mean_s": statistics.fmean(secs),
        "swaps_per_sec": (1.0 / med) if med > 0 else 0.0,
        "ingest_drain_ops_per_sec": statistics.median(absorbed) / med,
        "segments": (len(store._segments) if segmented else 0),
    }


def run_sweep(cfg: dict) -> dict:
    out: dict = {"config": dict(cfg)}
    for mode, segmented in (("segmented", True), ("monolithic", False)):
        cells = {}
        for hu in cfg["hist_units"]:
            cells[str(hu * cfg["per_unit"])] = measure_mode(
                segmented, hu, cfg)
            last = cells[str(hu * cfg["per_unit"])]
            print(f"{mode:11s} hist={hu * cfg['per_unit']:>6d} ops: "
                  f"swap p50 {last['swap_median_s'] * 1e3:8.2f} ms, "
                  f"drain {last['ingest_drain_ops_per_sec']:9.0f} ops/s",
                  flush=True)
        meds = [cells[str(hu * cfg["per_unit"])]["swap_median_s"]
                for hu in cfg["hist_units"]]
        out[mode] = cells
        out.setdefault("flatness_ratio", {})[mode] = (
            meds[-1] / meds[0] if meds[0] > 0 else float("inf"))
    # the guarded metric: segmented swap throughput at the LARGEST
    # history — exactly where the monolithic path degrades
    biggest = str(cfg["hist_units"][-1] * cfg["per_unit"])
    out["swaps_per_sec"] = out["segmented"][biggest]["swaps_per_sec"]
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
    for tier in results:
        fr = results[tier]["flatness_ratio"]
        print(f"[{tier}] swap-latency growth over "
              f"{results[tier]['config']['hist_units'][-1] // results[tier]['config']['hist_units'][0]}x history: "
              f"segmented {fr['segmented']:.2f}x vs monolithic "
              f"{fr['monolithic']:.2f}x", flush=True)
    write_artifact(args.out, make_artifact("segments", results))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
