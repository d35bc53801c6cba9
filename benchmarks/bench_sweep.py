"""Time-sweep (``evolve``) queries vs B independent point queries.

The ISSUE this benchmark guards: an evolution plot over B sample times
used to cost B full query dispatches — B reconstructions, B device
round-trips — even though consecutive samples differ by a handful of
ops.  ``store.evolve`` executes the whole sweep as ONE device program
(reconstruct once at ``t_lo``, then alternate apply-segment/measure in
a ``lax.scan``), so a 64-point dashboard sweep must run several times
faster than 64 independent point queries while staying bit-identical
to them.

Protocol, per layout (dense / edge): prime a churning op stream over a
bounded node set, seal segments as history grows, then time

* ``sweep``  — one ``store.evolve(measure, t_lo, t_hi)`` call,
* ``points`` — the same B sample times issued as B *independent*
  ``evaluate_many`` calls (the naive dashboard loop), and
* ``points_batched`` — the B point queries co-batched in one
  ``evaluate_many`` (the engine's own grouping, recorded for honesty —
  the sweep must beat the naive loop; the batched number shows how
  much of the win is batching vs the incremental scan),

asserting the sweep output is bit-equal to the stacked point results
before trusting any timing.  The artifact records per-layout medians,
the sweep/points speedup, and the merged-delta-tree coverage counts
(``window_cover`` leaf vs ``merged=True``) — tree ops must be strictly
below leaf ops on the long-history store.

``--smoke`` runs the down-scaled config only (CI fast lane;
``scripts/check_bench_baseline.py --bench sweep`` compares its
sweeps/sec against the committed artifact).

  PYTHONPATH=src python benchmarks/bench_sweep.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

OUT_JSON = os.path.join(HERE, "BENCH_sweep.json")

# sweep_units is B, the number of sampled times per evolve call; the
# acceptance criterion is the FULL config's 64-unit window
FULL = dict(n_cap=64, per_unit=24, hist_units=256, seal_every=8,
            sweep_units=64, stride=1, measure="num_edges",
            n_iters=5, warmup=1)
SMOKE = dict(n_cap=48, per_unit=12, hist_units=64, seal_every=4,
             sweep_units=32, stride=1, measure="num_edges",
             n_iters=3, warmup=1)


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


def _build_store(layout: str, cfg: dict):
    import numpy as np

    from repro.core.delta import ADD_NODE
    from repro.core.store import Op, TemporalGraphStore

    rng = np.random.default_rng(13)
    n_cap = cfg["n_cap"]
    store = TemporalGraphStore(n_cap=n_cap, layout=layout)
    store.ingest([Op(ADD_NODE, v, v, 1) for v in range(n_cap)])
    t = 1
    for u in range(cfg["hist_units"]):
        t += 1
        store.ingest(_churn_unit(rng, n_cap, t, cfg["per_unit"]))
        if (u + 1) % cfg["seal_every"] == 0:
            store.advance_to(t)
            store.freeze_serving_state()
    store.advance_to(t)
    store.freeze_serving_state()
    return store


def _median_time(fn, n_iters: int, warmup: int) -> float:
    for _ in range(warmup):
        fn()
    secs = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        fn()
        secs.append(time.perf_counter() - t0)
    return statistics.median(secs)


def _cover_stats(view, t_lo: int, t_hi: int) -> dict:
    leaf = view.window_cover(t_lo, t_hi)
    tree = view.window_cover(t_lo, t_hi, merged=True)
    return {
        "leaf_items": len(leaf),
        "leaf_ops": int(sum(s.n_ops for s in leaf)),
        "tree_items": len(tree),
        "tree_ops": int(sum(s.n_ops for s in tree)),
    }


def measure_layout(layout: str, cfg: dict) -> dict:
    import numpy as np

    from repro.core.plans import Query

    store = _build_store(layout, cfg)
    stride = cfg["stride"]
    t_hi = store.t_cur - 1
    t_lo = t_hi - (cfg["sweep_units"] - 1) * stride
    assert t_lo >= 2, (t_lo, store.t_cur)
    measure = cfg["measure"]
    ts = list(range(t_lo, t_hi + 1, stride))
    point_qs = [Query("point", "global", measure, t_k=t) for t in ts]

    # bit-exactness gate before any timing is trusted
    swept = np.asarray(store.evolve(measure, t_lo, t_hi, stride=stride))
    pts = np.asarray(store.evaluate_many(point_qs))
    if not np.array_equal(swept, pts):
        raise AssertionError(
            f"sweep != points on {layout}: {swept} vs {pts}")

    sweep_s = _median_time(
        lambda: store.evolve(measure, t_lo, t_hi, stride=stride),
        cfg["n_iters"], cfg["warmup"])

    def points_independent():
        for q in point_qs:
            store.evaluate_many([q])

    points_s = _median_time(points_independent, cfg["n_iters"],
                            cfg["warmup"])
    batched_s = _median_time(lambda: store.evaluate_many(point_qs),
                             cfg["n_iters"], cfg["warmup"])

    cell = {
        "samples": len(ts),
        "window": [int(t_lo), int(t_hi)],
        "sweep_median_s": sweep_s,
        "points_independent_median_s": points_s,
        "points_batched_median_s": batched_s,
        "speedup_vs_points": points_s / sweep_s if sweep_s > 0 else 0.0,
        "speedup_vs_batched": batched_s / sweep_s if sweep_s > 0 else 0.0,
        "sweeps_per_sec": (1.0 / sweep_s) if sweep_s > 0 else 0.0,
    }
    if layout == "dense":
        view = store.delta_view()
        cell["cover"] = {
            "sweep_window": _cover_stats(view, t_lo, t_hi),
            "full_history": _cover_stats(view, 0, store.t_cur),
        }
        full = cell["cover"]["full_history"]
        if full["tree_ops"] >= full["leaf_ops"]:
            raise AssertionError(
                "merged tree did not shrink the full-history cover: "
                f"{full}")
    return cell


def run_sweep(cfg: dict) -> dict:
    out: dict = {"config": dict(cfg)}
    for layout in ("dense", "edge"):
        cell = measure_layout(layout, cfg)
        out[layout] = cell
        print(f"{layout:5s}: sweep B={cell['samples']} "
              f"{cell['sweep_median_s'] * 1e3:7.2f} ms vs points "
              f"{cell['points_independent_median_s'] * 1e3:8.2f} ms "
              f"({cell['speedup_vs_points']:5.1f}x, batched "
              f"{cell['speedup_vs_batched']:4.1f}x)", flush=True)
    full = out["dense"]["cover"]["full_history"]
    print(f"cover (full history): tree {full['tree_items']} items / "
          f"{full['tree_ops']} ops vs leaf {full['leaf_items']} items / "
          f"{full['leaf_ops']} ops", flush=True)
    # the guarded metric: whole-sweep dispatch throughput on the
    # default layout — a regression to per-sample dispatch tanks it
    out["sweeps_per_sec"] = out["dense"]["sweeps_per_sec"]
    out["speedup_vs_points"] = min(
        out["dense"]["speedup_vs_points"], out["edge"]["speedup_vs_points"])
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
    write_artifact(args.out, make_artifact("sweep", results))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
