"""Benchmark driver: one function per paper table/figure (+ the
framework benches). Prints ``name,us_per_call,derived`` CSV lines.

Sections that persist results refresh their ``BENCH_*.json`` artifacts
through the shared schema in ``benchmarks/artifacts.py`` (name, qps,
device_count, git sha), so artifacts are comparable across PRs.  Any
section raising an exception is reported AND makes the driver exit
non-zero — a red benchmark run never looks green.

Every section runs in this one process.  The device-count sweep
(``bench_distributed.py``) starts one child per device count, and a
child cannot use a chip this process already holds, so it runs on its
own.

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graphs / fewer reps")
    args = ap.parse_args()

    failures = []

    def section(name, fn):
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}")

    # Paper Table 3 — dataset
    from benchmarks import bench_table3_dataset

    def t3():
        rows, dt, _ = bench_table3_dataset.run()
        for name, got, target, relerr in rows:
            print(f"{name},{got},target={target} rel_err={relerr:.4f}")
        print(f"table3/build_seconds,{dt:.2f},")

    section("paper Table 3 (dataset)", t3)

    # Paper Figure 1 — four query plans vs time depth
    from benchmarks import bench_fig1_plans

    def f1():
        store = None
        if args.fast:
            from repro.core.generate import EvolutionParams, build_store
            store = build_store(600, EvolutionParams(
                m_attach=4, lam_extra=1.0, lam_remove=1.0), seed=1)
        for name, ops, ms in bench_fig1_plans.run(
                store=store, reps=2 if args.fast else 3):
            print(f"{name},{ms*1e3:.1f},ops_applied={ops}")

    section("paper Figure 1 (query plans)", f1)

    # Reconstruction engines (paper-faithful vs beyond-paper)
    from benchmarks import bench_reconstruction

    def rec():
        for name, ms in bench_reconstruction.run(
                n_nodes=384 if args.fast else 1024,
                reps=2 if args.fast else 3):
            if "speedup" in name:  # dimensionless ratio
                print(f"{name},{ms:.1f}x,")
            else:
                print(f"{name},{ms*1e3:.1f},")

    section("reconstruction engines", rec)

    # Batched multi-query engine throughput
    from benchmarks import bench_engine_batch

    def eb():
        rows, result = bench_engine_batch.run(
            n_nodes=150 if args.fast else 300,
            n_queries=64 if args.fast else 256,
            reps=2 if args.fast else 3)
        for name, val, note in rows:
            print(f"{name},{val},{note}")
        if not args.fast:   # --fast numbers are not comparable
            bench_engine_batch.write_json(result)

    section("engine batched serving", eb)

    # Kernels
    from benchmarks import bench_kernels

    def ker():
        for name, val, note in bench_kernels.run():
            print(f"{name},{val},{note}")

    section("kernels", ker)

    # Delta checkpointing
    from benchmarks import bench_checkpoint

    def ck():
        for name, val, note in bench_checkpoint.run():
            print(f"{name},{val},{note}")

    section("delta checkpoint store", ck)

    # Roofline summary (from cached dry-run artifacts)
    from benchmarks import roofline_report

    def roof():
        import os
        base = roofline_report.DRYRUN
        if not os.path.isdir(base):
            print("roofline,SKIP,no dryrun results yet")
            return
        for mesh in sorted(os.listdir(base)):
            s = roofline_report.summary(mesh)
            print(f"roofline/{mesh},{s['ok']} ok,"
                  f"{s['skipped']} skipped {s['errors']} errors")

    section("roofline summary", roof)

    if failures:
        print(f"\n{len(failures)} section(s) failed:", file=sys.stderr)
        for name, e in failures:
            print(f"  {name}: {e}", file=sys.stderr)
        sys.exit(1)
    print("\nall benchmark sections completed")


if __name__ == "__main__":
    main()
