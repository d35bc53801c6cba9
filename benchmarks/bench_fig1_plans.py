"""Paper Figure 1: run time of a point node-centric degree query at
increasing time depth (x-axis backwards from the current snapshot,
measured in #ops applied), for the four plans:

  two-phase, hybrid, two-phase-index, hybrid-index

plus the paper-faithful *sequential* two-phase baseline (one-op-at-a-
time replay — what the Java/Neo4j implementation does) so the
beyond-paper vectorized gain is visible (EXPERIMENTS.md §Perf).

Audited against the segmented-by-default store: ``store.delta()`` is
the monolithic compat view (``SegmentedDeltaView.full_delta``), so the
plan timings here measure the same device log as before segmentation.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.generate import paper_table3
from repro.core.index import count_window_ops
from repro.core.plans import (hybrid_point_degree,
                              hybrid_point_degree_indexed, two_phase,
                              Query)


def _timeit(fn, reps=3):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e3, out  # ms, value


def run(store=None, depths=(0.1, 0.3, 0.5, 0.7, 0.9), reps=3,
        sequential_too=True, seq_depths=(0.3, 0.9)):
    """Figure 1: the sequential (paper-faithful, Neo4j-like one-op-at-a-
    time) baseline is measured at fewer depths with reps=1 — it is
    ~100-1000× slower than the vectorized engine, which is the point."""
    store = store or paper_table3()
    d = store.delta()
    index = store.node_index()
    rng = np.random.default_rng(0)
    rows = []
    for frac in depths:
        t_q = int(store.t_cur * (1 - frac))
        ops_applied = int(count_window_ops(d, t_q, store.t_cur))
        v = int(rng.integers(0, store.n_cap))
        q = Query("point", "node", "degree", t_k=t_q, v=v)

        plans = {
            "two_phase": lambda: two_phase(store.current, d, store.t_cur,
                                           q, partial_rows=True),
            "hybrid": lambda: hybrid_point_degree(store.current, d, v,
                                                  t_q, store.t_cur),
            "two_phase_index": lambda: two_phase(
                store.current, d, store.t_cur, q, partial_rows=True,
                passes=1),
            "hybrid_index": lambda: hybrid_point_degree_indexed(
                store.current, d, index, v, t_q, store.t_cur, 2048),
        }
        if sequential_too and frac in seq_depths:
            plans["two_phase_sequential"] = lambda: two_phase(
                store.current, d, store.t_cur, q, sequential=True)
        vals = {}
        ms = {}
        for name, fn in plans.items():
            r = 1 if name == "two_phase_sequential" else reps
            ms[name], out = _timeit(fn, r)
            vals[name] = int(np.asarray(jax.device_get(out)))
        assert len(set(vals.values())) == 1, (vals, frac)
        for name, m in ms.items():
            rows.append((f"fig1/{name}", ops_applied, m))
    return rows


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, ops, ms in run():
        print(f"{name},{ms*1e3:.1f},ops_applied={ops}")


if __name__ == "__main__":
    main()
