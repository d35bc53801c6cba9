"""Reconstruction engines at increasing depth: the paper-faithful
sequential replay vs the vectorized last-writer-wins (beyond-paper) vs
the Pallas delta_apply kernel (interpret mode on CPU — reported for
completeness, its target is TPU), and the effect of materialized
snapshots with time- vs operation-based selection.

Audited against the segmented-by-default store: ``store.delta()`` and
``snapshot_at`` route through the segmented view unchanged, so these
numbers remain comparable across the segmentation PRs."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.generate import EvolutionParams, build_store
from repro.core.materialize import MaterializationPolicy
from repro.core.reconstruct import reconstruct_dense, reconstruct_sequential


def _timeit(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e3


def run(n_nodes=1024, reps=3, with_kernel=False):
    store = build_store(n_nodes, EvolutionParams(
        m_attach=4, lam_extra=1.0, lam_remove=1.2), seed=2)
    d = store.delta()
    rows = []
    for frac in (0.25, 0.5, 0.75, 1.0):
        t_q = int(store.t_cur * (1 - frac))
        seq = _timeit(lambda: reconstruct_sequential(
            store.current, d, store.t_cur, t_q).adj, reps)
        vec = _timeit(lambda: reconstruct_dense(
            store.current, d, store.t_cur, t_q).adj, reps)
        rows.append((f"recon/sequential@{frac}", seq))
        rows.append((f"recon/vectorized@{frac}", vec))
        rows.append((f"recon/speedup@{frac}", seq / vec))
        if with_kernel:
            from repro.kernels.delta_apply import delta_apply
            interpret = jax.default_backend() == "cpu"
            k = _timeit(lambda: delta_apply(
                store.current, d, store.t_cur, t_q, tile=256,
                cap=1 << 14, interpret=interpret)[0].adj, reps)
            rows.append((f"recon/pallas{'_interpret' * interpret}@{frac}",
                         k))

    # materialization: reconstruct at random times with/without snapshots
    store_m = build_store(n_nodes, EvolutionParams(
        m_attach=4, lam_extra=1.0, lam_remove=1.2), seed=2,
        policy=MaterializationPolicy(kind="opcount", op_budget=2000))
    rng = np.random.default_rng(0)
    ts = [int(x) for x in rng.integers(0, store_m.t_cur, 5)]
    for sel in ("time", "ops"):
        tot = 0.0
        for t in ts:
            tot += _timeit(lambda: store_m.snapshot_at(
                t, use_materialized=True, selection=sel).adj, 1)
        rows.append((f"recon/materialized_{sel}", tot / len(ts)))
    # windowed (temporal-index) reconstruction: anchor selection now
    # shrinks the work the LWW scatter does
    for sel in ("time", "ops"):
        tot = 0.0
        for t in ts:
            tot += _timeit(lambda: store_m.snapshot_at(
                t, use_materialized=True, selection=sel,
                windowed=True).adj, 1)
        rows.append((f"recon/materialized_{sel}_windowed", tot / len(ts)))
    tot = 0.0
    for t in ts:
        tot += _timeit(lambda: store_m.snapshot_at(
            t, use_materialized=False).adj, 1)
    rows.append(("recon/no_materialization", tot / len(ts)))
    return rows


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, ms in run():
        print(f"{name},{ms*1e3:.1f},")


if __name__ == "__main__":
    main()
