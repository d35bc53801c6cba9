"""Paper Table 3: synthetic dataset characteristics (targets vs
achieved by our generator).

Audited against the segmented-by-default store: ``store.stats()``
counts ops across sealed segments + open tail, matching the targets
to <0.1% rel err."""
from __future__ import annotations

import time

TARGETS = {"inserted_nodes": 5063, "inserted_edges": 41067,
           "removed_edges": 18280, "total_ops": 64410}


def run(seed=7):
    from repro.core.generate import paper_table3
    t0 = time.perf_counter()
    store = paper_table3(seed=seed)
    dt = time.perf_counter() - t0
    stats = store.stats()
    rows = []
    for k, target in TARGETS.items():
        got = stats[k]
        rows.append((f"table3/{k}", got, target,
                     abs(got - target) / target))
    return rows, dt, store


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows, dt, _ = run()
    for name, got, target, relerr in rows:
        print(f"{name},{got},target={target},rel_err={relerr:.4f}")
    print(f"table3/build_seconds,{dt:.2f},")


if __name__ == "__main__":
    main()
