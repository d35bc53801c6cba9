"""Delta-checkpoint store: save/restore latency, chain-reconstruction
depth scaling, storage split (snapshots vs deltas) per policy."""
from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import DeltaCheckpointStore, DeltaPolicy
from repro.config import TrainConfig, reduced
from repro.configs import get_config
from repro.runtime import init_train_state


def run():
    rows = []
    cfg = reduced(get_config("smollm-360m"))
    tcfg = TrainConfig(param_dtype="float32")
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(state))
    for kind in ("periodic", "opcount", "similarity"):
        with tempfile.TemporaryDirectory() as d:
            store = DeltaCheckpointStore(
                d, DeltaPolicy(kind=kind, period=5, op_budget=5e6,
                               drift=0.01))
            t0 = time.perf_counter()
            s = state
            for step in range(12):
                s = jax.tree.map(
                    lambda x: x + 0.001 if jnp.issubdtype(
                        x.dtype, jnp.floating) else x, s)
                store.save(step, s)
            save_ms = (time.perf_counter() - t0) / 12 * 1e3
            t0 = time.perf_counter()
            store.restore(0, state)   # deepest chain
            restore_ms = (time.perf_counter() - t0) * 1e3
            b = store.storage_bytes()
            rows.append((f"ckpt/{kind}/save_ms", save_ms,
                         f"state={n_bytes/1e6:.1f}MB"))
            rows.append((f"ckpt/{kind}/restore_depth12_ms", restore_ms,
                         f"snapshots={len(store.manifest['snapshots'])}"))
            rows.append((f"ckpt/{kind}/bytes_snapshots", b["snapshots"],
                         ""))
            rows.append((f"ckpt/{kind}/bytes_deltas", b["deltas"], ""))
    return rows


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, val, note in run():
        print(f"{name},{val},{note}")


if __name__ == "__main__":
    main()
