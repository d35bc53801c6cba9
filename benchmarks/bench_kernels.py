"""Kernel micro-benchmarks.

Pallas kernels execute in interpret mode on CPU (their target is TPU,
where they compile), so the honest comparison here is allclose vs the
oracle plus the XLA path's walltime."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e3


def run():
    rows = []
    from repro.core.generate import EvolutionParams, build_store
    from repro.kernels.delta_apply import delta_apply, delta_apply_ref

    interpret = jax.default_backend() == "cpu"
    store = build_store(512, EvolutionParams(m_attach=4, lam_extra=1.0,
                                             lam_remove=1.2), seed=3)
    d = store.delta()
    tq = store.t_cur // 2
    g_k, ovf = delta_apply(store.current, d, store.t_cur, tq, tile=128,
                           cap=4096, interpret=interpret)
    g_r = delta_apply_ref(store.current, d, store.t_cur, tq)
    ok = bool(jnp.all(g_k.adj == g_r.adj)) and not bool(ovf)
    rows.append(("kernel/delta_apply_allclose", float(ok),
                 f"tile=128 cap=4096 M={int(d.n_ops)}"))
    rows.append(("kernel/delta_apply_ref_xla_ms",
                 _timeit(lambda: delta_apply_ref(
                     store.current, d, store.t_cur, tq).adj), ""))

    from repro.kernels.degree_series import (degree_series_kernel,
                                             degree_series_ref)
    out, ovf = degree_series_kernel(store.current, d, tq, 16, tile=128,
                                    cap=8192, interpret=interpret)
    ref = degree_series_ref(store.current, d, tq, store.t_cur, 16)
    rows.append(("kernel/degree_series_allclose",
                 float(bool(jnp.all(out == ref)) and not bool(ovf)), ""))

    from repro.kernels.flash_attention import attention_ref, flash_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)),
                    dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)),
                    dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)),
                    dtype=jnp.float32)
    out = flash_attention(q, k, v, True, None, None, 128, 128, True)
    ref = attention_ref(q, k, v, causal=True, scale=64 ** -0.5)
    err = float(jnp.max(jnp.abs(out - ref)))
    rows.append(("kernel/flash_attention_max_err", err, "256x256 GQA2"))
    rows.append(("kernel/attention_ref_xla_ms",
                 _timeit(lambda: attention_ref(q, k, v, causal=True,
                                               scale=64 ** -0.5)), ""))
    return rows


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for name, val, note in run():
        print(f"{name},{val},{note}")


if __name__ == "__main__":
    main()
