"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the graph kernels and the
served edge-layout program are compiled here at their real shapes:
what Mosaic or XLA would refuse on the chip (unaligned dynamic slices,
a program that does not fit 16 GB) fails in this file, on the CPU, on
every run.  Nothing executes, so results are checked elsewhere
(tests/test_kernels.py in interpret mode, ``chip_smoke.py`` on the
chip).

The topology is described inside a fixture: only one process at a time
may load the TPU library, and a description made at import would fail
in every other test worker.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library otherwise writes its compiler logs to a fixed
    # directory under /tmp, shared by every checkout on the machine
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


# (kernel, data shape, op-block shape, static args) at the defaults the
# kernels ship with: tile 256/512, cap 1024, 64 buckets; dense at the
# chip smoke's dense size, the others at its edge size (N=2^19, E=2^22).
def _kernel_cases():
    from repro.kernels.degree_series import degree_series_tiles
    from repro.kernels.delta_apply import delta_apply_tiles
    from repro.kernels.edge_delta_apply import edge_delta_apply_tiles
    from repro.kernels.evolve_sweep import sweep_series_tiles
    n_dense, n, e, cap = 4096, 2 ** 19, 2 ** 22, 1024
    return {
        "delta_apply": (delta_apply_tiles, (n_dense, n_dense),
                        (n_dense // 256, n_dense // 256, 4, cap),
                        dict(tile=256, cap=cap)),
        "edge_delta_apply": (edge_delta_apply_tiles, (e,),
                             (e // 512, 4, cap), dict(tile=512, cap=cap)),
        "degree_series": (degree_series_tiles, (n,), (n // 256, 4, cap),
                          dict(tile=256, cap=cap, num_buckets=64)),
        "sweep": (sweep_series_tiles, (n,), (n // 256, 4, cap),
                  dict(tile=256, cap=cap, num_buckets=64)),
    }


@pytest.mark.parametrize("name", ["delta_apply", "edge_delta_apply",
                                  "degree_series", "sweep"])
def test_graph_kernel_compiles_for_v5e(one_chip, name):
    fn, data_shape, ops_shape, static = _kernel_cases()[name]
    compiled = jax.jit(lambda d, o: fn(d, o, **static)).lower(
        _i32(data_shape, one_chip), _i32(ops_shape, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not XLA
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_served_edge_program_fits_one_chip(one_chip):
    """The served edge-layout point program (``_run_group`` dispatches
    it for two-phase groups) at N=2^19 nodes, E=M=2^22 slots/ops and a
    group of 64 fits one chip's HBM."""
    from repro.core.delta import Delta
    from repro.core.engine import batch_edge_two_phase_point
    from repro.core.graph import EdgeGraph
    n, e, m, b = 2 ** 19, 2 ** 22, 2 ** 22, 64

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    g = EdgeGraph(nodes=arr((n,), jnp.bool_), eu=arr((e,)), ev=arr((e,)),
                  emask=arr((e,), jnp.bool_), n_edges_reg=arr(()))
    d = Delta(op=arr((m,)), u=arr((m,)), v=arr((m,)), slot=arr((m,)),
              t=arr((m,)), n_ops=arr(()))
    for measure, scope in (("degree", "node"), ("num_edges", "global"),
                           ("avg_degree", "global")):
        compiled = batch_edge_two_phase_point.lower(
            g, d, arr(()), arr((b,)), arr((b,)), measure=measure,
            scope=scope).compile()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
        assert total < V5E_HBM_BYTES, (measure, total)


def test_dense_point_program_gathers_no_cell(one_chip):
    """The dense point program of the analytics cell (``triangles``, a
    group of 8, n_cap 5,120, delta capacity 8,192) decides each key
    from its scattered key: no gather in the optimized program yields
    n² or more elements (one per adjacency cell), and it fits one
    chip's HBM."""
    import re

    from repro.core.delta import Delta
    from repro.core.engine import batch_two_phase_point
    from repro.core.graph import DenseGraph
    n, m, b = 5120, 8192, 8

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    g = DenseGraph(nodes=arr((n,), jnp.bool_), adj=arr((n, n), jnp.bool_))
    d = Delta(op=arr((m,)), u=arr((m,)), v=arr((m,)), slot=arr((m,)),
              t=arr((m,)), n_ops=arr(()))
    compiled = batch_two_phase_point.lower(
        g, d, arr(()), arr((b,)), arr((b,)), measure="triangles",
        scope="global").compile()
    gathers = re.findall(r"=\s*\w+\[([\d,]*)\]\S*\s+gather\(",
                         compiled.as_text())
    sizes = [int(np.prod([int(x) for x in dims.split(",") if x]))
             for dims in gathers]
    assert all(s < n * n for s in sizes), sizes
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
