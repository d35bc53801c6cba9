"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(the graph kernels are passed interpret=True here, since the CPU has no
Mosaic backend; tests/test_tpu_compile.py compiles them for a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.generate import EvolutionParams, build_store
from repro.core.reconstruct import reconstruct_dense


@pytest.fixture(scope="module")
def kstore():
    return build_store(
        90, EvolutionParams(m_attach=3, lam_extra=1.0, lam_remove=1.5,
                            p_remove_node=0.02), seed=5, n_cap=128)


class TestDeltaApply:
    @pytest.mark.parametrize("tile", [32, 64, 128])
    def test_backward_sweep(self, kstore, tile):
        from repro.kernels.delta_apply import delta_apply, delta_apply_ref
        d = kstore.delta()
        for tq in [0, kstore.t_cur // 2, kstore.t_cur]:
            g, ovf = delta_apply(kstore.current, d, kstore.t_cur, tq,
                                 tile=tile, cap=2048, interpret=True)
            ref = delta_apply_ref(kstore.current, d, kstore.t_cur, tq)
            assert not bool(ovf)
            assert bool(jnp.all(g.adj == ref.adj)), (tile, tq)
            assert bool(jnp.all(g.nodes == ref.nodes)), (tile, tq)

    def test_forward(self, kstore):
        from repro.kernels.delta_apply import delta_apply, delta_apply_ref
        d = kstore.delta()
        t_a = 5
        anchor = delta_apply_ref(kstore.current, d, kstore.t_cur, t_a)
        g, ovf = delta_apply(anchor, d, t_a, kstore.t_cur, tile=64,
                             cap=2048, interpret=True)
        assert not bool(ovf)
        assert bool(jnp.all(g.adj == kstore.current.adj))

    def test_matches_core(self, kstore):
        from repro.kernels.delta_apply import delta_apply
        d = kstore.delta()
        tq = kstore.t_cur // 3
        g, _ = delta_apply(kstore.current, d, kstore.t_cur, tq, tile=64,
                           cap=2048, interpret=True)
        rr = reconstruct_dense(kstore.current, d, kstore.t_cur, tq)
        assert bool(jnp.all(g.adj == rr.adj))

    def test_overflow_flag(self, kstore):
        from repro.kernels.delta_apply import delta_apply
        d = kstore.delta()
        _, ovf = delta_apply(kstore.current, d, kstore.t_cur, 0, tile=128,
                             cap=8, interpret=True)
        assert bool(ovf)

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_row_blocks_concatenate_to_full(self, kstore, n_shards):
        """Shard-safe bucketing: reconstructing each row block
        independently (its own tile padding, global columns) and
        concatenating equals the full reconstruction — the contract the
        row-sharded mesh relies on."""
        from repro.kernels.delta_apply.ops import delta_apply_row_block
        d = kstore.delta()
        n = kstore.n_cap
        rb = n // n_shards
        for tq in [0, kstore.t_cur // 2]:
            ref = reconstruct_dense(kstore.current, d, kstore.t_cur, tq)
            nodes, adjs = [], []
            for row0 in range(0, n, rb):
                nb, ab, ovf = delta_apply_row_block(
                    kstore.current.nodes[row0:row0 + rb],
                    kstore.current.adj[row0:row0 + rb], d, kstore.t_cur,
                    tq, row0, tile=32, cap=2048, interpret=True)
                assert not bool(ovf)
                nodes.append(nb)
                adjs.append(ab)
            assert bool(jnp.all(jnp.concatenate(adjs) == ref.adj))
            assert bool(jnp.all(jnp.concatenate(nodes) == ref.nodes))

    def test_row_block_pad_band_excludes_next_shard(self, kstore):
        """A block whose row count is not a tile multiple pads up to
        the tile — ops owned by the NEXT shard must not leak into the
        pad band (they would burn cap slots and raise a spurious
        overflow), and a non-uniform split must still stitch exactly."""
        from repro.core.delta import delta_from_numpy
        from repro.kernels.delta_apply.ops import (delta_apply_row_block,
                                                   bucket_ops)
        # crafted log: 30 edge ops all touching row 50, which belongs
        # to the SECOND shard of a (0..48, 48..128) split; shard 1's
        # pad band covers rows 48..63 and must stay empty
        k = 30
        ops = np.full(k, 2, np.int32)                       # ADD_EDGE
        us = np.full(k, 50, np.int32)
        vs = np.arange(64, 64 + k, dtype=np.int32)
        d50 = delta_from_numpy(ops, us, vs, np.zeros(k, np.int32),
                               np.arange(1, k + 1, dtype=np.int32))
        blocks, ovf = bucket_ops(d50, 128, 0, k, 32, 8, True,
                                 n_rows=64, row0=0, n_valid_rows=48)
        assert not bool(ovf)
        assert int(jnp.sum(blocks[..., 3, :])) == 0   # nothing bucketed
        # and the real-store non-uniform split stitches bit-exactly
        d = kstore.delta()
        tq = kstore.t_cur // 2
        ref = reconstruct_dense(kstore.current, d, kstore.t_cur, tq)
        nodes, adjs = [], []
        for row0, rcount in ((0, 48), (48, 80)):
            nb, ab, ovf = delta_apply_row_block(
                kstore.current.nodes[row0:row0 + rcount],
                kstore.current.adj[row0:row0 + rcount], d, kstore.t_cur,
                tq, row0, tile=32, cap=2048, interpret=True)
            assert not bool(ovf), (row0, rcount)
            nodes.append(nb)
            adjs.append(ab)
        assert bool(jnp.all(jnp.concatenate(adjs) == ref.adj))
        assert bool(jnp.all(jnp.concatenate(nodes) == ref.nodes))


class TestEdgeDeltaApply:
    """Slot-space LWW kernel: oracle parity, direction sweep, the
    reconstruct_edge cross-check, overflow, and slot-block shard
    safety (the contract the slot-sharded mesh relies on)."""

    @pytest.mark.parametrize("tile", [32, 64, 128])
    def test_backward_sweep(self, kstore, tile):
        from repro.kernels.edge_delta_apply import (edge_delta_apply,
                                                    edge_delta_apply_ref)
        d = kstore.delta()
        cur = kstore.current_edge_snapshot()
        for tq in [0, kstore.t_cur // 2, kstore.t_cur]:
            g, ovf = edge_delta_apply(cur, d, kstore.t_cur, tq,
                                      tile=tile, cap=2048, interpret=True)
            ref = edge_delta_apply_ref(cur, d, kstore.t_cur, tq)
            assert not bool(ovf)
            assert bool(jnp.all(g.emask == ref.emask)), (tile, tq)
            assert bool(jnp.all(g.nodes == ref.nodes)), (tile, tq)

    def test_forward(self, kstore):
        from repro.kernels.edge_delta_apply import (edge_delta_apply,
                                                    edge_delta_apply_ref)
        d = kstore.delta()
        cur = kstore.current_edge_snapshot()
        t_a = 5
        anchor = edge_delta_apply_ref(cur, d, kstore.t_cur, t_a)
        g, ovf = edge_delta_apply(anchor, d, t_a, kstore.t_cur, tile=64,
                                  cap=2048, interpret=True)
        assert not bool(ovf)
        assert bool(jnp.all(g.emask == cur.emask))

    def test_matches_core_and_dense(self, kstore):
        """Kernel == reconstruct_edge, and its dense projection ==
        reconstruct_dense — the layout-equivalence triangle."""
        from repro.core.reconstruct import reconstruct_edge
        from repro.kernels.edge_delta_apply import edge_delta_apply
        d = kstore.delta()
        cur = kstore.current_edge_snapshot()
        tq = kstore.t_cur // 3
        g, _ = edge_delta_apply(cur, d, kstore.t_cur, tq, tile=64,
                                cap=2048, interpret=True)
        rr = reconstruct_edge(cur, d, kstore.t_cur, tq)
        assert bool(jnp.all(g.emask == rr.emask))
        dense = reconstruct_dense(kstore.current, d, kstore.t_cur, tq)
        assert bool(jnp.all(g.to_dense().adj == dense.adj))
        assert bool(jnp.all(g.nodes == dense.nodes))

    def test_overflow_flag(self, kstore):
        from repro.kernels.edge_delta_apply import edge_delta_apply
        d = kstore.delta()
        cur = kstore.current_edge_snapshot()
        _, ovf = edge_delta_apply(cur, d, kstore.t_cur, 0, tile=512,
                                  cap=8, interpret=True)
        assert bool(ovf)

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_slot_blocks_concatenate_to_full(self, kstore, n_shards):
        from repro.core.reconstruct import reconstruct_edge
        from repro.kernels.edge_delta_apply import (
            edge_delta_apply_slot_block)
        d = kstore.delta()
        cur = kstore.current_edge_snapshot()
        e = cur.e_cap
        w = e // n_shards
        for tq in [0, kstore.t_cur // 2]:
            ref = reconstruct_edge(cur, d, kstore.t_cur, tq)
            masks = []
            for slot0 in range(0, e, w):
                nb, em, ovf = edge_delta_apply_slot_block(
                    cur.nodes, cur.emask[slot0:slot0 + w], d,
                    kstore.t_cur, tq, slot0, tile=32, cap=2048, interpret=True)
                assert not bool(ovf)
                masks.append(em)
                assert bool(jnp.all(nb == ref.nodes))
            assert bool(jnp.all(jnp.concatenate(masks) == ref.emask)), \
                (n_shards, tq)

    def test_slot_block_pad_band_excludes_next_shard(self, kstore):
        """A block whose slot count is not a tile multiple pads up to
        the tile — ops owned by the NEXT shard must not leak into the
        pad band, and a non-uniform split must still stitch exactly."""
        from repro.core.delta import delta_from_numpy
        from repro.core.reconstruct import reconstruct_edge
        from repro.kernels.edge_delta_apply import (
            bucket_slot_ops, edge_delta_apply_slot_block)
        # crafted log: 30 edge ops all on slot 50, which belongs to the
        # SECOND shard of a (0..48, 48..e) split; shard 1's pad band
        # covers slots 48..63 and must stay empty
        k = 30
        ops = np.full(k, 2, np.int32)                       # ADD_EDGE
        us = np.zeros(k, np.int32)
        vs = np.arange(1, k + 1, dtype=np.int32)
        d50 = delta_from_numpy(ops, us, vs, np.full(k, 50, np.int32),
                               np.arange(1, k + 1, dtype=np.int32))
        blocks, ovf = bucket_slot_ops(d50, 64, 0, k, 32, 8, True,
                                      slot0=0, n_valid_slots=48)
        assert not bool(ovf)
        assert int(jnp.sum(blocks[..., 2, :])) == 0   # nothing bucketed
        # and the real-store non-uniform split stitches bit-exactly
        d = kstore.delta()
        cur = kstore.current_edge_snapshot()
        tq = kstore.t_cur // 2
        ref = reconstruct_edge(cur, d, kstore.t_cur, tq)
        masks = []
        for slot0, scount in ((0, 48), (48, cur.e_cap - 48)):
            _, em, ovf = edge_delta_apply_slot_block(
                cur.nodes, cur.emask[slot0:slot0 + scount], d,
                kstore.t_cur, tq, slot0, tile=32, cap=2048, interpret=True)
            assert not bool(ovf), (slot0, scount)
            masks.append(em)
        assert bool(jnp.all(jnp.concatenate(masks) == ref.emask))


class TestDegreeSeries:
    @pytest.mark.parametrize("tile,buckets", [(32, 8), (64, 16), (128, 5)])
    def test_sweep(self, kstore, tile, buckets):
        from repro.kernels.degree_series import (degree_series_kernel,
                                                 degree_series_ref)
        d = kstore.delta()
        tk = kstore.t_cur // 3
        out, ovf = degree_series_kernel(kstore.current, d, tk, buckets,
                                        tile=tile, cap=4096, interpret=True)
        assert not bool(ovf)
        ref = degree_series_ref(kstore.current, d, tk, kstore.t_cur,
                                buckets)
        assert bool(jnp.all(out == ref)), (tile, buckets)

    def test_node_blocks_concatenate_to_full(self, kstore):
        """Shard-safe event bucketing: per-node-block series stitched
        along the node axis equal the full-kernel series."""
        from repro.kernels.degree_series import degree_series_kernel
        from repro.kernels.degree_series.ops import degree_series_rows
        d = kstore.delta()
        tk = kstore.t_cur // 3
        buckets = 8
        full, ovf = degree_series_kernel(kstore.current, d, tk, buckets,
                                         tile=32, cap=4096, interpret=True)
        assert not bool(ovf)
        deg = kstore.current.degrees()
        n = kstore.n_cap
        parts = []
        for row0 in range(0, n, n // 4):
            s, ovf = degree_series_rows(deg[row0:row0 + n // 4], d, tk,
                                        buckets, row0=row0, tile=32,
                                        cap=4096, interpret=True)
            assert not bool(ovf)
            parts.append(s)
        assert bool(jnp.all(jnp.concatenate(parts, axis=1) == full))


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,hq,hkv,sq,skv,d,causal,window,bq,bk",
        [(2, 4, 2, 64, 64, 32, True, None, 32, 32),
         (1, 4, 1, 64, 64, 16, True, 24, 16, 16),
         (1, 2, 2, 40, 72, 32, False, None, 16, 32),
         (1, 1, 1, 100, 100, 8, True, 16, 32, 32)])
    def test_sweep(self, dtype, b, hq, hkv, sq, skv, d, causal, window,
                   bq, bk):
        from repro.kernels.flash_attention import (attention_ref,
                                                   flash_attention)
        rng = np.random.default_rng(42)
        q = jnp.asarray(rng.standard_normal((b, hq, sq, d)),
                        dtype=dtype)
        k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)),
                        dtype=dtype)
        v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)),
                        dtype=dtype)
        out = flash_attention(q, k, v, causal, window, None, bq, bk, True)
        ref = attention_ref(q, k, v, causal=causal, window=window,
                            scale=d ** -0.5)
        tol = 3e-5 if dtype == jnp.float32 else 3e-2
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < tol

    def test_grad_matches_reference(self):
        from repro.kernels.flash_attention import (attention_ref,
                                                   flash_attention)
        rng = np.random.default_rng(1)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 32, 16)),
                               dtype=jnp.float32) for _ in range(3))

        def l_kernel(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, None, None, 16, 16,
                                True) ** 2)

        def l_ref(q, k, v):
            return jnp.sum(attention_ref(q, k, v, causal=True,
                                         scale=16 ** -0.5) ** 2)

        g1 = jax.grad(l_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(l_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4


class TestSSDScan:
    @pytest.mark.parametrize(
        "b,s,h,p,n,chunk",
        [(2, 64, 3, 8, 16, 16), (1, 100, 2, 16, 8, 32),
         (2, 128, 4, 32, 64, 128), (1, 48, 1, 64, 128, 16)])
    def test_sweep(self, b, s, h, p, n, chunk):
        from repro.kernels.ssd_scan import ssd_ref, ssd_scan
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((b, s, h, p)),
                        dtype=jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)),
                         dtype=jnp.float32)
        a = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), dtype=jnp.float32)
        B = jnp.asarray(rng.standard_normal((b, s, n)),
                        dtype=jnp.float32)
        C = jnp.asarray(rng.standard_normal((b, s, n)),
                        dtype=jnp.float32)
        y = ssd_scan(x, dt, a, B, C, chunk=chunk)
        ref = ssd_ref(x, dt, a, B, C)
        assert float(jnp.max(jnp.abs(y - ref))) < 5e-5

    def test_matches_model_ssd(self):
        """Kernel == the model stack's chunked-XLA SSD."""
        from repro.kernels.ssd_scan import ssd_scan
        from repro.models.ssm import ssd_chunked
        rng = np.random.default_rng(8)
        x = jnp.asarray(rng.standard_normal((2, 64, 3, 8)),
                        dtype=jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (2, 64, 3)),
                         dtype=jnp.float32)
        a = jnp.asarray(-rng.uniform(0.5, 2.0, (3,)), dtype=jnp.float32)
        B = jnp.asarray(rng.standard_normal((2, 64, 16)),
                        dtype=jnp.float32)
        C = jnp.asarray(rng.standard_normal((2, 64, 16)),
                        dtype=jnp.float32)
        y1 = ssd_scan(x, dt, a, B, C, chunk=16)
        y2, _ = ssd_chunked(x, dt, a, B, C, 16)
        assert float(jnp.max(jnp.abs(y1 - y2))) < 5e-5
