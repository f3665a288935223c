"""Pallas kernels vs the XLA reference implementations — NUMERICS ONLY.

These run in interpret mode on the CPU mesh and cannot catch Mosaic
compile-time failures (round-2 postmortem). The on-chip compile gates are:
benchmarks/tpu_kernel_check.py (manual, compile + numerics on the real
chip), the engine's init-time probe compile with XLA fallback
(engine.LLMEngine._probe_pallas_compile), and __graft_entry__.entry() which
builds its step with use_pallas=True on TPU for the driver's compile check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.ops.attention import (
    paged_decode_attention_xla, ragged_prefill_attention_xla)
from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import flash_ragged_prefill
from kubernetes_gpu_cluster_tpu.ops.pallas.paged_decode import pallas_paged_decode


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("nh,nkv,hd,ps", [(4, 2, 32, 8), (8, 8, 64, 16)])
    def test_matches_xla(self, nh, nkv, hd, ps):
        B, P, pps = 4, 9, 3
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
        k_pool = jnp.asarray(rng.standard_normal((P, ps, nkv * hd)), jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((P, ps, nkv * hd)), jnp.float32)
        k_cur = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
        v_cur = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
        page_tables = jnp.asarray(
            rng.permutation(np.arange(1, 1 + B * pps)).reshape(B, pps), jnp.int32)
        # Heterogeneous contexts incl. ctx=1 (empty pool) and a padding row.
        context_lens = jnp.asarray([1, ps + 2, 2 * ps, 0], jnp.int32)

        ref = paged_decode_attention_xla(q, k_pool, v_pool, page_tables,
                                         context_lens, k_cur, v_cur, 0.125)
        got = pallas_paged_decode(q, k_pool, v_pool, page_tables,
                                  context_lens, k_cur, v_cur, 0.125,
                                  interpret=True)
        # Padding row (ctx=0) is garbage in both paths; compare real rows.
        np.testing.assert_allclose(np.asarray(got)[:3], np.asarray(ref)[:3],
                                   rtol=2e-5, atol=2e-5)

    def test_stacked_pool_layer_index(self):
        B, P, ps, nkv, nh, hd, pps, L = 2, 5, 8, 2, 4, 32, 2, 3
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
        pool_k = jnp.asarray(rng.standard_normal((L, P, ps, nkv * hd)), jnp.float32)
        pool_v = jnp.asarray(rng.standard_normal((L, P, ps, nkv * hd)), jnp.float32)
        k_cur = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
        v_cur = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
        pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        cl = jnp.asarray([ps + 1, 5], jnp.int32)
        for layer in range(L):
            ref = paged_decode_attention_xla(q, pool_k[layer], pool_v[layer],
                                             pt, cl, k_cur, v_cur, 0.2)
            got = pallas_paged_decode(q, pool_k, pool_v, pt, cl, k_cur, v_cur,
                                      0.2, layer=layer, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)


def _bf16_case(nh, nkv, hd, seed, L=3, ps=128, q_gain=1.0):
    """A stacked bf16 pool and one batch holding every context kind: an
    empty pool, exactly one page, one token past a page, several chunks,
    and a padding row. q is float32 and holds no bf16 numbers: the output
    is float32, so the comparison sees past a bf16 output's rounding, and a
    kernel that rounded q to the pool's dtype would show."""
    ctx = np.asarray([1, ps + 1, ps + 2, 3 * ps + 5, 0], np.int32)
    B, pps = len(ctx), 5
    tables = np.zeros((B, pps), np.int32)
    page = 1
    for b in range(B):
        for j in range(-(-max(int(ctx[b]) - 1, 0) // ps)):
            tables[b, j] = page
            page += 1
    rng = np.random.default_rng(seed)

    def bf(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    kd = nkv * hd
    return dict(q=jnp.asarray(q_gain * rng.standard_normal((B, nh, hd)),
                              jnp.float32),
                k_pool=bf(L, page, ps, kd), v_pool=bf(L, page, ps, kd),
                tables=jnp.asarray(tables), ctx=jnp.asarray(ctx),
                k_cur=bf(B, nkv, hd), v_cur=bf(B, nkv, hd))


def _f32_reference(c, scale, layer, q=None):
    f32 = jnp.float32
    return np.asarray(paged_decode_attention_xla(
        c["q"] if q is None else q, c["k_pool"][layer].astype(f32),
        c["v_pool"][layer].astype(f32), c["tables"], c["ctx"],
        c["k_cur"].astype(f32), c["v_cur"].astype(f32), scale))


def _pallas(c, scale, layer, **kw):
    return np.asarray(pallas_paged_decode(
        c["q"], c["k_pool"], c["v_pool"], c["tables"], c["ctx"], c["k_cur"],
        c["v_cur"], scale, layer=layer, interpret=True, **kw))


class TestPagedDecodeBf16Pool:
    """The served dtype: a bf16 pool under the float32 arithmetic the kernel
    ships with, against a float32 reference of the same bf16 inputs. The
    tolerance is float32's, as for the float32 pools above: a kernel that
    handed the MXU bf16 probabilities would miss it by two orders (2^-9
    against values up to ~4.5), one that split them into two bf16 terms
    (2^-17) by a hair."""

    @pytest.mark.parametrize("layer", [0, 1, 2])
    @pytest.mark.parametrize("nh,nkv,hd", [
        (8, 2, 128),    # g = 4 (qwen3-4b's shard under tp=4)
        (7, 1, 128),    # g = 7 (qwen2.5-7b's)
        (4, 4, 128),    # g = 1
        (8, 2, 64),     # head_dim 64 (OPT, TinyLlama): half-tile blocks
        (4, 4, 64),
    ])
    def test_matches_float32_reference(self, nh, nkv, hd, layer):
        c = _bf16_case(nh, nkv, hd, seed=10 * nh + layer)
        scale = hd ** -0.5
        ref = _f32_reference(c, scale, layer)
        got = _pallas(c, scale, layer)
        assert got.dtype == np.float32
        # The padding row (ctx = 0) is garbage in both paths.
        np.testing.assert_allclose(got[:4], ref[:4], rtol=2e-5, atol=2e-5)

    def test_score_product_does_not_round_q(self):
        """Scores of magnitude ~25 and a scale that is no power of two: a
        kernel that scaled q and THEN rounded it to the pool's dtype would
        move logits by ~0.05 and the output far past the tolerance."""
        nh, nkv, hd, scale, layer = 8, 2, 128, 0.3, 1
        c = _bf16_case(nh, nkv, hd, seed=5, q_gain=8.0)
        ref = _f32_reference(c, scale, layer)
        rounded_q = ((c["q"] * scale).astype(jnp.bfloat16)
                     .astype(jnp.float32) / scale)
        assert np.abs(_f32_reference(c, scale, layer, q=rounded_q)[:4]
                      - ref[:4]).max() > 50 * 2e-5
        np.testing.assert_allclose(_pallas(c, scale, layer)[:4], ref[:4],
                                   rtol=2e-5, atol=2e-5)

    def test_bf16_query_as_served(self):
        """q in bf16 as the model hands it over: a bf16 output, within its
        own rounding of the reference."""
        c = _bf16_case(8, 2, 128, seed=6)
        c["q"] = c["q"].astype(jnp.bfloat16)
        got = _pallas(c, 0.125, 2)
        assert got.dtype == jnp.bfloat16
        ref = _f32_reference(c, 0.125, 2, q=c["q"].astype(jnp.float32))
        np.testing.assert_allclose(got[:4].astype(np.float32), ref[:4],
                                   rtol=2.0 ** -8, atol=2.0 ** -8)

    def test_output_does_not_depend_on_stream_depth(self):
        from kubernetes_gpu_cluster_tpu.ops.pallas import paged_decode
        c = _bf16_case(8, 2, 128, seed=7)
        assert paged_decode._NUM_BUFS not in (1, 2)
        want = _pallas(c, 0.125, 1)
        for nb in (1, 2, paged_decode._NUM_BUFS):
            np.testing.assert_array_equal(_pallas(c, 0.125, 1, num_bufs=nb),
                                          want)
        with pytest.raises(ValueError, match="VMEM"):
            _pallas(c, 0.125, 1, num_bufs=64)

    def test_kernel_reads_no_setting_from_the_environment(self, monkeypatch):
        """The stream's depth and the chunk's size are the module's own: a
        call looks up none of this package's variables (the parent read the
        depth from one on every call)."""
        import os
        looked_up = []
        getitem = os._Environ.__getitem__

        def recording(env, key):
            looked_up.append(key)
            return getitem(env, key)
        monkeypatch.setattr(os._Environ, "__getitem__", recording)
        os.environ.get("KGCT_PROBE")          # the recorder sees a lookup
        c = _bf16_case(4, 4, 128, seed=8)
        _pallas(c, 0.125, 0)
        assert [k for k in looked_up if k.startswith("KGCT_")] == [
            "KGCT_PROBE"]


class TestFlashPrefillKernel:
    @pytest.mark.parametrize("T,block", [(64, 16), (128, 128)])
    def test_matches_xla(self, T, block):
        nh, nkv, hd = 4, 2, 32
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        # Three segments + trailing padding.
        lens = [T // 4, T // 3, T // 4]
        seg = np.full(T, -1, np.int32)
        pos = np.zeros(T, np.int32)
        i = 0
        for s, n in enumerate(lens):
            seg[i:i+n] = s
            pos[i:i+n] = np.arange(n)
            i += n
        seg_ids = jnp.asarray(seg)
        positions = jnp.asarray(pos)

        ref = ragged_prefill_attention_xla(q, k, v, seg_ids, positions, 0.125)
        got = flash_ragged_prefill(q, k, v, seg_ids, positions, 0.125,
                                   block_q=block, block_k=block, interpret=True)
        real = seg >= 0
        np.testing.assert_allclose(np.asarray(got)[real], np.asarray(ref)[real],
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_head_mapping(self):
        """Each q head must read its own kv head (h // g), not head 0."""
        T, nh, nkv, hd = 32, 4, 4, 32   # distinct kv per q head
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        seg_ids = jnp.zeros(T, jnp.int32)
        positions = jnp.arange(T, dtype=jnp.int32)
        ref = ragged_prefill_attention_xla(q, k, v, seg_ids, positions, 0.2)
        got = flash_ragged_prefill(q, k, v, seg_ids, positions, 0.2,
                                   block_q=16, block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# (heads, kv heads, q/k width, v width): a latent model's materialised form
# (as many kv heads as heads, a narrower v), GQA 4 at 128 and at 64.
PREFILL_GEOMETRIES = {"g1-192-128": (4, 4, 192, 128),
                      "gqa4-128": (8, 2, 128, 128),
                      "gqa4-64": (8, 2, 64, 64)}
# name -> (T, segment lengths, block_q, block_k); what is left of T is tail
# padding. Key tiles of 32 and q blocks of 16 unless said.
PREFILL_BATCHES = {
    "one-segment": (96, [96], 16, 32),
    # a boundary inside key tile [32, 64) and q block [32, 48); one on the
    # edge of q block [80, 96) that is no tile's edge
    "boundaries-in-tile-and-on-q-edge": (96, [40, 40, 16], 16, 32),
    # 100 = 3 tiles and 4 keys = 6 q blocks and 4 rows
    "T-no-multiple-of-the-tile": (100, [60, 40], 16, 32),
    "tail-padding-inside-a-q-block": (96, [70], 16, 32),
    "q-blocks-of-padding-alone": (96, [30, 20], 16, 32),
    # the blocks the wrapper derives itself: 512 keys a tile, a boundary
    # inside tile [512, 1024), the window of the last segment starting there
    "default-blocks": (1100, [600, 300], None, 512),
}


class TestFlashPrefillWalk:
    """``flash_ragged_prefill`` by blocks of kv heads, loops over the key
    tiles that exist and masks only where a diagonal or a segment boundary
    lies: every geometry x every kind of batch against the dense reference."""

    @staticmethod
    def _batch(T, lens, nh, nkv, hd, hv, dtype):
        rng = np.random.default_rng(T + sum(lens) + nh)
        q = jnp.asarray(rng.standard_normal((T, nh, hd)), dtype)
        k = jnp.asarray(rng.standard_normal((T, nkv, hd)), dtype)
        v = jnp.asarray(rng.standard_normal((T, nkv, hv)), dtype)
        pad = T - sum(lens)
        seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens)]
                             + [np.full(pad, -1)]).astype(np.int32)
        pos = np.concatenate([np.arange(n) for n in lens]
                             + [np.zeros(pad)]).astype(np.int32)
        return q, k, v, jnp.asarray(seg), jnp.asarray(pos), seg >= 0

    @pytest.mark.parametrize("batch", list(PREFILL_BATCHES))
    @pytest.mark.parametrize("geometry", list(PREFILL_GEOMETRIES))
    def test_matches_xla(self, geometry, batch):
        nh, nkv, hd, hv = PREFILL_GEOMETRIES[geometry]
        T, lens, bq, bk = PREFILL_BATCHES[batch]
        q, k, v, seg, pos, real = self._batch(T, lens, nh, nkv, hd, hv,
                                              jnp.float32)
        ref = ragged_prefill_attention_xla(q, k, v, seg, pos, 0.125)
        got = np.asarray(flash_ragged_prefill(
            q, k, v, seg, pos, 0.125, block_q=bq, block_k=bk, interpret=True))
        assert got.shape == (T, nh, hv)
        np.testing.assert_allclose(got[real], np.asarray(ref)[real],
                                   rtol=2e-5, atol=2e-5)
        assert not got[~real].any()           # padding rows come out zeros

    @pytest.mark.parametrize("geometry", list(PREFILL_GEOMETRIES))
    def test_bf16_operands_within_their_declared_roundings(self, geometry):
        """bf16 inputs go to the MXU as they are, q times ``scale`` as one
        bf16 value and p as one bf16 term. Against a float32 reference of the
        same values with that q, an element is off by the bf16 output's own
        rounding (half a unit in its last place) and at most 2^-8 of the
        attention-weighted mean of |v| (p's rounding)."""
        nh, nkv, hd, hv = PREFILL_GEOMETRIES[geometry]
        T, lens, bq, bk = PREFILL_BATCHES["boundaries-in-tile-and-on-q-edge"]
        q, k, v, seg, pos, real = self._batch(T, lens, nh, nkv, hd, hv,
                                              jnp.bfloat16)
        f32 = jnp.float32
        q_r = (q.astype(f32) * 0.125).astype(jnp.bfloat16).astype(f32)
        ref, mean_abs_v = (
            np.asarray(ragged_prefill_attention_xla(
                q_r, k.astype(f32), vv, seg, pos, 1.0))[real]
            for vv in (v.astype(f32), jnp.abs(v.astype(f32))))
        got = flash_ragged_prefill(q, k, v, seg, pos, 0.125, block_q=bq,
                                   block_k=bk, interpret=True)
        assert got.dtype == jnp.bfloat16
        d = np.abs(np.asarray(got.astype(f32))[real] - ref)
        assert (d <= 2.0 ** -8 * 1.02 * np.abs(ref) + 2.0 ** -8 * mean_abs_v
                + 2e-5).all()

    def test_windows_walk_what_exists(self):
        """The four tile indices of a q block: from its first row's segment
        start to its last row's diagonal, unmasked where the tile lies under
        the first row inside the block's one segment, empty for padding."""
        from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import (
            _windows)
        seg = np.concatenate([np.full(600, 0), np.full(1100, 1),
                              np.full(348, -1)]).astype(np.int32)
        win = np.asarray(_windows(jnp.asarray(seg), 2048, 256, 512))
        win = win.reshape(8, 4).tolist()
        assert win[0] == [0, 0, 0, 1]     # rows 0-255: the diagonal alone
        assert win[1] == [0, 0, 0, 1]     # rows 256-511: still tile 0's
        assert win[2] == [0, 0, 0, 2]     # the boundary at 600 crosses it
        assert win[3] == [1, 2, 2, 2]     # segment 1 starts inside tile 1
        assert win[4] == [1, 2, 2, 3]     # rows 1024-1279: tile 1 masked,
        assert win[5] == [1, 2, 2, 3]     # no whole tile under the rows yet
        assert win[6] == [1, 1, 1, 4]     # padding from 1700 crosses it
        assert win[7][0] == win[7][3]     # padding alone: nothing walked
        one = np.asarray(_windows(jnp.zeros(2048, jnp.int32), 2048, 256,
                                  512)).reshape(8, 4).tolist()
        assert one[7] == [0, 0, 3, 4] and one[2] == [0, 0, 1, 2]


class TestInt4MatmulKernel:
    """W4A16 dequant-fused matmul kernel (ops/pallas/int4_matmul.py) vs the
    XLA fusion path and the explicit dequant reference — interpret mode
    (the on-chip compile gate is benchmarks/tpu_kernel_check.py)."""

    @pytest.mark.parametrize("K,N,gs", [(512, 256, 128), (256, 128, 64)])
    def test_matches_dequant_reference(self, K, N, gs):
        from kubernetes_gpu_cluster_tpu.ops.pallas.int4_matmul import (
            pallas_int4_matmul)
        from kubernetes_gpu_cluster_tpu.ops.quant import (int4_matmul_xla,
                                                          quantize_tensor_int4,
                                                          unpack_int4)
        T = 5
        rng = np.random.default_rng(7)
        w = rng.standard_normal((K, N)).astype(np.float32)
        x = jnp.asarray(rng.standard_normal((T, K)), jnp.float32)
        packed, scale = quantize_tensor_int4(w, gs)
        deq = (unpack_int4(packed).astype(np.float32)
               .reshape(K // gs, gs, N) * scale[:, None, :]).reshape(K, N)
        ref = np.asarray(x) @ deq
        got = pallas_int4_matmul(x, jnp.asarray(packed), jnp.asarray(scale),
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-4)
        # and the XLA fusion path agrees with the same reference
        xla = int4_matmul_xla(x, jnp.asarray(packed), jnp.asarray(scale))
        np.testing.assert_allclose(np.asarray(xla), ref, rtol=2e-5, atol=2e-4)

    def test_unaligned_dims_fall_back_to_xla(self):
        """Non-128-multiple N must not compute a wrong padded edge: the
        wrapper falls back to the XLA path (documented in the wrapper)."""
        from kubernetes_gpu_cluster_tpu.ops.pallas.int4_matmul import (
            pallas_int4_matmul)
        from kubernetes_gpu_cluster_tpu.ops.quant import (int4_matmul_xla,
                                                          quantize_tensor_int4)
        rng = np.random.default_rng(8)
        K, N, gs = 128, 96, 64                  # N % 128 != 0
        w = rng.standard_normal((K, N)).astype(np.float32)
        x = jnp.asarray(rng.standard_normal((3, K)), jnp.float32)
        packed, scale = quantize_tensor_int4(w, gs)
        got = pallas_int4_matmul(x, jnp.asarray(packed), jnp.asarray(scale))
        ref = int4_matmul_xla(x, jnp.asarray(packed), jnp.asarray(scale))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref))


class TestPallasUnderMesh:
    """The shard_map tp wrappers (ops.attention.*_tp): kernel-under-mesh
    semantics on the 8-device CPU mesh in interpret mode. The on-chip gate
    for this path is the engine's per-shard probe compile
    (LLMEngine._probe_pallas_compile(tp))."""

    def test_paged_decode_tp_matches_oracle(self):
        from kubernetes_gpu_cluster_tpu.ops.attention import (
            paged_decode_attention_tp)
        from kubernetes_gpu_cluster_tpu.parallel import make_mesh

        mesh = make_mesh(tp=2, dp=4)
        B, P, ps, nkv, nh, hd, pps, L = 4, 9, 8, 2, 4, 32, 3, 2
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
        pool_k = jnp.asarray(rng.standard_normal((L, P, ps, nkv * hd)), jnp.float32)
        pool_v = jnp.asarray(rng.standard_normal((L, P, ps, nkv * hd)), jnp.float32)
        k_cur = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
        v_cur = jnp.asarray(rng.standard_normal((B, nkv, hd)), jnp.float32)
        pt = jnp.asarray(rng.permutation(np.arange(1, 1 + B * pps)).reshape(B, pps),
                         jnp.int32)
        cl = jnp.asarray([1, ps + 2, 2 * ps, 3], jnp.int32)
        for layer in range(L):
            ref = paged_decode_attention_xla(q, pool_k[layer], pool_v[layer],
                                             pt, cl, k_cur, v_cur, 0.125)
            got = paged_decode_attention_tp(mesh, q, pool_k, pool_v, pt, cl,
                                            k_cur, v_cur, 0.125, layer=layer,
                                            interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("nh,nkv,hd,hv", [
        (4, 2, 32, 32),      # one kv head a shard, two q heads on it
        (2, 2, 48, 32)])     # one head a shard, a narrower v
    def test_flash_prefill_tp_matches_oracle(self, nh, nkv, hd, hv):
        from kubernetes_gpu_cluster_tpu.ops.attention import (
            ragged_prefill_attention_tp)
        from kubernetes_gpu_cluster_tpu.parallel import make_mesh

        mesh = make_mesh(tp=2)
        T = 64
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((T, nkv, hv)), jnp.float32)
        seg = np.concatenate([np.full(30, 0), np.full(20, 1), np.full(14, -1)])
        pos = np.concatenate([np.arange(30), np.arange(20), np.zeros(14)])
        seg = jnp.asarray(seg, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        ref = ragged_prefill_attention_xla(q, k, v, seg, pos, 0.125)
        got = ragged_prefill_attention_tp(mesh, q, k, v, seg, pos, 0.125,
                                          interpret=True)
        mask = np.asarray(seg) >= 0
        np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(ref)[mask],
                                   rtol=2e-5, atol=2e-5)

    def test_engine_decode_via_attn_mesh(self):
        """A full decode forward with the kernels on under a tp mesh (the
        engine's GSPMD + Pallas path) must match the plain XLA forward. interpret-mode Pallas inside
        the real model forward, under jit, on the tp=2 mesh."""
        import functools

        from kubernetes_gpu_cluster_tpu.config import (CacheConfig,
                                                       get_model_config)
        from kubernetes_gpu_cluster_tpu.engine.kv_cache import allocate_kv_cache
        from kubernetes_gpu_cluster_tpu.models import llama as model_lib
        from kubernetes_gpu_cluster_tpu.parallel import make_mesh
        from kubernetes_gpu_cluster_tpu.parallel.sharding import (
            kv_cache_sharding, param_shardings)
        import kubernetes_gpu_cluster_tpu.ops.attention as attn

        cfg = get_model_config("debug-tiny")
        mesh = make_mesh(tp=2, dp=4)
        params = model_lib.init_params(cfg, jax.random.key(0))
        kv = allocate_kv_cache(cfg, CacheConfig(page_size=8, num_pages=17), 17)

        B, pps = 2, 2
        meta = model_lib.StepMeta(
            positions=jnp.asarray([5, 3], jnp.int32),
            slot_mapping=jnp.asarray([1 * 8 + 5, 3 * 8 + 3], jnp.int32),
            page_tables=jnp.asarray([[1, 2], [3, 4]], jnp.int32),
            context_lens=jnp.asarray([6, 4], jnp.int32))
        tokens = jnp.asarray([7, 11], jnp.int32)

        ref, ref_kv, _ = model_lib.forward(params, cfg, tokens, meta, kv)

        # Route the tp wrappers' kernels (attention and the post-scan KV
        # write) through interpret mode (CPU mesh).
        orig = attn.paged_decode_attention_tp
        orig_write = attn.write_kv_pages_all_tp
        def tp_interp(mesh_, *a, **kw):
            return orig(mesh_, *a, **{**kw, "interpret": True})
        attn.paged_decode_attention_tp = tp_interp
        attn.write_kv_pages_all_tp = functools.partial(orig_write,
                                                       interpret=True)
        try:
            sharded_params = jax.device_put(params, param_shardings(mesh, cfg))
            sharded_kv = jax.tree.map(
                functools.partial(jax.device_put,
                                  device=kv_cache_sharding(mesh, cfg)), kv)
            got, got_kv, _ = jax.jit(
                lambda p, k: model_lib.forward(
                    p, cfg, tokens, meta, k,
                    attn.Kernels(use_pallas=True, tp_mesh=mesh))
            )(sharded_params, sharded_kv)
        finally:
            attn.paged_decode_attention_tp = orig
            attn.write_kv_pages_all_tp = orig_write
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # The sharded matmuls sum in another order, so the rows written are
        # close, not equal; every row not addressed is untouched (zeros).
        for g, r in zip(got_kv[:2], ref_kv[:2]):     # K and V pools
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-5, atol=2e-5)
            flat = np.asarray(g).reshape(g.shape[0], -1, g.shape[-1])
            rest = np.delete(flat, np.asarray(meta.slot_mapping), axis=1)
            assert not rest.any() and flat[:, 13].any()


class TestFlashPrefillHistory:
    """flash_prefill_history vs prefill_history_attention_xla — the chunked
    prefill kernel (history pages streamed via page-table index maps + flat
    causal chunk phase)."""

    def _mk(self, T, hist_len, nh=4, nkv=2, hd=32, ps=8, pps=4, L=2,
            pad=0, seed=0):
        from kubernetes_gpu_cluster_tpu.ops.attention import (
            prefill_history_attention_xla)
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
        seg = jnp.asarray(
            np.where(np.arange(T) < T - pad, 0, -1), jnp.int32)
        pos = jnp.asarray(
            np.where(np.arange(T) < T - pad,
                     hist_len + np.arange(T), 0), jnp.int32)
        pool_k = jnp.asarray(
            rng.standard_normal((L, 1 + pps, ps, nkv * hd)), jnp.float32)
        pool_v = jnp.asarray(
            rng.standard_normal((L, 1 + pps, ps, nkv * hd)), jnp.float32)
        pt = jnp.asarray(1 + np.arange(pps), jnp.int32)
        return (q, k, v, seg, pos, pool_k, pool_v, pt,
                jnp.asarray(hist_len, jnp.int32), hd ** -0.5,
                prefill_history_attention_xla)

    @pytest.mark.parametrize("T,hist_len,pad", [
        (16, 0, 0),     # first chunk: no history at all
        (16, 13, 0),    # partial page history
        (16, 32, 4),    # full pages + tail padding
        (32, 20, 7),    # multi-qblock with blocks smaller than T
    ])
    def test_matches_xla(self, T, hist_len, pad):
        from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
            flash_prefill_history)
        (q, k, v, seg, pos, pk, pv, pt, hl, scale, oracle) = self._mk(
            T, hist_len, pad=pad)
        for layer in range(2):
            ref = oracle(q, k, v, seg, pos, pk, pv, pt, hl, scale,
                         layer=jnp.asarray(layer))
            got = flash_prefill_history(q, k, v, seg, pos, pk, pv, pt, hl,
                                        scale, layer=jnp.asarray(layer),
                                        block_q=8, block_k=8, interpret=True)
            mask = np.asarray(seg) >= 0
            np.testing.assert_allclose(np.asarray(got)[mask],
                                       np.asarray(ref)[mask],
                                       rtol=2e-5, atol=2e-5)

    def test_flat_pool_and_jit(self):
        """3-D (single-layer) pool path, under jit with a traced hist_len."""
        from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
            flash_prefill_history)
        (q, k, v, seg, pos, pk, pv, pt, hl, scale, oracle) = self._mk(
            16, 11, pad=2, seed=3)
        ref = oracle(q, k, v, seg, pos, pk[0], pv[0], pt, hl, scale)
        fn = jax.jit(lambda *a: flash_prefill_history(
            *a, scale, block_q=8, block_k=8, interpret=True))
        got = fn(q, k, v, seg, pos, pk[0], pv[0], pt, hl)
        mask = np.asarray(seg) >= 0
        np.testing.assert_allclose(np.asarray(got)[mask],
                                   np.asarray(ref)[mask],
                                   rtol=2e-5, atol=2e-5)

    # The geometries the kernel's 128-lane blocks split (two kv heads a
    # block at head_dim 64, one at 128, a tp shard's single block), in the
    # pool's dtype. bf16 inputs are held to the XLA reference at
    # ``Precision.HIGHEST`` on float32 copies of the same bf16 values, within
    # the rounding of the kernel's own bf16 output: a relative 2^-8 (bf16
    # keeps 8 significant bits, so rounding to nearest moves a value by at
    # most 2^-8 of itself), with 1 % of room and 1e-5 for the float32 sums
    # under it. Anything coarser inside the kernel (a one-term bf16 p, a
    # selector product that rounds the accumulator) adds its own rounding
    # and fails. float32 inputs keep the float32 tolerance.
    GEOMETRIES = {             # nh, nkv, hd
        "hd64-kd512": (16, 8, 64),      # four lane blocks, two heads each
        "hd128-kd256": (4, 2, 128),     # two lane blocks, one head each
        "tp-shard-kd128": (4, 1, 128),  # one block
    }
    CASES = {                  # T, hist_len, pad (ps 16, a table of 8)
        "fresh": (32, 0, 0),
        "partial-page": (32, 13, 0),
        "pages-and-tail-padding": (48, 89, 5),
        "ragged-blocks": (40, 35, 3),   # T no multiple of block_q, block_k
    }

    def _check(self, geometry, case, dtype, **blocks):
        from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
            flash_prefill_history)
        nh, nkv, hd = self.GEOMETRIES[geometry]
        T, hist_len, pad = self.CASES[case]
        (q, k, v, seg, pos, pk, pv, pt, hl, scale, oracle) = self._mk(
            T, hist_len, nh=nh, nkv=nkv, hd=hd, ps=16, pps=8, pad=pad, seed=5)
        q, k, v, pk, pv = (a.astype(dtype) for a in (q, k, v, pk, pv))
        layer = jnp.asarray(1)
        with jax.default_matmul_precision("highest"):
            ref = oracle(*(a.astype(jnp.float32) for a in (q, k, v)), seg,
                         pos, pk.astype(jnp.float32), pv.astype(jnp.float32),
                         pt, hl, scale, layer=layer)
        got = flash_prefill_history(q, k, v, seg, pos, pk, pv, pt, hl, scale,
                                    layer=layer, interpret=True, **blocks)
        assert got.dtype == dtype and got.shape == q.shape
        tol = (dict(rtol=2.0 ** -8 * 1.01, atol=1e-5)
               if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5))
        mask = np.asarray(seg) >= 0
        got = np.asarray(got.astype(jnp.float32))
        np.testing.assert_allclose(got[mask], np.asarray(ref)[mask], **tol)
        assert not got[~mask].any()       # tail padding comes out as zeros

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    @pytest.mark.parametrize("case", list(CASES))
    @pytest.mark.parametrize("geometry", list(GEOMETRIES))
    def test_lane_blocks_match_xla_highest(self, geometry, case, dtype):
        self._check(geometry, case, dtype, block_q=16, block_k=32)

    @pytest.mark.parametrize("geometry", list(GEOMETRIES))
    def test_default_blocks(self, geometry):
        """The blocks the wrapper derives itself: a history tile of the
        whole table, a chunk tile of the chunk's length."""
        self._check(geometry, "ragged-blocks", jnp.bfloat16)


def test_flash_prefill_partial_final_block():
    """T not a multiple of block_k: the partial final K/V block's padding is
    undefined memory (NaN in interpret mode) and must not poison real rows
    (regression: 0*NaN in the p@v contraction NaN'd the last q block)."""
    T, nh, nkv, hd = 300, 4, 2, 32
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    seg = jnp.asarray(np.where(np.arange(T) < 280, 0, -1), jnp.int32)
    pos = jnp.asarray(np.where(np.arange(T) < 280, np.arange(T), 0), jnp.int32)
    ref = ragged_prefill_attention_xla(q, k, v, seg, pos, 0.125)
    got = flash_ragged_prefill(q, k, v, seg, pos, 0.125, interpret=True)
    mask = np.asarray(seg) >= 0
    assert np.isfinite(np.asarray(got)[mask]).all()
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(ref)[mask],
                               rtol=2e-5, atol=2e-5)


def test_prefill_history_tp_matches_oracle():
    """The hist-kernel tp wrapper (chunked prefill under GSPMD meshes):
    interpret parity on the CPU tp=2 mesh vs the XLA oracle."""
    from kubernetes_gpu_cluster_tpu.ops.attention import (
        prefill_history_attention_tp, prefill_history_attention_xla)
    from kubernetes_gpu_cluster_tpu.parallel import make_mesh

    mesh = make_mesh(tp=2, dp=4)
    T, nh, nkv, hd, ps, pps, L = 16, 4, 2, 32, 8, 4, 2
    hist_len = 13
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    seg = jnp.asarray(np.where(np.arange(T) < T - 3, 0, -1), jnp.int32)
    pos = jnp.asarray(np.where(np.arange(T) < T - 3,
                               hist_len + np.arange(T), 0), jnp.int32)
    pk = jnp.asarray(rng.standard_normal((L, 1 + pps, ps, nkv * hd)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((L, 1 + pps, ps, nkv * hd)), jnp.float32)
    pt = jnp.asarray(1 + np.arange(pps), jnp.int32)
    hl = jnp.asarray(hist_len, jnp.int32)
    for layer in range(L):
        ref = prefill_history_attention_xla(q, k, v, seg, pos, pk, pv, pt,
                                            hl, 0.125, layer=jnp.asarray(layer))
        got = prefill_history_attention_tp(mesh, q, k, v, seg, pos, pk, pv,
                                           pt, hl, 0.125,
                                           layer=jnp.asarray(layer),
                                           interpret=True)
        mask = np.asarray(seg) >= 0
        np.testing.assert_allclose(np.asarray(got)[mask],
                                   np.asarray(ref)[mask],
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# KV page write kernel (ops/pallas/kv_write.py) against the XLA loop
# ---------------------------------------------------------------------------

_KVW_PS = 16


def _run_slots(pages, start, n):
    """Slots of n consecutive tokens of one sequence whose page table is
    ``pages``, the first at offset ``start`` of its first page."""
    return [pages[(start + j) // _KVW_PS] * _KVW_PS + (start + j) % _KVW_PS
            for j in range(n)]


# name -> (slots, VMEM budget override forcing 16-token blocks or None).
_KVW_CASES = {
    # decode-like: one token per page, odd and even offsets
    "decode_T1": ([_KVW_PS * 4 + 3], None),
    "decode_T5": ([_KVW_PS * (p + 1) + o
                   for p, o in enumerate([0, 7, 8, 15, 2])], None),
    "decode_T64": ([_KVW_PS * (1 + (p * 7) % 64) + (p * 5) % _KVW_PS
                    for p in range(64)], None),
    # prefill-like: two sequences packed back to back, the second at token
    # offset 27 (no multiple of a tile), runs crossing tile and page edges
    # over non-adjacent pages, then padding rows on the scrap page
    "prefill_two_seqs": (_run_slots([9, 2, 5], 5, 27)
                         + _run_slots([7, 3], 9, 20) + [0] * 3, None),
    # spec-like: k+1 = 4 adjacent tokens a row, some straddling a tile
    "spec_rows": (sum((_run_slots([p], o, 4) for p, o in
                       [(1, 0), (2, 5), (3, 6), (4, 7), (5, 12)]), [])
                  + [0] * 4, None),
    # padding only: every row hits scrap slot 0
    "all_padding": ([0] * 8, None),
    # several grid steps: a tile shared across a block edge, a padding run
    # across one, a block that is only partly there
    "blocks_prefill_T300": (_run_slots(list(range(40, 20, -1)), 3, 293)
                            + [0] * 7, 1),
    "blocks_decode_T40": ([_KVW_PS * (1 + p) + (p * 3) % _KVW_PS
                           for p in range(29)] + [0] * 11, 1),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(_KVW_CASES))
def test_kv_write_kernel_is_bitwise_the_loop(monkeypatch, case, dtype):
    """Interpret mode against the XLA loop AND a NumPy row assignment, the
    whole pool bitwise: addressed rows hold the new rows, every other row
    keeps its sentinel. (The ref bitcast that gives the kernel its 32-bit
    view of a 16-bit pool exists only on the chip: there
    benchmarks/tpu_kernel_check.py makes the same comparison.)"""
    from kubernetes_gpu_cluster_tpu.ops.attention import (
        write_kv_pages_all_xla)
    from kubernetes_gpu_cluster_tpu.ops.pallas import kv_write as kvw

    slots, budget = _KVW_CASES[case]
    if budget is not None:
        monkeypatch.setattr(kvw, "_VMEM_BUDGET", budget)
    L, P, kd, T = 3, 66, 128, len(slots)
    rng = np.random.default_rng(T)
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    pool_k = jnp.asarray(rng.standard_normal((L, P, _KVW_PS, kd)), dtype)
    pool_v = jnp.asarray(rng.standard_normal((L, P, _KVW_PS, kd)), dtype)
    # New rows arrive in the model's dtype; the write casts to the pool's.
    k_all = jnp.asarray(rng.standard_normal((L, T, kd)), jnp.bfloat16)
    v_all = jnp.asarray(rng.standard_normal((L, T, kd)), jnp.bfloat16)
    slots = jnp.asarray(slots, jnp.int32)

    want = []
    for pool, rows in ((pool_k, k_all), (pool_v, v_all)):
        flat = np.asarray(pool).view(bits).reshape(L, P * _KVW_PS, kd).copy()
        new = np.asarray(rows.astype(dtype)).view(bits)
        for t, s in enumerate(np.asarray(slots)):     # last write wins
            flat[:, s] = new[:, t]
        want.append(flat.reshape(L, P, _KVW_PS, kd))
    loop = write_kv_pages_all_xla(pool_k, pool_v, k_all, v_all, slots)
    got = jax.jit(lambda *a: kvw.kv_write(*a, interpret=True))(
        pool_k, pool_v, k_all, v_all, slots)
    for w, ref, out in zip(want, loop, got):
        np.testing.assert_array_equal(np.asarray(ref).view(bits), w)
        np.testing.assert_array_equal(np.asarray(out).view(bits), w)


def test_kv_write_blocks_follow_from_the_shapes():
    """Tokens per grid step come from T, the pool's depth and width and its
    element size: whole VMEM tiles of the new rows, never more than T needs,
    never under one tile however deep the pool, never over what the core
    has read semaphores for."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.kv_write import _block_tokens
    assert _block_tokens(64, 36, 1024, 2) == 32        # qwen3-4b, bf16
    assert _block_tokens(2048, 36, 1024, 2) == 32
    assert _block_tokens(1, 36, 1024, 2) == 16
    assert _block_tokens(64, 36, 1024, 4) == 16        # float32 pool
    assert _block_tokens(5, 2, 128, 4) == 8
    assert _block_tokens(2048, 2, 128, 2) == 128       # read semaphores
    assert _block_tokens(2048, 400, 8192, 2) == 16


@pytest.mark.parametrize("shape, dtype, match", [
    ((2, 4, 16, 96), jnp.bfloat16, "multiple of 128"),
    ((2, 4, 16, 128), jnp.int8, "16- and 32-bit"),
    ((2, 4, 12, 128), jnp.bfloat16, "page_size 12"),
])
def test_kv_write_refuses_at_trace_time(shape, dtype, match):
    """What Mosaic would refuse with a layout error is said in words."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.kv_write import kv_write
    pool = jnp.zeros(shape, dtype)
    rows = jnp.zeros((shape[0], 4, shape[-1]), jnp.bfloat16)
    with pytest.raises(ValueError, match=match):
        kv_write(pool, pool, rows, rows, jnp.zeros((4,), jnp.int32))


def test_kv_write_tp_matches_loop():
    """The shard_map wrapper (pool and new rows split on the lane dim under
    a GSPMD tp mesh): interpret parity on the CPU tp=2 mesh, bitwise."""
    from kubernetes_gpu_cluster_tpu.ops.attention import (
        write_kv_pages_all_tp, write_kv_pages_all_xla)
    from kubernetes_gpu_cluster_tpu.parallel import make_mesh

    mesh = make_mesh(tp=2, dp=4)
    L, P, kd = 2, 12, 256
    slots = jnp.asarray(_KVW_CASES["prefill_two_seqs"][0], jnp.int32)
    T = slots.shape[0]
    rng = np.random.default_rng(21)
    pool_k = jnp.asarray(rng.standard_normal((L, P, _KVW_PS, kd)), jnp.bfloat16)
    pool_v = jnp.asarray(rng.standard_normal((L, P, _KVW_PS, kd)), jnp.bfloat16)
    k_all = jnp.asarray(rng.standard_normal((L, T, kd)), jnp.bfloat16)
    v_all = jnp.asarray(rng.standard_normal((L, T, kd)), jnp.bfloat16)
    want = write_kv_pages_all_xla(pool_k, pool_v, k_all, v_all, slots)
    got = write_kv_pages_all_tp(mesh, pool_k, pool_v, k_all, v_all, slots,
                                interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g).view(np.uint16),
                                      np.asarray(w).view(np.uint16))
