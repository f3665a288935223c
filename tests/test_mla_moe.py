"""Latent attention (MLA) + fine-grained experts on the serving path, held to
the plain float32 reference (perfbench/reference/kimi_vl_a3b_lm.py: the ONE
copy, the benchmark's, which also writes the cell's goldens) on
``debug-mla-moe`` with seeded weights.

Tolerances, with their reasons: the served path and the reference are both
float32 here, so they differ only by the ORDER of float32 sums (absorbed
against materialised attention, grouped against per-expert dispatch, XLA's
default CPU matmul against "highest"). Logits are O(1); 2e-4 absolute is
~50x the 4e-6 seen and far under what a wrong row, page, position, expert or
weight moves them by (>= 1e-2)."""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (CacheConfig, EngineConfig,
                                               ParallelConfig,
                                               SchedulerConfig,
                                               apply_hf_overrides,
                                               get_model_config,
                                               cache_kind_refusal)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine import kv_cache as kvc
from kubernetes_gpu_cluster_tpu.models import llama
from perfbench.reference import kimi_vl_a3b_lm as ref
from kubernetes_gpu_cluster_tpu.ops import attention

LOGIT_TOL = 2e-4
CFG = get_model_config("debug-mla-moe")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def _engine(params, **sched):
    kw = dict(max_num_seqs=4, max_prefill_tokens=64, decode_buckets=(1, 2, 4),
              prefill_buckets=(32, 64))
    kw.update(sched)
    return LLMEngine(EngineConfig(
        model=CFG, cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(**kw)), params=params)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(3, CFG.vocab_size, n).tolist()


def test_the_warmed_step_programs_leave_the_latent_pool_as_it_was(params):
    """``warm_full_window`` and ``warm_mixed_steps`` over latent pages and
    routed experts: padding alone, written to the scrap page, so a warmed
    engine serves what an unwarmed one serves, and the switches that leave
    no mixed step to meet (``mixed_batch_enabled`` off) make the second a
    no-op."""
    prompts = [_prompt(n, n) for n in (9, 40, 70, 21, 12)]
    greedy = SamplingParams(max_tokens=12, temperature=0.0)
    want = [o.output_token_ids
            for o in _engine(params).generate(prompts, greedy)]
    eng = _engine(params)
    before = eng.compiled_step_variants()
    eng.warm_full_window()
    eng.warm_mixed_steps()
    assert eng.compiled_step_variants() == before + 2
    alloc = eng.scheduler.allocator
    assert alloc.num_free == alloc.num_pages - 1
    assert [o.output_token_ids for o in eng.generate(prompts, greedy)] == want
    off = _engine(params, mixed_batch_enabled=False)
    before = off.compiled_step_variants()
    off.warm_mixed_steps()
    assert off.compiled_step_variants() == before


def _served_vs_reference(eng, params, prompts, max_tokens=5):
    """Greedy generation with logprobs through the engine; every emitted
    token's log-probability and top-1 against the reference's full forward
    pass over prompt + emitted tokens (logits, not just tokens)."""
    outs = eng.generate(prompts, SamplingParams(
        max_tokens=max_tokens, temperature=0.0, logprobs=1))
    for p, o in zip(prompts, outs):
        ids = list(o.output_token_ids)
        lp = jax.nn.log_softmax(ref.forward(params, CFG, p + ids), axis=-1)
        rows = np.asarray(lp[len(p) - 1:len(p) - 1 + len(ids)])
        assert ids == rows.argmax(-1).tolist()
        np.testing.assert_allclose(
            np.asarray(o.output_logprobs, np.float32),
            rows[np.arange(len(ids)), ids], atol=LOGIT_TOL)
    return outs


# -- (a) served prefill then decode through the paged latent cache ------------

class TestServedAgainstReference:
    def test_prefill_then_decode_logits(self, params):
        cache = CacheConfig(page_size=16, num_pages=16)
        kv = kvc.allocate_kv_cache(CFG, cache, 16)
        assert kv.v is None and kv.k.shape == (4, 16, 16, 128)
        toks = _prompt(40, 1)
        T = 48
        ar = jnp.arange(T)
        meta = llama.StepMeta(
            seg_ids=jnp.where(ar < 40, 0, -1), positions=ar % 40,
            slot_mapping=jnp.where(ar < 40, 16 + ar, 0),
            logits_indices=jnp.array([39]))
        hid, kv, _ = llama.forward(
            params, CFG, jnp.asarray(toks + [0] * 8), meta, kv)
        want = ref.forward(params, CFG, toks)
        np.testing.assert_allclose(llama.compute_logits(params, CFG, hid)[0],
                                   want[-1], atol=LOGIT_TOL)
        nxt = int(jnp.argmax(want[-1]))
        dm = llama.StepMeta(
            positions=jnp.array([40]), slot_mapping=jnp.array([16 + 40]),
            page_tables=jnp.array([[1, 2, 3, 4]]),
            context_lens=jnp.array([41]))
        hid, kv, _ = llama.forward(params, CFG, jnp.array([nxt]), dm, kv)
        np.testing.assert_allclose(
            llama.compute_logits(params, CFG, hid)[0],
            ref.forward(params, CFG, toks + [nxt])[-1], atol=LOGIT_TOL)

    def test_engine_prompt_in_two_chunks_and_a_mixed_step(self, params):
        eng = _engine(params)
        kinds = []
        orig = eng.obs.on_step
        eng.obs.on_step = lambda rec: (kinds.append(rec["kind"]),
                                       orig(rec))[1]
        # 100 tokens > the 64-token budget: taken in two chunks, the second
        # (and the first) beside the 40-token prompt's decode row.
        _served_vs_reference(eng, params, [_prompt(40, 2), _prompt(100, 3)])
        assert "mixed" in kinds and "decode" in kinds
        assert eng.obs.moe_routed_pairs["decode"] > 0
        assert eng.obs.moe_expert_load_max_ratio >= 1.0

    def test_device_and_host_count_the_same_pairs(self, params):
        """Steps wide enough for the grouped path (192 tokens > the dense
        dispatch's 128), padding in both parts: the load the device returns
        sums to what the host has always reckoned from the real tokens, the
        logits stand, and the grouped path's tile fill is reported."""
        eng = _engine(params, prefill_buckets=(192,), max_prefill_tokens=192)
        device, host = [], []
        on_load, on_step = eng.obs.on_expert_load, eng.obs.on_step
        eng.obs.on_expert_load = lambda load, grouped: (
            device.append((sum(int(np.asarray(a).sum()) for a in load),
                           grouped)), on_load(load, grouped))[1]
        eng.obs.on_step = lambda rec: (
            host.append((rec["routed_pairs"], True))
            if rec["kind"] in ("prefill", "mixed") else None,
            on_step(rec))[1]
        # 40 tokens in a 192-token prefill; 250 in two chunks, beside the
        # first prompt's decode row in a 4-row bucket.
        _served_vs_reference(eng, params, [_prompt(40, 8), _prompt(250, 9)])
        assert len(device) >= 3 and device == host
        assert 0 < eng.obs.moe_grouped_tile_fill_share <= 100
        assert ("kgct_moe_grouped_tile_fill_share %.4f"
                % eng.obs.moe_grouped_tile_fill_share
                ) in eng.obs.render_prometheus()

    def test_engine_chunked_without_mixed_batching(self, params):
        eng = _engine(params, mixed_batch_enabled=False)
        _served_vs_reference(eng, params, [_prompt(100, 4)])

    def test_prefix_cache_hit(self, params):
        eng = _engine(params, enable_prefix_caching=True)
        shared = _prompt(48, 5)
        _served_vs_reference(eng, params, [shared + _prompt(9, 6)])
        pc = eng.scheduler.prefix_cache
        hits = pc.hits
        # The second prompt's first three pages come from the cache: its
        # chunk attends them in the pool (absorbed form), a page is a page.
        _served_vs_reference(eng, params, [shared + _prompt(11, 7)])
        assert pc.hits > hits


# -- (b) absorbed equals materialised -----------------------------------------

def test_absorbed_attention_equals_materialised(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    T = 24
    x = jax.random.normal(jax.random.key(3), (T, CFG.hidden_size))
    pos = jnp.arange(T)
    q, row = llama._mla_qkv(lp, CFG, x, pos)
    seg = jnp.zeros((T,), jnp.int32)
    scale = CFG.head_dim ** -0.5
    k, v = llama.mla_materialise(lp, CFG, row)
    want = attention.ragged_prefill_attention_xla(q, k, v, seg, pos, scale)
    assert want.shape == (T, CFG.num_heads, CFG.v_head_dim)
    got = llama.mla_absorbed(
        lp, CFG, q, row,
        lambda qa, rows: attention.ragged_prefill_attention_xla(
            qa, rows, rows, seg, pos, scale))
    # float32 both ways; the absorbed form sums over 64 latent dims where
    # the materialised one sums over 48 head dims: rounding order only.
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- (c) the router, and grouped against dense dispatch ------------------------

class TestRouterAndDispatch:
    def test_sigmoid_bias_for_choice_only_normalise_scale(self):
        cfg = CFG.replace(num_experts=4, num_experts_per_tok=2)
        x = jnp.eye(2, 4)
        logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 3.0, 0.1]])
        # x is two unit rows, so x @ router is these logits
        lp = {"router": jnp.linalg.pinv(x) @ logits,
              "router_bias": jnp.array([0.0, 0.0, 0.0, 5.0])}
        idx, w = llama.moe_route(lp, x, cfg)
        s = jax.nn.sigmoid(logits)
        # the bias makes expert 3 a choice of every token ...
        assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
        assert sorted(np.asarray(idx[1]).tolist()) == [2, 3]
        # ... and never enters the weights: raw scores, normalised, scaled
        for t, chosen in enumerate(([0, 3], [2, 3])):
            raw = np.asarray(s[t, jnp.array(chosen)])
            want = raw / raw.sum() * cfg.routed_scaling_factor
            got = dict(zip(np.asarray(idx[t]).tolist(),
                           np.asarray(w[t]).tolist()))
            np.testing.assert_allclose([got[e] for e in chosen], want,
                                       rtol=1e-6)

    def test_softmax_class_equals_softmax_over_the_top_k_logits(self):
        cfg = get_model_config("debug-moe")
        lp = {"router": jax.random.normal(jax.random.key(1),
                                          (cfg.hidden_size, 4))}
        x = jax.random.normal(jax.random.key(2), (9, cfg.hidden_size))
        idx, w = llama.moe_route(lp, x, cfg)
        vals, want_idx = jax.lax.top_k(x @ lp["router"], 2)
        assert (idx == want_idx).all()
        np.testing.assert_allclose(w, jax.nn.softmax(vals, -1), atol=1e-6)

    @pytest.mark.parametrize("name", ["debug-moe", "debug-mla-moe"])
    def test_grouped_equals_dense_dispatch(self, name):
        cfg = get_model_config(name)
        p = llama.init_params(cfg, jax.random.key(5))
        lp = jax.tree.map(lambda a: a[0], p["layers"])
        x = jax.random.normal(jax.random.key(6), (37, cfg.hidden_size))
        idx, w = llama.moe_route(lp, x, cfg)
        sizes = jnp.bincount(idx.reshape(-1), length=cfg.num_experts)  # oracle
        sizes = sizes.astype(jnp.int32)
        got = llama.experts_grouped(lp, x, idx, w, sizes)
        want = llama.experts_dense(lp, x, idx, w, cfg)
        # the same products summed in another order, float32
        np.testing.assert_allclose(got, want, atol=2e-5)
        # ... and read in place from the whole stack, as the layer scan
        # does: layer 1's groups among the stack's, the others empty
        stack = {n: p["layers"][n] for n in ("w_gate", "w_up", "w_down")}
        lp1 = jax.tree.map(lambda a: a[1], p["layers"])
        np.testing.assert_allclose(
            llama.experts_grouped(stack, x, idx, w, sizes, jnp.int32(1)),
            llama.experts_grouped(lp1, x, idx, w, sizes), atol=2e-5)

    # sizes of the groups, rows handed beyond the pairs, (K, N), and the
    # bytes a weight tile may take (None: the module's)
    GROUPED_MATMUL_CASES = {
        "empty groups between full ones":
            ([0, 0, 100, 0, 0, 150, 0, 50], 0, (128, 256), None),
        "one group holds every row": ([0, 300, 0], 0, (128, 256), None),
        "sizes 1, tile - 1, tile, tile + 1":
            ([1, 127, 128, 129], 0, (128, 256), None),
        "a long tail of padding pairs behind the groups":
            ([40, 0, 7, 130], 600, (128, 256), None),
        "a stack of 4 x 8 groups, one layer's not empty":
            ([0] * 16 + [9, 0, 140, 33, 0, 128, 1, 60] + [0] * 8, 0,
             (128, 256), None),
        "mixtral's class: N in more than one tile":
            ([200, 0, 90, 260], 0, (256, 512), 256 * 128 * 4 * 2),
        "bfloat16 rows and weights":
            ([33, 190, 0, 16], 50, (128, 256), None),
        "real-valued float32": ([0, 100, 0, 150, 50], 40, (128, 256), None),
        "real-valued bfloat16": ([33, 190, 0, 16], 50, (128, 256), None),
    }

    @pytest.mark.parametrize("case", list(GROUPED_MATMUL_CASES))
    def test_grouped_matmul_kernel_equals_ragged_dot(self, case,
                                                     monkeypatch):
        """The kernel (interpret mode) against ``ragged_dot`` over the same
        layout on the rows inside groups: BITWISE on whole numbers, where no
        order of the float32 sums differs, and on normal values to 1e-4
        (float32 operands; bfloat16 ones to their own products' rounding),
        which a kernel that rounded its operands or its sums would miss."""
        from kubernetes_gpu_cluster_tpu.ops.pallas import (
            grouped_matmul as gm)
        # whole K; the widest N that divides and fits (kimi; mixtral)
        assert gm.tiling(2048, 1408, 2) == (128, 2048, 1408)
        assert gm.tiling(4096, 14336, 2) == (128, 4096, 512)
        sizes, tail, (K, N), tile_bytes = self.GROUPED_MATMUL_CASES[case]
        dtype = jnp.bfloat16 if "bfloat16" in case else jnp.float32
        if tile_bytes is not None:
            monkeypatch.setattr(gm, "_RHS_TILE_BYTES", tile_bytes)
            assert gm.tiling(K, N, 4)[2] < N
        sizes = np.asarray(sizes, np.int32)
        m = gm.padded_rows(int(sizes.sum()) + tail, int((sizes > 0).sum()))
        if "real-valued" in case:
            x = jax.random.normal(jax.random.key(0), (m, K), dtype)
            w = jax.random.normal(jax.random.key(1), (len(sizes), K, N),
                                  dtype)
        else:
            x = jax.random.randint(jax.random.key(0), (m, K), -4,
                                   5).astype(dtype)
            w = jax.random.randint(jax.random.key(1), (len(sizes), K, N), -4,
                                   5).astype(dtype)
        got = np.asarray(gm.grouped_matmul(x, w, jnp.asarray(sizes),
                                           interpret=True))
        want = np.asarray(jax.lax.ragged_dot(
            x, w, jnp.asarray(gm.aligned_sizes(sizes)),
            preferred_element_type=jnp.float32))
        assert got.shape == (m, N) and got.dtype == np.float32
        inside = np.zeros(m, bool)
        for start, size in zip(gm.group_starts(sizes), sizes):
            assert start % gm.ROW_ALIGN == 0
            inside[start:start + size] = True
        assert inside.sum() == sizes.sum() and np.abs(want[inside]).max() > 0
        if "real-valued" in case:   # f32 accumulation, the sums' order
            np.testing.assert_allclose(got[inside], want[inside], atol=1e-4)
        else:
            np.testing.assert_array_equal(got[inside], want[inside])

    @pytest.mark.parametrize("sizes", [
        [0, 0, 100, 0, 0, 150, 0, 50], [1, 127, 128, 129], [0, 0, 0],
        [198] * 64, [144, 80, 266, 0, 129] * 12])
    def test_host_visit_count_is_the_kernels_grid(self, sizes):
        """The gauge's rule (NumPy, on the host) and the kernel's grid (JAX,
        on the device) are one function of the sizes: the same count, and
        every visit a tile of its own group's rows."""
        from kubernetes_gpu_cluster_tpu.ops.pallas import (
            grouped_matmul as gm)
        sizes = np.asarray(sizes, np.int32)
        m = gm.padded_rows(int(sizes.sum()), len(sizes))
        group, row, _, count = (np.asarray(a) for a in gm.visit_list(
            jnp.asarray(sizes), m))
        assert count.tolist() == [gm.group_visits(sizes).sum()]
        want = [(g, start + t * gm.TILE_ROWS)
                for g, (start, size) in enumerate(
                    zip(gm.group_starts(sizes), sizes))
                for t in range(-(-size // gm.TILE_ROWS))]
        assert list(zip(group[:count[0]], row[:count[0]])) == want
        assert len(group) >= count[0] and (row + gm.TILE_ROWS <= m).all()
        fill = gm.tile_fill_share(sizes)
        assert fill == (sizes.sum() / (len(want) * gm.TILE_ROWS)
                        if want else 0.0)

    def test_grouped_dispatch_is_one_test_and_refuses_int8_experts(self):
        """``grouped_dispatch`` is the engine's word and the step's size,
        nothing else; experts the kernel cannot take are refused, not
        quietly sent another way than the host's gauge would think."""
        cfg = get_model_config("debug-moe")
        on = attention.Kernels(grouped_experts=True)
        assert [llama.grouped_dispatch(T, cfg, on) for T in (4, 64, 192)] \
            == [not llama.dense_dispatch_pays(T, cfg) for T in (4, 64, 192)]
        assert not llama.grouped_dispatch(192, cfg, attention.Kernels())
        lp = jax.tree.map(lambda a: a[0],
                          llama.init_params(cfg, jax.random.key(5))["layers"])
        lp = {**lp, "w_gate": lp["w_gate"].astype(jnp.int8)}
        x = jnp.zeros((192, cfg.hidden_size))
        with pytest.raises(ValueError, match="quantized"):
            llama._moe_mlp(lp, x, cfg, kernels=on)

    @pytest.mark.parametrize("name", ["debug-moe", "debug-mla-moe"])
    def test_padding_is_not_routed_and_rows_outside_groups_are_not_read(
            self, name, monkeypatch):
        """A step with padding tokens: their pairs are in no group, the real
        tokens' results are bit for bit what they are when the padding is
        routed too (the parent's), and nothing the matmuls leave in the rows
        outside the groups reaches the result."""
        from kubernetes_gpu_cluster_tpu.ops.pallas import (
            grouped_matmul as gm)
        cfg = get_model_config(name)
        lp = jax.tree.map(lambda a: a[0],
                          llama.init_params(cfg, jax.random.key(5))["layers"])
        T, k, E = 150, cfg.num_experts_per_tok, cfg.num_experts
        x = jax.random.normal(jax.random.key(6), (T, cfg.hidden_size))
        valid = jnp.arange(T) % 5 != 3            # padding in between
        load = []
        llama._moe_mlp(lp, x, cfg, load_out=load, valid=valid,
                       kernels=attention.Kernels(grouped_experts=True))
        assert int(load[0].sum()) == k * int(valid.sum())
        idx, w = llama.moe_route(lp, x, cfg)
        all_routed = jnp.sum(jax.nn.one_hot(idx.reshape(-1), E,
                                            dtype=jnp.int32), axis=0)
        parents = llama.experts_grouped(lp, x, idx, w, all_routed)
        mine = llama.experts_grouped(lp, x, idx, w, load[0], valid=valid)
        np.testing.assert_array_equal(mine[valid], parents[valid])
        assert not np.asarray(mine[~valid]).any()
        assert np.abs(np.asarray(parents[~valid])).min() > 0

        def poisoned(lhs, rhs, sizes):      # the kernel's contract, harshly
            out = jax.lax.ragged_dot(lhs, rhs, gm.aligned_sizes(sizes),
                                     preferred_element_type=jnp.float32)
            rows = jnp.arange(lhs.shape[0])[:, None]
            starts = gm.group_starts(sizes)
            inside = ((rows >= starts) & (rows < starts + sizes)).any(axis=1)
            return jnp.where(inside[:, None], out, jnp.nan)
        monkeypatch.setattr(gm, "grouped_matmul", poisoned)
        np.testing.assert_array_equal(
            llama.experts_grouped(lp, x, idx, w, load[0], use_pallas=True,
                                  valid=valid), mine)

    @pytest.mark.parametrize("name,T,dense", [
        # kimi-vl-a3b, 6 of 64: every expert is hit from ~43 tokens on
        ("kimi-vl-a3b", 8, False), ("kimi-vl-a3b", 16, False),
        ("kimi-vl-a3b", 32, False), ("kimi-vl-a3b", 64, True),
        ("kimi-vl-a3b", 128, True), ("kimi-vl-a3b", 256, False),
        ("kimi-vl-a3b", 2112, False),
        # Mixtral, 2 of 8: from 16 tokens on
        ("mixtral-8x7b", 8, False), ("mixtral-8x7b", 16, True),
        ("mixtral-8x7b", 64, True), ("mixtral-8x7b", 512, False)])
    def test_dense_dispatch_only_where_it_wastes_nothing(self, name, T,
                                                         dense):
        """The choice the chip's in-program readings back (models/llama.py's
        comment): dense where every expert is hit anyway AND the step is
        under the FLOP-to-byte balance; grouped for a few rows (it skips the
        experts nobody was sent to) and for prefill-sized steps."""
        assert llama.dense_dispatch_pays(T, get_model_config(name)) is dense

    def test_every_token_to_one_expert_drops_nothing(self, params):
        lp = dict(jax.tree.map(lambda a: a[0], params["layers"]))
        k = CFG.num_experts_per_tok
        # a bias that forces the same k experts on every token
        lp["router_bias"] = jnp.where(jnp.arange(CFG.num_experts) < k, 9.0,
                                      0.0)
        T = llama.DENSE_DISPATCH_MAX_TOKENS + 44     # the grouped path
        x = jax.random.normal(jax.random.key(7), (T, CFG.hidden_size))
        load = []
        got = llama._moe_mlp(lp, x, CFG, load_out=load,
                             kernels=attention.Kernels(grouped_experts=True))
        assert np.asarray(load[0]).tolist() == [T] * k + [0] * (
            CFG.num_experts - k)
        idx, w = llama.moe_route(lp, x, CFG)
        want = llama.experts_dense(lp, x, idx, w, CFG) + llama._dense_mlp(
            {"w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
             "w_down": lp["ws_down"]}, x, CFG)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert np.abs(np.asarray(got)).min(axis=1).max() > 0  # no zero row


# -- (d) page bytes, pool size, the latent page write --------------------------

class TestLatentPages:
    def test_bytes_per_token_and_page(self):
        cache = CacheConfig(page_size=128)
        kimi = apply_hf_overrides(get_model_config("kimi-vl-a3b"),
                                  {"num_hidden_layers": 9})
        assert kimi.kv_row_dim == 576 and kimi.kv_row_padded == 640
        assert kimi.num_dense_layers == 1 and kimi.kv_pools == 1
        # stored: 640 lanes x 2 B x 9 layers; 576 of them are the row
        assert kvc.kv_cache_bytes_per_token(kimi, cache) == 640 * 2 * 9
        assert kvc.kv_row_padding_share(kimi) == pytest.approx(64 / 640)
        assert kvc.kv_cache_bytes_per_page(kimi, cache) == 128 * 11520
        qwen = get_model_config("qwen3-4b")
        assert kvc.kv_cache_bytes_per_token(qwen, cache) == 147456
        assert kvc.kv_row_padding_share(qwen) == 0.0

    def test_derive_num_pages_counts_one_pool(self):
        cache = CacheConfig(page_size=128)
        kimi = apply_hf_overrides(get_model_config("kimi-vl-a3b"),
                                  {"num_hidden_layers": 9})
        free = 4 * 10**9
        n = kvc.derive_num_pages(kimi, cache, 4096, 64, free)
        assert n == int(free * 0.9) // (128 * 11520)
        assert n * 128 > 300_000    # ~350-400 k tokens in ~4 GB (ISSUE 26)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_latent_kv_write_kernel_is_the_row_loop(self, dtype):
        from kubernetes_gpu_cluster_tpu.ops.pallas.kv_write import kv_write
        L, P, ps, R = 3, 6, 16, 128
        pool = jax.random.normal(jax.random.key(1), (L, P, ps, R)).astype(
            dtype)
        rows = jax.random.normal(jax.random.key(2), (L, 11, R)).astype(dtype)
        slots = jnp.array([16, 17, 18, 40, 41, 0, 0, 0, 90, 91, 95],
                          jnp.int32)
        want, none = attention.write_kv_pages_all_xla(pool, None, rows, None,
                                                      slots)
        got, none2 = kv_write(pool, None, rows, None, slots, interpret=True)
        assert none is None and none2 is None
        assert bool((want == got).all())        # bitwise
        assert bool((got[:, 1, 0] == rows[:, 0]).all())

    def test_shared_row_kernels_equal_their_xla_twins(self):
        from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
            flash_prefill_history_shared)
        from kubernetes_gpu_cluster_tpu.ops.pallas.latent_decode import (
            latent_paged_decode)
        ks = jax.random.split(jax.random.key(1), 5)
        B, nh, R, ps, P, L = 3, 4, 128, 16, 12, 2
        pool = jax.random.normal(ks[0], (L, P, ps, R))
        q = jax.random.normal(ks[1], (B, nh, R))
        cur = jax.random.normal(ks[2], (B, 1, R))
        pt = jnp.array([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
        ctx = jnp.array([40, 17, 1], jnp.int32)
        lyr = jnp.int32(1)
        np.testing.assert_allclose(
            latent_paged_decode(q, pool, pt, ctx, cur, 0.1, layer=lyr,
                                interpret=True),
            attention.paged_decode_attention_xla(q, pool, None, pt, ctx, cur,
                                                 None, 0.1, layer=lyr),
            atol=2e-6)
        T = 40
        qh = jax.random.normal(ks[3], (T, nh, R))
        rows = jax.random.normal(ks[4], (T, 1, R))
        seg = jnp.where(jnp.arange(T) < 35, 0, -1)
        for hist in (0, 20, 48):
            pos = hist + jnp.arange(T)
            want = attention.prefill_history_attention_xla(
                qh, rows, None, seg, pos, pool, None, pt[0], jnp.int32(hist),
                0.1, layer=lyr)
            got = flash_prefill_history_shared(
                qh, rows, seg, pos, pool, pt[0], jnp.int32(hist), 0.1,
                layer=lyr, interpret=True, block_q=16, block_k=16)
            np.testing.assert_allclose(got[:35], want[:35], atol=4e-6)

    def test_materialised_prefill_kernel_takes_a_narrower_v(self):
        from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import (
            flash_ragged_prefill)
        ks = jax.random.split(jax.random.key(2), 3)
        T, nh = 40, 4
        q = jax.random.normal(ks[0], (T, nh, 48))
        k = jax.random.normal(ks[1], (T, nh, 48))
        v = jax.random.normal(ks[2], (T, nh, 32))
        ar = jnp.arange(T)
        seg = jnp.where(ar < 20, 0, jnp.where(ar < 35, 1, -1))
        want = attention.ragged_prefill_attention_xla(q, k, v, seg, ar, 0.2)
        got = flash_ragged_prefill(q, k, v, seg, ar, 0.2, interpret=True,
                                   block_q=16, block_k=16)
        assert got.shape == (T, nh, 32)
        np.testing.assert_allclose(got[:35], want[:35], atol=2e-6)


# -- (e) what is not carried refuses at start, by name -------------------------

def _cfg(**kw):
    base = dict(model=CFG, cache=CacheConfig(page_size=16, num_pages=16))
    base.update(kw)
    return EngineConfig(**base)


@pytest.mark.parametrize("flag, config, extra", [
    ("--tensor-parallel-size", _cfg(parallel=ParallelConfig(tp=2)), {}),
    ("--pipeline-parallel-size", _cfg(parallel=ParallelConfig(pp=2)), {}),
    ("--sequence-parallel-size", _cfg(parallel=ParallelConfig(sp=2)), {}),
    ("--expert-parallel-size", _cfg(parallel=ParallelConfig(ep=2)), {}),
    ("--enable-spec-decode",
     _cfg(scheduler=SchedulerConfig(spec_decode_enabled=True)), {}),
    ("--swap-space-gb",
     _cfg(cache=CacheConfig(page_size=16, num_pages=16, swap_space_gb=1.0)),
     {}),
    ("--quantization int8", _cfg(model=CFG.replace(quantization="int8")),
     {}),
    ("--quantization int4", _cfg(model=CFG.replace(quantization="int4")),
     {}),
    ("--role prefill", _cfg(), {"role": "prefill"}),
    ("--role decode", _cfg(), {"role": "decode"}),
    ("--fleet-prefix-cache", _cfg(), {"fleet_prefix_cache": True}),
    ("--peer-pool", _cfg(), {"peer_pool": "http://peer:8000"}),
])
def test_refused_flag_is_named_with_its_mechanism(flag, config, extra):
    msg = cache_kind_refusal(config, **extra)
    assert msg is not None and msg.startswith(flag) and CFG.name in msg
    assert "\n" not in msg and len(msg.split(": ", 1)[1]) > 20
    if not extra:   # the engine refuses too, before it builds anything
        with pytest.raises(ValueError, match=flag.split()[0]):
            LLMEngine(config)


def test_dense_models_are_refused_nothing():
    tiny = get_model_config("debug-tiny")
    assert cache_kind_refusal(EngineConfig(
        model=tiny, parallel=ParallelConfig(tp=2),
        scheduler=SchedulerConfig(spec_decode_enabled=True)),
        role="prefill", fleet_prefix_cache=True) is None


def test_cli_exits_non_zero_with_one_line_naming_the_flag(capsys):
    from kubernetes_gpu_cluster_tpu.serving import api_server
    with pytest.raises(SystemExit) as e:
        api_server.main(["--model", "debug-mla-moe", "--port", "0",
                         "--swap-space-gb", "1"])
    assert e.value.code != 0
    assert "--swap-space-gb with debug-mla-moe" in capsys.readouterr().err


def test_kv_wire_paths_refuse_a_latent_model(params):
    eng = _engine(params)
    for call in (lambda: eng.export_held("r"), lambda: eng.export_running("r"),
                 lambda: eng.export_prefix([1, 2, 3]),
                 lambda: eng.enable_fleet_spill(lambda *a: True)):
        with pytest.raises(ValueError, match="latent pages"):
            call()


def test_hf_overrides_are_shape_keys_only():
    cut = apply_hf_overrides(get_model_config("kimi-vl-a3b"),
                             {"num_hidden_layers": 9})
    assert cut.num_layers == 9 and cut.num_dense_layers == 1
    assert cut.hidden_size == 2048 and cut.num_experts == 64
    for bad in ({"rope_theta": 1}, {"num_hidden_layers": 1.5},
                {"num_hidden_layers": True}, {"num_hidden_layers": 1}):
        with pytest.raises(ValueError, match="--hf-overrides"):
            apply_hf_overrides(get_model_config("kimi-vl-a3b"), bad)


# -- (f) a synthetic kimi_vl checkpoint round-trips ----------------------------

def _write_kimi_vl_checkpoint(path, cfg, rng):
    """An HF-layout kimi_vl directory: nested text_config, the decoder under
    ``language_model.``, tower tensors beside it. Returns the raw tensors."""
    from safetensors.numpy import save_file
    d, nh = cfg.hidden_size, cfg.num_heads
    r, nope, rope, vd = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    t = {}

    def w(name, *shape):
        t[name] = (rng.standard_normal(shape) * 0.05).astype(np.float32)
    root = "language_model."
    w(root + "model.embed_tokens.weight", cfg.vocab_size, d)
    w(root + "model.norm.weight", d)
    w(root + "lm_head.weight", cfg.vocab_size, d)
    w("vision_tower.patch_embed.proj.weight", 8, 3, 2, 2)
    w("multi_modal_projector.linear_1.weight", 8, 8)
    for l in range(cfg.num_layers):
        p = f"{root}model.layers.{l}."
        w(p + "input_layernorm.weight", d)
        w(p + "post_attention_layernorm.weight", d)
        w(p + "self_attn.q_proj.weight", nh * (nope + rope), d)
        w(p + "self_attn.kv_a_proj_with_mqa.weight", r + rope, d)
        w(p + "self_attn.kv_a_layernorm.weight", r)
        w(p + "self_attn.kv_b_proj.weight", nh * (nope + vd), r)
        w(p + "self_attn.o_proj.weight", d, nh * vd)
        if l < cfg.first_k_dense_replace:
            ffs = [("", cfg.intermediate_size)]
        else:
            w(p + "mlp.gate.weight", cfg.num_experts, d)
            w(p + "mlp.gate.e_score_correction_bias", cfg.num_experts)
            ffs = [(f"experts.{e}.", cfg.moe_intermediate_size)
                   for e in range(cfg.num_experts)]
            ffs.append(("shared_experts.",
                        cfg.num_shared_experts * cfg.moe_intermediate_size))
        for sub, ff in ffs:
            w(f"{p}mlp.{sub}gate_proj.weight", ff, d)
            w(f"{p}mlp.{sub}up_proj.weight", ff, d)
            w(f"{p}mlp.{sub}down_proj.weight", d, ff)
    save_file(t, str(path / "model.safetensors"))
    text = {
        "model_type": "deepseek_v3", "vocab_size": cfg.vocab_size,
        "hidden_size": d, "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers, "num_attention_heads": nh,
        "num_key_value_heads": nh, "n_shared_experts": 2,
        "n_routed_experts": cfg.num_experts, "routed_scaling_factor": 2.446,
        "kv_lora_rank": r, "q_lora_rank": None, "qk_rope_head_dim": rope,
        "v_head_dim": vd, "qk_nope_head_dim": nope,
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "num_experts_per_tok": cfg.num_experts_per_tok, "moe_layer_freq": 1,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "hidden_act": "silu",
        "rms_norm_eps": 1e-5, "rope_theta": 800000, "rope_scaling": None,
        "attention_bias": False, "tie_word_embeddings": False,
        "max_position_embeddings": 512}
    (path / "config.json").write_text(json.dumps({
        "model_type": "kimi_vl", "architectures":
        ["KimiVLForConditionalGeneration"], "text_config": text,
        "vision_config": {"model_type": "moonvit"}}))
    return t


def _interleaved_rope(x, positions, theta):
    """RoPE as the published code defines it: pairs (x[2i], x[2i+1])."""
    half = x.shape[-1] // 2
    inv = theta ** -(np.arange(half, dtype=np.float32) / half)
    ang = positions[:, None].astype(np.float32) * inv
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return a * cos - b * sin, b * cos + a * sin


def test_synthetic_kimi_vl_checkpoint_round_trips(tmp_path):
    from kubernetes_gpu_cluster_tpu.engine import weights
    rng = np.random.default_rng(0)
    raw = _write_kimi_vl_checkpoint(tmp_path, CFG, rng)
    cfg = weights.config_from_hf(str(tmp_path), "synthetic-kimi").replace(
        dtype="float32")
    for f in ("num_layers", "num_experts", "num_experts_per_tok",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "head_dim", "moe_intermediate_size",
              "num_shared_experts", "first_k_dense_replace", "scoring_func",
              "routed_scaling_factor", "norm_topk_prob", "rope_theta"):
        assert getattr(cfg, f) == getattr(CFG, f), f
    import logging
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    weights.logger.addHandler(handler)
    try:
        p = weights.load_weights(str(tmp_path), cfg)
    finally:
        weights.logger.removeHandler(handler)
    want = llama.init_params(CFG, jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda a: a.shape, want)
    assert p["layers"]["router_bias"].dtype == jnp.float32

    # kv_b_proj split per head; tensors land where the forward reads them
    pre = "language_model.model.layers.2."
    kv_b = raw[pre + "self_attn.kv_b_proj.weight"].reshape(
        CFG.num_heads, CFG.qk_nope_head_dim + CFG.v_head_dim, -1)
    np.testing.assert_array_equal(
        p["layers"]["w_uk"][1, 3], kv_b[3, :CFG.qk_nope_head_dim].T)
    np.testing.assert_array_equal(
        p["layers"]["w_uv"][1, 3], kv_b[3, CFG.qk_nope_head_dim:].T)
    np.testing.assert_array_equal(
        p["layers"]["w_gate"][1, 5],
        raw[pre + "mlp.experts.5.gate_proj.weight"].T)
    np.testing.assert_array_equal(
        p["dense_layers"]["w_down"][0],
        raw["language_model.model.layers.0.mlp.down_proj.weight"].T)

    # The loader's de-interleave + the decoder's half-split RoPE score as
    # the published interleaved RoPE on the checkpoint's own columns does.
    x = rng.standard_normal((6, CFG.hidden_size)).astype(np.float32)
    pos = np.arange(6)
    lp = jax.tree.map(lambda a: a[1], p["layers"])
    q, row = llama._mla_qkv(lp, cfg, jnp.asarray(x), jnp.asarray(pos))
    nope, rope, r = CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.kv_lora_rank
    q_raw = (x @ raw[pre + "self_attn.q_proj.weight"].T).reshape(
        6, CFG.num_heads, nope + rope)[..., nope:]
    k_raw = (x @ raw[pre + "self_attn.kv_a_proj_with_mqa.weight"].T)[
        :, None, r:]
    qa, qb = _interleaved_rope(q_raw, pos, CFG.rope_theta)
    ka, kb = _interleaved_rope(k_raw, pos, CFG.rope_theta)
    want_scores = np.einsum("thd,sd->hts", qa, ka[:, 0]) + np.einsum(
        "thd,sd->hts", qb, kb[:, 0])
    got_scores = np.einsum("thd,sd->hts", np.asarray(q[..., nope:]),
                           np.asarray(row[:, r:r + rope]))
    np.testing.assert_allclose(got_scores, want_scores, atol=1e-5)

    skipped = [line for line in lines if "skipped" in line]
    assert len(skipped) == 1 and "skipped 2 tensors" in skipped[0] \
        and "text only" in skipped[0]
    # and the loaded tree runs: served logits equal the reference's
    toks = _prompt(12, 9)
    lg = ref.forward(p, cfg, toks)
    assert lg.shape == (12, CFG.vocab_size) and bool(jnp.isfinite(lg).all())


# -- (g) image content in a chat request is refused -----------------------------

@pytest.fixture(scope="module")
def api_client(params):
    from aiohttp.test_utils import TestClient, TestServer

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
    loop = asyncio.new_event_loop()
    server = build_server(EngineConfig(
        model=CFG, cache=CacheConfig(page_size=16, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=2, max_prefill_tokens=64,
                                  decode_buckets=(1, 2),
                                  prefill_buckets=(32, 64))),
        None, "debug-mla-moe", params=params)
    client = TestClient(TestServer(server.build_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()


class TestHTTP:
    def test_image_part_gets_a_400_that_says_so(self, api_client):
        client, loop = api_client

        async def go():
            body = {"model": "debug-mla-moe", "max_tokens": 2, "messages": [
                {"role": "user", "content": [
                    {"type": "text", "text": "what is this?"},
                    {"type": "image_url",
                     "image_url": {"url": "data:image/png;base64,AAAA"}}]}]}
            r = await client.post("/v1/chat/completions", json=body)
            return r.status, await r.json()
        status, out = loop.run_until_complete(go())
        assert status == 400
        msg = out["error"]["message"]
        assert "image_url" in msg and "text only" in msg \
            and "debug-mla-moe" in msg

    def test_text_parts_and_completions_are_served(self, api_client):
        client, loop = api_client

        async def go():
            chat = await client.post("/v1/chat/completions", json={
                "model": "debug-mla-moe", "max_tokens": 3, "temperature": 0,
                "messages": [{"role": "user", "content": [
                    {"type": "text", "text": "hello "},
                    {"type": "text", "text": "there"}]}]})
            comp = await client.post("/v1/completions", json={
                "model": "debug-mla-moe", "max_tokens": 3, "temperature": 0,
                "prompt": [5, 6, 7, 8]})
            health = await client.get("/health")
            metrics = await client.get("/metrics")
            return (chat.status, comp.status, await comp.json(),
                    await health.json(), await metrics.text())
        chat, comp, out, health, metrics = loop.run_until_complete(go())
        assert chat == 200 and comp == 200
        assert out["usage"]["completion_tokens"] == 3
        assert health["kv_layout"] == "latent"
        assert health["kv_bytes_per_token"] == 4 * 128 * 4
        assert health["kv_row_padding_share"] == 0.375
        assert health["weight_bytes"] > 0
        assert "kgct_kv_bytes_per_token 2048" in metrics
        assert "kgct_kv_row_padding_share 0.3750" in metrics
        assert 'kgct_moe_routed_pairs_total{step_kind="decode"}' in metrics
        assert "kgct_moe_expert_load_max_ratio" in metrics
