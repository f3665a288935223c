"""Residual streams (manifold-constrained hyper-connections), the low-rank
query and YaRN on the serving path, held to the plain float32 reference
(perfbench/reference/xing4_0.py: the ONE copy, the benchmark's, which also
writes the cell's goldens) on ``debug-hc-mla-moe`` with seeded weights.

Tolerances, with their reasons: the served path and the reference are both
float32 here, so they differ only by the ORDER of float32 sums (absorbed
against materialised attention, grouped against per-expert dispatch, the
fori_loop against the Python loop of Sinkhorn rounds, XLA's default CPU
matmul against "highest"). Logits are O(1); 2e-4 absolute is 30x the 6e-6
seen and far under what a planted fault moves them by
(``test_planted_faults_fail``: a transposed residual map reads 9.5e-2, a
wrong stream index 1.7, the read and write maps swapped 2.6, coefficients
rounded to bf16 1.4e-2)."""

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
                                               cache_kind_refusal,
                                               get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine import kv_cache as kvc
from kubernetes_gpu_cluster_tpu.engine import weights
from kubernetes_gpu_cluster_tpu.engine.engine import step_workspace_bytes
from kubernetes_gpu_cluster_tpu.models import llama
from kubernetes_gpu_cluster_tpu.ops import attention, hyper_conn, rope
from kubernetes_gpu_cluster_tpu.ops.pallas import hc_mix
from perfbench.reference import xing4_0 as ref

LOGIT_TOL = 2e-4
CFG = get_model_config("debug-hc-mla-moe")
HC = hyper_conn.settings(CFG)

# The catalog's row, as its config.json reads.
XING_HF = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


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


def _step_kinds(eng):
    kinds, orig = [], eng.obs.on_step
    eng.obs.on_step = lambda rec: (kinds.append(rec["kind"]), orig(rec))[1]
    return kinds


def _prefill_gap(params, toks, kernels=attention.NO_KERNELS):
    """A fresh 40-token chunk in a 48-token step, then one decode row over
    the latent pages: the largest gap of the two logit rows to the
    reference's."""
    kv = kvc.allocate_kv_cache(CFG, CacheConfig(page_size=16, num_pages=16),
                               16)
    ar = jnp.arange(48)
    meta = llama.StepMeta(
        seg_ids=jnp.where(ar < 40, 0, -1), positions=ar % 40,
        slot_mapping=jnp.where(ar < 40, 16 + ar, 0),
        logits_indices=jnp.array([39]))
    hid, kv, raw = llama.forward(params, CFG, jnp.asarray(toks + [0] * 8),
                                 meta, kv, kernels)
    assert raw.shape == (48, CFG.hc_mult * CFG.hidden_size)
    want = ref.forward(params, CFG, toks)
    gap = float(jnp.abs(llama.compute_logits(params, CFG, hid)[0]
                        - want[-1]).max())
    nxt = int(jnp.argmax(want[-1]))
    dm = llama.StepMeta(
        positions=jnp.array([40]), slot_mapping=jnp.array([16 + 40]),
        page_tables=jnp.array([[1, 2, 3, 4]]), context_lens=jnp.array([41]))
    hid, kv, _ = llama.forward(params, CFG, jnp.array([nxt]), dm, kv, kernels)
    return max(gap, float(jnp.abs(
        llama.compute_logits(params, CFG, hid)[0]
        - ref.forward(params, CFG, toks + [nxt])[-1]).max()))


# -- (a) served against the reference, on logits --------------------------------

class TestServedAgainstReference:
    def test_fresh_chunk_then_a_decode_row(self, params):
        assert _prefill_gap(params, _prompt(40, 1)) < LOGIT_TOL

    def test_chunk_with_history_and_a_mixed_step(self, params):
        eng = _engine(params)
        kinds = _step_kinds(eng)
        # 100 tokens > the 64-token budget: two chunks, the second over its
        # history in the latent pages, beside the 40-token prompt's row.
        _served_vs_reference(eng, params, [_prompt(40, 2), _prompt(100, 3)])
        assert "mixed" in kinds and "decode" in kinds

    def test_packed_prefill_and_the_eight_step_window(self, params):
        eng = _engine(params, mixed_batch_enabled=False)
        assert eng.config.scheduler.decode_window == 8
        kinds = _step_kinds(eng)
        # three prompts side by side in one 64-token prefill, then windows
        _served_vs_reference(eng, params,
                             [_prompt(n, n) for n in (9, 21, 30)],
                             max_tokens=18)
        assert kinds.count("prefill") == 1 and kinds.count("decode") >= 2

    def test_grouped_dispatch_beside_streams(self, params):
        """Steps wide enough for the grouped expert path (192 > 128)."""
        eng = _engine(params, prefill_buckets=(192,), max_prefill_tokens=192)
        _served_vs_reference(eng, params, [_prompt(40, 8), _prompt(250, 9)])


def test_absorbed_attention_equals_materialised(params):
    """With the query through its latent and YaRN's m^2 in the scale."""
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    assert "wq" not in lp and lp["w_qa"].shape == (CFG.hidden_size, 48)
    T = 24
    x = jax.random.normal(jax.random.key(3), (T, CFG.hidden_size))
    pos = jnp.arange(T)
    q, row = llama._mla_qkv(lp, CFG, x, pos)
    seg = jnp.zeros((T,), jnp.int32)
    scale = CFG.attn_scale
    assert scale == pytest.approx(48 ** -0.5 * (0.1 * np.log(4) + 1) ** 2)
    k, v = llama.mla_materialise(lp, CFG, row)
    want = attention.ragged_prefill_attention_xla(q, k, v, seg, pos, scale)
    got = llama.mla_absorbed(
        lp, CFG, q, row,
        lambda qa, rows: attention.ragged_prefill_attention_xla(
            qa, rows, rows, seg, pos, scale))
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- (b) the stream mixers -----------------------------------------------------

def _mixer_inputs(T, n, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)
    x = normal(ks[0], (T, n * d)).astype(dtype)
    w = normal(ks[1], (n * d, 2 * n + n * n)) * (n * d) ** -0.5
    phi = hyper_conn.pack(w[:, :n], w[:, n:2 * n],
                          w[:, 2 * n:].reshape(n * d, n, n)).astype(dtype)
    bias = hyper_conn.pack(normal(ks[2], (n,)), normal(ks[3], (n,)),
                           jnp.eye(n) + 0.5 * normal(ks[4], (n, n)))
    f = normal(ks[5], (T, d)).astype(dtype)
    return x, phi, jnp.array([1.0, 0.7, 0.25]), bias, f


def _numpy_mixers(x, phi, alpha, bias, f, hc):
    """The equations token by token in float64."""
    x, phi, bias, f = (np.asarray(a, np.float64) for a in (x, phi, bias, f))
    alpha = np.asarray(alpha, np.float64)
    T, d = f.shape
    n = hc.n
    x = x.reshape(T, n, d)
    y, new = np.zeros((T, d)), np.zeros((T, n, d))
    pre, post, res = np.zeros((T, n)), np.zeros((T, n)), np.zeros((T, n, n))
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    for t in range(T):
        flat = x[t].reshape(-1)
        u = (flat / np.sqrt(np.mean(flat ** 2) + hc.rms_eps)) @ phi
        for j in range(n):
            pre[t, j] = sig(alpha[0] * u[j] + bias[j])
            post[t, j] = 2 * sig(alpha[1] * u[8 + j] + bias[8 + j])
        m = np.array([[np.exp(np.clip(
            alpha[2] * u[64 + 8 * i + j] + bias[64 + 8 * i + j], *hc.clamp))
            for j in range(n)] for i in range(n)])
        for _ in range(hc.iters):
            m = m / (m.sum(axis=0, keepdims=True) + hc.eps)
            m = m / (m.sum(axis=1, keepdims=True) + hc.eps)
        res[t] = m
        y[t] = sum(pre[t, j] * x[t, j] for j in range(n))
        for i in range(n):
            new[t, i] = sum(m[i, j] * x[t, j] for j in range(n)) \
                + post[t, i] * f[t]
    return y, new, pre, post, res


@pytest.mark.parametrize("n, d", [(4, 128), (3, 64), (1, 32)])
def test_xla_mixers_equal_the_equations_in_float64(n, d):
    hc = hyper_conn.HCSettings(n, 20, 1e-6, (-30.0, 30.0), 1e-6)
    x, phi, alpha, bias, f = _mixer_inputs(12, n, d, seed=n)
    y, coef = hyper_conn.hc_pre_xla(x, phi, alpha, bias, hc)
    new = hyper_conn.hc_post_xla(x, f, coef)
    y64, new64, pre, post, res = _numpy_mixers(x, phi, alpha, bias, f, hc)
    got = hyper_conn.unpack(coef, n)
    for a, b in zip(got, (pre, post, res)):
        np.testing.assert_allclose(a, b, atol=2e-6)
    np.testing.assert_allclose(y, y64, atol=1e-5)
    np.testing.assert_allclose(new.reshape(new64.shape), new64, atol=1e-5)
    # what is not a coefficient's lane holds zero
    assert float(jnp.abs(coef).sum()) == pytest.approx(
        float(sum(jnp.abs(a).sum() for a in got)), rel=1e-6)


def test_the_residual_map_is_doubly_stochastic(params):
    """Over the model's own mixers and the streams a prompt really has: the
    rows AND the columns of H_res sum to 1 within 1e-5 after the 20 rounds
    (rows by the last normalisation; columns because the draw converges:
    ``models.llama._init_stream_mixers``)."""
    toks = jnp.asarray(_prompt(64, 11), jnp.int32)
    X = ref.hidden_states(params, CFG, toks)       # streams a model has
    flat = X.reshape(X.shape[0], -1)
    worst = 0.0
    for stack in ("dense_layers", "layers"):
        for l in range(2):
            lp = jax.tree.map(lambda a: a[l], params[stack])
            for site in llama.HC_SITES:
                _, coef = hyper_conn.hc_pre_xla(
                    flat, lp[f"hc_{site}_phi"], lp[f"hc_{site}_alpha"],
                    lp[f"hc_{site}_bias"], HC)
                pre, post, res = hyper_conn.unpack(coef, CFG.hc_mult)
                assert bool((res > 0).all())
                assert bool(((pre > 0) & (pre < 1) & (post > 0)
                             & (post < 2)).all())
                worst = max(worst, float(jnp.abs(res.sum(-1) - 1).max()),
                            float(jnp.abs(res.sum(-2) - 1).max()))
                want = ref.stream_coefficients(
                    jax.tree.map(lambda a: a.astype(jnp.float32), lp), CFG,
                    site, X)
                for a, b in zip((pre, post, res), want):
                    np.testing.assert_allclose(a, b, atol=2e-6)
    assert worst < 1e-5


@pytest.mark.parametrize("T, n, d, dtype", [
    (8, 4, 128, jnp.float32),       # a decode bucket: one whole block
    (136, 4, 256, jnp.float32),     # a block and a partial one
    (64, 3, 128, jnp.bfloat16),     # streams that do not fill a row of 4
])
def test_kernels_equal_their_xla_twins(T, n, d, dtype):
    """``ops/pallas/hc_mix.py`` in interpret mode: the lane butterflies
    against plain sums."""
    hc = hyper_conn.HCSettings(n, 20, 1e-6, (-30.0, 30.0), 1e-6)
    x, phi, alpha, bias, f = _mixer_inputs(T, n, d, dtype, seed=T)
    y0, c0 = hyper_conn.hc_pre_xla(x, phi, alpha, bias, hc)
    y1, c1 = hc_mix.hc_pre(x, phi, alpha, bias, hc, interpret=True)
    np.testing.assert_allclose(c1, c0, atol=2e-6)
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f32(y1), f32(y0), atol=tol)
    np.testing.assert_allclose(
        f32(hc_mix.hc_post(x, f, c0, interpret=True)),
        f32(hyper_conn.hc_post_xla(x, f, c0)), atol=tol)


def test_one_stream_is_the_plain_residual():
    """n = 1 is a case of the pair, not another path: a model without
    streams has no mixer tensors and carries [T, d]."""
    cfg = get_model_config("debug-mla-moe")
    assert cfg.hc_mult == 1
    p = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    assert not [k for k in p["layers"] if k.startswith("hc_")]
    ours = jax.eval_shape(lambda: llama.init_params(CFG, jax.random.key(0)))
    for stack in ("dense_layers", "layers"):
        assert ours[stack]["hc_attn_phi"].shape == (2, 4 * 128, 128)
        assert ours[stack]["hc_mlp_alpha"].shape == (2, 3)
        assert ours[stack]["hc_mlp_bias"].dtype == jnp.float32


# -- (c) planted faults ----------------------------------------------------------

def _swap(coef):      # the read through H_post / 2, the write through 2 H_pre
    pre, post, res = hyper_conn.unpack(coef, CFG.hc_mult)
    return hyper_conn.pack(post / 2, 2 * pre, res)


FAULTS = {
    "wrong-index": lambda coef: hyper_conn.pack(*(
        jnp.roll(a, 1, axis=1) if i == 0 else a
        for i, a in enumerate(hyper_conn.unpack(coef, CFG.hc_mult)))),
    "transposed-H_res": lambda coef: hyper_conn.pack(*(
        jnp.swapaxes(a, 1, 2) if i == 2 else a
        for i, a in enumerate(hyper_conn.unpack(coef, CFG.hc_mult)))),
    "swapped-pre-post": _swap,
    "bf16-coefficients": lambda coef: coef.astype(jnp.bfloat16
                                                  ).astype(jnp.float32),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_faults_fail(params, monkeypatch, fault):
    """What the tolerance is for: a wrong stream index, a transposed
    residual map, the read and write maps swapped, and coefficients rounded
    to bf16 each read over it."""
    toks = _prompt(40, 1)
    sound = hyper_conn.hc_pre_xla

    def broken(x, phi, alpha, bias, hc):
        _, coef = sound(x, phi, alpha, bias, hc)
        coef = FAULTS[fault](coef)
        y = jnp.sum(coef[:, :hc.n, None] * x.reshape(x.shape[0], hc.n, -1),
                    axis=1)
        return y.astype(x.dtype), coef

    monkeypatch.setattr(hyper_conn, "hc_pre_xla", broken)
    assert _prefill_gap(params, toks) > 5 * LOGIT_TOL


# -- (d) YaRN ---------------------------------------------------------------------

def test_yarn_numbers_of_the_published_block():
    sc = XING_HF["rope_scaling"]
    # cd(t) = 64 ln(4096 / (2 pi t)) / (2 ln 10000): cd(32) = 10.47, cd(1) =
    # 22.51, so the ramp runs from pair 10 to pair 23
    cd = lambda t: 64 * np.log(4096 / (2 * np.pi * t)) / (2 * np.log(10000))
    assert (np.floor(cd(32)), np.ceil(cd(1))) == (10, 23)
    inv = rope.scaled_inv_freq(64, 10000.0, sc)
    i = np.arange(32)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = 10000.0 ** (-2 * i / 64) * ((1 - ramp) + ramp / 64)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    np.testing.assert_allclose(inv[:11], 10000.0 ** (-2 * i[:11] / 64),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[23:], 10000.0 ** (-2 * i[23:] / 64) / 64,
                               rtol=1e-6)
    assert inv[16] == pytest.approx(0.01 * (7 / 13 + 6 / 13 / 64), rel=1e-5)
    m = 0.1 * np.log(64) + 1
    assert m == pytest.approx(1.41589, abs=1e-5)
    assert rope.yarn_attn_factor(sc) == pytest.approx(2.00474, abs=1e-5)
    assert rope.yarn_cos_factor(sc) == 1.0
    cfg = get_model_config("xing4.0-29b-a4b")
    assert cfg.attn_scale == pytest.approx(0.144680, abs=1e-6)
    # mscale alone (mscale_all_dim 0) scales cos and sin, not the scores
    only = dict(sc, mscale_all_dim=0, mscale=0.5)
    assert rope.yarn_attn_factor(only) == 1.0
    assert rope.yarn_cos_factor(only) == pytest.approx(0.05 * np.log(64) + 1)
    cos, _ = rope.rope_cos_sin(jnp.zeros((1,), jnp.int32), 64, 10000.0,
                               scaling=only)
    np.testing.assert_allclose(cos, 0.05 * np.log(64) + 1, rtol=1e-6)
    # a model without it keeps its scale to the bit
    assert get_model_config("kimi-vl-a3b").attn_scale == 192 ** -0.5


# -- (e) config.json, the preset, refusals ------------------------------------------

def _hf_dir(tmp_path, **changes):
    hf = {**XING_HF, **changes}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_the_catalog_row_loads_as_the_preset(tmp_path, monkeypatch):
    said = []
    monkeypatch.setattr(weights.logger, "info",
                        lambda msg, *a: said.append(msg % a))
    cfg = weights.config_from_hf(_hf_dir(tmp_path), name="xing4.0-29b-a4b")
    assert "multi-token-prediction module is not served" in said[0]
    preset = get_model_config("xing4.0-29b-a4b")
    # config.json's positions are capped at what one server holds
    assert cfg.max_model_len == 8192
    assert cfg.replace(max_model_len=4096) == preset
    assert (preset.hc_mult, preset.q_lora_rank, preset.num_dense_layers) == (
        4, 768, 2)
    cut = apply_hf_overrides(preset, {"num_hidden_layers": 8})
    assert cut.layer_sections == ((("attention",), 2, True),
                                  (("attention",), 6, False))
    assert llama.layer_stacks(cut) == {"dense_layers": 2, "layers": 6}


@pytest.mark.parametrize("changes, names", [
    ({"n_group": 8}, "n_group=8"),
    ({"topk_group": 4}, "topk_group=4"),
    ({"rope_scaling": {"type": "dynamic", "factor": 2.0}},
     "rope_scaling.*'dynamic'"),
    ({"hc_gate_kind": "tanh"}, "hc_gate_kind"),
    ({"hidden_act": "gelu"}, "hidden_act='gelu'"),
])
def test_config_json_refusals_are_by_name(tmp_path, changes, names):
    with pytest.raises(ValueError, match=names):
        weights.config_from_hf(_hf_dir(tmp_path, **changes), name="x")


def test_streams_refuse_a_residual_multiplier_and_a_checkpoint(tmp_path):
    with pytest.raises(ValueError, match="residual_multiplier 0.5 with "
                                         "hc_mult 4"):
        CFG.replace(residual_multiplier=0.5)
    with pytest.raises(ValueError, match="hc_mult 9"):
        CFG.replace(hc_mult=9)
    with pytest.raises(ValueError, match="no loader for a checkpoint with "
                                         "residual streams"):
        weights.load_weights(str(tmp_path), CFG)


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
    ("--role prefill", _cfg(), {"role": "prefill"}),
    ("--fleet-prefix-cache", _cfg(), {"fleet_prefix_cache": True}),
    ("--peer-pool", _cfg(), {"peer_pool": "http://peer:8000"}),
])
def test_refused_flag_is_named_with_its_mechanism(flag, config, extra):
    """A model with streams is a latent model: every flag a latent model is
    refused, it is refused, the pipeline (one carried stream) among them."""
    msg = cache_kind_refusal(config, **extra)
    assert msg is not None and msg.startswith(flag) and CFG.name in msg
    if not extra:
        with pytest.raises(ValueError, match=flag.split()[0]):
            LLMEngine(config)


def test_the_workspace_counts_every_stream():
    one = EngineConfig(model=CFG.replace(hc_mult=1),
                       cache=CacheConfig(page_size=16, num_pages=16))
    four = EngineConfig(model=CFG,
                        cache=CacheConfig(page_size=16, num_pages=16))
    T = (one.scheduler.prefill_buckets[-1] + one.scheduler.decode_buckets[-1])
    assert step_workspace_bytes(four) - step_workspace_bytes(one) == (
        4 * T * 3 * CFG.hidden_size * 4 + 4 * T * 128 * 4)


# -- (f) through the HTTP surface ----------------------------------------------------

def test_served_over_http_with_its_streams_on_health(params):
    from aiohttp.test_utils import TestClient, TestServer

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
    loop = asyncio.new_event_loop()
    server = build_server(EngineConfig(
        model=CFG, cache=CacheConfig(page_size=16, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=2, max_prefill_tokens=64,
                                  decode_buckets=(1, 2),
                                  prefill_buckets=(32, 64))),
        None, "debug-hc-mla-moe", params=params)
    client = TestClient(TestServer(server.build_app()), loop=loop)

    async def go():
        await client.start_server()
        comp = await client.post("/v1/completions", json={
            "model": "debug-hc-mla-moe", "max_tokens": 3, "temperature": 0,
            "prompt": [5, 6, 7, 8]})
        health = await client.get("/health")
        out = comp.status, await comp.json(), await health.json()
        await client.close()
        return out
    status, out, health = loop.run_until_complete(go())
    loop.close()
    assert status == 200 and out["usage"]["completion_tokens"] == 3
    assert health["residual_streams"] == 4
    assert health["kv_layout"] == "latent"
