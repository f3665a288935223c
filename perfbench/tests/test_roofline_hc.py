"""``roofline_hc.py`` against the hand arithmetic of ISSUE 42 and the
program's own weight tree, the two new readers on made-up contexts, the new
cell's entries, and the benchmark's copy of the reference against the
program's."""

import json
from types import SimpleNamespace

import pytest

from perfbench import readers
from perfbench import roofline_hc as rf
from perfbench import roofline_latent_moe as lm
from perfbench.spec import ROOT, Benchmark

XING = json.loads(
    (ROOT / "perfbench/configs/xing4.0-29b-a4b-bf16.json").read_text())
CELL = "xing4.0-29b-a4b-bf16.batch-decode-2k"


def test_parameters_by_hand():
    # W_qa 3584x768, W_qb 768x6144, W_kva 3584x576, W_kvb 512x8192, W_o 4096x3584
    assert rf.attention_params(XING) == (2_752_512 + 4_718_592 + 2_064_384
                                         + 4_194_304 + 14_680_064) == 28_409_856
    # the full-rank query the kimi-vl count assumes would be 14.55 M larger
    assert lm.attention_params(XING) - rf.attention_params(XING) == \
        3584 * 6144 - 2_752_512 - 4_718_592
    # a sublayer's mixer as published: 14336 x 24 + 24 biases + 3 gains
    assert rf.mixer_params(XING) == 14336 * 24 + 24 + 3
    # ... and as stored: Phi in 128 lanes, bf16
    assert rf.mixer_stored_bytes(XING) == 14336 * 128 * 2 + 131 * 4
    assert lm.expert_params(XING) == 3 * 3584 * 1024 == 11_010_048
    expert_layer = rf.layer_fixed_bytes(XING, False) + 64 * 11_010_048 * 2
    assert expert_layer / 1e9 == pytest.approx(1.497, abs=0.002)
    assert rf.layer_fixed_bytes(XING, True) / 1e9 == pytest.approx(
        0.2624, abs=0.0005)
    # 2 x 0.262 + 6 x 1.497 + 1.879 (embedding and head)
    assert rf.resident_weight_bytes(XING) / 1e9 == pytest.approx(11.38,
                                                                 abs=0.01)


def test_weight_count_is_healths_weight_bytes():
    """``/health``'s ``weight_bytes`` is the sum over the engine's tree: the
    count is held to the tree's shapes, to the byte."""
    import jax

    import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
    from kubernetes_gpu_cluster_tpu.models.llama import init_params
    from perfbench.reference.write_golden import model_config
    cfg = model_config(XING)
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert rf.resident_weight_bytes(XING) == held == 11_382_182_592


def test_a_decode_step_by_hand():
    assert lm.experts_touched_share(XING, 62) == pytest.approx(0.9817,
                                                               abs=0.0005)
    # every layer's fixed part, 62.8 of 64 experts a layer, the head
    weights = rf.streamed_weight_bytes(XING, 62)
    assert weights / 1e9 == pytest.approx(10.29, abs=0.02)
    assert lm.kv_bytes_per_token(XING) == 1152 * 8
    # a row's streams: in once for the mix, in and out once for the write
    assert rf.hc_pre_bytes(XING, 64) == (
        64 * (4 + 1) * 3584 * 2 + rf.mixer_stored_bytes(XING) + 64 * 512)
    assert rf.hc_post_bytes(XING, 64) == 64 * 9 * 3584 * 2 + 64 * 512
    assert rf.hc_pre_bytes(XING, 2112) / 1e6 == pytest.approx(80.4, abs=0.1)
    assert rf.hc_post_bytes(XING, 2112) / 1e6 == pytest.approx(137.3,
                                                               abs=0.1)
    assert rf.stream_step_bytes(XING, 64) == 16 * (
        rf.hc_pre_bytes(XING, 64) - rf.mixer_stored_bytes(XING)
        + rf.hc_post_bytes(XING, 64))
    step = rf.decode_step_bytes(XING, 62, 115_000)
    assert step == pytest.approx(weights + 9216 * 115_000
                                 + rf.stream_step_bytes(XING, 62))
    assert step / 819e9 * 1e3 == pytest.approx(13.96, abs=0.1)      # ms


def _ctx(**kw):
    base = dict(config=XING, peaks={"hbm_bytes_per_s": 819e9},
                profile={"start": 10.0, "end": 13.0}, values={},
                samples=[{"t": 9.0, "rows": 64, "context_tokens": 1},
                         {"t": 11.0, "rows": 64, "context_tokens": 110_000},
                         {"t": 12.0, "rows": 62, "context_tokens": 120_000}],
                trace=None, scrape_after={}, window=(10.0, 13.0))
    base.update(kw)
    return base


KIMI = json.loads(
    (ROOT / "perfbench/configs/kimi-vl-a3b-lm-bf16.json").read_text())


def test_step_share_reader():
    read = readers.load("hc_step_hbm_share")
    spec = Benchmark().layer_metric("hc_latent_moe_decode_hbm_share")
    ctx = _ctx(values={"decode_step_ms": 18.0})
    want = rf.decode_step_bytes(XING, 63, 115_000) / 819e9 / 0.018 * 100
    assert read(spec, ctx) == pytest.approx(want)
    assert 60 < want < 100
    # nothing to read: no step time, no capture, a model without streams
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(values={"decode_step_ms": 18.0},
                           profile={})) is None
    assert read(spec, _ctx(values={"decode_step_ms": 18.0},
                           config=KIMI)) is None


def test_kernel_share_reader_counts_what_lies_in_hbm():
    from perfbench.readers.hc_kernel_hbm_share import hbm_bytes
    read = readers.load("hc_kernel_hbm_share")
    bench = Benchmark()
    # as a capture names them (my chip run, PR 42): S(1) is on-chip memory
    pre = ('%hc_pre.20 = (bf16[2112,3584]{1,0:T(8,128)(2,1)}, f32[2112,128]'
           '{1,0:T(8,128)}) custom-call(bf16[2112,14336]{1,0:T(8,128)(2,1)'
           'S(1)} %bitcast.923, bf16[14336,128]{1,0:T(8,128)(2,1)} %dynamic-'
           'slice_bitcast_fusion.25, f32[2,128]{1,0:T(2,128)S(1)} %pad.29), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints'
           '={bf16[2112,14336]{1,0}, bf16[14336,128]{1,0}, f32[2,128]{1,0}}')
    pre64 = ('%hc_pre.22 = (bf16[64,3584]{1,0:T(8,128)(2,1)S(1)}, f32[64,128]'
             '{1,0:T(8,128)S(1)}) custom-call(bf16[64,14336]{1,0:T(8,128)'
             '(2,1)} %x, bf16[14336,128]{1,0:T(8,128)(2,1)S(1)} %phi, '
             'f32[2,128]{1,0:T(2,128)S(1)} %ab), custom_call_target="x"')
    post = ('%hc_post.33 = bf16[2112,14336]{1,0:T(8,128)(2,1)S(1)} custom-'
            'call(bf16[2112,14336]{1,0:T(8,128)(2,1)S(1)} %custom-call.79, '
            'bf16[2112,3584]{1,0:T(8,128)(2,1)} %fusion.704, f32[2112,128]'
            '{1,0:T(8,128)S(1)} %custom-call.97), custom_call_target="x"')
    other = ('%fusion.1 = bf16[2112,3584]{1,0} fusion(bf16[2112,3584]{1,0} '
             '%jit_hc_pre_.52), kind=kLoop')
    # y and the coefficient rows out, Phi in; the streams are resident
    assert hbm_bytes(pre) == 2112 * 3584 * 2 + 2112 * 128 * 4 + 14336 * 128 * 2
    assert hbm_bytes(pre64) == 64 * 14336 * 2
    assert hbm_bytes(post) == 2112 * 3584 * 2       # the sublayer's result
    # all of it in HBM is the algorithm's count
    in_hbm = lambda hlo: hlo.replace("S(1)", "")
    assert hbm_bytes(in_hbm(pre)) == rf.hc_pre_bytes(XING, 2112) - 131 * 4 \
        + 2 * 128 * 4
    assert hbm_bytes(in_hbm(post)) == rf.hc_post_bytes(XING, 2112)
    dev = SimpleNamespace(ops=[(0.0, 200e3, pre), (300e3, 20e3, pre64),
                               (400e3, 250e3, post), (700e3, 900e3, other)],
                          modules=[])
    trace = SimpleNamespace(devices=[dev])
    got = read(bench.layer_metric("hc_pre_kernel_hbm_share"),
               _ctx(trace=trace))
    want = (hbm_bytes(pre) + hbm_bytes(pre64)) / 819e9 / 220e-6 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    got = read(bench.layer_metric("hc_post_kernel_hbm_share"),
               _ctx(trace=trace))
    assert got == pytest.approx(2112 * 3584 * 2 / 819e9 / 250e-6 * 100)
    # a program without the kernels (the parent commit), a model without
    # streams, a run without a capture: nothing, no raise
    none = SimpleNamespace(devices=[SimpleNamespace(
        ops=[(0.0, 400e3, other)], modules=[])])
    spec = bench.layer_metric("hc_pre_kernel_hbm_share")
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx(trace=trace, config=KIMI)) is None
    assert read(spec, _ctx()) is None


def test_the_cells_entries():
    bench = Benchmark()
    bench.validate()
    cell = bench.cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"hc_pre_kernel_hbm_share", "hc_post_kernel_hbm_share",
            "hc_mix_share", "hc_latent_moe_decode_hbm_share",
            "latent_decode_kernel_hbm_share", "grouped_matmul_roofline",
            "grouped_matmul_tile_fill_share", "moe_expert_load_max_ratio",
            "mixed_step_ms", "decode_step_inproc_ms", "mixed_step_inproc_ms",
            "steps_dispatched_behind_share", "decode_step_ms"} <= names
    # its byte count is the full-rank one: not this cell's
    assert "latent_moe_decode_hbm_share" not in names
    assert cell.traffic_name == "batch-decode-2k" and cell.chips == 1
    assert cell.load == bench.cell(
        "kimi-vl-a3b-lm-bf16.batch-decode-2k").load
    assert cell.traffic["output_len"]["max"] + 1920 < \
        cell.config["max_position_embeddings"]
    for other in bench.cell_names():
        if other != CELL:
            assert not {m["name"] for m in bench.cell(other).per_layer} & {
                "hc_pre_kernel_hbm_share", "hc_post_kernel_hbm_share",
                "hc_mix_share", "hc_latent_moe_decode_hbm_share"}
    op_share = readers.load("trace_op_share")
    # a consumer of hc_pre's tuple names its element ``jit_hc_pre_``: not ours
    dev = SimpleNamespace(
        ops=[(0.0, 100.0, "%hc_pre.1 = (bf16[64,3584]) custom-call(%a)"),
             (100.0, 300.0, "%fusion.2 = bf16[64,576] fusion(bf16[64,3584] "
                            "%jit_hc_pre_.112)"),
             (400.0, 100.0, "%hc_post.1 = bf16[64,14336] custom-call(%c)")],
        modules=[])
    assert op_share(bench.layer_metric("hc_mix_share"), _ctx(
        trace=SimpleNamespace(devices=[dev]))) == pytest.approx(40.0)


def test_configuration_holds_the_catalogs_numbers():
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    for key, value in published.items():
        assert XING[key] == value, key
    assert XING["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert XING["num_hidden_layers"] == 8 \
        and XING["max_position_embeddings"] == 4096
    assert XING["published"] == {"num_hidden_layers": 40,
                                 "max_position_embeddings": 262144}
    assert "stage 1 of 5" in XING["deployment"]


def test_there_is_one_copy_of_the_reference():
    assert (ROOT / "perfbench/reference/xing4_0.py").is_file()
    assert not (ROOT / "kubernetes_gpu_cluster_tpu/models/reference").exists()
    assert "from perfbench.reference import xing4_0" in (
        ROOT / "tests/test_hc_mla.py").read_text()
