"""``roofline_latent_moe.py`` against the hand arithmetic of ISSUE 26, the
new readers on made-up contexts, and the benchmark's copy of the reference
against the program's."""

import json
from types import SimpleNamespace

import pytest

from perfbench import readers
from perfbench import roofline_latent_moe as rf
from perfbench.spec import ROOT, Benchmark

KIMI = json.loads(
    (ROOT / "perfbench/configs/kimi-vl-a3b-lm-bf16.json").read_text())
CELL = "kimi-vl-a3b-lm-bf16.batch-decode-2k"


def test_parameters_by_hand():
    # W_q 2048x3072, W_kva 2048x576, W_kvb 512x4096, W_o 2048x2048
    assert rf.attention_params(KIMI) == \
        6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 == 13_762_560
    assert rf.expert_params(KIMI) == 3 * 2048 * 1408 == 8_650_752
    # beside the experts: attention, 2 shared experts, the router
    assert rf.expert_layer_fixed_params(KIMI) == \
        13_762_560 + 17_301_504 + 131_072
    assert rf.dense_layer_params(KIMI) == 13_762_560 + 3 * 2048 * 11264
    layer = rf.expert_layer_fixed_params(KIMI) + 64 * rf.expert_params(KIMI)
    assert layer * 2 / 1e9 == pytest.approx(1.170, abs=0.001)
    assert rf.dense_layer_params(KIMI) * 2 / 1e9 == pytest.approx(0.166,
                                                                  abs=0.001)
    # 0.166 + 8 x 1.170 + 1.342 (embedding and head)
    assert rf.resident_weight_bytes(KIMI) / 1e9 == pytest.approx(10.87,
                                                                 abs=0.01)


def test_decode_step_streams_10_19_gb_and_10368_bytes_a_token():
    assert rf.experts_touched_share(KIMI, 64) == pytest.approx(0.998,
                                                               abs=0.001)
    # 8 x 1.170 + 0.166 + 0.671 (head): every expert at 64 rows, nearly
    assert rf.streamed_weight_bytes(KIMI, 64) / 1e9 == pytest.approx(
        10.19, abs=0.02)
    assert rf.streamed_weight_bytes(KIMI, 1e9) / 1e9 == pytest.approx(
        10.19, abs=0.005)
    # one row of a decode step touches 6 of 64 experts a layer
    one = rf.streamed_weight_bytes(KIMI, 1)
    assert one == pytest.approx(2 * (
        rf.dense_layer_params(KIMI) + 8 * (rf.expert_layer_fixed_params(KIMI)
                                           + 6 * rf.expert_params(KIMI))
        + 2048 * 163840))
    assert rf.kv_row_bytes(KIMI) == 1152
    assert rf.kv_bytes_per_token(KIMI) == 10_368
    # 115 k cached tokens: 1.2 GB of latent rows beside 10.2 GB of weights
    step = rf.decode_step_bytes(KIMI, 64, 115_000)
    assert (step - rf.streamed_weight_bytes(KIMI, 64)) / 1e9 == \
        pytest.approx(1.19, abs=0.01)
    assert step / 819e9 * 1e3 == pytest.approx(13.9, abs=0.1)     # ms
    assert rf.latent_decode_kernel_bytes(KIMI, 64, 115_000) == \
        1152 * (115_000 + 2 * 64 * 16)
    assert rf.expert_flops_per_token(KIMI) == 2 * 8_650_752 * 8


def _ctx(**kw):
    base = dict(config=KIMI, peaks={"hbm_bytes_per_s": 819e9},
                profile={"start": 10.0, "end": 13.0}, values={},
                samples=[{"t": 9.0, "rows": 64, "context_tokens": 1},
                         {"t": 11.0, "rows": 64, "context_tokens": 110_000},
                         {"t": 12.0, "rows": 62, "context_tokens": 120_000}],
                trace=None, scrape_after={}, window=(10.0, 13.0))
    base.update(kw)
    return base


def test_step_share_reader():
    read = readers.load("latent_moe_step_hbm_share")
    spec = Benchmark().layer_metric("latent_moe_decode_hbm_share")
    ctx = _ctx(values={"decode_step_ms": 20.0})
    want = rf.decode_step_bytes(KIMI, 63, 115_000) / 819e9 / 0.020 * 100
    assert read(spec, ctx) == pytest.approx(want)
    assert 60 < want < 75
    # nothing to read: no step time, no capture, or a dense configuration
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(values={"decode_step_ms": 20.0},
                           profile={})) is None
    dense = json.loads(
        (ROOT / "perfbench/configs/qwen3-4b-bf16.json").read_text())
    assert read(spec, _ctx(values={"decode_step_ms": 20.0},
                           config=dense)) is None


def test_kernel_share_reader_sums_the_named_events():
    read = readers.load("latent_kernel_hbm_share")
    spec = Benchmark().layer_metric("latent_decode_kernel_hbm_share")
    hlo = ('%latent_paged_decode.12 = bf16[64,16,640]{2,1,0} custom-call('
           '%a), custom_call_target="tpu_custom_call"')
    other = '%fusion.1 = bf16[64,2048]{1,0} fusion(%b), kind=kLoop'
    dev = SimpleNamespace(ops=[(0.0, 400e3, hlo), (500e3, 300e3, other),
                               (900e3, 600e3, hlo)], modules=[])
    trace = SimpleNamespace(devices=[dev])
    got = read(spec, _ctx(trace=trace))
    want = 2 * rf.latent_decode_kernel_bytes(KIMI, 63, 115_000) / 819e9 \
        / 1e-3 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    # a program without the kernel (the parent commit): nothing, no raise
    none = SimpleNamespace(devices=[SimpleNamespace(
        ops=[(0.0, 400e3, other)], modules=[])])
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx()) is None


def test_grouped_matmul_roofline_reader():
    read = readers.load("kernel_flops_share")
    spec = Benchmark().layer_metric("grouped_matmul_roofline")
    assert rf.grouped_matmul_flops(KIMI, 12672) == 2 * 12672 * 2048 * 1408
    up = ('%grouped_matmul.3 = f32[12672,1408]{1,0:T(8,128)} custom-call('
          '%a, %b), custom_call_target="tpu_custom_call"')
    down = up.replace("matmul.3", "matmul.5").replace("1408]", "2048]")
    other = ('%fusion.1 = bf16[12672,2048]{1,0} fusion(%grouped_matmul.3), '
             'kind=kLoop')
    dev = SimpleNamespace(ops=[(0.0, 1.0e6, up), (2e6, 1.0e6, down),
                               (4e6, 5e6, other)], modules=[])
    got = read(spec, _ctx(trace=SimpleNamespace(devices=[dev]),
                          peaks={"bf16_flops_per_s": 197e12}))
    want = 2 * 2 * 12672 * 2048 * 1408 / 197e12 / 2e-3 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    none = SimpleNamespace(devices=[SimpleNamespace(
        ops=[(0.0, 1e6, other)], modules=[])])
    assert read(spec, _ctx(trace=none,
                           peaks={"bf16_flops_per_s": 197e12})) is None


def test_gauge_reader_and_the_cells_metrics():
    read = readers.load("prom_gauge_window_mean")
    spec = Benchmark().layer_metric("moe_expert_load_max_ratio")
    fam = "kgct_moe_expert_load_max_ratio"
    # an untraced run keeps no scrapes: the gauge at the window's end
    assert read(spec, _ctx(scrape_after={(fam, ()): 1.31})) == 1.31
    assert read(spec, _ctx()) is None     # a server without the gauge
    # a traced run: the mean of the scrapes INSIDE the window
    samples = [{"t": t, "scrape": {(fam, ()): v}}
               for t, v in ((0.5, 9.0), (1.5, 1.2), (2.5, 1.8), (3.5, 9.0))]
    samples.append({"t": 2.0})                  # a missed scrape
    assert read(spec, _ctx(window=(1.0, 3.0), samples=samples,
                           scrape_after={(fam, ()): 9.0})) \
        == pytest.approx(1.5)
    bench = Benchmark()
    bench.validate()
    cell = bench.cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"latent_moe_decode_hbm_share", "latent_decode_kernel_hbm_share",
            "mixed_step_ms", "moe_expert_load_max_ratio",
            "grouped_matmul_roofline", "decode_hbm_share",
            "decode_step_ms"} <= names
    assert cell.traffic["prompt_len"] == {"dist": "uniform", "min": 1024,
                                          "max": 1920}
    assert cell.traffic["output_len"]["max"] + 1920 < \
        cell.config["max_position_embeddings"]
    assert cell.load["clients"] == 64 and cell.chips == 1
    qwen = {m["name"] for m in bench.cell(
        "qwen3-4b-bf16.batch-decode").per_layer}
    assert not qwen & {"latent_moe_decode_hbm_share", "mixed_step_ms",
                       "latent_decode_kernel_hbm_share",
                       "moe_expert_load_max_ratio"}


def test_configuration_holds_the_catalogs_numbers():
    published = {
        "vocab_size": 163840, "hidden_size": 2048, "intermediate_size": 11264,
        "moe_intermediate_size": 1408, "num_attention_heads": 16,
        "n_shared_experts": 2, "n_routed_experts": 64, "ep_size": 1,
        "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
        "moe_layer_freq": 1, "first_k_dense_replace": 1,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_theta": 800000}
    for key, value in published.items():
        assert KIMI[key] == value, key
    assert KIMI["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert KIMI["num_hidden_layers"] == 9 \
        and KIMI["max_position_embeddings"] == 4096
    assert "27" in KIMI["reduced_why"]["num_hidden_layers"]
    assert "131072" in KIMI["reduced_why"]["max_position_embeddings"]
    assert "stage 1 of 3" in KIMI["deployment"]


def test_there_is_one_copy_of_the_reference():
    """The program's tests hold the served path to the benchmark's file; a
    second copy in the package could drift from the goldens' source."""
    assert (ROOT / "perfbench/reference/kimi_vl_a3b_lm.py").is_file()
    assert not (ROOT / "kubernetes_gpu_cluster_tpu/models/reference").exists()
    assert "from perfbench.reference import kimi_vl_a3b_lm" in (
        ROOT / "tests/test_mla_moe.py").read_text()
