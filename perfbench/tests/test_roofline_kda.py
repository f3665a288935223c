"""``roofline_kda.py`` against the hand arithmetic of ISSUE 35 and the engine's
own tree, the new readers on made-up contexts, the new cell through
``Benchmark.validate()``, and every accepted per-layer metric without a
``workloads`` list on the new configuration file (a number or None, never a
raise)."""

import json
from types import SimpleNamespace

import pytest

from perfbench import readers
from perfbench import roofline_kda as rf
from perfbench.spec import ROOT, Benchmark

KIMI = json.loads(
    (ROOT / "perfbench/configs/kimi-linear-48b-a3b-bf16.json").read_text())
CELL = "kimi-linear-48b-a3b-bf16.batch-decode-2k"
NEW = {"kda_update_kernel_hbm_share", "kda_moe_decode_hbm_share",
       "kda_mixed_step_ms", "moe_pairs_held_share"}


def test_parameters_by_hand():
    assert rf.layer_counts(KIMI) == (7, 2)
    assert (rf.kda_width(KIMI), rf.router_width(KIMI)) == (4096, 256)
    # q|k|v 2304 x 12288, out 4096 x 2304, the convs 4 x 12288, the two
    # low-rank pairs 2 x (2304 x 128 + 128 x 4096), beta 2304 x 32, the
    # per-head norm 128; dt_bias 4096 and A_log 32 in float32
    model, f32 = rf.kda_mixer_params(KIMI)
    assert model == (28_311_552 + 9_437_184 + 49_152 + 2 * 819_200
                     + 73_728 + 128)
    assert f32 == 4096 + 32 and model / 1e6 == pytest.approx(39.5, abs=0.05)
    # W_q 2304 x 32 x 192, W_kva 2304 x 576 with its norm, W_kvb 512 x 32 x
    # 256, W_o 4096 x 2304
    assert rf.mla_mixer_params(KIMI) == (14_155_776 + 1_327_104 + 512
                                         + 4_194_304 + 9_437_184)
    assert rf.expert_params(KIMI) == 3 * 2304 * 1024 == 7_077_888
    # ISSUE 35's table: 8 x 64 experts 7.25 GB; all of it 8.55 GB
    assert 8 * 64 * rf.expert_params(KIMI) * 2 / 1e9 == pytest.approx(
        7.25, abs=0.005)
    # the served tree's own count (/health weight_bytes on the v5e, and
    # eval_shape of models.llama.init_params: PERF.md, PR 35)
    assert rf.resident_weight_bytes(KIMI) == 8_554_580_096


def test_state_pages_and_a_decode_step_by_hand():
    # 32 heads x 128 x 128 float32 = 2 MiB, and 3 conv rows of 12288 bf16
    assert rf.state_bytes_per_row_layer(KIMI) == 2_097_152 + 73_728
    assert rf.state_bytes_per_seq(KIMI) == 7 * 2_170_880 == 15_196_160
    assert 65 * rf.state_bytes_per_seq(KIMI) == 987_750_400   # /health
    # 2 latent layers x (512 + 64) x 2 B
    assert rf.kv_bytes_per_token(KIMI) == 2304
    # 64 rows x 8 of 256: a held expert is missed with (1 - 8/256)^64
    assert rf.experts_hit_share(KIMI, 64) == pytest.approx(0.869, abs=0.001)
    assert rf.experts_hit_share(KIMI, 0) == 0.0
    # ... so a step streams 0.869 x 7.25 GB of experts beside 1.12 GB of
    # other weights and the head: 7.42 GB; all of them 8.37 GB
    assert rf.streamed_weight_bytes(KIMI, 64) / 1e9 == pytest.approx(
        7.42, abs=0.01)
    assert rf.streamed_weight_bytes(KIMI, 1e9) == (
        rf.resident_weight_bytes(KIMI) - 2304 * 40960 * 2)
    # the float32 state twice: 1.88 GB of the slots' 1.95; pages 0.28 GB
    step = rf.decode_step_bytes(KIMI, 64, 120_000)
    assert 2 * 64 * 7 * 2_097_152 / 1e9 == pytest.approx(1.88, abs=0.005)
    assert (step - rf.streamed_weight_bytes(KIMI, 64)) / 1e9 == \
        pytest.approx(1.945 + 0.276, abs=0.005)
    assert step / 819e9 * 1e3 == pytest.approx(11.8, abs=0.1)     # ms
    # one call of the update: 64 rows' state there and back, and per row
    # alpha, k, q, v, beta, o (4096 float32 each)
    assert rf.kda_update_kernel_bytes(KIMI, 64) == \
        64 * (2 * 2_097_152 + 6 * 16_384)
    # 8 operations a state element against 8 bytes of it: far under the
    # chip's 240 FLOP a byte
    assert rf.kda_update_kernel_flops(KIMI, 64) == 64 * 8 * 524_288


def _ctx(**kw):
    base = dict(config=KIMI, peaks={"hbm_bytes_per_s": 819e9},
                profile={"start": 10.0, "end": 45.0}, values={},
                samples=[{"t": 9.0, "rows": 64, "context_tokens": 1},
                         {"t": 11.0, "rows": 64, "context_tokens": 110_000},
                         {"t": 12.0, "rows": 62, "context_tokens": 120_000},
                         {"t": 20.0, "rows": 30, "context_tokens": 70_000},
                         {"t": 40.0, "rows": 2, "context_tokens": 5_000}],
                trace=SimpleNamespace(devices=[], window_s=2.5),
                scrape_before={}, scrape_after={},
                window=(10.0, 13.0), records=[])
    base.update(kw)
    return base


def test_step_share_reader():
    read = readers.load("kda_step_hbm_share")
    spec = Benchmark().layer_metric("kda_moe_decode_hbm_share")
    want = rf.decode_step_bytes(KIMI, 63, 115_000) / 819e9 / 0.020 * 100
    assert read(spec, _ctx(values={"decode_step_ms": 20.0})) == \
        pytest.approx(want)
    assert 50 < want < 65
    # nothing to read: no step time, no capture, no KDA layers
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(values={"decode_step_ms": 20.0},
                           profile={})) is None
    assert read(spec, _ctx(values={"decode_step_ms": 20.0},
                           trace=None)) is None
    for other in ("qwen3-4b-bf16", "kimi-vl-a3b-lm-bf16",
                  "granite-4.0-h-micro-bf16"):
        cfg = json.loads(
            (ROOT / f"perfbench/configs/{other}.json").read_text())
        assert read(spec, _ctx(values={"decode_step_ms": 20.0},
                               config=cfg)) is None


def test_kernel_share_reader_sums_the_named_events():
    read = readers.load("kda_kernel_hbm_share")
    spec = Benchmark().layer_metric("kda_update_kernel_hbm_share")
    hlo = ('%kda_update.7 = (f32[7,65,4096,128]{3,2,1,0}, f32[64,1,4096]'
           '{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"')
    other = '%fusion.1 = bf16[64,2304]{1,0} fusion(%b), kind=kLoop'
    dev = SimpleNamespace(ops=[(0.0, 400e3, hlo), (500e3, 300e3, other),
                               (900e3, 420e3, hlo)], modules=[])
    got = read(spec, _ctx(trace=SimpleNamespace(devices=[dev],
                                                window_s=2.5)))
    want = 2 * rf.kda_update_kernel_bytes(KIMI, 63) / 819e9 \
        / 0.82e-3 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    # a program without the kernel (the parent commit's): nothing, no raise
    none = SimpleNamespace(window_s=2.5, devices=[SimpleNamespace(
        ops=[(0.0, 400e3, other)], modules=[])])
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(trace=None)) is None        # an untraced run
    granite = json.loads((ROOT / "perfbench/configs/"
                          "granite-4.0-h-micro-bf16.json").read_text())
    assert read(spec, _ctx(config=granite, trace=SimpleNamespace(
        devices=[dev], window_s=2.5))) is None


def test_held_share_gauge_reader():
    read = readers.load("prom_gauge_window_mean")
    spec = Benchmark().layer_metric("moe_pairs_held_share")
    fam = "kgct_moe_pairs_held_share"
    samples = [{"t": t, "scrape": {(fam, ()): v}}
               for t, v in ((10.5, 24.0), (11.5, 26.0), (14.0, 90.0))]
    assert read(spec, _ctx(samples=samples)) == pytest.approx(25.0)
    # a program without the gauge (the parent commit's, a model that holds
    # every expert): nothing
    assert read(spec, _ctx()) is None


def test_the_cell_loads_and_reports_what_it_must():
    bench = Benchmark()
    bench.validate()
    cell = bench.cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names and "mixed_step_ms" not in names
    assert {"moe_expert_load_max_ratio", "steps_dispatched_behind_share",
            "decode_step_ms", "decode_hbm_share"} <= names
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "tpot_p90_ms", "out_tok_s", "setup_s"}
    assert cell.chips == 1 and cell.load["clients"] == 64
    assert cell.load["ladder"] == {"mixed_rows": [], "packed": [1, 2]}
    assert cell.traffic_name == "batch-decode-2k"
    assert cell.traffic["output_len"]["max"] + 1920 < \
        cell.config["max_position_embeddings"]
    assert cell.golden_path.is_file()
    golden = json.loads(cell.golden_path.read_text())
    assert golden["captured_on"]["platform"] == "cpu"      # the reference's
    assert "kimi_linear.py" in golden["about"]
    # the new metrics are this cell's alone
    for other in bench.cell_names():
        if other != CELL:
            assert not NEW & {m["name"] for m in bench.cell(other).per_layer}
    entry = next(w for w in bench.doc["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "share" in entry["why"]


def test_accepted_metrics_without_a_list_never_raise_on_the_new_file():
    """What a traced run of the new cell computes besides its own: each
    reads a number or nothing from a context that holds only the
    configuration."""
    bench = Benchmark()
    for m in bench.doc["per_layer"]:
        if "workloads" in m:
            continue
        spec = bench.layer_metric(m["name"])
        got = readers.load(spec["reader"])(
            spec, _ctx(values={"decode_step_ms": 20.0}, trace=None))
        assert got is None or isinstance(got, float), m["name"]
    # decode_hbm_share DOES read here, from a dense-GQA byte model (9 layers
    # of K|V at 32 heads of 72, a dense MLP of 9216 in every layer, no
    # experts, no state): it means nothing in this cell (PERF.md section 7)
    spec = bench.layer_metric("decode_hbm_share")
    got = readers.load("roofline")(
        spec, _ctx(values={"decode_step_ms": 20.0}, trace=None,
                   profile={"start": 10.0, "end": 13.0}))
    from perfbench import roofline
    assert roofline.kv_bytes_per_token(KIMI) == 9 * 2 * 32 * 72 * 2
    assert got == pytest.approx(
        (roofline.streamed_weight_bytes(KIMI) + 82_944 * 115_000)
        / 819e9 / 0.020 * 100)


def test_configuration_holds_the_catalogs_numbers():
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128}
    for key, value in published.items():
        assert KIMI[key] == value, key
    assert KIMI["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert (KIMI["mla_use_nope"], KIMI["moe_renormalize"],
            KIMI["moe_router_activation_func"], KIMI["q_lora_rank"],
            KIMI["tie_word_embeddings"]) == (True, True, "sigmoid", None,
                                            False)
    # the cut, each with its published value beside it
    assert KIMI["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "model_max_length",
                               "max_position_embeddings"]
    assert (KIMI["num_hidden_layers"], KIMI["num_experts"],
            KIMI["vocab_size"], KIMI["model_max_length"]) == (
        9, 64, 40960, 4096)
    assert KIMI["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "model_max_length": 1048576}
    for key, said in (("num_hidden_layers", "27"), ("num_experts", "256"),
                      ("vocab_size", "163840"),
                      ("model_max_length", "1048576")):
        assert said in KIMI["reduced_why"][key], key
    assert "FLOAT32" in KIMI["assumed"]["state_dtype"]
    assert "ASSUMED" in KIMI["assumed"]["checkpoint_tensor_names"]
    assert "3-stage" in KIMI["deployment"] and "4 chips" in KIMI["deployment"]
    overrides = json.loads(KIMI["server_flags"][1])
    assert KIMI["server_flags"][0] == "--hf-overrides" and overrides == {
        "num_hidden_layers": 9, "experts_held": 64, "vocab_size": 40960}
    entry = next(c for c in Benchmark().doc["configs"]
                 if c["name"] == KIMI["name"])
    assert entry["reduced"] == KIMI["reduced"]
    assert entry["source"] == KIMI["source"]


def test_there_is_one_copy_of_the_reference():
    assert (ROOT / "perfbench/reference/kimi_linear.py").is_file()
    assert "from perfbench.reference import kimi_linear" in (
        ROOT / "tests/test_kda_hybrid.py").read_text()
