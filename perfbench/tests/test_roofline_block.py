"""``roofline_block.py`` against the hand arithmetic of ISSUE 50 and the
engine's own tree, the new readers on made-up contexts, the new cell through
``Benchmark.validate()``, the configuration file against the catalog's row,
and every accepted per-layer metric without a ``workloads`` list on the new
configuration file (a number or None, never a raise)."""

import json
from types import SimpleNamespace

import pytest

from perfbench import readers
from perfbench import roofline_block as rf
from perfbench.spec import ROOT, Benchmark

SDAR = json.loads(
    (ROOT / "perfbench/configs/sdar-30b-a3b-chat-bf16.json").read_text())
CELL = "sdar-30b-a3b-chat-bf16.batch-decode-2k"
NEW = {"block_step_hbm_share", "block_attend_kernel_hbm_share",
       "block_tokens_per_pass"}
# The catalog's row, as its config.json reads.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def _ctx(**kw):
    samples = [{"t": 10.0 + 0.25 * i, "rows": 64, "context_tokens": 128_000}
               for i in range(12)]
    base = dict(config=SDAR, peaks={"hbm_bytes_per_s": 819e9}, values={},
                profile={"start": 10.0, "end": 13.0}, samples=samples,
                trace=SimpleNamespace(devices=[], window_s=2.5),
                scrape_before={}, scrape_after={},
                window=(10.0, 13.0), records=[])
    base.update(kw)
    return base


def test_parameters_by_hand():
    # q 2048 x 4096, k and v 2048 x 512 each, o 4096 x 2048: 18.87 M; the
    # two norms, the q and k head norms, the router 2048 x 128
    assert rf.layer_fixed_params(SDAR) == (18_874_368 + 4096 + 256 + 262_144)
    assert rf.expert_params(SDAR) == 3 * 2048 * 768 == 4_718_592
    # 128 experts a layer: 603.98 M (ISSUE 50); a layer 623.1 M = 1.246 GB
    assert 128 * rf.expert_params(SDAR) == 603_979_776
    # /health weight_bytes on the v5e (my chip run, PR 50): 6 layers, the
    # embedding, the head and the last norm
    assert rf.resident_weight_bytes(SDAR) == 8_722_111_488
    assert rf.block_length(SDAR) == 4


def test_a_pass_by_hand():
    # 6 layers x 2 x 4 heads x 128 x 2 B (ISSUE 50: 12,288 B a token)
    assert rf.kv_bytes_per_token(SDAR) == 12_288
    # 64 rows x 4 positions x 8 of 128: every expert is hit
    # (1 - (15/16)^256 = 1 - 7e-8); one row: 4 positions reach 22.8 %
    whole = rf.streamed_weight_bytes(SDAR, 64)
    assert whole == pytest.approx(8_722_111_488 - 2048 * 151_936 * 2, rel=1e-6)
    assert rf.streamed_weight_bytes(SDAR, 1) < 0.4 * whole
    # ISSUE 50: 7.48 GB of layers + 0.62 GB of head = 9.9 ms at 819 GB/s,
    # and 64 x 2 k tokens of history 1.6 GB = 1.9 ms more
    assert whole / 819e9 * 1e3 == pytest.approx(9.9, abs=0.05)
    assert rf.pass_bytes(SDAR, 64, 128_000) - whole == 128_000 * 12_288
    assert rf.pass_bytes(SDAR, 64, 128_000) / 819e9 * 1e3 == pytest.approx(
        11.8, abs=0.05)
    # the kernel, one layer of one pass: 64 rows of 2000 tokens hold
    # 1000 + 32 whole pages of 2 x 128 x 1024 B
    assert rf.block_attend_kernel_bytes(SDAR, 64, 128_000) == \
        1032 * 2 * 128 * 1024
    assert rf.block_attend_kernel_bytes(SDAR, 0, 0) == 0.0


def test_step_share_reader():
    read = readers.load("block_step_hbm_share")
    spec = Benchmark().layer_metric("block_step_hbm_share")
    want = rf.pass_bytes(SDAR, 64, 128_000) / 819e9 / 0.0154 * 100
    assert read(spec, _ctx(values={"decode_step_ms": 15.4})) == \
        pytest.approx(want)
    assert 70 < want < 85
    # nothing to read: no step time, no capture, no block model
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(values={"decode_step_ms": 15.4},
                           profile={})) is None
    assert read(spec, _ctx(values={"decode_step_ms": 15.4},
                           trace=None)) is None
    for other in ("qwen3-4b-bf16", "kimi-vl-a3b-lm-bf16", "glm-5.2-bf16"):
        cfg = json.loads(
            (ROOT / f"perfbench/configs/{other}.json").read_text())
        assert read(spec, _ctx(values={"decode_step_ms": 15.4},
                               config=cfg)) is None


def test_kernel_share_reader_sums_the_named_events():
    read = readers.load("block_attend_kernel_hbm_share")
    spec = Benchmark().layer_metric("block_attend_kernel_hbm_share")
    hlo = ('%block_attend.11 = bf16[64,128,128]{2,1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call"')
    other = '%paged_decode.3 = bf16[64,32,128]{2,1,0} custom-call(%b)'
    dev = SimpleNamespace(ops=[(0.0, 450e3, hlo), (500e3, 300e3, other),
                               (900e3, 470e3, hlo)], modules=[])
    got = read(spec, _ctx(trace=SimpleNamespace(devices=[dev],
                                                window_s=2.5)))
    want = 2 * rf.block_attend_kernel_bytes(SDAR, 64, 128_000) / 819e9 \
        / 0.92e-3 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    # a program without the kernel (the parent commit's): nothing, no raise
    none = SimpleNamespace(window_s=2.5, devices=[SimpleNamespace(
        ops=[(0.0, 400e3, other)], modules=[])])
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(trace=None)) is None        # an untraced run
    qwen = json.loads(
        (ROOT / "perfbench/configs/qwen3-4b-bf16.json").read_text())
    assert read(spec, _ctx(config=qwen, trace=SimpleNamespace(
        devices=[dev], window_s=2.5))) is None


def test_tokens_per_pass_reader():
    read = readers.load("prom_counter_ratio")
    spec = Benchmark().layer_metric("block_tokens_per_pass")
    num, den = ("kgct_block_tokens_transferred_total", ()), \
        ("kgct_block_passes_total", ())
    got = read(spec, _ctx(scrape_before={num: 1000.0, den: 1250.0},
                          scrape_after={num: 9000.0, den: 11250.0}))
    assert got == pytest.approx(0.8)
    # a program without the counters (the parent commit's): nothing
    assert read(spec, _ctx()) is None


def test_the_cell_loads_and_reports_what_it_must():
    bench = Benchmark()
    bench.validate()
    cell = bench.cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names
    assert {"decode_step_ms", "decode_step_inproc_ms", "mixed_step_ms",
            "moe_expert_load_max_ratio", "grouped_matmul_roofline",
            "chunk_hist_kernel_share", "decode_hbm_share"} <= names
    # a block model's chunks run flash_prefill_hist, never the one-token
    # decode kernels or another configuration's byte models
    assert not names & {"flash_prefill_kernel_share",
                        "latent_moe_decode_hbm_share",
                        "latent_decode_kernel_hbm_share",
                        "dsa_moe_decode_hbm_share", "moe_pairs_held_share"}
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "tpot_p90_ms", "out_tok_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic_name == "batch-decode-2k"
    assert cell.load == {"clients": 64, "start_wave": 8,
                         "start_wave_gap_s": 0.4,
                         "ladder": {"mixed_rows": [], "packed": [1, 2]}}
    for other in bench.cell_names():
        if other != CELL:
            assert not NEW & {m["name"]
                              for m in bench.cell(other).per_layer}
    entry = next(w for w in bench.doc["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "0.8" in entry["why"]
    assert bench.doc["workloads"][-1] is entry          # appended, last
    assert [m["name"] for m in bench.doc["per_layer"][-3:]] == [
        "block_step_hbm_share", "block_attend_kernel_hbm_share",
        "block_tokens_per_pass"]


def test_accepted_metrics_without_a_list_never_raise_on_the_new_file():
    bench = Benchmark()
    for m in bench.doc["per_layer"]:
        if "workloads" in m:
            continue
        spec = bench.layer_metric(m["name"])
        got = readers.load(spec["reader"])(
            spec, _ctx(values={"decode_step_ms": 15.4}, trace=None))
        assert got is None or isinstance(got, float), m["name"]
    # decode_hbm_share reads here from the dense-GQA byte model (a dense MLP
    # of 6144 in every layer, no experts): it means nothing in this cell
    # and is listed because the accepted benchmark's own test
    # (test_roofline_dsa) holds every cell but glm's to report it
    spec = bench.layer_metric("decode_hbm_share")
    got = readers.load("roofline")(
        spec, _ctx(values={"decode_step_ms": 15.4}, trace=None))
    assert 0 < got < 30


def test_configuration_holds_the_catalogs_numbers():
    for key, value in CATALOG.items():
        if key not in SDAR["reduced"]:
            assert SDAR[key] == value, key
    assert SDAR["reduced"] == ["num_hidden_layers",
                               "max_position_embeddings"]
    assert (SDAR["num_hidden_layers"], SDAR["max_position_embeddings"]) \
        == (6, 4096)
    assert SDAR["published"] == {"num_hidden_layers": 48,
                                 "max_position_embeddings": 32768}
    for key, said in (("num_hidden_layers", "48"),
                      ("max_position_embeddings", "32768")):
        assert said in SDAR["reduced_why"][key], key
    assert (SDAR["block_length"], SDAR["denoising_steps"], SDAR["remasking"],
            SDAR["confidence_threshold"], SDAR["mask_token_id"]) == (
        4, 4, "low_confidence_dynamic", 0.9, 151669)
    for key in ("block_length", "denoising_steps", "remasking",
                "confidence_threshold", "mask_token_id", "logit_shift",
                "prompt_prefill", "tie_rules", "checkpoint_tensor_names",
                "random_init"):
        assert key in SDAR["assumed"], key
    assert "ASSUMED" in SDAR["assumed"]["checkpoint_tensor_names"]
    assert "stage 1 of the 8" in SDAR["deployment"]
    assert SDAR["server_flags"] == ["--hf-overrides",
                                    '{"num_hidden_layers": 6}']
    assert SDAR["warmup"]["decode_window"] == 8
    entry = next(c for c in Benchmark().doc["configs"]
                 if c["name"] == SDAR["name"])
    assert entry["reduced"] == SDAR["reduced"]
    assert entry["source"] == SDAR["source"]
    # the preset the server builds IS these numbers
    from perfbench.reference.write_golden import model_config
    cfg = model_config(SDAR)
    assert (cfg.num_layers, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.expert_width, cfg.vocab_size, cfg.block_length,
            cfg.denoising_steps, cfg.mask_token_id,
            cfg.confidence_threshold) == (6, 128, 8, 768, 151936, 4, 4,
                                          151669, 0.9)


def test_there_is_one_copy_of_the_reference():
    assert (ROOT / "perfbench/reference/sdar_moe.py").is_file()
    assert "from perfbench.reference import sdar_moe" in (
        ROOT / "tests/test_block_diffusion.py").read_text()
