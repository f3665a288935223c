"""``roofline_ssm.py`` against the hand arithmetic of ISSUE 32, the new
readers on made-up contexts, the new cell through ``Benchmark.validate()``,
and every accepted per-layer metric without a ``workloads`` list on the new
configuration file (a number or None, never a raise)."""

import json
from types import SimpleNamespace

import pytest

from perfbench import readers
from perfbench import roofline_ssm as rf
from perfbench.spec import ROOT, Benchmark

GRANITE = json.loads(
    (ROOT / "perfbench/configs/granite-4.0-h-micro-bf16.json").read_text())
CELL = "granite-4.0-h-micro-bf16.batch-decode-2k"
NEW = {"hybrid_decode_hbm_share", "ssm_update_kernel_hbm_share",
       "hybrid_mixed_step_ms", "state_slots_used_peak_share"}


def test_parameters_by_hand():
    assert rf.layer_counts(GRANITE) == (36, 4)
    assert (rf.d_inner(GRANITE), rf.conv_channels(GRANITE)) == (4096, 4352)
    # in-projection 2048 x (4096 + 4352 + 64), out-projection 4096 x 2048,
    # conv 4352 x (4 + 1), dt_bias + A_log + D, the gated norm
    assert rf.state_mixer_params(GRANITE) == \
        17_432_576 + 8_388_608 + 21_760 + 192 + 4096
    assert rf.mlp_params(GRANITE) == 3 * 2048 * 8192 == 50_331_648
    # W_q and W_o 2048 x 2048, W_k and W_v 2048 x 512
    assert rf.attention_params(GRANITE) == 2 * 4_194_304 + 2 * 1_048_576
    assert rf.layer_params(GRANITE, "mamba") / 1e6 == pytest.approx(
        76.18, abs=0.01)
    assert rf.layer_params(GRANITE, "attention") / 1e6 == pytest.approx(
        60.82, abs=0.01)
    # 36 x 76.18 M + 4 x 60.82 M + 205.5 M (one table: the head is tied)
    assert rf.resident_weight_bytes(GRANITE) / 1e9 == pytest.approx(
        6.383, abs=0.002)
    assert rf.streamed_weight_bytes(GRANITE) == \
        rf.resident_weight_bytes(GRANITE)
    # the served tree's own count (/health weight_bytes; tests/test_ssm_hybrid
    # holds state_bytes_per_seq to the same hand count from the program's side)
    assert rf.resident_weight_bytes(GRANITE) == 6_382_806_016


def test_state_and_pages_by_hand():
    # 64 heads x 64 x 128 float32 = 2 MiB, and 3 conv rows of 4352 bf16
    assert rf.state_bytes_per_row_layer(GRANITE) == 2_097_152 + 26_112
    assert rf.state_bytes_per_seq(GRANITE) == 36 * 2_123_264 == 76_437_504
    assert 65 * rf.state_bytes_per_seq(GRANITE) / 1e9 == pytest.approx(
        4.97, abs=0.005)
    # 4 attention layers x (K + V) x 8 heads x 64 x 2 B
    assert rf.kv_bytes_per_token(GRANITE) == 8192
    # a decode step at 64 rows and 115 k cached tokens: 6.38 GB of weights,
    # 9.78 GB of state (9.66 of it the recurrent state), 0.94 GB of pages
    step = rf.decode_step_bytes(GRANITE, 64, 115_000)
    assert 2 * 64 * 36 * 2_097_152 / 1e9 == pytest.approx(9.66, abs=0.01)
    assert (step - rf.streamed_weight_bytes(GRANITE)) / 1e9 == \
        pytest.approx(9.78 + 0.94, abs=0.01)
    assert step / 819e9 * 1e3 == pytest.approx(20.9, abs=0.1)     # ms
    # one call of the update: 64 rows' state there and back, and per row
    # decay, dt * x, y (4096 float32 each) and B, C (128 each)
    assert rf.ssm_update_kernel_bytes(GRANITE, 64) == \
        64 * (2 * 2_097_152 + 3 * 16_384 + 2 * 512)
    assert rf.ssm_update_kernel_bytes(GRANITE, 64) / 819e9 * 1e6 == \
        pytest.approx(331.7, abs=0.1)                             # us


def test_flops_by_hand():
    # a token in one state layer at chunk 256: C B^T 65,536, the masked
    # product against x 2,097,152, the increment and the carried read
    # 1,048,576 each
    assert rf.scan_flops_per_token(GRANITE) == \
        65_536 + 2_097_152 + 2 * 1_048_576
    # a mixed step's 2048 tokens through 36 scans: 0.31 TFLOP of ~13.4
    scan = 2048 * 36 * rf.scan_flops_per_token(GRANITE)
    assert scan / 1e12 == pytest.approx(0.314, abs=0.002)
    assert 2048 * rf.matmul_flops_per_token(GRANITE) / 1e12 == \
        pytest.approx(13.07, abs=0.02)


def _ctx(**kw):
    # the POST lasts until stop_trace() returns (t = 45): only the samples
    # of the capture itself (2.5 s and a second to start) are the step's
    base = dict(config=GRANITE, peaks={"hbm_bytes_per_s": 819e9},
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
    read = readers.load("hybrid_step_hbm_share")
    spec = Benchmark().layer_metric("hybrid_decode_hbm_share")
    want = rf.decode_step_bytes(GRANITE, 63, 115_000) / 819e9 / 0.025 * 100
    assert read(spec, _ctx(values={"decode_step_ms": 25.0})) == \
        pytest.approx(want)
    assert 75 < want < 90
    # nothing to read: no step time, no capture, no state layers
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(values={"decode_step_ms": 25.0},
                           profile={})) is None
    assert read(spec, _ctx(values={"decode_step_ms": 25.0},
                           trace=None)) is None
    for other in ("qwen3-4b-bf16", "kimi-vl-a3b-lm-bf16"):
        cfg = json.loads(
            (ROOT / f"perfbench/configs/{other}.json").read_text())
        assert read(spec, _ctx(values={"decode_step_ms": 25.0},
                               config=cfg)) is None


def test_kernel_share_reader_sums_the_named_events():
    read = readers.load("ssm_kernel_hbm_share")
    spec = Benchmark().layer_metric("ssm_update_kernel_hbm_share")
    hlo = ('%ssm_update.7 = (f32[36,65,128,4096]{3,2,1,0}, f32[64,1,4096]'
           '{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"')
    other = '%fusion.1 = bf16[64,2048]{1,0} fusion(%b), kind=kLoop'
    dev = SimpleNamespace(ops=[(0.0, 400e3, hlo), (500e3, 300e3, other),
                               (900e3, 420e3, hlo)], modules=[])
    got = read(spec, _ctx(trace=SimpleNamespace(devices=[dev],
                                                window_s=2.5)))
    want = 2 * rf.ssm_update_kernel_bytes(GRANITE, 63) / 819e9 \
        / 0.82e-3 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    # a program without the kernel: nothing, no raise
    none = SimpleNamespace(window_s=2.5, devices=[SimpleNamespace(
        ops=[(0.0, 400e3, other)], modules=[])])
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(trace=None)) is None        # an untraced run


def test_slot_gauge_reader():
    read = readers.load("prom_gauge_sampled")
    spec = Benchmark().layer_metric("state_slots_used_peak_share")
    free, total = "kgct_state_slots_free", "kgct_state_slots_total"
    samples = [{"t": t, "scrape": {(free, ()): f, (total, ()): 64.0}}
               for t, f in ((10.5, 3.0), (11.5, 1.0), (12.5, 2.0))]
    assert read(spec, _ctx(samples=samples)) == pytest.approx(
        100 * (1 - 1 / 64))
    assert read(spec, _ctx()) is None          # an untraced run: no scrapes


def test_the_cell_loads_and_reports_what_it_must():
    bench = Benchmark()
    bench.validate()
    cell = bench.cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names and "mixed_step_ms" not in names
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
    assert "granite_4_0_h.py" in golden["about"]
    # the new metrics are this cell's alone
    for other in bench.cell_names():
        if other != CELL:
            assert not NEW & {m["name"] for m in bench.cell(other).per_layer}


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
            spec, _ctx(values={"decode_step_ms": 25.0}, trace=None))
        assert got is None or isinstance(got, float), m["name"]
    # decode_hbm_share DOES read here, from a dense-GQA byte model (40
    # layers of K|V, attention projections in every layer, no state): PERF.md
    # section 7
    spec = bench.layer_metric("decode_hbm_share")
    got = readers.load("roofline")(
        spec, _ctx(values={"decode_step_ms": 25.0}, trace=None,
                   profile={"start": 10.0, "end": 13.0}))
    from perfbench import roofline
    assert roofline.kv_bytes_per_token(GRANITE) == 81_920
    assert roofline.streamed_weight_bytes(GRANITE) / 1e9 == pytest.approx(
        5.28, abs=0.01)
    assert got == pytest.approx(
        (roofline.streamed_weight_bytes(GRANITE) + 81_920 * 115_000)
        / 819e9 / 0.025 * 100)


def test_configuration_holds_the_catalogs_numbers():
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "shared_intermediate_size": 8192,
        "vocab_size": 100352}
    for key, value in published.items():
        assert GRANITE[key] == value, key
    assert GRANITE["layer_types"] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert GRANITE["reduced"] == ["max_position_embeddings"]
    assert GRANITE["max_position_embeddings"] == 4096
    assert "131072" in GRANITE["reduced_why"]["max_position_embeddings"]
    assert "FLOAT32" in GRANITE["assumed"]["state_dtype"]
    assert GRANITE["server_flags"] == []


def test_there_is_one_copy_of_the_reference():
    assert (ROOT / "perfbench/reference/granite_4_0_h.py").is_file()
    assert "from perfbench.reference import granite_4_0_h" in (
        ROOT / "tests/test_ssm_hybrid.py").read_text()
