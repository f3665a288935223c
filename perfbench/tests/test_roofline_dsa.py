"""``roofline_dsa.py`` against the hand arithmetic of ISSUE 46 and the engine's
own tree, the new readers on made-up contexts and traces, the new cell
through ``Benchmark.validate()``."""

import json
from types import SimpleNamespace

import pytest

from perfbench import readers
from perfbench import roofline_dsa as rf
from perfbench.spec import ROOT, Benchmark

GLM = json.loads((ROOT / "perfbench/configs/glm-5.2-bf16.json").read_text())
CELL = "glm-5.2-bf16.batch-long-8k"
NEW = {"dsa_select_share", "dsa_index_roofline",
       "dsa_chosen_attend_hbm_share", "dsa_moe_decode_hbm_share",
       "dsa_chosen_share"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_parameters_by_hand():
    assert rf.has_indexer(GLM) and rf.index_layers(GLM) == 2
    assert rf.router_width(GLM) == 256 and GLM["n_routed_experts"] == 16
    # q_a 6144 x 2048 with its norm, q_b 2048 x 64 x 256, kv_a 6144 x 576
    # with its norm, kv_b 512 x 64 x (192 + 256), o 64 x 256 x 6144
    assert rf.mla_mixer_params(GLM) == (12_582_912 + 2048 + 33_554_432
                                        + 3_538_944 + 512 + 14_680_064
                                        + 100_663_296)
    assert rf.mla_mixer_params(GLM) / 1e6 == pytest.approx(165.0, abs=0.05)
    # wq_b 2048 x 32 x 128, wk 6144 x 128 with LayerNorm, weights_proj
    # 6144 x 32
    assert rf.indexer_params(GLM) == 8_388_608 + 786_432 + 256 + 196_608
    assert rf.indexer_params(GLM) / 1e6 == pytest.approx(9.37, abs=0.005)
    assert rf.expert_params(GLM) == 3 * 6144 * 2048 == 37_748_736
    # the dense layer (it holds an indexer): attention, indexer, two norms,
    # a SwiGLU of 12288
    assert rf.layer_fixed_bytes(GLM, 0) == 2 * (
        rf.mla_mixer_params(GLM) + rf.indexer_params(GLM) + 2 * 6144
        + 3 * 6144 * 12288)
    # a 'shared' expert layer: attention, norms, the shared expert, and the
    # router over 256 with its bias in float32
    assert rf.layer_fixed_bytes(GLM, 1) == 2 * (
        rf.mla_mixer_params(GLM) + 2 * 6144 + 37_748_736) + 4 * 6145 * 256
    assert rf.layer_fixed_bytes(GLM, 4) - rf.layer_fixed_bytes(GLM, 1) == \
        2 * rf.indexer_params(GLM)
    # ISSUE 46's table: 5 x 16 experts 6.04 GB; all of it 9.38 GB
    assert 5 * 16 * rf.expert_params(GLM) * 2 / 1e9 == pytest.approx(
        6.04, abs=0.005)
    # the served tree's own count (eval_shape of models.llama.init_params
    # under the configuration's overrides; /health weight_bytes on the v5e)
    assert rf.resident_weight_bytes(GLM) == 9_395_437_568
    assert rf.resident_weight_bytes(GLM) / 9.38e9 == pytest.approx(1, abs=0.01)


def test_rows_keys_and_a_decode_step_by_hand():
    # [c 512 | k_pe 64] in five 128-lane tiles, bf16
    assert rf.latent_row_elements(GLM) == 640
    assert rf.latent_row_elements(GLM, padded=False) == 576
    # 16 rows at 8.2 k of context choose 2048 each: 2.6 MB a row a layer
    ctx = 16 * 8200
    assert rf.chosen_tokens(GLM, 16, ctx) == 16 * 2048
    assert rf.chosen_rows_bytes(GLM, 16, ctx) == 16 * 2048 * 1280
    assert rf.chosen_rows_bytes(GLM, 1, 8200) / 1e6 == pytest.approx(
        2.62, abs=0.005)
    # a row that sees less than index_topk reads what it sees
    assert rf.chosen_tokens(GLM, 4, 4 * 500) == 2000
    assert rf.chosen_tokens(GLM, 0, 0) == 0.0
    # one index key a token: 128 x 2 B; two indexers over 131 k tokens 67 MB
    assert rf.index_key_bytes(GLM, 1) == 256
    assert 2 * rf.index_key_bytes(GLM, ctx) / 1e6 == pytest.approx(
        67.2, abs=0.05)
    # a 2048-token chunk behind 6144 of history: 2048 x (6144 + 1024.5)
    # visible pairs x 32 x 128 x 2: 0.12 TFLOP an indexer
    pairs = 2048 * 6144 + 2048 * 2049 / 2
    assert rf.index_score_flops(GLM, 1, pairs) / 1e12 == pytest.approx(
        0.12, abs=0.005)
    # a decode step's scores are bound by the keys, not the arithmetic:
    # 16 x 8200 x 8192 FLOP = 1.07 GFLOP = 5.5 us; 33.6 MB = 41 us
    assert rf.index_least_seconds(GLM, PEAKS, 16, ctx) == pytest.approx(
        33_587_200 / 819e9)
    # 16 rows x 8 of 256: a held expert is missed with (1 - 8/256)^16
    assert rf.experts_hit_share(GLM, 16) == pytest.approx(0.398, abs=0.001)
    assert 16 * rf.experts_hit_share(GLM, 16) == pytest.approx(6.37, abs=0.01)
    # ... so a step streams 6.4 x 5 experts (2.41 GB) beside 3.12 GB of
    # other weights and the head, 0.25 GB of chosen rows and 0.07 of keys
    w = rf.streamed_weight_bytes(GLM, 16)
    assert w / 1e9 == pytest.approx(5.52, abs=0.01)
    assert rf.streamed_weight_bytes(GLM, 1e9) == (
        rf.resident_weight_bytes(GLM) - 6144 * 19360 * 2)
    step = rf.decode_step_bytes(GLM, 16, ctx)
    assert step - w == 6 * 16 * 2048 * 1280 + 2 * ctx * 256
    assert step / 819e9 * 1e3 == pytest.approx(7.13, abs=0.02)     # ms


def _ctx(**kw):
    base = dict(config=GLM, peaks=PEAKS,
                profile={"start": 10.0, "end": 45.0}, values={},
                samples=[{"t": 9.0, "rows": 16, "context_tokens": 1},
                         {"t": 11.0, "rows": 16, "context_tokens": 130_000},
                         {"t": 12.0, "rows": 14, "context_tokens": 120_000},
                         {"t": 20.0, "rows": 8, "context_tokens": 70_000},
                         {"t": 40.0, "rows": 2, "context_tokens": 5_000}],
                trace=SimpleNamespace(devices=[], window_s=2.5),
                scrape_before={}, scrape_after={},
                window=(10.0, 13.0), records=[])
    base.update(kw)
    return base


def _others():
    for path in sorted((ROOT / "perfbench/configs").glob("*-bf16.json")):
        if path.stem != "glm-5.2-bf16":
            yield json.loads(path.read_text())


def test_step_share_reader():
    read = readers.load("dsa_step_hbm_share")
    spec = Benchmark().layer_metric("dsa_moe_decode_hbm_share")
    want = rf.decode_step_bytes(GLM, 15, 125_000) / 819e9 / 0.010 * 100
    assert read(spec, _ctx(values={"decode_step_ms": 10.0})) == \
        pytest.approx(want)
    assert 60 < want < 80
    # nothing to read: no step time, no capture, no indexer
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(values={"decode_step_ms": 10.0},
                           profile={})) is None
    assert read(spec, _ctx(values={"decode_step_ms": 10.0},
                           trace=None)) is None
    for cfg in _others():
        assert read(spec, _ctx(values={"decode_step_ms": 10.0},
                               config=cfg)) is None


# As the v5e's compiler writes them (the decode window and the mixed step of
# the cut, compiled for a described chip): the indexer's conditional returns
# the rows' choice, which of it exists and the layer's new index keys ...
COND = ('%cond.86 = (s32[16,2048]{1,0:T(8,128)}, pred[16,2048]{1,0:T(8,128)'
        '(4,1)}, bf16[1,1,16,128]{3,2,1,0:T(8,128)(2,1)}) conditional('
        '%convert_element_type.1061, %tuple.244, %tuple.250), '
        'branch_computations={%region_21.38, %region_22.41}')
COND_MIXED = ('%conditional.18 = (pred[2048,10240]{1,0:T(8,128)(4,1)}, '
              's32[64,2048]{1,0:T(8,128)}, pred[64,2048]{1,0:T(8,128)(4,1)}, '
              'bf16[1,1,2112,128]{2,3,1,0:T(8,128)(2,1)}) conditional('
              '%convert_element_type.1010, %tuple.372, %tuple.373)')
# ... and a step program has other conditionals: a substep's top
# log-probabilities, the mixed step's penalties, bias and top-p.
TOP_LOGPROBS = ('%cond.83 = (s32[1,16,5]{1,2,0:T(8,128)}, f32[1,16,5]{1,2,0:'
                'T(8,128)}) conditional(%convert_element_type.1032, '
                '%cond.82, %tuple.218)')
PENALTIES = ('%conditional.8 = (f32[64,19360]{1,0:T(8,128)}) conditional('
             '%convert_element_type.253, %conditional.7, %tuple.465)')
INNER = '%fusion.9 = f32[16,12289]{1,0} fusion(%k), kind=kOutput'
GATHER = ('%fusion.654 = bf16[32768,640]{1,0:T(8,128)(2,1)S(1)} fusion('
          'bf16[1180416,640]{1,0} %bitcast.522, s32[32768]{0} %idx), '
          'kind=kCustom, calls=%fused_computation.3')
OWN = ('%broadcast_select_fusion.4 = bf16[16,2048,640]{2,1,0:T(8,128)(2,1)} '
       'fusion(bf16[16,2048,640]{2,1,0} %bitcast.543, pred[16,2048]{1,0} %c, '
       'bf16[16,640]{1,0} %row), kind=kLoop')
SCORES = ('%fusion.664 = f32[16,64,2048]{2,1,0} fusion(bf16[16,2048,640]'
          '{2,1,0} %broadcast_select_fusion.4, bf16[16,64,640]{2,0,1} %q), '
          'kind=kOutput')
OTHER = '%fusion.1 = bf16[16,6144]{1,0} fusion(%b), kind=kLoop'


def _trace():
    dev = SimpleNamespace(
        modules=[(0.0, 5e6, "jit_decode_window_greedy(1)"),
                 (6e6, 4e6, "jit_mixed_step(2)")],
        ops=[(0.0, 100e3, COND), (10e3, 60e3, INNER),      # a 'full' layer
             (200e3, 1e3, COND),                           # a 'shared' one
             (210e3, 50e3, TOP_LOGPROBS),                  # not an indexer's
             (300e3, 40e3, GATHER), (340e3, 10e3, OWN), (360e3, 30e3, SCORES),
             (400e3, 70e3, OTHER),
             (6.1e6, 900e3, COND_MIXED),                   # a mixed step's
             (7.2e6, 20e3, PENALTIES)])
    return SimpleNamespace(devices=[dev], window_s=2.5)


def test_an_indexers_conditional_is_told_by_the_choice_it_returns():
    from perfbench.readers.dsa_index_roofline import is_indexer_conditional
    assert is_indexer_conditional(COND, 2048)
    assert is_indexer_conditional(COND_MIXED, 2048)
    for other in (TOP_LOGPROBS, PENALTIES, INNER, GATHER, OWN,
                  COND.replace(" conditional(", " fusion(")):
        assert not is_indexer_conditional(other, 2048)
    assert not is_indexer_conditional(COND, 1024)     # another model's k
    # the five expert layers share ONE scanned body: one of the five events
    # is an indexer's; the dense layer, a section of one, is in none
    assert rf.layers_in_conditionals(GLM) == (1, 5)
    assert rf.layers_in_conditionals(dict(
        GLM, first_k_dense_replace=3,
        indexer_types=["full"] * 3 + ["shared", "full", "shared"])) == (4, 6)


def test_index_roofline_reads_the_decode_windows_indexer_events():
    read = readers.load("dsa_index_roofline")
    spec = Benchmark().layer_metric("dsa_index_roofline")
    got = read(spec, _ctx(trace=_trace()))
    # two events in the window (the mixed step's is outside, the top
    # log-probabilities' conditional is not an indexer's), of which the
    # indexers' share is 1 of 5; the fusion inside is not counted again
    want = 2 * (1 / 5) * rf.index_least_seconds(GLM, PEAKS, 15, 125_000) \
        / 101e-6 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    none = SimpleNamespace(window_s=2.5, devices=[SimpleNamespace(
        ops=[(0.0, 400e3, OTHER), (500e3, 50e3, TOP_LOGPROBS)],
        modules=[(0.0, 5e6, "jit_decode_window_greedy(1)")])])
    assert read(spec, _ctx(trace=none)) is None   # a program without one
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(trace=None)) is None            # an untraced run
    for cfg in _others():
        assert read(spec, _ctx(config=cfg, trace=_trace())) is None


def test_select_share_is_the_indexers_share_of_busy_time():
    read = readers.load("dsa_select_share")
    spec = Benchmark().layer_metric("dsa_select_share")
    busy = (100e3 + 1e3 + 50e3 + 50e3 + 30e3 + 70e3 + 900e3 + 20e3)
    # the events seen are the expert section's one indexer; the dense
    # layer's, of the same shapes, is in no conditional: x 2 / 1
    assert read(spec, _ctx(trace=_trace())) == pytest.approx(
        (100e3 + 1e3 + 900e3) * 2 / busy * 100)
    none = SimpleNamespace(window_s=2.5, devices=[SimpleNamespace(
        ops=[(0.0, 400e3, OTHER), (500e3, 50e3, PENALTIES)], modules=[])])
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx(trace=None)) is None
    for cfg in _others():
        assert read(spec, _ctx(config=cfg, trace=_trace())) is None


def test_rows_attend_reader_finds_the_chosen_rows_by_their_shape():
    read = readers.load("dsa_rows_attend_hbm_share")
    spec = Benchmark().layer_metric("dsa_chosen_attend_hbm_share")
    got = read(spec, _ctx(trace=_trace()))
    # one layer call (one [16, 2048, 640] made); the flat gather before it
    # and its consumer count into the time
    want = rf.chosen_rows_bytes(GLM, 15, 125_000) / 819e9 / 80e-6 * 100
    assert got == pytest.approx(want) and 0 < got < 100
    none = SimpleNamespace(window_s=2.5, devices=[SimpleNamespace(
        ops=[(0.0, 400e3, OTHER)], modules=[])])
    assert read(spec, _ctx(trace=none)) is None
    assert read(spec, _ctx(trace=None)) is None
    for cfg in _others():
        assert read(spec, _ctx(config=cfg, trace=_trace())) is None


def test_chosen_share_is_the_ratio_of_the_two_counters():
    read = readers.load("prom_counter_ratio")
    spec = Benchmark().layer_metric("dsa_chosen_share")
    before = {("kgct_dsa_chosen_tokens_total", ()): 1000.0,
              ("kgct_dsa_visible_tokens_total", ()): 4000.0}
    after = {("kgct_dsa_chosen_tokens_total", ()): 1000.0 + 2048 * 50,
             ("kgct_dsa_visible_tokens_total", ()): 4000.0 + 8192 * 50}
    assert read(spec, _ctx(scrape_before=before, scrape_after=after)) == \
        pytest.approx(25.0)
    # a program without the counters (the parent commit's): nothing
    assert read(spec, _ctx()) is None
    assert read(spec, _ctx(scrape_before=before, scrape_after=before)) is None


def test_the_cell_loads_and_reports_what_it_must():
    bench = Benchmark()
    bench.validate()
    cell = bench.cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names
    # (not moe_pairs_held_share: the benchmark's own test of kimi-linear's
    # cell holds that metric to be that cell's alone)
    assert {"mixed_step_ms", "moe_expert_load_max_ratio",
            "steps_dispatched_behind_share",
            "flash_prefill_kernel_share", "decode_step_ms",
            "prefill_mean_ms"} <= names
    # other byte models, and counts over all routed pairs
    assert not names & {"decode_hbm_share", "latent_moe_decode_hbm_share",
                        "latent_decode_kernel_hbm_share",
                        "grouped_matmul_roofline",
                        "grouped_matmul_tile_fill_share"}
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms", "tpot_p90_ms", "out_tok_s", "setup_s"}
    assert cell.chips == 1 and cell.traffic_name == "batch-long-8k"
    assert cell.load == {"clients": 16, "start_wave": 4,
                         "start_wave_gap_s": 2.0,
                         "ladder": {"mixed_rows": [], "packed": [1]}}
    t = cell.traffic
    assert (t["loop"], t["per_client"], t["preroll_s"], t["min_prerolls"],
            t["stall_s"]) == ("closed", 4, 20, 2, 10)    # ISSUE 46's
    assert t["prompt_len"] == {"dist": "uniform", "min": 7168, "max": 8064}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert t["sampling"] == {"temperature": 0.0}
    assert 8064 + 1024 < cell.config["max_position_embeddings"] == 12288
    assert "--max-num-seqs" in cell.config["server_flags"]
    # the first probe is two chunks behind history, and long enough that
    # every compared position chooses 2048 of more than twice as many
    assert cell.golden_path.is_file()
    golden = json.loads(cell.golden_path.read_text())
    assert golden["captured_on"]["platform"] == "cpu"      # the reference's
    assert "glm5_2.py" in golden["about"]
    assert len(golden["probes"][0]["prompt"]) >= 4400
    assert len(golden["probes"][1]["prompt"]) == 24
    # the new metrics are this cell's alone; decode_hbm_share is the five
    # accepted cells'
    for other in bench.cell_names():
        if other != CELL:
            theirs = {m["name"] for m in bench.cell(other).per_layer}
            assert not NEW & theirs and "decode_hbm_share" in theirs
    entry = next(w for w in bench.doc["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "2048" in entry["why"]


def test_the_configuration_file_keeps_every_published_width():
    """Every number of the catalog row's config under the same key, but for
    the keys ``reduced`` names; no width among them."""
    entry = next(c for c in Benchmark().doc["configs"]
                 if c["name"] == "glm-5.2-bf16")
    assert entry["reduced"] == GLM["reduced"]
    assert set(GLM["reduced"]) == set(GLM["reduced_why"]) == \
        set(GLM["published"])
    for key in GLM["reduced"]:
        assert key == "vocab_size" or (
            not key.endswith(("_dim", "_rank", "_size", "_heads"))
            and key != "num_experts_per_tok")
    assert (GLM["hidden_size"], GLM["intermediate_size"],
            GLM["moe_intermediate_size"], GLM["q_lora_rank"],
            GLM["kv_lora_rank"], GLM["index_head_dim"], GLM["index_n_heads"],
            GLM["index_topk"], GLM["num_experts_per_tok"]) == (
        6144, 12288, 2048, 2048, 512, 128, 32, 2048, 8)
    assert len(GLM["indexer_types"]) == len(GLM["mlp_layer_types"]) == \
        GLM["num_hidden_layers"] == 6
