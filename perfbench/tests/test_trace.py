"""The trace reducer against a small RECORDED trace: 0.26 s of the device
plane (operation and module lines) of a traced ``qwen3-4b-bf16.batch-decode``
run on a TPU v5e (PR 23): one packed prefill step, one greedy decode window
of 8 steps at 64 rows, and the eager one-op modules the host issues between
them. Expected values were worked out independently (a 100 ns raster of the
operation intervals for the busy time; the module durations read off the
file)."""

from pathlib import Path

import pytest

from perfbench import trace as tr
from perfbench.readers import load as load_reader

SLICE = Path(__file__).resolve().parents[1] / "testdata" / \
    "batch_decode_slice.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tr.load(SLICE)


def test_planes_and_lines(summary):
    assert [d.name for d in summary.devices] == ["/device:TPU:0"]
    dev = summary.devices[0]
    assert len(dev.ops) == 24879 and len(dev.modules) == 18
    assert summary.window_s == pytest.approx(0.259958862, rel=1e-9)


def test_busy_is_the_union_not_the_sum(summary):
    dev = summary.devices[0]
    # the operation line nests (while > fusion): the plain sum overcounts
    assert sum(d for _, d, _ in dev.ops) / 1e9 > 0.4
    assert tr.busy_seconds(dev) == pytest.approx(0.2450738, abs=2e-6)
    assert tr.mean_busy_seconds(summary) == tr.busy_seconds(dev)
    idle = load_reader("trace_idle")({"scale": 100.0}, {"trace": summary})
    assert idle == pytest.approx(100 * (1 - 0.2450738 / 0.259958862),
                                 abs=1e-3)      # 5.73 %


def test_module_time_and_decode_step(summary):
    dev = summary.devices[0]
    assert tr.module_time(dev, "decode_window") == \
        (pytest.approx(0.159312967), 1)
    assert tr.module_time(dev, "prefill_step") == \
        (pytest.approx(0.036707, abs=1e-6), 1)
    assert tr.module_time(dev, "no_such_module") == (0.0, 0)
    spec = {"contains": "decode_window", "steps_per_module": "decode_window",
            "scale": 1000.0}
    ctx = {"trace": summary, "config": {"warmup": {"decode_window": 8}}}
    step_ms = load_reader("trace_module_time")(spec, ctx)
    assert step_ms == pytest.approx(159.312967 / 8)         # 19.9 ms a step


def test_self_time_by_operation_sums_to_busy(summary):
    dev = summary.devices[0]
    ops = tr.op_seconds_by_name(dev)
    assert sum(ops.values()) == pytest.approx(tr.busy_seconds(dev), rel=1e-6)
    top = max(ops.items(), key=lambda kv: kv[1])
    # the Pallas paged-decode kernel is the largest single operation
    assert top[0].startswith("%closed_call.20 custom-call")
    assert top[1] == pytest.approx(0.0721066, abs=1e-6)
    share = load_reader("trace_op_share")(
        {"contains": ["custom-call"], "scale": 100.0}, {"trace": summary})
    assert share == pytest.approx(100 * 0.078023918 / 0.2450738, abs=1e-3)


def test_idle_gaps_name_the_modules_around_them(summary):
    gaps = tr.idle_gaps(summary.devices[0], 3)
    assert [round(s, 6) for _, s in gaps] == [0.003658, 0.003351, 0.002502]
    assert gaps[1][0] == "jit__unstack->jit__unstack"


def test_short_names():
    assert tr.short("jit_decode_window_greedy(2735105749287230563)") == \
        "jit_decode_window_greedy"
    hlo = ('%sort.9 = (f32[64,151936]{1,0:T(8,128)S(1)}, s32[64,151936]'
           '{1,0:T(8,128)}) sort(f32[64,151936]{1,0:T(8,128)} %gte.187), '
           'dimensions={1}')
    assert tr.short_op(hlo) == "%sort.9 sort"
    call = ('%closed_call.21 = bf16[2048,32,128]{2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(s32[32]{0:T(128)S(1)} %b), '
            'custom_call_target="tpu_custom_call"')
    assert tr.short_op(call) == "%closed_call.21 custom-call tpu_custom_call"


def test_readers_return_nothing_without_a_device_plane():
    empty = tr.TraceSummary([], 0.0, 1e9)
    for name, spec in (("trace_idle", {}),
                       ("trace_op_share", {"contains": ["x"]}),
                       ("trace_module_time", {"contains": "x"})):
        assert load_reader(name)(spec, {"trace": empty, "config": {}}) is None
        assert load_reader(name)(spec, {"trace": None, "config": {}}) is None


def test_roofline_reader_by_hand():
    import json
    from perfbench.spec import ROOT
    cfg = json.loads((ROOT / "perfbench/configs/qwen3-4b-bf16.json")
                     .read_text())
    ctx = {"values": {"decode_step_ms": 20.0}, "config": cfg,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "profile": {"start": 10.0, "end": 13.0},
           "samples": [{"t": 9.0, "rows": 64, "context_tokens": 1},
                       {"t": 11.0, "rows": 64, "context_tokens": 20000},
                       {"t": 12.0, "rows": 64, "context_tokens": 30000}]}
    got = load_reader("roofline")({"step_metric": "decode_step_ms",
                                   "scale": 100.0}, ctx)
    # (8,044,544,000 + 25,000 x 147,456) B / 819e9 B/s = 14.32 ms of 20 ms
    assert got == pytest.approx(100 * (8_044_544_000 + 25_000 * 147_456)
                                / 819e9 / 0.020)
    assert got == pytest.approx(71.6, abs=0.1)
