import json
from pathlib import Path

import pytest

from perfbench import roofline as rf
from perfbench.spec import ROOT

Q3 = json.loads((ROOT / "perfbench/configs/qwen3-4b-bf16.json").read_text())
Q25 = json.loads((Path(__file__).parent
                  / "data/configs/qwen2.5-7b-int8.json").read_text())


def test_qwen3_4b_bf16_by_hand():
    # per layer: q 2560x4096, k and v 2560x1024 each, o 4096x2560,
    # gate/up/down 3 x 2560x9728; 36 layers; tied head 2560x151936
    layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    params = 36 * layer + 2560 * 151936
    assert params == 4_022_272_000
    assert rf.streamed_weight_bytes(Q3) == 2 * params          # 8.04 GB
    assert rf.resident_weight_bytes(Q3) == 2 * params          # tied: once
    assert rf.resident_weight_bytes(Q3) / 1e9 == pytest.approx(8.04, abs=0.01)
    assert rf.kv_bytes_per_token(Q3) == 147_456      # 2 x 36 x 8 x 128 x 2
    assert rf.matmul_flops_per_token(Q3) == 2 * params
    # 64 rows at 400 tokens: 8.04 GB + 64 x 400 x 147456 B
    assert rf.decode_step_bytes(Q3, 64 * 400) == 2 * params + 3_774_873_600
    assert rf.attention_flops_per_token(Q3, 1000) == 4 * 36 * 32 * 128 * 1000


def test_qwen25_7b_int8_by_hand():
    cols = 28 * (3584 + 2 * 512 + 3584 + 2 * 18944 + 3584) + 152064
    layer = 3584 * 3584 + 2 * 3584 * 512 + 3584 * 3584 + 3 * 3584 * 18944
    params = 28 * layer + 3584 * 152064
    assert params == 7_070_285_824
    # int8: one byte a weight and one f32 scale per output column
    assert rf.streamed_weight_bytes(Q25) == params + 4 * cols   # 7.08 GB
    assert rf.resident_weight_bytes(Q25) == \
        params + 4 * cols + 2 * 3584 * 152064                 # + bf16 embed
    assert rf.resident_weight_bytes(Q25) / 1e9 == pytest.approx(8.17, abs=0.01)
    assert rf.kv_bytes_per_token(Q25) == 57_344        # 2 x 28 x 4 x 128 x 2
    assert rf.matmul_flops_per_token(Q25) == 2 * params     # 14.1 GFLOP


def test_peaks_table_and_unknown_device():
    v5e = rf.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(rf.UnknownDevice):
        rf.peaks_for("TPU v9 imaginary")
    with pytest.raises(rf.UnknownDevice):
        rf.peaks_for("_source")
    with pytest.raises(rf.UnknownDevice):
        rf.peaks_for("cpu")
