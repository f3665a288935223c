import json
import re
import shutil
from pathlib import Path

import pytest

from perfbench import readers
from perfbench.spec import ROOT, Benchmark, SpecError, check_name, check_unit

REHEARSAL = Path(__file__).parent / "data" / "rehearsal"


def test_real_benchmark_resolves_and_validates():
    b = Benchmark()
    b.validate()
    for name in b.cell_names():
        cell = b.cell(name)
        assert cell.config["preset"] and cell.traffic["loop"] in ("open",
                                                                  "closed")
        assert ("rate_rps" in cell.load) != ("clients" in cell.load)
        for m in cell.per_layer:
            assert callable(readers.load(m["reader"]))


def test_contract_shape_of_benchmark_json():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["perfbench"]
    assert 1 <= doc["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (doc["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    assert (ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_names_units_and_file_names_use_allowed_characters():
    for good in ("ttft_p50_ms.batch", "qwen2.5-7b-int8", "_x", "9a"):
        check_name(good, "t")
    for bad in ("", "a b", "a,b", "a/b", "-a", ".a", "µs", "x" * 65):
        with pytest.raises(SpecError):
            check_name(bad, "t")
    for good in ("tokens/s", "%", "ms", "rows"):
        check_unit(good, "t")
    for bad in ("tokens per second", "", "µs", "x" * 17):
        with pytest.raises(SpecError):
            check_unit(bad, "t")
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    here = ROOT / "perfbench"
    for p in here.rglob("*"):
        rel = p.relative_to(ROOT).as_posix()
        if ".cache" in rel or "__pycache__" in rel:
            continue
        assert ok.match(rel), rel


def test_a_cell_added_as_files_only_is_picked_up(tmp_path):
    """A later PR's whole cell: one workloads entry, a new end-to-end and a
    new per-layer metric for it, plus files of its own. No existing file is
    edited (BENCHMARK.json only gains entries)."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["end_to_end"].append({"name": "ttft_p50_ms", "unit": "ms",
                              "better": "lower", "bound": 0.1,
                              "source": "host_clock",
                              "workloads": ["qwen3-4b-bf16.chat-burst"]})
    doc["workloads"].append({"name": "qwen3-4b-bf16.chat-burst",
                             "config": "qwen3-4b-bf16",
                             "traffic": "chat-burst", "chips": 1,
                             "why": "bursty arrivals"})
    doc["per_layer"].append({"name": "prefill_tokens", "unit": "tokens",
                             "better": "higher", "source": "program_counter",
                             "layer": "scheduler", "moves": "out_tok_s",
                             "workloads": ["qwen3-4b-bf16.chat-burst"]})
    doc["per_layer"].append({"name": "queue_wait_p50_ms", "unit": "ms",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler", "moves": "ttft_p50_ms",
                             "workloads": ["qwen3-4b-bf16.chat-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    new = tmp_path / "perfbench"
    for d in ("traffic", "cells", "layer_metrics"):
        (new / d).mkdir(parents=True)
    burst = json.loads((Path(__file__).parent
                        / "data/traffic/chat-steady.json").read_text())
    burst["arrivals"] = {"process": "uniform"}
    (new / "traffic/chat-burst.json").write_text(json.dumps(burst))
    (new / "cells/qwen3-4b-bf16.chat-burst.json").write_text(
        json.dumps({"rate_rps": 2.5}))
    (new / "layer_metrics/prefill_tokens.json").write_text(json.dumps(
        {"reader": "prom_counter_delta",
         "family": "kgct_prefill_tokens_total"}))
    shutil.copy(REHEARSAL / "perfbench/layer_metrics/queue_wait_p50_ms.json",
                new / "layer_metrics/queue_wait_p50_ms.json")
    b = Benchmark(tmp_path)
    b.validate()
    cell = b.cell("qwen3-4b-bf16.chat-burst")
    assert cell.traffic["arrivals"]["process"] == "uniform"
    assert cell.load["rate_rps"] == 2.5
    assert {"prefill_tokens", "queue_wait_p50_ms"} <= \
        {m["name"] for m in cell.per_layer}
    assert "ttft_p50_ms" in [m["name"] for m in cell.end_to_end]
    # ... and the cell that was there does not report the new cell's metrics
    old = b.cell("qwen3-4b-bf16.batch-decode")
    assert "ttft_p50_ms" not in [m["name"] for m in old.end_to_end]
    assert "prefill_tokens" not in [m["name"] for m in old.per_layer]


def test_unknown_names_are_errors(tmp_path):
    b = Benchmark()
    with pytest.raises(SpecError):
        b.cell("no-such-cell")
    with pytest.raises(SpecError):
        b.traffic("no-such-traffic")
    with pytest.raises(SpecError):
        b.layer_metric("no_such_metric")


def test_rehearsal_root_resolves():
    b = Benchmark(REHEARSAL)
    b.validate()
    assert b.cell("debug-tiny.tiny-chat").config["preset"] == "debug-tiny"
