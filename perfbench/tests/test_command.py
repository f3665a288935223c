"""The whole command, end to end: what it prints with no chip (cheap), and a
CPU rehearsal of every phase (slow: starts a debug-tiny server)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
REHEARSAL = Path(__file__).parent / "data" / "rehearsal"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(*args, timeout=600):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=ROOT,
        capture_output=True, text=True, timeout=timeout)


def test_no_chip_is_a_nonzero_exit_and_no_result_line():
    """JAX is held to the CPU here: the server's first log line says so and
    the run stops before any weight is built."""
    p = _run("--workload", "debug-tiny.tiny-chat", "--seed", "1",
             "--seconds", "3", "--trace", "0", "--root", str(REHEARSAL),
             timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs the accelerator" in p.stderr


def test_unknown_workload_is_a_nonzero_exit_and_no_result_line():
    p = _run("--workload", "nope", "--seed", "1", "--seconds", "3",
             "--trace", "0", timeout=60)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.slow
@pytest.mark.parametrize("cell,trace", [("debug-tiny.tiny-chat", "0"),
                                        ("debug-tiny.tiny-chat", "1"),
                                        ("debug-tiny.tiny-batch", "0"),
                                        ("debug-tiny.tiny-batch", "1")])
def test_cpu_rehearsal_of_the_whole_command(cell, trace):
    """Synthetic tokenizer, probes, ladder, pre-roll with a probe beside the
    load, a 3-second window, (traced) a profile written inside the checkout,
    drain. The timed requests carry no ``logprobs``;
    that tokens were counted at all proves the tokenizer path emits a
    token-bearing frame per step."""
    doc = json.loads((REHEARSAL / "BENCHMARK.json").read_text())
    p = _run("--workload", cell, "--seed", str(2**31 + 5), "--seconds", "3",
             "--trace", trace, "--cpu-rehearsal", "--root", str(REHEARSAL))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    # exactly the contract's keys on the LAST line (the driver refuses any
    # other); whatever else the run saw is on the line before it
    assert set(line) == CONTRACT_KEYS | ({"breakdown"} & set(line))
    assert "breakdown" not in line or trace == "1"
    extras = json.loads(lines[-2])
    assert {"observed", "end_to_end", "rows_at_probe"} <= set(extras)
    assert not set(extras) & CONTRACT_KEYS
    assert set(line["device"]) >= DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"        # and says so
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    kind = "per_layer" if trace == "1" else "end_to_end"
    allowed = {m["name"] for m in doc[kind]
               if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace == "0":
        assert set(line["metrics"]) == allowed
        assert line["metrics"]["out_tok_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        # no device plane on the CPU: the trace readers return nothing and
        # the harness leaves their metrics out
        assert "device_idle_share" not in line["metrics"]
        assert "batch_rows_mean" in line["metrics"]
    # nothing of the run lies at the program's fixed paths outside the
    # checkout (serve.py, KGCT_FLIGHT_DIR)
    assert "kgct-profile" not in p.stderr and "/tmp/kgct-flight" not in p.stderr
