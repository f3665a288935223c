"""Bench stdout contract + prefill roofline model.

The r5 official record landed ``"parsed": null`` because the driver-side
parser failed silently on the captured transcript. The contract is now
symmetric and documented: ``emit_result`` guarantees the last stdout line
is the JSON result, ``bench.py --help`` documents that guarantee, and
``parse_result_line`` is the reference consumer — these tests pin that a
driver-captured multi-line transcript (noise before AND after flushes,
blank lines, progress spam) round-trips, and that failures RAISE instead
of yielding null.
"""

import json
import math

import pytest

import bench


def _fake_results():
    return [{
        "model": "debug-tiny", "quantization": None, "batch": 8,
        "decode_window": 4, "prefill_budget": 256,
        "decode_tokens_per_sec": 123.4,
        "sampled_over_greedy": 0.95,
        "mixed_batch": True,
        "ttft_decomposition": {"queue_ms": 1.0, "prefill_ms": 2.0,
                               "first_fetch_ms": 3.0, "samples": 8},
    }]


class TestTranscriptParsing:
    def test_noisy_multiline_transcript_round_trips(self):
        """A realistic driver capture: library spam, blank lines, progress
        dots before the result line, trailing newlines after it."""
        result = bench.assemble_output(_fake_results(), "cpu")
        transcript = (
            "INFO something initialized\n"
            "downloading... 47%\n"
            "\n"
            "{'not': 'the result — a repr, not JSON'}\n"
            "warmup window 3/3 done\n"
            + json.dumps(result) + "\n\n"
        )
        parsed = bench.parse_result_line(transcript)
        assert parsed["value"] == 123.4
        assert parsed["unit"] == "tokens/s/chip"
        assert parsed["mixed_batch"] is True

    def test_emit_result_then_parse_round_trips(self, capsys):
        """emit_result -> parse_result_line is the full contract loop,
        including earlier unflushed stdout noise."""
        print("earlier unflushed noise")
        print("more noise { with: braces }")
        bench.emit_result(bench.assemble_output(_fake_results(), "cpu"))
        captured = capsys.readouterr().out
        parsed = bench.parse_result_line(captured)
        assert parsed["backend"] == "cpu"
        assert not math.isnan(parsed["vs_baseline"])

    def test_garbage_last_line_raises_not_null(self):
        with pytest.raises(ValueError, match="not the bench result JSON"):
            bench.parse_result_line("noise\n" + json.dumps({"ok": 1})
                                    + "\ntrailing non-json garbage\n")

    def test_empty_transcript_raises(self):
        with pytest.raises(ValueError, match="empty bench stdout"):
            bench.parse_result_line("\n\n   \n")

    def test_non_object_result_raises(self):
        with pytest.raises(ValueError, match="expected a JSON object"):
            bench.parse_result_line("[1, 2, 3]\n")


class TestDriverRecordGuard:
    """The official-record failure modes of the r4/r5 driver captures: a
    result behind a noisy WARNING/INFO preamble parsed fine (pinned by
    TestTranscriptParsing's noisy-transcript test); r5's record landed
    "parsed": null because its result line outgrew the driver's 2000-char
    tail window and the capture DECAPITATED it. emit_result now bounds the
    line (RESULT_LINE_MAX) so a tail capture can never cut the head off
    again. (The captures themselves were deleted with the rest of the
    r01-r05 records in PR 21; the decapitation is reproduced here.)"""

    def test_decapitated_tail_raises_not_null(self):
        """The r5 failure mode itself: the tail window cut the head off an
        oversized result line. parse_result_line must RAISE (the driver
        records the error) — a silent null is how r5's numbers vanished."""
        line = json.dumps(self._oversized_result())
        tail = line[-2000:]
        assert len(line) > 2000 and not tail.startswith("{")
        with pytest.raises(ValueError, match="not the bench result JSON"):
            bench.parse_result_line(tail)

    def _oversized_result(self):
        # r05-scale: many configs, each carrying the nested bench blocks
        configs = [dict(_fake_results()[0],
                        roofline={"hbm_gbps": 575.7, "mfu": 0.29,
                                  "chip": {"hbm_gbps_peak": 819.0}},
                        sustained_load={"ttft_p50_ms": 3436.8,
                                        "ttft_p95_ms": 6331.1},
                        speculative={"spec": {"acceptance_ratio": 0.8}},
                        trial=i)
                   for i in range(8)]
        return bench.assemble_output(configs, "tpu")

    def test_oversized_result_survives_a_2000_char_tail(self, capsys):
        out = self._oversized_result()
        assert len(json.dumps(out)) > 2000   # genuinely r05-sized
        print("warmup noise " * 40)
        bench.emit_result(out)
        captured = capsys.readouterr()
        tail = captured.out[-2000:]          # the driver's capture window
        parsed = bench.parse_result_line(tail)
        assert parsed["value"] == out["value"]
        assert parsed["metric"] == out["metric"]
        assert parsed["configs_on_stderr"] is True
        # nothing lost: the full result rides stderr
        full_lines = [ln for ln in captured.err.splitlines()
                      if ln.startswith("FULL_RESULT: ")]
        assert len(full_lines) == 1
        assert json.loads(full_lines[0][len("FULL_RESULT: "):]) == out

    def test_result_line_always_bounded(self, capsys):
        bench.emit_result(self._oversized_result())
        last = capsys.readouterr().out.splitlines()[-1]
        assert len(last) <= bench.RESULT_LINE_MAX < 2000

    def test_headline_bloat_degrades_but_never_fails(self, capsys):
        """Even when a headline block itself outgrows the bound (so
        dropping configs isn't enough), emit_result degrades block by
        block — the primary metric/value/unit always land on stdout,
        bounded. It must never raise or emit an unbounded line."""
        out = self._oversized_result()
        out["ttft_decomposition"] = {f"k{i}": float(i) for i in range(400)}
        bench.emit_result(out)
        last = capsys.readouterr().out.splitlines()[-1]
        assert len(last) <= bench.RESULT_LINE_MAX
        parsed = json.loads(last)
        assert parsed["metric"] == out["metric"]
        assert parsed["value"] == out["value"]
        assert "ttft_decomposition" not in parsed

    def test_small_result_passes_through_unshrunk(self, capsys):
        out = bench.assemble_output(_fake_results(), "cpu")
        assert bench.compact_result(out) is out
        bench.emit_result(out)
        parsed = bench.parse_result_line(capsys.readouterr().out)
        assert parsed == json.loads(json.dumps(out))
        assert "configs" in parsed

    def test_help_documents_the_bound(self):
        text = bench.build_arg_parser().format_help()
        assert "RESULT_LINE_MAX" in text and "tail" in text.lower()


class TestHelpDocumentsContract:
    def test_help_text_states_last_line_contract(self):
        text = bench.build_arg_parser().format_help()
        assert "LAST non-empty line of stdout" in text
        assert "single-line JSON object" in text
        assert "parse_result_line" in text

    def test_help_lists_env_knobs(self):
        text = bench.build_arg_parser().format_help()
        for knob in ("KGCT_BENCH_MODEL", "KGCT_BENCH_MIXED",
                     "KGCT_BENCH_PREFILL_BUDGET"):
            assert knob in text


class TestPrefillRoofline:
    def _mcfg(self):
        from kubernetes_gpu_cluster_tpu.config import get_model_config
        return get_model_config("tinyllama-1.1b")

    def test_fields_and_sanity(self):
        pf = bench._roofline_prefill(self._mcfg(), None, 2048)
        for k in ("tokens_modeled", "flops_per_step", "flops_per_token",
                  "bytes_per_step", "flops_per_byte", "compute_bound_ms",
                  "hbm_bound_ms"):
            assert k in pf, k
        assert pf["tokens_modeled"] == 2048
        assert pf["flops_per_step"] > 0 and pf["bytes_per_step"] > 0
        assert pf["flops_per_byte"] > 0
        # budget-sized prefill is compute-bound: its arithmetic intensity
        # beats the chip's FLOPs/byte balance point, so the compute bound is
        # the binding one — the TTFT arithmetic target
        balance = (bench.CHIP_TFLOPS_BF16 * 1e12) / (bench.CHIP_HBM_GBPS * 1e9)
        assert pf["flops_per_byte"] > balance
        assert pf["compute_bound_ms"] > pf["hbm_bound_ms"]

    def test_intensity_grows_with_tokens(self):
        """More tokens amortize the same weight stream: FLOPs/byte must be
        monotone in T (the reason mixed batching rides prefill steps)."""
        mcfg = self._mcfg()
        small = bench._roofline_prefill(mcfg, None, 128)
        big = bench._roofline_prefill(mcfg, None, 4096)
        assert big["flops_per_byte"] > small["flops_per_byte"]

    def test_int8_halves_weight_stream(self):
        mcfg = self._mcfg()
        bf16 = bench._roofline_prefill(mcfg, None, 512)
        q8 = bench._roofline_prefill(mcfg, "int8", 512)
        assert q8["bytes_per_step"] < bf16["bytes_per_step"]
        assert q8["flops_per_step"] == bf16["flops_per_step"]

    def test_int4_packs_below_int8(self):
        """The int4 rung streams packed bytes + group scales: under int8's
        stream but above an idealized scale-free half (the scales are real
        bytes; pretending otherwise would flatter the roofline)."""
        mcfg = self._mcfg()
        q8 = bench._roofline_prefill(mcfg, "int8", 512)
        q4 = bench._roofline_prefill(mcfg, "int4", 512)
        assert q4["bytes_per_step"] < q8["bytes_per_step"]
        assert q4["flops_per_step"] == q8["flops_per_step"]
        w8 = bench._weight_stream_bytes(mcfg, "int8")
        w4 = bench._weight_stream_bytes(mcfg, "int4")
        assert w8 // 2 < w4 <= 0.55 * w8

    def test_json_serializable(self):
        pf = bench._roofline_prefill(self._mcfg(), "int8", 1024)
        assert json.loads(json.dumps(pf)) == pf


class TestPrefixReuseContract:
    """The prefix_reuse phase must ride the bounded last-line contract: its
    headline field survives parse_result_line and the full block lives in
    the primary config (falling to stderr with the rest of "configs" when
    the line must shrink)."""

    def test_headline_parses_in_last_line(self):
        results = _fake_results()
        results[-1]["prefix_reuse"] = {
            "n_requests": 6, "shared_prefix_tokens": 128, "tail_tokens": 16,
            "ttft_cold_p50_ms": 11.2, "ttft_warm_p50_ms": 5.6,
            "warm_over_cold": 0.5, "cache_hits": 6, "cache_misses": 7,
        }
        out = bench.assemble_output(results, "cpu")
        parsed = bench.parse_result_line(json.dumps(out) + "\n")
        assert parsed["prefix_warm_over_cold_ttft"] == 0.5
        assert parsed["configs"][-1]["prefix_reuse"]["cache_hits"] == 6

    def test_headline_is_droppable_under_the_bound(self):
        assert "prefix_warm_over_cold_ttft" in bench._DROPPABLE_HEADLINE
        out = bench.assemble_output(_fake_results(), "cpu")
        line = json.dumps(bench.compact_result(out))
        assert len(line) <= bench.RESULT_LINE_MAX

    def test_absent_phase_yields_null_headline(self):
        out = bench.assemble_output(_fake_results(), "cpu")
        assert out["prefix_warm_over_cold_ttft"] is None


class TestRouterPhaseContract:
    """KGCT_BENCH_ROUTER rides the bounded last-line contract like the
    other phases: headline parseable from the last stdout line, droppable
    under the byte bound, null when the phase was skipped."""

    def test_headline_parses_in_last_line(self):
        results = _fake_results()
        results[-1]["router_affinity"] = {
            "replicas": 2, "sessions": 3, "rounds": 3,
            "least_inflight": {"ttft_warm_p50_ms": 15.2,
                               "per_replica": [{"hit_ratio": 0.4}]},
            "prefix_affinity": {"ttft_warm_p50_ms": 11.3,
                                "affinity_hit_ratio": 1.0,
                                "per_replica": [{"hit_ratio": 0.667}]},
            "warm_ttft_ratio": 0.743,
        }
        out = bench.assemble_output(results, "cpu")
        parsed = bench.parse_result_line(json.dumps(out) + "\n")
        assert parsed["router_affinity_warm_over_li_ttft"] == 0.743
        assert (parsed["configs"][-1]["router_affinity"]["prefix_affinity"]
                ["affinity_hit_ratio"]) == 1.0

    def test_headline_is_droppable_under_the_bound(self):
        assert ("router_affinity_warm_over_li_ttft"
                in bench._DROPPABLE_HEADLINE)
        out = bench.assemble_output(_fake_results(), "cpu")
        line = json.dumps(bench.compact_result(out))
        assert len(line) <= bench.RESULT_LINE_MAX

    def test_absent_phase_yields_null_headline(self):
        out = bench.assemble_output(_fake_results(), "cpu")
        assert out["router_affinity_warm_over_li_ttft"] is None


class TestDrainPhaseContract:
    """KGCT_BENCH_DRAIN rides the bounded last-line contract like the
    other phases: headline parseable from the last stdout line, droppable
    under the byte bound, null when the phase was skipped."""

    def test_headline_parses_in_last_line(self):
        results = _fake_results()
        results[-1]["drain"] = {
            "sessions": 6, "max_new": 48,
            "wait": {"drain_seconds": 4.1, "complete_streams": 6,
                     "migrations_push_fallback": 3},
            "migrate": {"drain_seconds": 1.4, "complete_streams": 6,
                        "migrations_push_ok": 3,
                        "failovers": {"import": 3}},
            "drain_migrate_over_wait_seconds": 0.341,
        }
        out = bench.assemble_output(results, "cpu")
        parsed = bench.parse_result_line(json.dumps(out) + "\n")
        assert parsed["drain_migrate_over_wait_seconds"] == 0.341
        assert parsed["configs"][-1]["drain"]["migrate"][
            "migrations_push_ok"] == 3

    def test_headline_is_droppable_under_the_bound(self):
        assert ("drain_migrate_over_wait_seconds"
                in bench._DROPPABLE_HEADLINE)
        out = bench.assemble_output(_fake_results(), "cpu")
        line = json.dumps(bench.compact_result(out))
        assert len(line) <= bench.RESULT_LINE_MAX

    def test_absent_phase_yields_null_headline(self):
        out = bench.assemble_output(_fake_results(), "cpu")
        assert out["drain_migrate_over_wait_seconds"] is None

    def test_help_lists_drain_knobs(self):
        text = bench.build_arg_parser().format_help()
        for knob in ("KGCT_BENCH_DRAIN", "KGCT_BENCH_DRAIN_SESSIONS",
                     "KGCT_BENCH_DRAIN_MAX_NEW"):
            assert knob in text


class TestFleetCachePhaseContract:
    """KGCT_BENCH_FLEET_CACHE rides the bounded last-line contract like
    the other phases: headline parseable from the last stdout line,
    droppable under the byte bound, null when the phase was skipped."""

    def test_headline_parses_in_last_line(self):
        results = _fake_results()
        results[-1]["fleet_cache"] = {
            "sessions": 3, "shared_prefix_tokens": 384, "tail_tokens": 16,
            "recompute": {"warm_ttft_p50_ms": 30.5, "pulls_ok": 0},
            "pull": {"warm_ttft_p50_ms": 17.2, "pulls_ok": 4,
                     "pulled_bytes": 1580314},
            "fleet_prefix_pull_over_recompute_ttft": 0.564,
        }
        out = bench.assemble_output(results, "cpu")
        parsed = bench.parse_result_line(json.dumps(out) + "\n")
        assert parsed["fleet_prefix_pull_over_recompute_ttft"] == 0.564
        assert parsed["configs"][-1]["fleet_cache"]["pull"]["pulls_ok"] == 4

    def test_headline_is_droppable_under_the_bound(self):
        assert ("fleet_prefix_pull_over_recompute_ttft"
                in bench._DROPPABLE_HEADLINE)
        out = bench.assemble_output(_fake_results(), "cpu")
        line = json.dumps(bench.compact_result(out))
        assert len(line) <= bench.RESULT_LINE_MAX

    def test_absent_phase_yields_null_headline(self):
        out = bench.assemble_output(_fake_results(), "cpu")
        assert out["fleet_prefix_pull_over_recompute_ttft"] is None

    def test_help_lists_fleet_knobs(self):
        text = bench.build_arg_parser().format_help()
        for knob in ("KGCT_BENCH_FLEET_CACHE", "KGCT_BENCH_FLEET_SESSIONS",
                     "KGCT_BENCH_FLEET_SHARED", "KGCT_FLEET_BW_GBPS",
                     "KGCT_FLEET_FLOPS"):
            assert knob in text


class TestIntegrityHeadlineContract:
    """kv_integrity_overhead_ratio (the fleet-cache phase's third arm)
    rides the same bounded last-line contract: droppable, null when the
    phase was skipped."""

    def test_headline_parses_and_is_droppable(self):
        results = _fake_results()
        results[-1]["fleet_cache"] = {
            "pull": {"warm_ttft_p50_ms": 17.2},
            "pull_integrity_off": {"warm_ttft_p50_ms": 16.9},
            "kv_integrity_overhead_ratio": 1.018,
        }
        out = bench.assemble_output(results, "cpu")
        parsed = bench.parse_result_line(json.dumps(out) + "\n")
        assert parsed["kv_integrity_overhead_ratio"] == 1.018
        assert "kv_integrity_overhead_ratio" in bench._DROPPABLE_HEADLINE

    def test_absent_phase_yields_null_headline(self):
        out = bench.assemble_output(_fake_results(), "cpu")
        assert out["kv_integrity_overhead_ratio"] is None
