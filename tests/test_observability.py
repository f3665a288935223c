"""Observability subsystem unit tests: histogram exposition, trace ring +
Perfetto export, step-phase bookkeeping, lifecycle hooks."""

import json
import logging
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubernetes_gpu_cluster_tpu.observability import (  # noqa: E402
    PHASES, Histogram, Observability, SLOTracker, render_gauge)
from kubernetes_gpu_cluster_tpu.observability.flightrecorder import (  # noqa: E402
    FlightRecorder)
from kubernetes_gpu_cluster_tpu.observability.phases import (  # noqa: E402
    FRAME_STAGES, FrameClock, StepClock, StepPhaseStats)
from kubernetes_gpu_cluster_tpu.observability.trace import (  # noqa: E402
    RequestTracer, merge_perfetto)


def _rec(step, kind, rows, duration_s, new_tokens, t0=10.0, pred=None,
         wait_s=0.005, **extra):
    """A retired program's record as the engine hands it to ``on_step``:
    launched at ``t0``, dispatched 1 ms later, waited for ``wait_s`` and
    retired ``duration_s`` after the iteration began."""
    rec = {"step": step, "kind": kind, "rows": rows, "tokens": rows,
           "padded_tokens": rows, "behind": pred is not None, "pred": pred,
           "t_iter": t0, "t_launch": t0, "t_dispatched": t0 + 0.001,
           "t_wait": t0 + 0.002, "t_ready": t0 + 0.002 + wait_s,
           "t_retired": t0 + duration_s, "phases": [],
           "new_tokens": new_tokens}
    rec.update(extra)
    return rec


class _Seq:
    """Minimal Sequence stand-in carrying the lifecycle fields the
    Observability hooks read/write."""

    def __init__(self, rid, arrival=100.0):
        self.request_id = rid
        self.arrival_time = arrival
        self.first_token_time = None
        self.scheduled_time = None
        self.finish_time = None
        self.preempt_count = 0
        self.num_prompt_tokens = 8
        self.num_output_tokens = 0


class TestHistogram:
    def test_empty_renders_zero_and_nan_free(self):
        h = Histogram("t_seconds", "help")
        lines = h.render()
        assert "# TYPE t_seconds histogram" in lines
        assert any(l == "t_seconds_count 0" for l in lines)
        assert any(l == "t_seconds_sum 0" for l in lines)
        assert not any("nan" in l.lower() for l in lines)

    def test_bucket_monotonicity_and_sum_count(self):
        h = Histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        lines = h.render()
        cums = [int(l.split()[-1]) for l in lines if "_bucket" in l]
        assert cums == sorted(cums)
        assert cums[-1] == 5                       # +Inf == count
        assert any(l == "t_seconds_count 5" for l in lines)
        [s] = [float(l.split()[-1]) for l in lines if l.startswith("t_seconds_sum")]
        assert abs(s - 56.05) < 1e-9

    @pytest.mark.parametrize("value,cums", [
        (0.05, [1, 1, 1, 1]), (0.1, [1, 1, 1, 1]), (0.1000001, [0, 1, 1, 1]),
        (10.0, [0, 0, 1, 1]), (10.5, [0, 0, 0, 1])])
    def test_a_value_on_a_bound_falls_in_that_bucket(self, value, cums):
        """``le``: the first bucket whose bound is >= the value; above the
        last bound only +Inf counts it."""
        h = Histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
        h.observe(value)
        assert [int(l.split()[-1]) for l in h.render()
                if "_bucket" in l] == cums

    def test_nan_observation_dropped(self):
        h = Histogram("t_seconds")
        h.observe(float("nan"))
        assert h.count == 0

    def test_labeled_cells_render_separately(self):
        h = Histogram("t_seconds", labels=("outcome",))
        h.observe(0.2, ("finished",))
        h.observe(3.0, ("aborted",))
        text = "\n".join(h.render())
        assert 'outcome="finished"' in text and 'outcome="aborted"' in text
        assert text.count("_count") == 2

    def test_render_gauge_absent_when_none(self):
        assert render_gauge("g", None) == []
        assert render_gauge("g", float("nan")) == []
        assert render_gauge("g", 0.5) == ["# TYPE g gauge", "g 0.5"]


class TestRequestTracer:
    def test_ring_bounded(self):
        tr = RequestTracer(capacity=4)
        for i in range(10):
            tr.emit("queued", f"r{i}")
        evs = tr.events()
        assert len(evs) == 4 and evs[0].request_id == "r6"

    def test_step_events_never_evict_request_events(self):
        # Sustained decode emits one engine-wide instant per step; a flood
        # of them must not push request-lifecycle events off the ring.
        tr = RequestTracer(capacity=8)
        tr.emit("arrival", "a")
        for _ in range(100):
            tr.emit("decode", "", batch=4, tokens=4)
        kinds = [e.kind for e in tr.events()]
        assert "arrival" in kinds
        assert kinds.count("decode") <= 2      # capacity // 4
        tr.clear()
        assert tr.events() == []

    def test_perfetto_spans_pair_and_orphan_close_synthesized(self):
        tr = RequestTracer()
        tr.emit("arrival", "a")
        tr.emit("first_token", "a", ttft_ms=5.0)
        tr.emit("finish", "a", outcome="finished")
        tr.emit("finish", "orphan", outcome="finished")  # arrival fell off
        doc = tr.export_perfetto()
        evs = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
        a_phs = [e["ph"] for e in evs if e.get("id") == "a"]
        assert a_phs == ["b", "n", "e"]
        orphan = [e for e in evs if e.get("id") == "orphan"]
        assert [e["ph"] for e in orphan] == ["b", "e"]   # synthesized open
        json.loads(json.dumps(doc))                      # wire-serializable

    def test_perfetto_step_slices(self):
        tr = RequestTracer()
        recs = [{"step": 1, "kind": "decode", "batch": 4,
                 "phases": [("device_dispatch", 10.0, 0.002),
                            ("device_fetch", 10.002, 0.001)]}]
        doc = tr.export_perfetto(step_records=recs)
        slices = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {s["name"] for s in slices} == {"device_dispatch",
                                               "device_fetch"}
        assert all(s["dur"] > 0 for s in slices)


class TestFlightRecorder:
    def test_ring_bounded_and_disable(self):
        fr = FlightRecorder(capacity=4, enabled=True)
        for i in range(10):
            fr.record("queued", f"r{i}", {"n": i})
        events = fr.export()["events"]
        assert len(events) == 4 and events[0]["request_id"] == "r6"
        off = FlightRecorder(enabled=False)
        off.record("queued", "r0")
        assert off.export()["events"] == []
        assert off.dump("anything") is None

    def test_tracer_mirror_outlives_a_cleared_trace_ring(self):
        # The flight recorder is the crash capture: a scoped trace capture
        # (``/debug/trace?clear=1``) must not erase it — only KGCT_FLIGHT=0
        # silences it.
        fr = FlightRecorder(enabled=True)
        tr = RequestTracer(recorder=fr)
        tr.emit("arrival", "r1", prompt_tokens=8)
        assert [e.kind for e in tr.events()] == ["arrival"]
        tr.clear()
        assert tr.events() == []
        [ev] = fr.export()["events"]
        assert ev["kind"] == "arrival" and ev["request_id"] == "r1"
        assert ev["prompt_tokens"] == 8

    def test_snapshot_source_and_interval(self):
        fr = FlightRecorder(enabled=True, snapshot_interval_s=0.0)
        calls = []
        fr.set_snapshot_source(lambda: calls.append(1) or {"waiting": 3})
        fr.maybe_snapshot()
        fr.maybe_snapshot()
        snaps = [e for e in fr.export()["events"] if e["kind"] == "snapshot"]
        assert len(snaps) == 2 and snaps[0]["waiting"] == 3
        # A long interval rate-limits: the second call within the window
        # is a single monotonic read, no snapshot.
        slow = FlightRecorder(enabled=True, snapshot_interval_s=3600)
        slow.set_snapshot_source(lambda: {"waiting": 0})
        slow.maybe_snapshot()
        slow.maybe_snapshot()
        assert len([e for e in slow.export()["events"]
                    if e["kind"] == "snapshot"]) == 1
        # A raising source never propagates (the step loop must survive).
        fr.set_snapshot_source(lambda: 1 / 0)
        fr.maybe_snapshot()

    def test_dump_writes_trigger_and_ring(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))
        fr = FlightRecorder(enabled=True)
        fr.record("arrival", "r1", {"prompt_tokens": 4})
        path = fr.dump("watchdog_trip", trips=2)
        assert path is not None and path.startswith(str(tmp_path))
        doc = json.loads(open(path).read())
        assert doc["reason"] == "watchdog_trip"
        assert doc["info"] == {"trips": 2}
        kinds = [e["kind"] for e in doc["events"]]
        assert kinds == ["arrival", "watchdog_trip"]   # trigger appended
        assert doc["events"][-1]["trips"] == 2
        assert fr.dumps_total == 1 and fr.last_dump_path == path
        # unix anchor converts monotonic event ts to wall clock
        assert doc["unix_minus_monotonic"] + doc["events"][0]["ts"] > 0


class TestSLOTracker:
    def test_attainment_and_default_budget(self):
        slo = SLOTracker()                 # no operator budget
        assert slo.budget_ms == 1000.0     # north-star bar
        assert slo.attainment() == 1.0     # empty window: nothing missed
        slo.on_first_token(0.5)
        slo.on_first_token(0.9)
        slo.on_first_token(2.0)            # blows the 1 s bar
        assert abs(slo.attainment() - 2 / 3) < 1e-9
        slo.ttft_budget_ms = 3000.0        # operator budget overrides
        assert slo.attainment() == 1.0

    def test_goodput_counts_only_budget_meeting_requests(self):
        import time as _time

        slo = SLOTracker(ttft_budget_ms=1000.0, goodput_window_s=10.0)
        assert slo.goodput_tokens_per_sec() == 0.0
        slo.on_finish(0.5, 40)             # met budget: counts
        slo.on_finish(5.0, 1000)           # blew budget: excluded
        slo.on_finish(0.2, 0)              # no tokens: excluded
        # Simulate a 10 s observed span: the denominator is the observed
        # elapsed time capped at the window, never the bare window (a
        # fresh server's goodput must not be systematically understated).
        slo._window_start = _time.monotonic() - 10.0
        assert abs(slo.goodput_tokens_per_sec() - 4.0) < 0.01
        # Short observed span: same tokens over ~2 s reads ~20 tok/s.
        slo._window_start = _time.monotonic() - 2.0
        assert abs(slo.goodput_tokens_per_sec() - 20.0) < 0.2
        slo.clear()
        assert slo.goodput_tokens_per_sec() == 0.0
        assert slo.attainment() == 1.0

    def test_window_is_bounded(self):
        slo = SLOTracker(ttft_budget_ms=1000.0, window=4)
        for _ in range(10):
            slo.on_first_token(9.0)        # all misses
        slo.on_first_token(0.1)            # one recent hit
        assert abs(slo.attainment() - 1 / 4) < 1e-9


class TestMergePerfetto:
    def _doc(self, rid, t0_unix):
        tr = RequestTracer()
        tr.emit("arrival", rid)
        tr.emit("finish", rid, outcome="finished")
        doc = tr.export_perfetto(process_name="p")
        doc["kgctT0Unix"] = t0_unix        # pin the anchor for determinism
        return doc

    def test_rebase_pid_and_labels(self):
        a = self._doc("req-1", 100.0)      # earliest process: origin
        b = self._doc("req-1", 100.5)      # starts 0.5 s later
        merged = merge_perfetto([("kgct-router", a), ("kgct-engine x", b)])
        assert merged["kgctT0Unix"] == 100.0
        names = {e["args"]["name"] for e in merged["traceEvents"]
                 if e.get("name") == "process_name"}
        assert names == {"kgct-router", "kgct-engine x"}
        # Both processes carry the request span, correlated on the id...
        spans = [e for e in merged["traceEvents"]
                 if e.get("cat") == "request" and e.get("id") == "req-1"]
        assert {e["pid"] for e in spans} == {1, 2}
        # ...and the later process's events shifted by its anchor delta.
        b_open = min(e["ts"] for e in spans if e["pid"] == 2)
        assert b_open >= 0.5e6 - 1
        json.dumps(merged)                 # wire-serializable

    def test_empty_doc_merges_without_anchor(self):
        empty = RequestTracer().export_perfetto()
        assert empty["kgctT0Unix"] is None
        merged = merge_perfetto([("a", empty), ("b", self._doc("r", 5.0))])
        assert merged["kgctT0Unix"] == 5.0
        assert {e["pid"] for e in merged["traceEvents"]} == {1, 2}


class TestStepPhaseStats:
    def test_phase_context_accumulates(self):
        st = StepPhaseStats()
        st.start_step()
        with st.phase("schedule"):
            pass
        with st.phase("device_fetch"):
            pass
        st.end_step(step=1, kind="decode", batch=2, duration_s=0.01)
        assert st.counts["schedule"] == 1
        assert st.steps_recorded == 1
        assert st.step_records()[0]["kind"] == "decode"
        assert set(st.totals) == set(st.counts) == set(PHASES)
        assert st.totals["schedule"] >= 0.0

    def test_discard_drops_record_keeps_totals(self):
        st = StepPhaseStats()
        st.start_step()
        with st.phase("schedule"):
            pass
        total = st.totals["schedule"]
        # a launch that scheduled nothing: the next one starts a fresh list
        assert st.start_step() == []
        assert st.step_records() == []
        assert st.totals["schedule"] == total >= 0.0

    def test_detokenize_out_of_step_record(self):
        st = StepPhaseStats()
        st.record("detokenize", 0.004)
        assert st.counts["detokenize"] == 1
        assert st.totals["detokenize"] == 0.004
        # Out-of-step slices must not touch the engine thread's step-local
        # state (they arrive from the HTTP event-loop thread mid-step) —
        # they surface through detached_records() instead.
        assert st._current == []
        [rec] = st.detached_records()
        assert rec["kind"] == "http"
        assert [p[0] for p in rec["phases"]] == ["detokenize"]

    def test_clear_records_drops_rings_keeps_totals(self):
        st = StepPhaseStats()
        st.start_step()
        with st.phase("schedule"):
            pass
        st.end_step(step=1, kind="decode", batch=1, duration_s=0.01)
        st.record("detokenize", 0.002)
        st.clear_records()
        assert st.step_records() == [] and st.detached_records() == []
        assert st.counts["schedule"] == 1 and st.counts["detokenize"] == 1


class TestObservabilityLifecycle:
    def _run_request(self, obs, rid="r1", preempt=False):
        seq = _Seq(rid)
        obs.on_arrival(seq)
        obs.on_queued(seq, depth=1)
        seq.arrival_time = 0.0
        if preempt:
            obs.on_preempt(seq)
        obs.on_scheduled(seq, 1)
        seq.first_token_time = seq.scheduled_time + 0.05
        obs.on_first_token(seq, fetch_s=0.01)
        seq.num_output_tokens = 5
        obs.on_finish(seq, None)
        return seq

    def test_queue_ttft_e2e_histograms_fill(self):
        obs = Observability()
        self._run_request(obs)
        assert obs.queue_wait.count == 1
        assert obs.ttft.count == 1
        assert obs.e2e_latency.count == 1
        assert obs.tpot.count == 1
        # scheduling to first token (0.05 s) less the fetch (0.01 s)
        assert obs.prefill_latency.count == 1
        assert abs(obs.prefill_latency.sum - 0.04) < 1e-9

    def test_finish_idempotent_and_outcome_labels(self):
        obs = Observability()
        seq = self._run_request(obs, preempt=True)
        obs.on_finish(seq, None)       # double-finish: second is a no-op
        assert obs.e2e_latency.count == 1
        text = "\n".join(obs.e2e_latency.render())
        assert 'outcome="preempted"' in text

    def test_clear_trace_scopes_capture(self):
        obs = Observability()
        self._run_request(obs)
        phases = obs.phases.start_step()
        with obs.phases.phase("device_dispatch"):
            pass
        obs.on_step(_rec(1, "decode", 1, 0.01, 1, mode="greedy",
                         phases=phases))
        obs.phases.record("detokenize", 0.001)     # detached (HTTP thread)
        evs = obs.export_perfetto()["traceEvents"]
        assert {"device_dispatch", "detokenize"} <= {
            e["name"] for e in evs if e.get("ph") == "X"}
        obs.clear_trace()
        evs = obs.export_perfetto()["traceEvents"]
        # Metadata only: request spans, step slices AND detached slices all
        # emptied — a ?clear=1 scoped capture starts from nothing.
        assert {e.get("ph") for e in evs} == {"M"}
        assert obs.ttft.count == 1                 # /metrics state untouched

    def test_render_prometheus_fresh_is_nan_free(self):
        obs = Observability()
        text = "\n".join(obs.render_prometheus())
        assert "nan" not in text.lower()
        assert "kgct_step_phase_seconds_total" in text

    def test_aborted_requests_excluded_from_goodput(self):
        """Goodput counts DELIVERED work: an aborted request's tokens were
        generated but never received, so they must not inflate the
        autoscaler signal — a finished request with the same TTFT does."""
        obs = Observability()

        def run(rid, reason):
            seq = _Seq(rid)
            obs.on_arrival(seq)
            obs.on_scheduled(seq, 1)
            seq.arrival_time = seq.scheduled_time        # TTFT ~10 ms
            seq.first_token_time = seq.scheduled_time + 0.01
            obs.on_first_token(seq)
            seq.num_output_tokens = 50
            obs.on_finish(seq, reason)
        run("ra", "abort")
        assert obs.slo.goodput_tokens_per_sec() == 0.0
        run("rb", None)
        assert obs.slo.goodput_tokens_per_sec() > 0.0


class TestJsonLogFormat:
    def test_json_formatter_carries_request_id(self):
        from kubernetes_gpu_cluster_tpu.utils.logging import _JsonFormatter
        rec = logging.LogRecord("kgct.engine", logging.WARNING, __file__, 1,
                                "preempted %s", ("req-9",), None)
        rec.request_id = "req-9"
        entry = json.loads(_JsonFormatter().format(rec))
        assert entry["level"] == "WARNING"
        assert entry["msg"] == "preempted req-9"
        assert entry["request_id"] == "req-9"

    def test_plain_record_has_no_request_id(self):
        from kubernetes_gpu_cluster_tpu.utils.logging import _JsonFormatter
        rec = logging.LogRecord("kgct.x", logging.INFO, __file__, 1,
                                "hello", (), None)
        entry = json.loads(_JsonFormatter().format(rec))
        assert "request_id" not in entry


# -- the device queue's counters (engine._step) and the metric that reads them

def _queue_counter_engine(spec=False):
    from kubernetes_gpu_cluster_tpu.config import (
        CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
    from kubernetes_gpu_cluster_tpu.engine import LLMEngine
    return LLMEngine(EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=8, num_pages=64),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32, decode_buckets=(1, 2, 4),
            prefill_buckets=(16, 32), decode_window=4,
            spec_decode_enabled=spec, num_speculative_tokens=3)))


def _counter_lines(eng, family):
    return {l.split(" ")[0][len(family):]: int(l.split(" ")[1])
            for l in eng.obs.render_prometheus() if l.startswith(family)}


class TestDeviceQueueCounters:
    def test_counts_what_a_scripted_run_did(self):
        """One prompt, 13 tokens at a window of 4: a prefill with nothing
        before it, then three windows each queued behind an unfetched step
        (1 + 3 x 4 tokens: the third is known to reach ``max_tokens``, so
        nothing rides behind it); a second prompt then arrives between two
        windows and rides a mixed step behind one."""
        from kubernetes_gpu_cluster_tpu.engine import SamplingParams
        eng = _queue_counter_engine()
        sp = SamplingParams(max_tokens=13, temperature=0.0)
        eng.generate([[1, 2, 3]], sp)
        got = _counter_lines(eng, "kgct_steps_dispatched_total")
        assert got == {'{kind="prefill",behind="0"}': 1,
                       '{kind="decode",behind="1"}': 3}
        assert _counter_lines(eng, "kgct_chain_breaks_total") == {}
        eng.add_request("a", [5, 6, 7], sp)
        eng.step()
        eng.add_request("b", [9, 8, 7, 6], sp)
        while eng.has_unfinished_requests():
            eng.step()
        got = _counter_lines(eng, "kgct_steps_dispatched_total")
        assert got['{kind="mixed",behind="1"}'] == 1
        assert got['{kind="prefill",behind="0"}'] == 2
        assert sum(got.values()) == sum(eng.obs.steps_dispatched.values())
        text = "\n".join(eng.obs.render_prometheus())
        assert "# TYPE kgct_steps_dispatched_total counter" in text
        assert "# TYPE kgct_chain_breaks_total counter" in text

    def test_spec_step_is_never_behind_and_says_why(self):
        from kubernetes_gpu_cluster_tpu.engine import SamplingParams
        eng = _queue_counter_engine(spec=True)
        eng.generate([[7, 3, 9, 11] * 4],
                     SamplingParams(max_tokens=16, temperature=0.0))
        got = _counter_lines(eng, "kgct_steps_dispatched_total")
        assert got.get('{kind="spec",behind="0"}', 0) > 0
        assert not any('behind="1"' in k for k in got)
        breaks = _counter_lines(eng, "kgct_chain_breaks_total")
        assert set(breaks) == {'{reason="spec"}'}
        assert breaks['{reason="spec"}'] >= got['{kind="spec",behind="0"}']

    def test_layer_metric_file_reads_the_share_from_two_scrapes(self):
        """``steps_dispatched_behind_share`` is a data file over the reader
        the benchmark has: it loads, names its layer as BENCHMARK.json does,
        and ``prom_ratio`` reads the share of a canned pair of scrapes; a
        server without the family (the parent) reads nothing."""
        from perfbench import stats
        from perfbench.readers import prom_ratio
        from perfbench.spec import Benchmark
        bench = Benchmark()
        spec = bench.layer_metric("steps_dispatched_behind_share")
        entry = next(m for m in bench.doc["per_layer"]
                     if m["name"] == "steps_dispatched_behind_share")
        assert spec["reader"] == "prom_ratio"
        assert {k: spec[k] for k in ("unit", "layer", "moves")} == {
            k: entry[k] for k in ("unit", "layer", "moves")}
        assert entry["workloads"] == bench.cell_names()
        for cell in bench.cell_names():
            assert "steps_dispatched_behind_share" in [
                m["name"] for m in bench.cell(cell).per_layer]
        before = stats.parse_prometheus(
            'kgct_steps_dispatched_total{kind="decode",behind="1"} 100\n'
            'kgct_steps_dispatched_total{kind="decode",behind="0"} 40\n'
            'kgct_steps_dispatched_total{kind="mixed",behind="0"} 10\n'
            'kgct_chain_breaks_total{reason="no_pages"} 3\n')
        after = stats.parse_prometheus(
            'kgct_steps_dispatched_total{kind="decode",behind="1"} 280\n'
            'kgct_steps_dispatched_total{kind="decode",behind="0"} 45\n'
            'kgct_steps_dispatched_total{kind="mixed",behind="0"} 10\n'
            'kgct_steps_dispatched_total{kind="mixed",behind="1"} 15\n'
            'kgct_chain_breaks_total{reason="no_pages"} 8\n')
        ctx = {"scrape_before": before, "scrape_after": after}
        assert prom_ratio.read(spec, ctx) == 100.0 * 195 / 200
        parent = stats.parse_prometheus("kgct_step_duration_seconds_count 5\n")
        assert prom_ratio.read(
            spec, {"scrape_before": parent, "scrape_after": parent}) is None


# -- the step loop's own clock (phases.StepPhaseStats.retire, worker_turn) ---

def _stamps(step, kind, pred, t_dispatched, t_wait, t_ready):
    return {"step": step, "kind": kind, "pred": pred,
            "t_dispatched": t_dispatched, "t_wait": t_wait,
            "t_ready": t_ready}


class TestStepClock:
    def test_scripted_stamps_give_device_time_exactness_and_found_ready(self):
        st = StepPhaseStats()
        # 1: nothing in flight before it: it starts at its own dispatch
        a = st.retire(_stamps(1, "prefill", None, 10.000, 10.010, 10.050))
        assert a["wait_s"] == pytest.approx(0.040) and not a["found_ready"]
        assert a["device_s"] == pytest.approx(0.050) and a["exact"]
        assert a["ready_gap_s"] is None and a["lead_s"] is None
        # 2: queued at 10.020 behind 1, which ended at 10.050: it starts
        # there; the host was 30 ms ahead of the chip
        b = st.retire(_stamps(2, "decode", 1, 10.020, 10.060, 10.150))
        assert b["device_s"] == pytest.approx(0.100) and b["exact"]
        assert b["ready_gap_s"] == pytest.approx(0.100)
        assert b["lead_s"] == pytest.approx(0.030)
        # 3: dispatched AFTER its predecessor was ready (the chip stood 20
        # ms): it starts at its own dispatch; the lead is negative
        c = st.retire(_stamps(3, "decode", 2, 10.170, 10.180, 10.260))
        assert c["device_s"] == pytest.approx(0.090) and c["exact"]
        assert c["ready_gap_s"] == pytest.approx(0.110)
        assert c["lead_s"] == pytest.approx(-0.020)
        # 4: the host asked at 10.400, the chip had long finished: found
        # ready, so t_ready is the host's arrival and device_s is not exact
        d = st.retire(_stamps(4, "mixed", 3, 10.200, 10.400, 10.40002))
        assert d["found_ready"] and not d["exact"]
        assert d["wait_s"] == pytest.approx(20e-6)
        # 5: waited for, but behind one that was found ready: its start is
        # unknown, so it is not exact either; 6 behind 5 is exact again
        e = st.retire(_stamps(5, "decode", 4, 10.300, 10.410, 10.480))
        assert not e["found_ready"] and not e["exact"]
        f = st.retire(_stamps(6, "decode", 5, 10.420, 10.490, 10.580))
        assert f["exact"] and f["device_s"] == pytest.approx(0.100)
        # 8: launched with 7 in flight, but 7 is not what was retired last
        # (it never came back): no predecessor to reckon from
        g = st.retire(_stamps(8, "decode", 7, 10.600, 10.610, 10.700))
        assert g["ready_gap_s"] is None and g["device_s"] == pytest.approx(
            0.100)
        assert all(r["slow"] is None for r in (a, b, c, d, e, f, g))
        # the lead counts where the predecessor was waited for: not for 1
        # (none), 5 (behind 4, found ready) and 8 (7 never came back)
        assert [r["lead_exact"] for r in (a, b, c, d, e, f, g)] == [
            False, True, True, True, False, True, False]
        # the chip had nothing queued only before 3: 20 ms
        assert [r["starved_s"] for r in (a, b, d, e, f, g)] == [0.0] * 6
        assert c["starved_s"] == pytest.approx(0.020)

    def test_starved_seconds_at_a_chain_break_and_not_across_an_idle_turn(
            self):
        st = StepPhaseStats()
        st.worker_turn("host", 19.0)
        st.retire(_stamps(1, "decode", None, 20.000, 20.010, 20.100))
        # a chain break: 2 was scheduled only once 1 was fetched, with
        # nothing in flight (pred None): the chip stood from 1's end to
        # 2's dispatch
        b = st.retire(_stamps(2, "prefill", None, 20.130, 20.140, 20.200))
        assert b["starved_s"] == pytest.approx(0.030)
        assert b["lead_s"] is None and not b["lead_exact"]
        # the worker waited on its inbox between 2's end and 3's dispatch:
        # there was no request, the chip was not starved of anything
        st.worker_turn("inbox_wait", 20.250)
        st.worker_turn("host", 25.000)
        c = st.retire(_stamps(3, "prefill", None, 25.010, 25.020, 25.100))
        assert c["starved_s"] == 0.0
        # and the idle turn is behind: 4, chained to 3 but dispatched 5 ms
        # after its end, counts again
        d = st.retire(_stamps(4, "decode", 3, 25.105, 25.110, 25.200))
        assert d["starved_s"] == pytest.approx(0.005)
        assert d["lead_s"] == pytest.approx(-0.005) and d["lead_exact"]

    def test_slow_gap_is_classified_by_cause_both_ways(self):
        st = StepPhaseStats()
        t = 100.0
        st.retire(_stamps(1, "decode", None, t, t + 0.01, t + 0.1))
        for n in range(2, 12):           # ten windows of 100 ms: the mean
            r = st.retire(_stamps(n, "decode", n - 1, t + 0.05,
                                  t + 0.06, t + 0.1 * n))
            assert r["slow"] is None
        # the device ran long: queued in time (11 ended at t), waited for
        t = 101.1
        r = st.retire(_stamps(12, "decode", 11, t - 0.05, t - 0.04, t + 3.0))
        assert r["slow"] == "device" and r["ready_gap_s"] == pytest.approx(3)
        # the host came late: dispatched 2 s after the predecessor's end
        r = st.retire(_stamps(13, "decode", 12, t + 5.0, t + 5.01, t + 5.1))
        assert r["slow"] == "host" and r["lead_s"] == pytest.approx(-2.0)
        # the host came late to the FETCH: queued in time, found ready
        r = st.retire(_stamps(14, "decode", 13, t + 5.05, t + 7.0 - 1e-5,
                              t + 7.0))
        assert r["slow"] == "host" and r["found_ready"]
        # neither floor alone makes a gap slow: 0.3 s is 3x the mean but
        # under 0.5 s; and the slow ones did not move the mean
        r = st.retire(_stamps(15, "decode", 14, t + 6.9, t + 7.05, t + 7.3))
        assert r["slow"] is None
        assert st._gap_mean["decode"][0] / st._gap_mean["decode"][1] \
            == pytest.approx((10 * 0.1 + 0.3) / 11, rel=1e-6)
        # a kind of long programs: 0.8 s each is over the floor, not over
        # 3x their own mean (the first of a kind only starts the mean)
        for n in range(16, 20):
            g = t + 7.3 + 0.8 * (n - 15)
            r = st.retire(_stamps(n, "mixed", n - 1, g - 0.9, g - 0.4, g))
            assert r["slow"] is None

    def test_on_step_fills_the_program_series(self):
        obs = Observability()
        obs.on_step(_rec(1, "prefill", 2, 0.05, 2, t0=10.0, wait_s=0.030,
                         tokens=40, padded_tokens=64))
        obs.on_step(_rec(2, "decode", 2, 0.10, 16, t0=10.04, pred=1,
                         wait_s=0.080, tokens=16, padded_tokens=16,
                         mode="greedy", t_dispatched=10.020))
        obs.on_step(_rec(3, "decode", 2, 0.10, 16, t0=10.13, pred=2,
                         wait_s=0.00001, tokens=16, padded_tokens=32,
                         mode="greedy", t_dispatched=10.112))
        text = "\n".join(obs.render_prometheus())
        # 1 and 2 were waited for; 3 was found ready: not observed
        assert 'kgct_step_device_seconds_count{kind="prefill"} 1' in text
        assert 'kgct_step_device_seconds_count{kind="decode"} 1' in text
        assert 'kgct_steps_retired_total{kind="decode",waited="0"} 1' in text
        assert 'kgct_steps_retired_total{kind="decode",waited="1"} 1' in text
        assert 'kgct_step_tokens_total{kind="prefill",real="0"} 24' in text
        assert 'kgct_step_tokens_total{kind="decode",real="1"} 32' in text
        assert 'kgct_step_tokens_total{kind="decode",real="0"} 16' in text
        assert 'kgct_step_slow_seconds_total{cause="host"} 0' in text
        assert 'kgct_step_slow_total{cause="device"} 0' in text
        assert "kgct_step_seconds_count 3" in text     # one an iteration
        # the trace slices and the step events carry the program's number
        [ev] = [e for e in obs.tracer.events() if e.kind == "prefill"]
        assert ev.args["step"] == 1 and ev.args["device_ms"] == 31.0
        recs = obs.phases.step_records()
        assert [r["step"] for r in recs] == [1, 2, 3]
        assert recs[2]["args"]["exact"] is False
        # the lead: 2 behind 1 and 3 behind 2 (both waited for) count, 12
        # and 10 ms ahead of the chip; 4, launched with nothing in flight
        # 8 ms after 3's end (found ready: the host's arrival), adds that
        # to the starved seconds of ITS kind and nothing to the lead
        assert obs.step_lead.count == 2
        assert obs.step_lead.sum == pytest.approx(0.022)
        obs.on_step(_rec(4, "mixed", 2, 0.10, 2, t0=10.13901, wait_s=0.05,
                         tokens=8, padded_tokens=8))
        assert obs.step_lead.count == 2
        text = "\n".join(obs.render_prometheus())
        assert 'kgct_step_lead_seconds_count{kind="decode"} 2' in text
        assert 'kgct_device_starved_seconds_total{kind="mixed"} 0.008' in text
        assert 'kgct_device_starved_seconds_total{kind="decode"} 0' in text
        assert recs[1]["args"]["starved_ms"] == 0.0
        assert obs.phases.step_records()[3]["args"]["starved_ms"] == 8.0
        [ev] = [e for e in obs.tracer.events() if e.kind == "mixed"]
        assert ev.args["starved_ms"] == 8.0 and ev.args["lead_ms"] is None
        # a frame of program 3, written now: the delay whole and in five
        # stages that add up to it; a chunk no program made counts nowhere
        now = time.monotonic()
        clock = FrameClock(StepClock(3, now - 0.010))
        clock.program.t_retired = now - 0.008
        clock.t_posted, clock.t_woken = now - 0.007, now - 0.004
        clock.t_resumed = now - 0.003
        obs.on_frame(clock)
        obs.on_frame(None)
        assert obs.frame_delay.count == 1
        assert 0.010 <= obs.frame_delay.sum < 0.1
        cells = obs.frame_stage._cells
        assert list(cells) == [(s,) for s in FRAME_STAGES]
        assert [round(cells[(s,)][1], 6) for s in FRAME_STAGES[:4]] == [
            0.002, 0.001, 0.003, 0.001]
        assert 0.003 <= cells[("render",)][1] < 0.1
        assert obs.frame_stage.count == 5
        assert obs.frame_stage.sum == pytest.approx(obs.frame_delay.sum,
                                                    rel=1e-9)

    def test_worker_states_sum_to_the_threads_wall(self):
        """A thread that turns as the worker does (idle on the inbox,
        blocked on the device, busy) for a few hundred ms: whenever the
        three states are read, they sum to its life within 1 %."""
        import threading
        st = StepPhaseStats()
        assert sum(st.worker_seconds().values()) == 0.0   # no clock yet
        started = []

        def worker():
            st.worker_turn("host")
            started.append(time.monotonic())
            for _ in range(6):
                st.worker_turn("inbox_wait")
                time.sleep(0.02)
                st.worker_turn("host")
                time.sleep(0.005)
                t = time.monotonic()
                st.worker_turn("device_wait", t)
                time.sleep(0.03)
                t = time.monotonic()
                st.worker_turn("host", t)
        th = threading.Thread(target=worker)
        th.start()
        reads = []
        while th.is_alive():
            time.sleep(0.013)                 # mid-state, from outside
            if started:
                got, now = st.worker_seconds(), time.monotonic()
                reads.append((sum(got.values()), now - started[0]))
        th.join()
        got, life = st.worker_seconds(), time.monotonic() - started[0]
        assert len(reads) > 10
        for total, wall in reads[3:] + [(sum(got.values()), life)]:
            assert total == pytest.approx(wall, rel=0.01, abs=2e-4)
        assert got["inbox_wait"] >= 6 * 0.02
        assert got["device_wait"] >= 6 * 0.03
        assert 6 * 0.005 <= got["host"] < life - 0.3
        lines = [l for l in Observability().render_prometheus()
                 if l.startswith("kgct_worker_seconds_total")]
        assert [l.split("{")[1].split("}")[0] for l in lines] == [
            'state="host"', 'state="device_wait"', 'state="inbox_wait"']


# -- the metric files that read the new series, each from two scrapes ---------

_SCRAPE_BEFORE = """
kgct_step_device_seconds_sum{kind="decode"} 10.0
kgct_step_device_seconds_count{kind="decode"} 100
kgct_step_device_seconds_sum{kind="prefill"} 1.0
kgct_step_device_seconds_count{kind="prefill"} 20
kgct_worker_seconds_total{state="host"} 5.0
kgct_worker_seconds_total{state="device_wait"} 40.0
kgct_worker_seconds_total{state="inbox_wait"} 55.0
kgct_steps_retired_total{kind="decode",waited="1"} 100
kgct_step_slow_seconds_total{cause="host"} 21.5
kgct_step_slow_seconds_total{cause="device"} 0
kgct_step_tokens_total{kind="decode",real="1"} 1000
kgct_step_tokens_total{kind="decode",real="0"} 0
kgct_frame_delay_seconds_sum 1.0
kgct_frame_delay_seconds_count 1000
kgct_queue_wait_seconds_sum 2.0
kgct_queue_wait_seconds_count 10
kgct_prefill_seconds_sum 3.0
kgct_prefill_seconds_count 10
kgct_frame_stage_seconds_sum{stage="retire"} 0.1
kgct_frame_stage_seconds_count{stage="retire"} 1000
kgct_frame_stage_seconds_sum{stage="post"} 0.2
kgct_frame_stage_seconds_count{stage="post"} 1000
kgct_frame_stage_seconds_sum{stage="wake"} 0.3
kgct_frame_stage_seconds_count{stage="wake"} 1000
kgct_frame_stage_seconds_sum{stage="queue"} 0.3
kgct_frame_stage_seconds_count{stage="queue"} 1000
kgct_frame_stage_seconds_sum{stage="render"} 0.1
kgct_frame_stage_seconds_count{stage="render"} 1000
kgct_step_lead_seconds_sum{kind="decode"} 2.0
kgct_step_lead_seconds_count{kind="decode"} 100
kgct_device_starved_seconds_total{kind="decode"} 0
kgct_device_starved_seconds_total{kind="mixed"} 0.5
kgct_device_starved_seconds_total{kind="prefill"} 0
"""
_SCRAPE_AFTER = """
kgct_step_device_seconds_sum{kind="decode"} 34.0
kgct_step_device_seconds_count{kind="decode"} 300
kgct_step_device_seconds_sum{kind="prefill"} 2.0
kgct_step_device_seconds_count{kind="prefill"} 40
kgct_step_device_seconds_sum{kind="mixed"} 7.0
kgct_step_device_seconds_count{kind="mixed"} 100
kgct_worker_seconds_total{state="host"} 11.0
kgct_worker_seconds_total{state="device_wait"} 74.0
kgct_worker_seconds_total{state="inbox_wait"} 55.0
kgct_steps_retired_total{kind="decode",waited="1"} 297
kgct_steps_retired_total{kind="decode",waited="0"} 1
kgct_steps_retired_total{kind="mixed",waited="1"} 100
kgct_steps_retired_total{kind="mixed",waited="0"} 2
kgct_step_slow_seconds_total{cause="host"} 22.25
kgct_step_slow_seconds_total{cause="device"} 0.75
kgct_step_tokens_total{kind="decode",real="1"} 2500
kgct_step_tokens_total{kind="decode",real="0"} 100
kgct_step_tokens_total{kind="mixed",real="1"} 2000
kgct_step_tokens_total{kind="mixed",real="0"} 400
kgct_frame_delay_seconds_sum 4.0
kgct_frame_delay_seconds_count 2500
kgct_queue_wait_seconds_sum 2.9
kgct_queue_wait_seconds_count 40
kgct_prefill_seconds_sum 5.4
kgct_prefill_seconds_count 40
kgct_frame_stage_seconds_sum{stage="retire"} 0.25
kgct_frame_stage_seconds_count{stage="retire"} 2500
kgct_frame_stage_seconds_sum{stage="post"} 0.5
kgct_frame_stage_seconds_count{stage="post"} 2500
kgct_frame_stage_seconds_sum{stage="wake"} 1.2
kgct_frame_stage_seconds_count{stage="wake"} 2500
kgct_frame_stage_seconds_sum{stage="queue"} 1.5
kgct_frame_stage_seconds_count{stage="queue"} 2500
kgct_frame_stage_seconds_sum{stage="render"} 0.55
kgct_frame_stage_seconds_count{stage="render"} 2500
kgct_step_lead_seconds_sum{kind="decode"} 5.6
kgct_step_lead_seconds_count{kind="decode"} 300
kgct_step_lead_seconds_sum{kind="mixed"} 12.0
kgct_step_lead_seconds_count{kind="mixed"} 100
kgct_device_starved_seconds_total{kind="decode"} 0.04
kgct_device_starved_seconds_total{kind="mixed"} 0.75
kgct_device_starved_seconds_total{kind="prefill"} 0
"""
_NEW_METRICS = {
    # name: (reader, the value the two scrapes hold)
    "decode_step_inproc_ms": ("prom_hist_mean_where",
                              24.0 / 200 / 8 * 1000),
    "mixed_step_inproc_ms": ("prom_hist_mean_where", 70.0),
    "prefill_mixed_wall_share": ("prom_ratio", 100.0 * 8 / 32),
    "worker_host_share": ("prom_ratio", 100.0 * 6 / 40),
    "steps_found_ready_share": ("prom_ratio", 100.0 * 3 / 300),
    "slow_step_s_in_window": ("prom_counter_delta", 1.5),
    "step_padding_share": ("prom_ratio", 100.0 * 500 / 4000),
    "frame_delay_mean_ms": ("prom_hist_mean", 2.0),
    "queue_wait_mean_ms": ("prom_hist_mean", 30.0),
    "prefill_mean_ms": ("prom_hist_mean", 80.0),
    # the frame delay's five stages: they add up to frame_delay_mean_ms
    "frame_retire_mean_ms": ("prom_hist_mean_where", 0.1),
    "frame_post_mean_ms": ("prom_hist_mean_where", 0.2),
    "frame_wake_mean_ms": ("prom_hist_mean_where", 0.6),
    "frame_queue_mean_ms": ("prom_hist_mean_where", 0.8),
    "frame_render_mean_ms": ("prom_hist_mean_where", 0.3),
    # the lead by kind (a kind first seen inside the window counts from 0)
    "step_lead_decode_inproc_ms": ("prom_hist_mean_where", 18.0),
    "step_lead_mixed_inproc_ms": ("prom_hist_mean_where", 120.0),
    "device_starved_s_in_window": ("prom_counter_delta", 0.29),
}
# PR 52's eight: the data file is there, the entry in BENCHMARK.json is a
# `benchmark` PR's to add (PERF.md section 7: entries go at the list's end,
# where perfbench/tests/test_roofline_block.py holds sdar's three to be)
_NO_ENTRY_YET = {
    "frame_retire_mean_ms", "frame_post_mean_ms", "frame_wake_mean_ms",
    "frame_queue_mean_ms", "frame_render_mean_ms",
    "step_lead_decode_inproc_ms", "step_lead_mixed_inproc_ms",
    "device_starved_s_in_window"}


def test_the_five_frame_stages_add_up_to_the_frame_delay():
    assert sum(v for n, (_, v) in _NEW_METRICS.items()
               if n.startswith("frame_") and n != "frame_delay_mean_ms") \
        == pytest.approx(_NEW_METRICS["frame_delay_mean_ms"][1])


@pytest.mark.parametrize("name", sorted(_NEW_METRICS))
def test_new_metric_file_reads_its_value_from_two_scrapes(name):
    """Each is a data file of its own over a reader of the benchmark, named
    in BENCHMARK.json as its file says, listed for every cell; a server
    without the series (the parent) reads nothing and does not raise.
    PR 52's eight have their file and no entry yet (``_NO_ENTRY_YET``):
    their file is held to a layer and an end-to-end metric the benchmark
    has, which is what the entry will have to say."""
    from perfbench import readers, stats
    from perfbench.spec import Benchmark
    bench = Benchmark()
    spec = bench.layer_metric(name)
    entry = next((m for m in bench.doc["per_layer"] if m["name"] == name),
                 None)
    reader, want = _NEW_METRICS[name]
    assert spec["reader"] == reader
    assert (entry is None) == (name in _NO_ENTRY_YET)
    if entry is None:
        assert spec["layer"] in {m["layer"] for m in bench.doc["per_layer"]}
        assert spec["moves"] in {m["name"] for m in bench.doc["end_to_end"]}
        assert spec["source"] in ("program_span", "program_counter")
        assert spec["better"] in ("lower", "higher") and spec["unit"]
    else:
        assert {k: spec[k] for k in ("unit", "layer", "moves", "source")} \
            == {k: entry[k] for k in ("unit", "layer", "moves", "source")}
        assert entry["workloads"] == bench.cell_names()
    ctx = {"scrape_before": stats.parse_prometheus(_SCRAPE_BEFORE),
           "scrape_after": stats.parse_prometheus(_SCRAPE_AFTER),
           "config": {"warmup": {"decode_window": 8}}}
    assert readers.load(reader)(spec, ctx) == pytest.approx(want)
    parent = stats.parse_prometheus("kgct_step_seconds_count 5\n")
    assert readers.load(reader)(
        spec, dict(ctx, scrape_before=parent, scrape_after=parent)) is None


@pytest.mark.parametrize("name", ["slow_step_s_in_window",
                                  "device_starved_s_in_window"])
def test_a_clean_window_reads_zero_slow_seconds_not_nothing(name):
    """The slow-step and the starved series are there from the first
    scrape, at 0: a clean window reports 0, and only a program without them
    reports nothing."""
    from perfbench import readers, stats
    from perfbench.spec import Benchmark
    spec = Benchmark().layer_metric(name)
    fresh = stats.parse_prometheus(
        "\n".join(Observability().render_prometheus()))
    assert readers.load(spec["reader"])(
        spec, {"scrape_before": fresh, "scrape_after": fresh}) == 0.0


class TestStepQueueLead:
    """``trace_step_lead`` (perfbench/readers), on the small synthetic
    capture ``perfbench/tests/data/step_lead_capture.xplane.pb`` (its
    generator beside it says what it holds) and on hand-built lists."""

    CAPTURE = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "perfbench", "tests", "data", "step_lead_capture.xplane.pb")

    def test_reads_the_median_lead_from_the_synthetic_capture(self):
        from pathlib import Path

        from perfbench.readers import load, trace_step_lead
        from perfbench.spec import Benchmark
        bench = Benchmark()
        spec = bench.layer_metric("step_queue_lead_ms")
        entry = next(m for m in bench.doc["per_layer"]
                     if m["name"] == "step_queue_lead_ms")
        assert spec["reader"] == "trace_step_lead"
        assert entry["source"] == "program_span" == spec["source"]
        assert entry["better"] == "higher"
        assert entry["workloads"] == bench.cell_names()
        spans = trace_step_lead.dispatch_spans(Path(self.CAPTURE))
        # five name their kind; the sixth (no arguments: a program older
        # than them) is not there
        assert [k for _, _, k in spans] == ["decode", "mixed", "decode",
                                            "decode", "prefill"]
        # 740, 350 and 10 us: the median, in ms
        assert load("trace_step_lead")(
            spec, {"trace_path": self.CAPTURE}) == pytest.approx(0.350)
        # an untraced run has no capture: nothing, and nothing is looked up
        assert load("trace_step_lead")(
            spec, {"trace": None, "profile": {}}) is None

    def test_kind_is_checked_and_edge_modules_are_dropped(self):
        from perfbench.readers.trace_step_lead import leads_ns
        modules = [(0.0, 100.0, "jit_decode_window_greedy(1)"),
                   (100.0, 50.0, "jit_mixed_step(2)"),
                   (150.0, 5.0, "jit__unstack(3)"),
                   (160.0, 100.0, "jit_decode_window_sampled(4)"),
                   (260.0, 40.0, "jit_prefill_hist_step(5)"),
                   (300.0, 100.0, "jit_decode_window_greedy(1)")]
        assert leads_ns([(20.0, 30.0, "mixed")], modules) == [70.0]
        # the first STEP module after the span's start decides, not the
        # first module; a span whose module is of another kind is left out
        assert leads_ns([(120.0, 125.0, "decode")], modules) == [35.0]
        assert leads_ns([(20.0, 30.0, "decode")], modules) == []
        assert leads_ns([(200.0, 210.0, "prefill")], modules) == [50.0]
        # the module that ends the capture is dropped, and so is a span
        # after every module; a late dispatch reads a negative lead
        assert leads_ns([(270.0, 280.0, "decode"),
                         (500.0, 510.0, "decode")], modules) == []
        assert leads_ns([(155.0, 170.0, "decode")], modules) == [-10.0]
        assert leads_ns([(20.0, 30.0, "mixed")], []) == []
