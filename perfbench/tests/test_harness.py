"""The harness's own decisions that need no server: which requests count as
hung, what the latency population is, where a trace may be taken from, and
what the in-load probe is held to."""

import pytest

from perfbench import correctness, harness
from perfbench.client import Record


def _rec(sent, frames, ended=None, max_tokens=4, **kw):
    r = Record(index=0, phase="load", prompt_len=8, max_tokens=max_tokens,
               due=sent, sent=sent, frames=list(frames), ended=ended, **kw)
    if ended is not None and not kw.get("cancelled"):
        r.status, r.done, r.finish_reason = 200, True, "length"
    return r


def test_a_request_silent_for_stall_s_at_the_end_of_the_window_is_hung():
    w = (100.0, 140.0)
    live = _rec(135.0, [(136.0, 1), (139.9, 1)], ended=141.0, cancelled=True)
    slow_start = _rec(138.0, [], ended=141.0, cancelled=True)
    silent = _rec(110.0, [(111.0, 1), (125.0, 1)], ended=141.0,
                  cancelled=True)
    never = _rec(120.0, [], ended=None)
    woke_late = _rec(110.0, [(111.0, 1), (140.5, 1)], ended=141.0,
                     cancelled=True)
    ladder = _rec(90.0, [], ended=None)
    ladder.phase = "ladder"
    recs = [live, slow_start, silent, never, woke_late, ladder]
    assert harness.stalled_at_end(recs, w, 10.0) == [silent, never,
                                                     woke_late]
    assert harness.stalled_at_end(recs, w, 60.0) == []


def test_hung_requests_are_attempted_failed_and_beyond_every_percentile():
    w = (100.0, 140.0)
    ok = [_rec(101.0 + i, [(102.0 + i, 1), (102.1 + i, 1), (102.2 + i, 1),
                           (102.3 + i, 1)], ended=102.4 + i)
          for i in range(9)]
    hung = _rec(105.0, [(106.0, 1)], ended=None)
    raw = {"records": ok + [hung], "window": w, "setup_s": 1.0,
           "stall_s": 10.0}
    e = harness.end_to_end(raw, 40.0)
    assert (e["_attempted"], e["_failed"], e["_hung"]) == (10, 1, 1)
    assert e["tpot_p50_ms"] == pytest.approx(100.0)
    # the 90th percentile of ten falls on the hung one: no number, so the
    # metric is left out of the line (and the run is not correct)
    assert e["tpot_p90_ms"] is None
    assert e["out_tok_s"] == pytest.approx((9 * 4 + 1) / 40.0)


def test_a_trace_is_taken_only_from_the_runs_own_directory(tmp_path):
    with pytest.raises(harness.RunFailure):
        harness.collect_trace(tmp_path)
    run = tmp_path / "plugins/profile/2026_09_27_01_02_03"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(b"x")
    assert harness.collect_trace(tmp_path) == run / "vm.xplane.pb"
    other = tmp_path / "plugins/profile/2026_09_27_01_02_09"
    other.mkdir()
    (other / "vm.xplane.pb").write_bytes(b"y")
    with pytest.raises(harness.RunFailure):     # two captures: not ours alone
        harness.collect_trace(tmp_path)


def test_the_probe_beside_the_load_is_held_to_the_same_golden():
    golden = {"tolerance_logprob": 0.1, "probes": [
        {"tokens": [5, 6, 7], "logprobs": [-1.0, -2.0, -3.0]},
        {"tokens": [8], "logprobs": [-1.0]}]}
    near = {"usage_ok": True, "tokens": [5, 9, 1],
            "top": [{"5": -1.05}, {"6": -2.08, "9": -2.0}, {"1": -0.5}]}
    assert correctness.compare(golden, [near], " (beside the load)") == []
    far = dict(near, top=[{"5": -1.2}, {"6": -2.0}, {"7": -3.0}])
    (problem,) = correctness.compare(golden, [far], " (beside the load)")
    assert "probe 0 (beside the load) position 0" in problem
    assert correctness.max_logprob_gap(golden["probes"][0], near) == \
        pytest.approx(0.08)


def test_the_client_sees_a_stall_while_it_lasts_and_idle_time_is_none():
    import time
    from perfbench.client import LoadClient
    c = LoadClient("http://x", "m")
    assert c.take_worst_stall() == 0.0          # nothing open: no stall
    rec = _rec(time.perf_counter(), [])
    c._progress = time.perf_counter() - 3.0     # a stream opened 3 s ago
    c.inflight.add(rec)
    assert 3.0 <= c.take_worst_stall() < 3.5    # still running, and counted
    c._worst_stall = 1.25                       # a finished stall, recorded
    c.inflight.clear()
    assert c.take_worst_stall() == 1.25 and c.take_worst_stall() == 0.0
