"""``host_spans.py`` and the ``trace_idle_by_span`` reader: on hand-built
intervals (nesting, the deepest span, a gap across two spans, a gap outside
every span, shares that sum to 100), and on a RECORDED slice of a traced
``qwen3-4b-bf16.batch-decode`` run on a TPU v5e (PR 24): 0.46 s (two whole
decode windows, a mixed and a prefill step between them) of the device
plane's operation and module lines beside the ``kgct.*`` lines of
the host plane (the step loop's thread and the event loop's), cut from one
capture. Expected values on the slice were worked out independently (a
100 ns raster of the idle time under each span)."""

from pathlib import Path

import pytest

from perfbench import host_spans as hs
from perfbench import trace as tr
from perfbench.readers import load as load_reader
from perfbench.readers import trace_idle_by_span

SLICE = Path(__file__).resolve().parents[1] / "testdata" / \
    "batch_decode_host_spans_slice.xplane.pb"


def _dev(*busy):
    return tr.DeviceTrace("/device:TPU:0",
                          ops=[(s, e - s, "op") for s, e in busy])


STEP = [
    (100.0, 900.0, "kgct.step"),
    (120.0, 80.0, "kgct.schedule"),             # 120-200
    (300.0, 100.0, "kgct.host_prep"),           # 300-400
    (400.0, 50.0, "kgct.device_dispatch"),      # 400-450, touches host_prep
    (500.0, 300.0, "kgct.device_fetch"),        # 500-800
    (850.0, 100.0, "kgct.postproc"),            # 850-950
    (1000.0, 100.0, "kgct.worker.post"),        # after the step
]


def test_deepest_span_wins_and_the_parent_keeps_what_no_child_covers():
    segs = hs.deepest(STEP)
    assert segs == [
        (100.0, 120.0, "kgct.step"), (120.0, 200.0, "kgct.schedule"),
        (200.0, 300.0, "kgct.step"), (300.0, 400.0, "kgct.host_prep"),
        (400.0, 450.0, "kgct.device_dispatch"), (450.0, 500.0, "kgct.step"),
        (500.0, 800.0, "kgct.device_fetch"), (800.0, 850.0, "kgct.step"),
        (850.0, 950.0, "kgct.postproc"), (950.0, 1000.0, "kgct.step"),
        (1000.0, 1100.0, "kgct.worker.post")]
    # disjoint, sorted, and as long as the outermost spans together
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    assert sum(e - s for s, e, _ in segs) == 1000.0
    # the order the events come in does not matter
    assert hs.deepest(list(reversed(STEP))) == segs


def test_three_levels_and_a_child_that_outlasts_its_parent():
    spans = [(0.0, 100.0, "a"), (10.0, 50.0, "b"), (20.0, 10.0, "c"),
             (90.0, 30.0, "late")]          # 90-120, parent ends at 100
    assert hs.deepest(spans) == [
        (0.0, 10.0, "a"), (10.0, 20.0, "b"), (20.0, 30.0, "c"),
        (30.0, 60.0, "b"), (60.0, 90.0, "a"), (90.0, 100.0, "late")]


def test_idle_is_the_complement_between_first_and_last_operation():
    dev = _dev((50, 150), (140, 250), (600, 700), (1200, 1300))
    assert hs.idle_intervals(dev) == [(250, 600), (700, 1200)]
    assert hs.idle_intervals(_dev((0, 10))) == []
    assert hs.idle_intervals(_dev()) == []


def test_a_gap_across_two_spans_and_a_gap_outside_every_span():
    segs = hs.deepest(STEP)
    # 250-600: step self 250-300 and 450-500, host_prep 100, dispatch 50,
    # fetch 100; 700-1200: fetch 100, step self 50+50, postproc 100,
    # post 100, and 1100-1200 under nothing
    got = hs.split([(250.0, 600.0), (700.0, 1200.0)], segs)
    assert got == {"kgct.step": 200.0, "kgct.host_prep": 100.0,
                   "kgct.device_dispatch": 50.0, "kgct.device_fetch": 200.0,
                   "kgct.postproc": 100.0, "kgct.worker.post": 100.0,
                   hs.UNATTRIBUTED: 100.0}
    assert sum(got.values()) == 350.0 + 500.0
    assert hs.split([(2000.0, 2100.0)], segs) == {hs.UNATTRIBUTED: 100.0}
    assert hs.split([], segs) == {hs.UNATTRIBUTED: 0.0}


def _ctx(host, dev, path="hand-built"):
    trace_idle_by_span._SPLITS[Path(path)] = hs.idle_by_span(
        tr.TraceSummary([dev], 0.0, 2000.0), host)
    return {"trace_path": path}


SPECS = {
    "idle_attributed_share": {"spans": ["kgct.*"]},
    "idle_in_schedule_share": {"spans": ["kgct.schedule"]},
    "idle_in_host_prep_share": {"spans": ["kgct.host_prep"],
                                "self_of": "kgct.step"},
    "idle_in_postproc_share": {"spans": ["kgct.postproc"]},
    "idle_outside_step_share": {"spans": ["kgct.worker.admit",
                                          "kgct.worker.post",
                                          "kgct.worker.wait"]},
    "idle_in_device_dispatch_share": {"spans": ["kgct.device_dispatch"]},
    "idle_in_device_fetch_share": {"spans": ["kgct.device_fetch"]},
}


def test_the_metric_files_are_these_specs():
    import json
    for name, spec in SPECS.items():
        doc = json.loads((SLICE.parents[1] / "layer_metrics"
                          / f"{name}.json").read_text())
        assert doc["reader"] == "trace_idle_by_span" and doc["scale"] == 100.0
        assert {k: doc[k] for k in spec} == spec and \
            ("self_of" in doc) == ("self_of" in spec), name


@pytest.mark.parametrize("name,want", [
    ("idle_attributed_share", 750 / 850),
    ("idle_in_schedule_share", 0.0),
    ("idle_in_host_prep_share", 300 / 850),
    ("idle_in_postproc_share", 100 / 850),
    ("idle_outside_step_share", 100 / 850),
    ("idle_in_device_dispatch_share", 50 / 850),
    ("idle_in_device_fetch_share", 200 / 850)])
def test_reader_on_hand_built_intervals(name, want):
    loop_thread = [(0.0, 5000.0, "kgct.http.write")]    # never the worker
    host = hs.HostSpans(threads=[loop_thread, sorted(STEP)])
    ctx = _ctx(host, _dev((50, 250), (600, 700), (1200, 1300)))
    got = load_reader("trace_idle_by_span")(
        dict(SPECS[name], scale=100.0), ctx)
    assert got == pytest.approx(100 * want)


def test_shares_sum_to_100_with_the_unattributed_rest():
    host = hs.HostSpans(threads=[sorted(STEP)])
    ctx = _ctx(host, _dev((50, 250), (600, 700), (1200, 1300)))
    read = load_reader("trace_idle_by_span")
    v = {n: read(dict(s, scale=100.0), ctx) for n, s in SPECS.items()}
    parts = sum(x for n, x in v.items() if n != "idle_attributed_share")
    assert parts == pytest.approx(v["idle_attributed_share"])
    assert parts + (100 - v["idle_attributed_share"]) == pytest.approx(100)


def test_nothing_to_read_is_none_not_an_error(tmp_path):
    read = load_reader("trace_idle_by_span")
    spec = dict(SPECS["idle_attributed_share"], scale=100.0)
    # an untraced run: no trace, no capture reply; nothing is looked up
    assert read(spec, {"trace": None, "profile": {}}) is None
    assert read(spec, {"trace": None}) is None
    # a program that writes no kgct.* span (the parent of PR 24): a thread
    # without kgct.step is not the step loop's
    no_step = hs.HostSpans(threads=[[(0.0, 10.0, "kgct.http.write")]])
    assert no_step.worker() == [] and hs.HostSpans().worker() == []
    dev = _dev((0, 10), (20, 30))
    assert hs.idle_by_span(tr.TraceSummary([dev], 0, 30), no_step) is None
    assert read(spec, _ctx(no_step, dev, "no-spans")) is None
    # no device plane, or a device that was never idle
    host = hs.HostSpans(threads=[sorted(STEP)])
    assert hs.idle_by_span(tr.TraceSummary([], 0, 30), host) is None
    assert hs.idle_by_span(None, host) is None
    assert hs.idle_by_span(tr.TraceSummary([_dev((0, 10))], 0, 30),
                           host) is None


def test_the_capture_is_the_one_file_under_the_profile_root(tmp_path):
    with pytest.raises(RuntimeError, match="found 0"):
        hs.find_capture(tmp_path)
    run = tmp_path / "cell-s1-t1" / "plugins/profile/2026_09_27_01_02_03"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(b"x")
    assert hs.find_capture(tmp_path) == run / "vm.xplane.pb"
    other = tmp_path / "cell-s2-t1" / "plugins/profile/2026_09_27_01_02_09"
    other.mkdir(parents=True)
    (other / "vm.xplane.pb").write_bytes(b"y")
    with pytest.raises(RuntimeError, match="found 2"):  # not ours alone
        hs.find_capture(tmp_path)


def test_counter_difference_reader():
    read = load_reader("prom_counter_delta_diff")
    spec = {"family": "kgct_xla_compile_requests_total",
            "minus": "kgct_xla_compile_cache_hits_total"}
    before = {("kgct_xla_compile_requests_total", ()): 400.0,
              ("kgct_xla_compile_cache_hits_total", ()): 390.0}
    after = {("kgct_xla_compile_requests_total", ()): 407.0,
             ("kgct_xla_compile_cache_hits_total", ()): 395.0}
    assert read(spec, {"scrape_before": before, "scrape_after": after}) == 2.0
    assert read(spec, {"scrape_before": before,
                       "scrape_after": before}) == 0.0
    # the parent of PR 24 exports neither counter
    assert read(spec, {"scrape_before": {}, "scrape_after": {}}) is None


# -- the recorded slice --------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return tr.load(SLICE), hs.load(SLICE)


def test_slice_holds_the_device_lines_and_both_host_threads(recorded):
    summary, host = recorded
    assert [d.name for d in summary.devices] == ["/device:TPU:0"]
    dev = summary.devices[0]
    assert len(dev.ops) == 36704 and len(dev.modules) == 33
    # the kernels carry their names since PR 24, and still say custom-call
    assert any(tr.short_op(n).startswith("%paged_decode.")
               and "custom-call" in tr.short_op(n) for _, _, n in dev.ops)
    # ... and the module names decode_step_ms looks for are as they were
    assert tr.module_time(dev, "decode_window")[1] == 2
    assert sorted(len(t) for t in host.threads) == [36, 382]
    worker = host.worker()
    assert len(worker) == 36
    assert {n for _, _, n in worker} == {
        "kgct.step", "kgct.schedule", "kgct.host_prep",
        "kgct.device_dispatch", "kgct.device_fetch", "kgct.postproc",
        "kgct.worker.admit", "kgct.worker.post"}
    [loop_thread] = [t for t in host.threads if t is not worker]
    assert {n for _, _, n in loop_thread} == {"kgct.http.detokenize",
                                              "kgct.http.write"}
    # kgct.clock: (its start on the trace's clock, time.monotonic_ns() then)
    assert host.clock == (47599288.0, 1882028368202)


# a 100 ns raster of the idle time under the latest-started covering span
RASTER = {"kgct.worker.post": 41.503, "kgct.device_fetch": 15.351,
          "kgct.host_prep": 13.018, "kgct.step": 11.850,
          "kgct.postproc": 7.628, "kgct.schedule": 7.016,
          "kgct.device_dispatch": 2.003, "kgct.worker.admit": 0.228,
          hs.UNATTRIBUTED: 1.402}


def test_slice_idle_by_span_against_the_raster(recorded):
    summary, host = recorded
    idle = hs.idle_intervals(summary.devices[0])
    assert len(idle) == 198
    assert sum(b - a for a, b in idle) == pytest.approx(57428470.0)
    shares = hs.idle_by_span(summary, host)
    assert set(shares) == set(RASTER)
    for name, want in RASTER.items():
        assert 100 * shares[name] == pytest.approx(want, abs=0.02), name
    assert sum(shares.values()) == pytest.approx(1.0)


@pytest.mark.parametrize("name,want", [
    ("idle_attributed_share", 100 - 1.402),
    ("idle_in_schedule_share", 7.016),
    ("idle_in_host_prep_share", 13.018 + 11.850),
    ("idle_in_postproc_share", 7.628),
    ("idle_outside_step_share", 41.503 + 0.228),
    ("idle_in_device_dispatch_share", 2.003),
    ("idle_in_device_fetch_share", 15.351)])
def test_reader_on_the_slice(recorded, name, want):
    """Through the reader as the harness calls it, the capture's path in
    ``ctx`` (the parsed device planes given, or left for it to load)."""
    read = load_reader("trace_idle_by_span")
    spec = dict(SPECS[name], scale=100.0)
    trace_idle_by_span._SPLITS.pop(SLICE, None)
    got = read(spec, {"trace_path": SLICE, "trace": recorded[0]})
    assert got == pytest.approx(want, abs=0.03)
    trace_idle_by_span._SPLITS.pop(SLICE, None)
    assert read(spec, {"trace_path": str(SLICE), "trace": None}) == got


def test_old_slice_without_host_spans_reads_as_nothing():
    """PR 23's slice is the device plane alone, as a capture of the parent
    of PR 24 is: every metric of the reader is left out, nothing raises."""
    old = SLICE.with_name("batch_decode_slice.xplane.pb")
    assert hs.load(old).threads == [] and hs.load(old).clock is None
    read = load_reader("trace_idle_by_span")
    for spec in SPECS.values():
        assert read(dict(spec, scale=100.0), {"trace_path": old}) is None
