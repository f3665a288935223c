"""Host spans on the profiler's clock (observability/phases.py), the
``/debug/profile`` capture that does not stop the server, and the compile
counters fed by JAX's own monitoring events (utils/compile_cache.py).

Engine and server are debug-tiny on the CPU. ``jax.profiler.TraceAnnotation``
is replaced by a recorder where the test is about WHICH spans are written
and how they nest; one test makes a real capture and reads the
``.xplane.pb`` back."""

import asyncio
import tempfile
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.observability.phases import StepPhaseStats
from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
from kubernetes_gpu_cluster_tpu.utils.compile_cache import COMPILE_COUNTERS

STEP_PHASES = ("schedule", "host_prep", "device_dispatch", "device_fetch",
               "postproc")
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13, 14, 15, 16, 17]]


def _config():
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  decode_buckets=(1, 2, 4),
                                  prefill_buckets=(128, 256),
                                  decode_window=4))


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: every construction,
    and on enter the thread and the names open on that thread."""

    def __init__(self):
        self.made = []          # (name, kwargs)
        self.entered = []       # (name, thread id, names open around it)
        self._open = threading.local()
        rec = self

        class Annotation:
            def __init__(self, name, **kwargs):
                self.name = name
                rec.made.append((name, kwargs))

            def __enter__(self):
                stack = rec._open.__dict__.setdefault("stack", [])
                rec.entered.append((self.name, threading.get_ident(),
                                    tuple(stack)))
                stack.append(self.name)
                return self

            def __exit__(self, *exc):
                assert rec._open.stack.pop() == self.name   # LIFO per thread
                return False

        self.cls = Annotation

    def names(self):
        return {name for name, _, _ in self.entered}


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(_config())


def _generate(engine, capturing, params):
    engine.obs.phases.capturing = capturing
    try:
        return [(o.output_token_ids, o.finish_reason)
                for o in engine.generate(PROMPTS, params)]
    finally:
        engine.obs.phases.capturing = False


@pytest.fixture(scope="module")
def recorded_steps(engine):
    """Spans of a whole generate() with ``capturing`` True, the profiler's
    annotation class replaced by the recorder."""
    rec = Recorder()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.profiler, "TraceAnnotation", rec.cls)
        _generate(engine, True, SamplingParams(max_tokens=12,
                                               temperature=0.0))
    return rec


class TestStepSpans:
    def test_off_constructs_no_annotation(self, engine, monkeypatch):
        rec = Recorder()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
        assert engine.obs.phases.capturing is False
        outs = _generate(engine, False,
                         SamplingParams(max_tokens=12, temperature=0.0))
        assert len(outs) == len(PROMPTS) and engine.stats.steps > 0
        assert rec.made == []
        # the cumulative phase counters (the /metrics contract) still count
        assert all(engine.obs.phases.counts[p] > 0 for p in STEP_PHASES)

    @pytest.mark.parametrize("phase", STEP_PHASES)
    def test_on_every_phase_is_a_span_inside_the_step(self, recorded_steps,
                                                      phase):
        hits = [around for name, _, around in recorded_steps.entered
                if name == f"kgct.{phase}"]
        assert hits, f"no kgct.{phase} span"
        # a leaf directly under the step: phases do not nest in each other
        assert all(around == ("kgct.step",) for around in hits), hits[:3]

    def test_on_the_step_is_the_parent_and_names_both_programs(
            self, recorded_steps):
        """An iteration dispatches program n+1 and fetches program n: its
        span carries both numbers, not a count of iterations."""
        steps = [(name, kw) for name, kw in recorded_steps.made
                 if name == "kgct.step"]
        assert steps and all(set(kw) == {"launched", "retired"}
                             for _, kw in steps)
        # the first iteration finds nothing in flight: it launches the
        # program it then retires; from then on the one behind it
        first = steps[0][1]
        assert first["launched"] == first["retired"]
        assert all(kw["retired"] == kw["launched"] - 1
                   for _, kw in steps[1:])
        nums = [kw["retired"] for _, kw in steps]
        assert nums == list(range(nums[0], nums[0] + len(nums)))
        assert all(around == () for name, _, around
                   in recorded_steps.entered if name == "kgct.step")
        assert recorded_steps.names() == \
            {"kgct.step"} | {f"kgct.{p}" for p in STEP_PHASES}

    def test_dispatch_and_fetch_spans_carry_their_own_program(
            self, recorded_steps):
        """``kgct.device_dispatch`` and ``kgct.device_fetch`` name the
        program THEY serve (step, kind, rows): every program dispatched is
        fetched once, under the same number and kind, and inside one
        iteration the dispatch is the successor's."""
        made = recorded_steps.made
        disp = [kw for name, kw in made if name == "kgct.device_dispatch"]
        fetch = [kw for name, kw in made if name == "kgct.device_fetch"]
        assert disp and all(set(kw) == {"step", "kind", "rows"}
                            for kw in disp + fetch)
        key = lambda kw: (kw["step"], kw["kind"], kw["rows"])  # noqa: E731
        assert sorted(map(key, disp)) == sorted(map(key, fetch))
        assert len({kw["step"] for kw in disp}) == len(disp)
        assert {kw["kind"] for kw in disp} >= {"prefill", "decode"}
        # in the order they were made, a fetch of n follows the dispatch
        # of n+1 (the queue is one deep), but for the last program
        order = [(name, kw["step"]) for name, kw in made
                 if name in ("kgct.device_dispatch", "kgct.device_fetch")]
        for i, (name, n) in enumerate(order):
            if name == "kgct.device_fetch" and i + 1 < len(order):
                assert order[i - 1] in (("kgct.device_dispatch", n + 1),
                                        ("kgct.device_dispatch", n)), order

    @pytest.mark.parametrize("params", [
        SamplingParams(max_tokens=12, temperature=0.0),
        SamplingParams(max_tokens=12, temperature=0.8, top_p=0.9, seed=7),
    ], ids=["greedy", "seeded"])
    def test_token_ids_identical_on_and_off(self, engine, params):
        """Real annotations (no profiler session: they are no-ops in the
        profiler, but every enter/exit runs)."""
        off = _generate(engine, False, params)
        on = _generate(engine, True, params)
        assert on == off

    def test_phase_takes_one_clock_on_enter(self, monkeypatch):
        """One clock per end of a phase, and the start it records is on the
        request tracer's clock (time.monotonic)."""
        import kubernetes_gpu_cluster_tpu.observability.phases as ph
        reads = []

        class Clock:
            @staticmethod
            def monotonic():
                reads.append("monotonic")
                return 100.0 + len(reads)

            def __getattr__(self, name):
                raise AssertionError(f"phases read time.{name}")
        monkeypatch.setattr(ph, "time", Clock())
        stats = StepPhaseStats()
        with stats.phase("schedule"):
            pass
        assert reads == ["monotonic", "monotonic"]
        assert stats._current == [("schedule", 101.0, 1.0)]

    def test_span_off_is_one_shared_object(self):
        stats = StepPhaseStats()
        assert stats.span("worker.wait") is stats.span("step", step_num=3)
        with stats.span("worker.post"):
            pass


# -- the server: worker and HTTP spans, /debug/profile ------------------------

class _Served:
    def __init__(self, profile_dir):
        self.loop = asyncio.new_event_loop()
        self.server = build_server(_config(), tokenizer_path=None,
                                   model_name="debug-tiny",
                                   profile_dir=profile_dir)
        self.client = TestClient(TestServer(self.server.build_app()),
                                 loop=self.loop)
        self.loop.run_until_complete(self.client.start_server())
        self.phases = self.server.engine.engine.obs.phases

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    async def stream(self, max_tokens=24):
        r = await self.client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "hello world",
            "max_tokens": max_tokens, "temperature": 0.0, "stream": True})
        assert r.status == 200
        return await r.text()

    def close(self):
        self.run(self.client.close())
        self.loop.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = _Served(str(tmp_path_factory.mktemp("profile")))
    s.run(s.stream(8))          # compile before anything is timed
    yield s
    s.close()


@pytest.fixture(scope="module")
def recorded_serving(served):
    rec = Recorder()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.profiler, "TraceAnnotation", rec.cls)
        served.phases.capturing = True
        try:
            text = served.run(served.stream())
            # the worker goes back to waiting a moment after the last frame
            # (the wait it sat in when the capture began was entered unseen)
            served.run(asyncio.sleep(0.2))
        finally:
            served.phases.capturing = False
    assert text.rstrip().endswith("data: [DONE]")
    return rec


class TestServingSpans:
    @pytest.mark.parametrize("name,on_loop_thread", [
        ("kgct.worker.admit", False), ("kgct.worker.post", False),
        ("kgct.worker.wait", False), ("kgct.step", False),
        ("kgct.http.detokenize", True), ("kgct.http.write", True)])
    def test_span_and_its_thread(self, recorded_serving, name,
                                 on_loop_thread):
        hits = [(tid, around) for n, tid, around in recorded_serving.entered
                if n == name]
        assert hits, f"no {name} span; saw {recorded_serving.names()}"
        here = threading.get_ident()        # the loop runs on this thread
        assert all((tid == here) == on_loop_thread for tid, _ in hits)
        # the worker's three and the step are siblings: none inside another
        assert all(around == () for _, around in hits), hits[:3]

    def test_worker_turn_is_admit_step_post(self, recorded_serving):
        worker = [n for n, tid, _ in recorded_serving.entered
                  if tid != threading.get_ident()
                  and n in ("kgct.worker.admit", "kgct.step",
                            "kgct.worker.post", "kgct.worker.wait")]
        turns = "".join({"kgct.worker.admit": "a", "kgct.step": "s",
                         "kgct.worker.post": "p",
                         "kgct.worker.wait": "w"}[n] for n in worker)
        assert "asp" in turns
        # a step is always followed by the posting of what it returned
        assert "sa" not in turns and "sw" not in turns and "ss" not in turns


def _slow_profiler(monkeypatch, delay_s, calls):
    def start_trace(log_dir, profiler_options=None):
        calls.append(("start", log_dir, threading.get_ident(),
                      profiler_options.python_tracer_level))
        time.sleep(delay_s)             # blocks the thread it runs on

    def stop_trace():
        calls.append(("stop", None, threading.get_ident(), None))
        time.sleep(delay_s)
    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)


class TestProfileEndpoint:
    def test_loop_answers_while_the_profiler_starts_and_stops(
            self, served, monkeypatch):
        """start_trace/stop_trace that block for 0.6 s each: /health keeps
        answering at once, a second capture gets 409, ``capturing`` is True
        for the capture and False after, the reply names the directory
        handed to the profiler and both stamps."""
        calls = []
        _slow_profiler(monkeypatch, 0.6, calls)
        seen = {"health_ms": [], "capturing": []}

        async def go():
            t_before = time.monotonic_ns()
            cap = asyncio.ensure_future(
                served.client.post("/debug/profile?seconds=0.3"))
            await asyncio.sleep(0.05)
            second = await served.client.post("/debug/profile?seconds=0.1")
            while not cap.done():
                t0 = time.perf_counter()
                r = await served.client.get("/health")
                assert r.status == 200
                seen["health_ms"].append((time.perf_counter() - t0) * 1e3)
                seen["capturing"].append(served.phases.capturing)
                await asyncio.sleep(0.05)
            r = await cap
            return second.status, r.status, await r.json(), t_before
        second, status, reply, t_before = served.run(go())
        assert second == 409 and status == 200
        # ~1.5 s of capture, polled every 50 ms: had the profiler run on the
        # loop, two polls would each have waited 0.6 s
        assert len(seen["health_ms"]) >= 10
        assert max(seen["health_ms"]) < 300, seen["health_ms"]
        # spans are written for the capture's seconds and the start before
        # them, not through the stop (the last 0.6 s of polls)
        assert seen["capturing"][0] and not seen["capturing"][-1]
        assert 8 <= sum(seen["capturing"]) <= len(seen["capturing"]) - 6
        assert served.phases.capturing is False
        assert [c[0] for c in calls] == ["start", "stop"]
        assert calls[0][3] == 0         # no Python function tracer
        assert all(c[2] != threading.get_ident() for c in calls)
        assert reply["trace_dir"] == served.server._profile_dir == calls[0][1]
        assert reply["seconds"] == 0.3
        a, b = reply["started_monotonic_ns"], reply["stopping_monotonic_ns"]
        assert t_before < a < b <= time.monotonic_ns()
        assert 0.3e9 <= b - a < 0.6e9         # the sleep, not start or stop
        again = served.run(served.client.post("/debug/profile?seconds=0.1"))
        assert again.status == 200            # the gate opened again

    def test_failed_start_is_a_500_and_leaves_nothing_on(self, served,
                                                         monkeypatch):
        def boom(log_dir, profiler_options=None):
            raise RuntimeError("no profiler here")
        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: pytest.fail("stop without a start"))
        r = served.run(served.client.post("/debug/profile?seconds=0.1"))
        assert r.status == 500
        assert served.phases.capturing is False
        assert served.server._profile_busy is False

    def test_default_directory_is_this_process_own(self, monkeypatch,
                                                   tmp_path):
        """No --profile-dir: a directory of this process's own under the
        temporary directory (not a name every checkout on the machine
        shares), named on the first capture, kept for the next, and made by
        the profiler when it writes, not by the server."""
        import os
        calls = []
        _slow_profiler(monkeypatch, 0.0, calls)
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
        s = _Served(None)
        try:
            assert s.server._profile_dir is None and not list(
                tmp_path.iterdir())
            one = s.run(s.run(s.client.post(
                "/debug/profile?seconds=0.1")).json())
            two = s.run(s.run(s.client.post(
                "/debug/profile?seconds=0.1")).json())
        finally:
            s.close()
        named = Path(one["trace_dir"])
        assert named.parent == tmp_path and not named.exists()
        assert named.name.startswith(f"kgct-profile-{os.getpid()}-")
        assert two["trace_dir"] == one["trace_dir"] == calls[0][1]

    def test_real_capture_holds_the_host_spans_and_the_clock(self, served):
        """A capture on the CPU backend, a stream running through it: the
        ``.xplane.pb`` lies under --profile-dir, its host planes hold the
        ``kgct.*`` spans, and ``kgct.clock`` carries the reply's stamp, so
        /debug/trace's clock can be laid on the trace's."""
        from jax.profiler import ProfileData

        async def go():
            cap = asyncio.ensure_future(
                served.client.post("/debug/profile?seconds=0.5"))
            await asyncio.sleep(0.1)
            while not served.phases.capturing and not cap.done():
                await asyncio.sleep(0.02)
            text = await served.stream(16)
            return text, await (await cap).json()
        text, reply = served.run(go())
        assert text.rstrip().endswith("data: [DONE]")
        files = list(Path(reply["trace_dir"]).glob(
            "plugins/profile/*/*.xplane.pb"))
        assert len(files) == 1
        assert Path(reply["trace_dir"]) == Path(served.server._profile_dir)
        names, clock, args = set(), [], {}
        for plane in ProfileData.from_file(str(files[0])).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("kgct."):
                        names.add(ev.name)
                    if ev.name == "kgct.clock":
                        clock.append((ev.start_ns, dict(ev.stats)))
                    if ev.name in ("kgct.worker.post", "kgct.http.write",
                                   "kgct.device_fetch"):
                        args.setdefault(ev.name, []).append(dict(ev.stats))
        assert {"kgct.step", "kgct.worker.post", "kgct.http.write",
                "kgct.clock"} | {f"kgct.{p}" for p in STEP_PHASES} <= names
        # a frame's spans carry the number of the program whose fetch they
        # follow: the hand-over (with its rows) and the write
        fetched = {int(a["step"]) for a in args["kgct.device_fetch"]}
        posted = [a for a in args["kgct.worker.post"] if int(a["rows"])]
        assert posted and {int(a["step"]) for a in posted} <= fetched
        assert {int(a["step"]) for a in args["kgct.http.write"]} \
            & fetched
        # the dispatch spans carry their program's kind as the benchmark's
        # reader of the host's lead finds it (perfbench trace_step_lead)
        from perfbench.readers import trace_step_lead
        kinds = [k for _, _, k in trace_step_lead.dispatch_spans(files[0])]
        assert kinds and set(kinds) <= {"prefill", "decode", "mixed"}
        [(clock_ns, stats)] = clock
        assert int(stats["monotonic_ns"]) == reply["started_monotonic_ns"]
        # the stamp was taken just before the annotation: the offset between
        # the two clocks is stamp - start_ns to within the handler's own time
        assert clock_ns >= 0


# -- compile counters ---------------------------------------------------------

class TestCompileCounters:
    def _snap(self):
        c = COMPILE_COUNTERS
        return c.requests, c.seconds, c.cache_hits

    def test_rise_on_a_new_shape_only(self):
        """Every program that reaches the backend counts, an eager one-op
        one too; the same shape again does not."""
        COMPILE_COUNTERS.install()
        COMPILE_COUNTERS.install()          # once, however often asked

        @jax.jit
        def f(x):
            return x * 3 + 1
        # the inputs first: making them runs eager programs of its own
        a, b = jnp.ones((3, 5)), jnp.ones((7, 5))
        c = jnp.ones((13, 3, 2), jnp.int16)
        r0, s0, h0 = self._snap()
        f(a).block_until_ready()
        r1, s1, h1 = self._snap()
        assert r1 == r0 + 1 and s1 > s0
        f(a).block_until_ready()
        assert self._snap() == (r1, s1, h1)
        f(b).block_until_ready()                # a new shape
        assert self._snap()[0] == r1 + 1
        (c + c).block_until_ready()             # an eager one-op program
        assert self._snap()[0] == r1 + 2

    def test_cache_hit_counts_as_request_and_as_hit(self, tmp_path):
        """With a persistent cache: the first compilation is a request and
        no hit; once the in-memory caches are dropped the same program is
        a request again and a hit."""
        from jax.experimental.compilation_cache import (
            compilation_cache as cc)
        COMPILE_COUNTERS.install()
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_enable_compilation_cache")
        saved = {k: getattr(jax.config, k) for k in keys}
        try:
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            cc.reset_cache()

            def g(x):
                return jnp.tanh(x) * 5 - x
            x = jnp.ones((11, 3))
            r0, _, h0 = self._snap()
            jax.jit(g)(x).block_until_ready()
            r1, _, h1 = self._snap()
            assert (r1 - r0, h1 - h0) == (1, 0)
            jax.clear_caches()
            jax.jit(g)(x).block_until_ready()
            r2, _, h2 = self._snap()
            assert (r2 - r1, h2 - h1) == (1, 1)
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            cc.reset_cache()

    def test_metrics_render_them(self, served):
        text = served.run(served.run(served.client.get("/metrics")).text())
        for fam in ("kgct_xla_compile_requests_total",
                    "kgct_xla_compile_seconds_total",
                    "kgct_xla_compile_cache_hits_total"):
            assert f"# TYPE {fam} counter" in text
            [line] = [l for l in text.splitlines()
                      if l.startswith(fam + " ")]
            assert float(line.split()[-1]) >= 0
        assert "# HELP kgct_jit_compiles_total" in text
        requests = float([l for l in text.splitlines() if l.startswith(
            "kgct_xla_compile_requests_total ")][0].split()[-1])
        assert requests == COMPILE_COUNTERS.requests > 0
