"""Chaos suite: every resilience recovery path exercised under KGCT_FAULT
(deterministic fault injection, JAX_PLATFORMS=cpu, no real failures):

- admission control sheds a budget-blown request with 429 + Retry-After
  while unbudgeted requests keep flowing;
- SIGTERM drain finishes in-flight streams, rejects new work with 503, and
  flips /health before exit;
- an injected step stall trips the watchdog (health 503) and self-heals;
- a broadcast failure (dead follower) group-aborts in-flight work and the
  leader stays serveable;
- a follower whose leader dies (or goes silent) group-aborts and flips its
  liveness-tied health endpoint;
- router: connect-phase retry with backoff, stalled-stream circuit breaking
  with rebalance + recovery, bounded metrics scrapes, cold-start probing,
  and OpenAI-shaped 503s.

All tests are `chaos`-marked, seeded, and keep every sleep under 1 s.
"""

import asyncio
import dataclasses
import json
import socket
import threading
import time
import types
import urllib.error
import urllib.request

import pytest
from aiohttp.test_utils import TestClient, TestServer

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, ResilienceConfig, SchedulerConfig,
    get_model_config)
from kubernetes_gpu_cluster_tpu.engine import SamplingParams
from kubernetes_gpu_cluster_tpu.resilience import (DrainState, LoopLiveness,
                                                   configure_faults)
from kubernetes_gpu_cluster_tpu.resilience.drain import install_sigterm_drain
from kubernetes_gpu_cluster_tpu.serving.api_server import (TTFT_BUDGET_HEADER,
                                                           build_server)
from kubernetes_gpu_cluster_tpu.serving.multihost import (DirectiveFollower,
                                                          DirectiveLeader,
                                                          serve_follower_health)
from kubernetes_gpu_cluster_tpu.serving.router import Router

from test_serving import _assert_valid_exposition

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clear_faults():
    configure_faults(None)
    yield
    configure_faults(None)


def _engine_config(**res_kw):
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  decode_buckets=(1, 2, 4),
                                  prefill_buckets=(128, 256),
                                  decode_window=4),
        resilience=ResilienceConfig(**res_kw))


_SRV: dict = {}


@pytest.fixture(scope="module")
def chaos_client():
    """One engine + server for the module; watchdog tight enough to catch an
    injected 0.6 s stall within the test's polling window."""
    loop = asyncio.new_event_loop()
    server = build_server(_engine_config(watchdog_timeout_s=0.1),
                          tokenizer_path=None, model_name="debug-tiny")
    _SRV["api"] = server
    client = TestClient(TestServer(server.build_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield loop, client, server
    loop.run_until_complete(client.close())
    loop.close()


async def _complete(client, timeout_budget_ms=None, **body):
    body.setdefault("prompt", "hello")
    body.setdefault("max_tokens", 4)
    body.setdefault("temperature", 0.0)
    headers = {}
    if timeout_budget_ms is not None:
        headers[TTFT_BUDGET_HEADER] = str(timeout_budget_ms)
    return await client.post("/v1/completions", json=body, headers=headers)


class TestAdmissionShedding:
    def test_shed_429_with_retry_after(self, chaos_client):
        loop, client, server = chaos_client

        async def go():
            configure_faults("queue_wait_est:value=30")
            # Budget below the (forced) 30 s estimate: shed, not queued.
            t0 = time.monotonic()
            r = await _complete(client, timeout_budget_ms=1000)
            elapsed = time.monotonic() - t0
            assert r.status == 429
            assert elapsed < 1.0, "shed must be immediate, not queued"
            assert int(r.headers["Retry-After"]) >= 30
            err = (await r.json())["error"]
            assert err["type"] == "overloaded_error" and err["code"] == 429
            # Unbudgeted traffic is untouched (default budget is None).
            r2 = await _complete(client)
            assert r2.status == 200
            # Generous budget admits through the same estimate.
            r3 = await _complete(client, timeout_budget_ms=60_000)
            assert r3.status == 200
            configure_faults(None)
            assert server.admission.shed_total >= 1
        loop.run_until_complete(go())

    def test_invalid_budget_header_400(self, chaos_client):
        loop, client, _ = chaos_client

        async def go():
            r = await _complete(client, timeout_budget_ms="soon")
            assert r.status == 400
            r = await _complete(client, timeout_budget_ms=-5)
            assert r.status == 400
        loop.run_until_complete(go())

    def test_shed_counter_in_metrics(self, chaos_client):
        loop, client, _ = chaos_client

        async def go():
            r = await client.get("/metrics")
            text = await r.text()
            _assert_valid_exposition(text)
            shed = [l for l in text.splitlines()
                    if l.startswith("kgct_requests_shed_total")]
            assert shed and int(shed[0].split()[-1]) >= 1
            assert "kgct_watchdog_trips_total" in text
            assert "kgct_drain_state 0" in text
        loop.run_until_complete(go())


class TestWatchdog:
    def test_injected_stall_trips_health_then_recovers(self, chaos_client):
        loop, client, server = chaos_client

        async def go():
            configure_faults("step_stall:delay=0.6,times=1")
            task = asyncio.get_event_loop().create_task(
                _complete(client, max_tokens=2))
            # During the stalled step the watchdog (timeout 0.1 s) must flip
            # /health to 503.
            saw_503 = False
            for _ in range(40):
                r = await client.get("/health")
                if r.status == 503:
                    body = await r.json()
                    assert "watchdog" in body["status"]
                    saw_503 = True
                    break
                await asyncio.sleep(0.02)
            assert saw_503, "watchdog never tripped during injected stall"
            assert server.watchdog.trips >= 1
            # The stall ends; the request completes and health self-heals.
            r = await task
            assert r.status == 200
            for _ in range(40):
                r = await client.get("/health")
                if r.status == 200:
                    return
                await asyncio.sleep(0.02)
            raise AssertionError("health did not recover after stall ended")
        loop.run_until_complete(go())

    def test_watchdog_trip_dumps_flight_recorder(self, chaos_client,
                                                 monkeypatch, tmp_path):
        """A watchdog trip auto-dumps the black-box flight recorder: the
        file holds the triggering event plus the ring of events/snapshots
        that preceded the hang (the ISSUE's crash-capture contract)."""
        loop, client, server = chaos_client
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))

        async def go():
            configure_faults("step_stall:delay=0.6,times=1")
            task = asyncio.get_event_loop().create_task(
                _complete(client, max_tokens=2))
            dump = None
            for _ in range(80):
                dumps = sorted(tmp_path.glob("flight-watchdog_trip-*.json"))
                if dumps:
                    dump = dumps[0]
                    break
                await asyncio.sleep(0.025)
            r = await task
            assert r.status == 200
            assert dump is not None, "watchdog trip produced no dump"
            doc = json.loads(dump.read_text())
            assert doc["reason"] == "watchdog_trip"
            kinds = [e["kind"] for e in doc["events"]]
            assert "watchdog_trip" in kinds          # the trigger itself
            # The preceding seconds: lifecycle events and at least one
            # periodic state snapshot (queue depths / KV occupancy) from
            # the module's earlier traffic.
            assert "snapshot" in kinds
            snap = next(e for e in doc["events"] if e["kind"] == "snapshot")
            assert {"waiting", "running", "kv_pages_free"} <= set(snap)
            # Health recovers (the stall was transient).
            for _ in range(40):
                if (await client.get("/health")).status == 200:
                    return
                await asyncio.sleep(0.02)
            raise AssertionError("health did not recover after stall ended")
        loop.run_until_complete(go())


class TestGracefulDrain:
    def test_drain_finishes_inflight_and_rejects_new(self, chaos_client,
                                                     monkeypatch, tmp_path):
        loop, client, server = chaos_client
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "drain me", "max_tokens": 24, "temperature": 0.0,
                "stream": True})
            assert r.status == 200
            it = r.content.__aiter__()
            await it.__anext__()               # stream demonstrably started
            drained = []
            task = server.begin_drain(on_drained=lambda: drained.append(1))
            assert task is not None
            assert server.begin_drain() is None     # idempotent
            # Drain start auto-dumped the flight recorder (what was queued
            # or mid-stream when the SIGTERM landed outlives the pod).
            [dump] = sorted(tmp_path.glob("flight-sigterm_drain-*.json"))
            assert json.loads(dump.read_text())["reason"] == "sigterm_drain"
            # New admissions are rejected with the OpenAI envelope...
            r2 = await _complete(client)
            assert r2.status == 503
            err = (await r2.json())["error"]
            assert err["type"] == "overloaded_error"
            assert "Retry-After" in r2.headers
            # ...and /health flips so k8s takes the pod out of rotation.
            rh = await client.get("/health")
            assert rh.status == 503
            # The in-flight stream keeps going to [DONE].
            saw_done = False
            async for line in r.content:
                if line.decode().strip() == "data: [DONE]":
                    saw_done = True
            assert saw_done, "drain truncated an in-flight stream"
            await asyncio.wait_for(task, timeout=5)
            assert drained == [1]
            assert server.drain_state.gauge_value == 2
            rm = await client.get("/metrics")
            assert "kgct_drain_state 2" in await rm.text()
        loop.run_until_complete(go())
        # Reset for any later use of the module server: a real pod exits
        # after drain; the test server lives on.
        server.drain_state = DrainState()
        server.hub.drain = server.drain_state

    def test_migrate_fail_degrades_to_wait_it_out(self, chaos_client):
        """``migrate_fail`` chaos: the live-migration export raises before
        the sequence detaches, so THAT stream keeps decoding locally (the
        pre-migration wait-it-out drain) and still reaches [DONE] — with
        the fallback attributed in the migration series and a trace span."""
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            MIGRATE_URL_HEADER)
        loop, client, server = chaos_client

        async def go():
            configure_faults("migrate_fail")
            r = await client.post("/v1/completions", json={
                "prompt": "migrate me", "max_tokens": 16,
                "temperature": 0.0, "stream": True},
                headers={MIGRATE_URL_HEADER: "http://127.0.0.1:1"})
            assert r.status == 200
            it = r.content.__aiter__()
            await it.__anext__()               # stream demonstrably started
            task = server.begin_drain()
            assert task is not None
            saw_done, saw_error = False, False
            async for line in r.content:
                text = line.decode().strip()
                if text == "data: [DONE]":
                    saw_done = True
                elif text.startswith("data:") and '"error"' in text:
                    saw_error = True
            assert saw_done and not saw_error, \
                "migrate_fail must degrade to wait-it-out, not truncate"
            await asyncio.wait_for(task, timeout=10)
            assert server.migration.migrations.get(
                ("push", "fallback"), 0) >= 1
            assert server.migration.migrations.get(("push", "ok"), 0) == 0
            events = server.engine.engine.obs.flight.export()["events"]
            assert any(e["kind"] == "migrate"
                       and e.get("outcome") == "fallback" for e in events)
            rm = await client.get("/metrics")
            text = await rm.text()
            assert 'kgct_migrations_total{side="push",outcome="fallback"}' \
                in text
        loop.run_until_complete(go())
        server.drain_state = DrainState()
        server.hub.drain = server.drain_state

    def test_push_failure_reimports_locally(self, chaos_client):
        """Rung 2 of the push ladder: the export succeeded (the sequence
        detached) but the peer is unreachable — the snapshot re-imports
        LOCALLY and the stream resumes here as if never exported,
        byte-identical to an undrained run."""
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            MIGRATE_URL_HEADER)
        loop, client, server = chaos_client
        body = {"prompt": "push me somewhere", "max_tokens": 16,
                "temperature": 0.0}

        async def go():
            r = await client.post("/v1/completions", json=body)
            assert r.status == 200
            ref = (await r.json())["choices"][0]["text"]
            r = await client.post(
                "/v1/completions", json=dict(body, stream=True),
                headers={MIGRATE_URL_HEADER: "http://127.0.0.1:1"})
            assert r.status == 200
            chunks = []
            it = r.content.__aiter__()
            chunks.append(await it.__anext__())
            task = server.begin_drain()
            assert task is not None
            async for line in r.content:
                chunks.append(line)
            await asyncio.wait_for(task, timeout=10)
            text, saw_done = [], False
            for line in chunks:
                s = line.decode().strip()
                if s == "data: [DONE]":
                    saw_done = True
                elif s.startswith("data:"):
                    obj = json.loads(s[5:].strip())
                    assert "error" not in obj, obj
                    text.append(obj["choices"][0]["text"])
            assert saw_done
            assert "".join(text) == ref, \
                "local re-import must resume byte-identically"
            assert server.migration.migrations.get(
                ("push", "fallback"), 0) >= 1
        loop.run_until_complete(go())
        server.drain_state = DrainState()
        server.hub.drain = server.drain_state

    def test_sigterm_handler_drives_drain(self):
        import os
        import signal

        class _Eng:
            def has_unfinished_requests(self):
                return False

        shim = types.SimpleNamespace(engine=_Eng())

        async def scenario():
            loop = asyncio.get_running_loop()
            drain = DrainState()
            fired = []
            uninstall = install_sigterm_drain(
                loop, drain, shim, grace_s=1.0,
                on_drained=lambda: fired.append(1))
            try:
                os.kill(os.getpid(), signal.SIGTERM)
                deadline = time.monotonic() + 2.0
                while drain.gauge_value != 2 and time.monotonic() < deadline:
                    await asyncio.sleep(0.01)
                assert drain.gauge_value == 2 and fired == [1]
                # Repeat SIGTERM during/after drain is harmless.
                os.kill(os.getpid(), signal.SIGTERM)
                await asyncio.sleep(0.02)
            finally:
                uninstall()

        asyncio.run(scenario())


class TestResumeAndRecv:
    """The session-survivability server seams on the warm module server:
    /internal/resume reconstructs a relayed stream by token replay
    (byte-identical continuation, only new tokens emitted), and the
    migration-push receive direction of /internal/kv_handoff validates
    before parking."""

    def test_resume_token_replay_emits_only_new_tokens(self, chaos_client):
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            MIGRATE_URL_HEADER, REQUEST_ID_HEADER, RESUME_MODE_HEADER)
        loop, client, server = chaos_client
        body = {"prompt": "resume this stream", "max_tokens": 12,
                "temperature": 0.0}

        async def go():
            # A migration-registered stream embeds its per-frame token
            # ledger (what the router keeps, and what a failover replays).
            r = await client.post(
                "/v1/completions", json=dict(body, stream=True),
                headers={MIGRATE_URL_HEADER: "http://127.0.0.1:1"})
            assert r.status == 200
            frames = []
            async for line in r.content:
                s = line.decode().strip()
                if s.startswith("data:") and s != "data: [DONE]":
                    frames.append(json.loads(s[5:].strip()))
            toks = [t for f in frames for t in f.get("kgct_token_ids", [])]
            full = "".join(f["choices"][0]["text"] for f in frames)
            assert len(toks) == 12, "ledger must cover every token"
            # Replay the first 5 tokens' worth: the resumed stream must
            # carry ONLY the remainder, byte-identical.
            cut, prefix = 0, ""
            for f in frames:
                if cut >= 5:
                    break
                cut += len(f.get("kgct_token_ids", []))
                prefix += f["choices"][0]["text"]
            resume = await client.post(
                "/internal/resume",
                json={"body": body, "kind": "completion",
                      "relayed_token_ids": toks[:cut]},
                headers={REQUEST_ID_HEADER: "resume-replay-1"})
            assert resume.status == 200, await resume.text()
            assert resume.headers[RESUME_MODE_HEADER] == "recompute"
            got, saw_done = [], False
            async for line in resume.content:
                s = line.decode().strip()
                if s == "data: [DONE]":
                    saw_done = True
                elif s.startswith("data:"):
                    obj = json.loads(s[5:].strip())
                    assert "error" not in obj, obj
                    got.append(obj["choices"][0]["text"])
            assert saw_done
            assert "".join(got) == full[len(prefix):]
            assert server.migration.migrations.get(
                ("resume", "fallback"), 0) >= 1
            events = server.engine.engine.obs.flight.export()["events"]
            assert any(e["kind"] == "migrate"
                       and e.get("side") == "resume" for e in events)
        loop.run_until_complete(go())

    def test_resume_rejects_malformed_envelopes(self, chaos_client):
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)
        loop, client, _ = chaos_client

        async def go():
            hdr = {REQUEST_ID_HEADER: "resume-bad-1"}
            r = await client.post("/internal/resume", data=b"not json",
                                  headers=hdr)
            assert r.status == 400
            r = await client.post("/internal/resume", json={
                "body": "nope", "relayed_token_ids": []}, headers=hdr)
            assert r.status == 400
            r = await client.post("/internal/resume", json={
                "body": {"prompt": "x"},
                "relayed_token_ids": [1, "two"]}, headers=hdr)
            assert r.status == 400
            r = await client.post("/internal/resume", json={
                "body": {"prompt": "x"}, "relayed_token_ids": [],
                "kind": "mystery"}, headers=hdr)
            assert r.status == 400
        loop.run_until_complete(go())

    def test_recv_validates_before_parking(self, chaos_client):
        import numpy as np

        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)
        from kubernetes_gpu_cluster_tpu.serving.handoff import encode_handoff
        loop, client, server = chaos_client

        def blob(model="debug-tiny", mid_stream=True):
            k = np.zeros((1, 2, 4, 4), dtype="float32")
            state = {"model": model, "page_size": 16, "dtype": "float32",
                     "prompt_token_ids": [1, 2, 3],
                     "output_token_ids": [7], "output_logprobs": [-0.5],
                     "output_top_logprobs": [], "k": k, "v": k}
            if mid_stream:
                state["mid_stream"] = True
            # Speak the current wire dialect: the receiver requires the
            # integrity extension by default (a plain frame is a 426
            # skew rejection before any semantic validation).
            return encode_handoff(state, integrity=True)

        async def go():
            octet = {"Content-Type": "application/octet-stream",
                     REQUEST_ID_HEADER: "park-1"}
            errs0 = server.migration.migrations.get(("recv", "error"), 0)
            # Model mismatch: 409, never parked.
            r = await client.post("/internal/kv_handoff",
                                  data=blob(model="llama-3-8b"),
                                  headers=octet)
            assert r.status == 409
            # A held-prefill export is NOT a mid-stream state: 400.
            r = await client.post("/internal/kv_handoff",
                                  data=blob(mid_stream=False),
                                  headers=octet)
            assert r.status == 400
            # Garbage frame: 400.
            r = await client.post("/internal/kv_handoff", data=b"KVGARBAGE",
                                  headers=octet)
            assert r.status == 400
            assert server.migration.migrations.get(
                ("recv", "error"), 0) == errs0 + 3
            assert len(server.migrate_store) == 0
            # A well-formed push parks (and is claimable exactly once).
            r = await client.post("/internal/kv_handoff", data=blob(),
                                  headers=octet)
            assert r.status == 200
            assert (await r.json())["parked"] is True
            assert server.migrate_store.pop("park-1") is not None
            assert server.migrate_store.pop("park-1") is None
        loop.run_until_complete(go())


@pytest.fixture(scope="module")
def leader_client():
    """API server whose engine broadcasts step directives to a fake follower
    (a TCP sink) — the multihost leader path without a second engine."""
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    stop = threading.Event()

    def _sink():
        srv.settimeout(10)
        try:
            conn, _ = srv.accept()
        except OSError:
            return
        conn.settimeout(0.1)
        with conn:
            while not stop.is_set():
                try:
                    if not conn.recv(1 << 16):
                        return
                except socket.timeout:
                    continue
                except OSError:
                    return

    t = threading.Thread(target=_sink, daemon=True)
    t.start()
    leader = DirectiveLeader([f"127.0.0.1:{port}"],
                             heartbeat_interval_s=0)
    loop = asyncio.new_event_loop()
    server = build_server(_engine_config(), tokenizer_path=None,
                          model_name="debug-tiny", leader=leader)
    client = TestClient(TestServer(server.build_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield loop, client, server
    stop.set()
    loop.run_until_complete(client.close())
    loop.close()
    srv.close()


class TestMultihostLeader:
    def test_broadcast_fail_group_aborts_and_leader_stays_serveable(
            self, leader_client, monkeypatch, tmp_path):
        loop, client, server = leader_client
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))

        async def go():
            # Healthy lockstep first: broadcasts reach the fake follower.
            r = await _complete(client)
            assert r.status == 200
            assert server.engine.leader is not None
            # Kill the "rank": the 3rd broadcast of the next request (add,
            # then steps) raises — mid-generation, with work in flight.
            configure_faults("broadcast_fail:after=2,times=1")
            r2 = await _complete(client, max_tokens=32)
            assert r2.status >= 500     # in-flight waiter failed loudly
            # Group-abort left no orphaned device work behind...
            eng = server.engine.engine
            deadline = time.monotonic() + 5
            while eng.has_unfinished_requests() and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            assert not eng.has_unfinished_requests()
            # ...the broken process group is detached, and rank 0 serves on.
            assert server.engine.leader is None
            r3 = await _complete(client)
            assert r3.status == 200
            # The fatal group-abort auto-dumped the flight recorder with
            # the triggering event and the in-flight work it found.
            [dump] = sorted(tmp_path.glob("flight-group_abort-*.json"))
            doc = json.loads(dump.read_text())
            assert doc["reason"] == "group_abort"
            trigger = [e for e in doc["events"]
                       if e["kind"] == "group_abort"]
            assert trigger and trigger[-1]["requests"] >= 1
            # The ring captured the seconds before: the doomed request's
            # lifecycle events are in the dump.
            assert any(e["kind"] == "arrival" for e in doc["events"])
        loop.run_until_complete(go())


class _RecordingEngine:
    """Duck-typed LLMEngine for follower-side protocol tests (no jax)."""

    def __init__(self):
        self.added, self.aborted = [], []
        self.steps = 0
        self.scheduler = types.SimpleNamespace(waiting=[], running=[])

    def add_request(self, rid, ids, params):
        self.added.append(rid)
        self.scheduler.running.append(
            types.SimpleNamespace(request_id=rid))

    def abort_request(self, rid):
        self.aborted.append(rid)
        self.scheduler.running = [
            s for s in self.scheduler.running if s.request_id != rid]
        return True

    def has_unfinished_requests(self):
        return bool(self.scheduler.running or self.scheduler.waiting)

    def step(self):
        self.steps += 1
        return []


def _directive(adds=(), aborts=()):
    payload = {"adds": [[rid, ids, dataclasses.asdict(params)]
                        for rid, ids, params in adds],
               "aborts": list(aborts)}
    return (json.dumps(payload) + "\n").encode()


class TestMultihostFollower:
    def test_leader_close_group_aborts_and_health_flips(self):
        follower = DirectiveFollower(port=0, host="127.0.0.1")
        engine = _RecordingEngine()
        liveness = LoopLiveness(timeout_s=30)
        health = serve_follower_health(0, host="127.0.0.1",
                                       liveness=liveness)
        hport = health.server_address[1]

        def _health_status():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{hport}/health", timeout=2) as r:
                    return r.status
            except urllib.error.HTTPError as e:
                return e.code

        t = threading.Thread(
            target=follower.run,
            kwargs=dict(engine=engine, liveness=liveness,
                        liveness_timeout_s=5.0),
            daemon=True)
        t.start()
        conn = socket.create_connection(("127.0.0.1", follower.port),
                                        timeout=2)
        conn.sendall(_directive(
            adds=[("r1", [1, 2, 3], SamplingParams(max_tokens=4))]))
        deadline = time.monotonic() + 2
        while "r1" not in engine.added and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.added == ["r1"] and engine.steps == 1
        assert _health_status() == 200
        # Leader dies mid-flight: the follower group-aborts r1, exits its
        # loop, and its health endpoint goes 503 for kubelet to restart it.
        conn.close()
        t.join(timeout=2)
        assert not t.is_alive()
        assert engine.aborted == ["r1"]
        assert not engine.has_unfinished_requests()
        assert _health_status() == 503
        health.shutdown()

    def test_leader_silence_past_liveness_timeout_aborts(self):
        follower = DirectiveFollower(port=0, host="127.0.0.1")
        engine = _RecordingEngine()
        liveness = LoopLiveness(timeout_s=30)
        t = threading.Thread(
            target=follower.run,
            kwargs=dict(engine=engine, liveness=liveness,
                        liveness_timeout_s=0.2),
            daemon=True)
        t.start()
        conn = socket.create_connection(("127.0.0.1", follower.port),
                                        timeout=2)
        conn.sendall(_directive(
            adds=[("r1", [1], SamplingParams(max_tokens=4))]))
        # Keep the socket open but silent: no directives, no heartbeats.
        t.join(timeout=2)
        assert not t.is_alive(), "follower must declare a silent leader dead"
        assert engine.aborted == ["r1"]
        assert not liveness.alive()
        conn.close()

    def test_heartbeats_keep_idle_follower_alive(self):
        follower = DirectiveFollower(port=0, host="127.0.0.1")
        engine = _RecordingEngine()
        liveness = LoopLiveness(timeout_s=30)
        t = threading.Thread(
            target=follower.run,
            kwargs=dict(engine=engine, liveness=liveness,
                        liveness_timeout_s=0.3),
            daemon=True)
        t.start()
        leader = DirectiveLeader([f"127.0.0.1:{follower.port}"],
                                 heartbeat_interval_s=0.05)
        # First broadcast connects and starts the heartbeat thread.
        leader.broadcast([], [])
        # Idle for > liveness timeout: only heartbeats flow, and they are
        # enough — the follower must NOT declare the leader dead.
        time.sleep(0.6)
        assert t.is_alive()
        assert liveness.alive()
        assert engine.aborted == []
        leader.close()                    # stop directive: clean exit
        t.join(timeout=2)
        assert not t.is_alive()
        assert engine.aborted == []


class TestKVSwapChaos:
    def test_swap_out_failure_degrades_to_recompute_never_wedges(self):
        """KGCT_FAULT=kv_swap_fail: every swap-out raises inside the
        swapper. The scheduler must degrade each preemption to recompute —
        the victim re-prefills and finishes, nothing wedges, no sequence is
        stranded on the swapped queue, and no host page leaks."""
        from kubernetes_gpu_cluster_tpu.engine import LLMEngine

        cfg = EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(page_size=8, num_pages=8, swap_space_gb=0.05),
            scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                      decode_buckets=(1, 2, 4),
                                      prefill_buckets=(32, 64),
                                      decode_window=4))
        eng = LLMEngine(cfg)
        assert eng.swapper is not None
        configure_faults("kv_swap_fail")
        outs = eng.generate(
            [[9, 8, 7, 6], [1, 2, 3, 4], [5, 5, 5, 5]],
            SamplingParams(max_tokens=16, temperature=0.0))
        configure_faults(None)
        assert [o.finished for o in outs] == [True] * 3
        assert all(len(o.output_token_ids) == 16 for o in outs)
        kinds = eng.scheduler.num_preemptions_by_kind
        assert kinds["recompute"] > 0, "pressure never preempted"
        assert kinds["swap"] == 0, "a failed swap-out was counted as a swap"
        assert not eng.scheduler.swapped
        assert eng.swapper.host.num_in_use == 0
        assert not eng.has_unfinished_requests()


# --------------------------------------------------------------------------
# Router chaos
# --------------------------------------------------------------------------

async def _mini_replica(response_delay_s=0.0, metrics_delay_s=0.0,
                        stream_stall_s=0.0):
    """A stand-in engine replica: /health, /metrics, /v1/completions.
    ``response_delay_s`` delays the response headers (wedged pre-response);
    ``stream_stall_s`` sends one chunk then goes silent (mid-stream hang)."""
    from aiohttp import web as aioweb

    async def health(request):
        return aioweb.json_response({"status": "ok"})

    async def metrics(request):
        if metrics_delay_s:
            await asyncio.sleep(metrics_delay_s)
        return aioweb.Response(
            text="# TYPE kgct_requests_total counter\nkgct_requests_total 1\n",
            content_type="text/plain")

    async def completions(request):
        if response_delay_s:
            await asyncio.sleep(response_delay_s)
        if stream_stall_s:
            resp = aioweb.StreamResponse()
            await resp.prepare(request)
            await resp.write(b"data: first\n\n")
            await asyncio.sleep(stream_stall_s)   # then silence
            return resp
        return aioweb.json_response({"object": "completion", "ok": True})

    app = aioweb.Application()
    app.router.add_get("/health", health)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/v1/completions", completions)
    runner = aioweb.AppRunner(app)
    await runner.setup()
    site = aioweb.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, f"http://127.0.0.1:{runner.addresses[0][1]}"


async def _start_router(router):
    client = TestClient(TestServer(router.build_app()))
    await client.start_server()
    return client


class TestRouterChaos:
    def test_connect_fault_retried_with_backoff(self):
        async def scenario():
            runner, url = await _mini_replica()
            router = Router([url], health_interval_s=9999,
                            connect_retries=2, retry_backoff_s=0.01)
            client = await _start_router(router)
            try:
                configure_faults("router_connect:times=1")
                r = await client.post("/v1/completions", json={"prompt": "x"})
                # The injected connect failure is retried (bounded backoff)
                # and the request still succeeds.
                assert r.status == 200
                assert (await r.json())["ok"] is True
                assert router.retries_total >= 1
            finally:
                await client.close()
                await runner.cleanup()
        asyncio.run(scenario())

    def test_injected_hang_circuit_breaks_then_recovers(self):
        async def scenario():
            runner, url = await _mini_replica()
            router = Router([url], health_interval_s=9999, fail_threshold=1)
            client = await _start_router(router)
            try:
                configure_faults("replica_hang:times=1")
                r = await client.post("/v1/completions", json={"prompt": "x"})
                # Stream terminated mid-flight (truncation is the signal)
                # and the replica is circuit-broken.
                assert not router.replicas[0].healthy
                # With no healthy replica: OpenAI-shaped 503 + Retry-After.
                r2 = await client.post("/v1/completions",
                                       json={"prompt": "x"})
                assert r2.status == 503
                err = (await r2.json())["error"]
                assert err["type"] == "overloaded_error"
                assert int(r2.headers["Retry-After"]) >= 1
                # A 200 probe alone must NOT lift a traffic bench during
                # the cooldown (the wedge outlives one good /health)...
                assert router.replicas[0].benched_until > time.monotonic()
                await router._check(router.replicas[0])
                assert not router.replicas[0].healthy
                # ...after the cooldown lapses, the probe restores it and
                # traffic flows again.
                router.replicas[0].benched_until = 0.0
                await router._check(router.replicas[0])
                assert router.replicas[0].healthy
                r3 = await client.post("/v1/completions",
                                       json={"prompt": "x"})
                assert r3.status == 200
            finally:
                await client.close()
                await runner.cleanup()
        asyncio.run(scenario())

    def test_wedged_replica_no_response_rebalances(self):
        async def scenario():
            stall_runner, stall_url = await _mini_replica(
                response_delay_s=30.0)
            live_runner, live_url = await _mini_replica()
            # 1.5 s, not 0.3: the deadline also judges the LIVE replica, and
            # beside five other workers compiling (tier-1 under xdist) its
            # answer took longer than 0.3 s in three whole runs of three.
            router = Router([stall_url, live_url], health_interval_s=9999,
                            fail_threshold=1, response_timeout_s=1.5)
            client = await _start_router(router)
            try:
                # First request lands on the wedged replica (rr tie-break
                # picks index 0), exceeds the headers deadline, and circuit-
                # breaks it; the request was already sent so it is NOT
                # replayed (502, not silent double work).
                r = await client.post("/v1/completions", json={"prompt": "x"})
                assert r.status == 502
                assert not router.replicas[0].healthy
                # Traffic rebalances to the healthy peer.
                for _ in range(3):
                    r = await client.post("/v1/completions",
                                          json={"prompt": "x"})
                    assert r.status == 200
            finally:
                await client.close()
                await stall_runner.cleanup()
                await live_runner.cleanup()
        asyncio.run(scenario())

    def test_midstream_stall_circuit_breaks_and_rebalances(self):
        async def scenario():
            stall_runner, stall_url = await _mini_replica(
                stream_stall_s=30.0)
            live_runner, live_url = await _mini_replica()
            router = Router([stall_url, live_url], health_interval_s=9999,
                            fail_threshold=1, stall_timeout_s=0.3)
            client = await _start_router(router)
            try:
                # One chunk arrives, then silence past stall_timeout_s: the
                # committed client stream is terminated (truncation is the
                # signal) and the replica circuit-broken.
                r = await client.post("/v1/completions", json={"prompt": "x"})
                body = await r.read()
                assert b"first" in body          # stream had started
                assert not router.replicas[0].healthy
                # Traffic rebalances to the healthy peer.
                r2 = await client.post("/v1/completions",
                                       json={"prompt": "x"})
                assert r2.status == 200
                assert (await r2.json())["ok"] is True
            finally:
                await client.close()
                await stall_runner.cleanup()
                await live_runner.cleanup()
        asyncio.run(scenario())

    def test_retry_rounds_reach_benched_replica(self):
        """fail_threshold=1 benches the replica on its first injected
        connect failure — the retry round must still probe it (nothing was
        sent, so a desperation probe is safe) instead of giving up."""
        async def scenario():
            runner, url = await _mini_replica()
            router = Router([url], health_interval_s=9999, fail_threshold=1,
                            connect_retries=2, retry_backoff_s=0.01)
            client = await _start_router(router)
            try:
                configure_faults("router_connect:times=1")
                r = await client.post("/v1/completions", json={"prompt": "x"})
                assert r.status == 200      # retried despite being benched
                assert router.retries_total >= 1
            finally:
                await client.close()
                await runner.cleanup()
        asyncio.run(scenario())

    def test_metrics_scrape_skips_stragglers(self):
        async def scenario():
            slow_runner, slow_url = await _mini_replica(metrics_delay_s=30.0)
            fast_runner, fast_url = await _mini_replica()
            router = Router([slow_url, fast_url], health_interval_s=9999,
                            metrics_timeout_s=0.2)
            client = await _start_router(router)
            try:
                t0 = time.monotonic()
                r = await client.get("/metrics")
                assert time.monotonic() - t0 < 2.0, \
                    "one stalled replica must not hang the scrape"
                text = await r.text()
                _assert_valid_exposition(text)
                # The fast replica's series made it, relabelled; the
                # straggler's engine series did not (its router-level health
                # gauges legitimately remain).
                assert f'kgct_requests_total{{replica="{fast_url}"' in text
                assert not any(
                    line.startswith("kgct_requests_total") and slow_url in line
                    for line in text.splitlines())
                errs = [l for l in text.splitlines() if l.startswith(
                    "kgct_router_metrics_scrape_errors_total")]
                assert errs and int(errs[0].split()[-1]) == 1
            finally:
                await client.close()
                await slow_runner.cleanup()
                await fast_runner.cleanup()
        asyncio.run(scenario())

    def test_cold_start_probe_removes_dead_replica_immediately(self):
        async def scenario():
            runner, url = await _mini_replica()
            dead = "http://127.0.0.1:1"
            router = Router([dead, url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                # No interval wait: startup already probed both.
                assert router.replicas[0].healthy is False
                assert router.replicas[1].healthy is True
                r = await client.post("/v1/completions", json={"prompt": "x"})
                assert r.status == 200
            finally:
                await client.close()
                await runner.cleanup()
        asyncio.run(scenario())
