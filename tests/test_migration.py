"""Session survivability: live KV migration + transparent mid-stream failover.

Tier-1 keeps the CHEAP pins:

- the acceptance contract at the engine seam — a sequence exported
  MID-DECODE (``export_running``) and imported on a SECOND engine produces
  the same remaining tokens/logprobs as the uninterrupted run, for greedy
  AND seeded sampling with penalties — plus the token-replay
  (``resume_outputs``) recompute rung, byte-identical the same way;
- engine-free pins of the parking lot (MigrationStore bounds), the
  router's SSE relay parser (token-ledger strip), and the router failover
  ladder over stub replicas (``replica_kill_midstream`` chaos ->
  transparent splice; exhausted ladder -> clean truncated-stream error).

The real multi-engine topology (drain migration and kill-mid-stream
failover with actual engines behind the router) is @slow, per the tier-1
budget guard. The drain-path chaos pins that reuse the warm module server
live in tests/test_chaos.py.
"""

import asyncio
import json

import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.resilience.faults import configure_faults
from kubernetes_gpu_cluster_tpu.serving.errors import (
    MIGRATE_URL_HEADER, REQUEST_ID_HEADER, RESUME_MODE_HEADER)
from kubernetes_gpu_cluster_tpu.serving.handoff import (
    MigrationStore, decode_handoff, encode_handoff)


@pytest.fixture(autouse=True)
def _clean_faults():
    configure_faults(None)
    yield
    configure_faults(None)


def _engine_config(**sched_kw):
    kw = dict(max_num_seqs=4, max_prefill_tokens=64,
              decode_buckets=(1, 2), prefill_buckets=(64,),
              decode_window=4, mixed_batch_enabled=False)
    kw.update(sched_kw)
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(**kw))


@pytest.fixture(scope="module")
def engines():
    """(exporter, importer): two distinct engines with identical weights by
    construction — the acceptance criterion's 'second engine'. The state
    still crosses the full gather -> host buffer -> wire -> scatter path,
    which is exactly what distinct replicas exchange."""
    return LLMEngine(_engine_config()), LLMEngine(_engine_config())


PROMPT = np.random.default_rng(7).integers(1, 500, 40).tolist()


def _run_to_completion(eng, rid):
    final = None
    while eng.has_unfinished_requests():
        for o in eng.step():
            if o.request_id == rid and o.finished:
                final = o
    return final


def _step_until_outputs(eng, rid, n):
    """Step until the RUNNING sequence has committed >= n output tokens
    (mid-decode by construction: neither finished nor still prefilling)."""
    while True:
        seq = eng.scheduler.find_running(rid)
        if seq is not None and len(seq.output_token_ids) >= n:
            return seq
        assert eng.has_unfinished_requests(), \
            f"{rid} finished before reaching {n} outputs"
        eng.step()


def _drain_engine(eng):
    """Drain any in-flight window chain (deferred page releases happen at
    chain-drain time) so per-test page accounting is exact."""
    while eng.has_unfinished_requests():
        eng.step()


class TestMidStreamByteIdentity:
    """The acceptance pin: export mid-decode on engine 1, import on engine
    2, and the spliced run is byte-identical to the uninterrupted one."""

    def _roundtrip(self, engines, rid, params, split=4):
        e1, e2 = engines
        ref = e1.generate([PROMPT], params)[0]
        free1 = e1.scheduler.allocator.num_free
        free2 = e2.scheduler.allocator.num_free
        e1.add_request(f"{rid}-src", PROMPT, params)
        _step_until_outputs(e1, f"{rid}-src", split)
        state = e1.export_running(f"{rid}-src")
        assert state["mid_stream"] is True
        # The export is the committed history only — never the full run.
        assert len(state["output_token_ids"]) < len(ref.output_token_ids)
        assert ref.output_token_ids[:len(state["output_token_ids"])] == \
            state["output_token_ids"]
        # The sampling snapshot survives the wire (forensic + re-dispatch).
        rt = SamplingParams.from_state(state["sampling"])
        assert rt.seed == params.seed and rt.max_tokens == params.max_tokens
        state = decode_handoff(encode_handoff(state))   # actual wire bytes
        outs = e2.import_request(f"{rid}-dst", PROMPT, params, state)
        assert outs[0].new_token_ids == state["output_token_ids"]
        final = (_run_to_completion(e2, f"{rid}-dst")
                 if not outs[0].finished else outs[0])
        _drain_engine(e1)   # zombie chain: deferred page release
        assert e1.scheduler.allocator.num_free == free1, "exporter leaked"
        assert e2.scheduler.allocator.num_free == free2, "importer leaked"
        return ref, final

    def test_greedy_midstream_identical_to_uninterrupted(self, engines):
        params = SamplingParams(max_tokens=12, temperature=0.0,
                                logprobs=True)
        ref, got = self._roundtrip(engines, "g", params)
        assert got.output_token_ids == ref.output_token_ids
        np.testing.assert_allclose(got.output_logprobs, ref.output_logprobs,
                                   rtol=1e-5, atol=1e-5)
        assert got.finish_reason == ref.finish_reason

    def test_seeded_sampled_with_penalties_identical(self, engines):
        """Seeded sampling + presence/frequency penalties: the penalties
        read the output history the export carries, and the sample keys
        derive from (seed, position) — both engine-independent, so the
        migrated continuation cannot fork."""
        params = SamplingParams(max_tokens=12, temperature=0.9, top_k=30,
                                top_p=0.95, seed=17, presence_penalty=0.4,
                                frequency_penalty=0.3, logprobs=True)
        ref, got = self._roundtrip(engines, "s", params, split=5)
        assert got.output_token_ids == ref.output_token_ids
        np.testing.assert_allclose(got.output_logprobs, ref.output_logprobs,
                                   rtol=1e-5, atol=1e-5)

    def test_token_replay_resume_identical(self, engines):
        """The recompute rung (no migrated KV): already-relayed tokens are
        pre-seeded as OUTPUT history and admission replays prompt+outputs
        through the recompute-prefill path — same byte-identity contract,
        greedy and seeded."""
        e1, e2 = engines
        for tag, params in (
                ("rp-g", SamplingParams(max_tokens=10, temperature=0.0)),
                ("rp-s", SamplingParams(max_tokens=10, temperature=0.8,
                                        top_k=40, seed=23,
                                        presence_penalty=0.5))):
            ref = e1.generate([PROMPT], params)[0]
            e2.add_request(tag, PROMPT, params,
                           resume_outputs=ref.output_token_ids[:4])
            final = _run_to_completion(e2, tag)
            assert final.output_token_ids == ref.output_token_ids, tag

    def test_resume_history_already_stopped_rejected(self, engines):
        """A replay that already satisfies a stop condition has nothing
        left to generate — loud ValueError, not a hung entry."""
        e1, e2 = engines
        params = SamplingParams(max_tokens=4, temperature=0.0)
        ref = e1.generate([PROMPT], params)[0]
        with pytest.raises(ValueError, match="nothing to resume"):
            e2.add_request("rp-done", PROMPT, params,
                           resume_outputs=ref.output_token_ids)
        assert e2.scheduler.find_running("rp-done") is None
        _drain_engine(e2)

    def test_export_running_requires_a_running_sequence(self, engines):
        e1, _ = engines
        with pytest.raises(KeyError):
            e1.export_running("never-seen")
        # A WAITING sequence has no committed device pages worth shipping:
        # the drain's wait-it-out rung owns it, not the migration seam.
        e1.add_request("wt", PROMPT, SamplingParams(max_tokens=2,
                                                    temperature=0.0))
        try:
            with pytest.raises(KeyError):
                e1.export_running("wt")
        finally:
            _drain_engine(e1)

    def test_migrated_outcome_splits_out_in_observability(self, engines):
        """FinishReason.MIGRATE is locally terminal without a client-facing
        finish: the e2e outcome series labels it 'migrated' (the tokens
        WERE delivered — the goodput gate keeps them, and dashboards can
        split migrated finishes from real ones)."""
        e1, _ = engines
        params = SamplingParams(max_tokens=12, temperature=0.0)
        cell0 = e1.obs.e2e_latency._cells.get(("migrated",))
        n0 = cell0[2] if cell0 else 0
        e1.add_request("obs", PROMPT, params)
        _step_until_outputs(e1, "obs", 4)
        e1.export_running("obs")
        _drain_engine(e1)
        assert e1.obs.e2e_latency._cells[("migrated",)][2] == n0 + 1


class TestMigrationStore:
    """Engine-free bounds of the parking lot: a crashing fleet cannot
    balloon a healthy replica's host memory."""

    def test_cap_evicts_oldest(self):
        store = MigrationStore(cap=3, ttl_s=60.0)
        for i in range(5):
            store.put(f"r{i}", {"i": i})
        assert len(store) == 3
        assert store.pop("r0") is None and store.pop("r1") is None
        assert store.pop("r4") == {"i": 4}

    def test_ttl_expires(self):
        now = [0.0]
        store = MigrationStore(cap=4, ttl_s=10.0, clock=lambda: now[0])
        store.put("a", {"x": 1})
        now[0] = 5.0
        store.put("b", {"x": 2})
        now[0] = 10.5    # a's deadline (10.0) passed; b's (15.0) has not
        assert store.pop("a") is None
        assert store.pop("b") == {"x": 2}

    def test_repush_replaces_and_pop_consumes(self):
        store = MigrationStore(cap=2, ttl_s=60.0)
        store.put("a", {"v": 1})
        store.put("a", {"v": 2})
        assert len(store) == 1
        assert store.pop("a") == {"v": 2}
        assert store.pop("a") is None


class TestSSERelay:
    """Engine-free pins of the router's parse-mode relay: the embedded
    token ledger is kept (and stripped before the client), partial frames
    never leak, and non-ledger frames pass through byte-identical."""

    def _frame(self, text, toks=None, **extra):
        obj = {"choices": [{"text": text}], **extra}
        if toks is not None:
            obj["kgct_token_ids"] = toks
        return b"data: " + json.dumps(obj).encode() + b"\n\n"

    def test_ledger_kept_and_stripped(self):
        from kubernetes_gpu_cluster_tpu.serving.router import _SSERelay
        relay = _SSERelay()
        out = relay.feed(self._frame("a", [1, 2]) + self._frame("b", [3]))
        assert relay.tokens == [1, 2, 3]
        assert b"kgct_token_ids" not in out
        assert b'"text": "a"' in out and b'"text": "b"' in out
        assert not relay.done
        out = relay.feed(b"data: [DONE]\n\n")
        assert relay.done and b"[DONE]" in out

    def test_partial_frame_buffered_and_resettable(self):
        from kubernetes_gpu_cluster_tpu.serving.router import _SSERelay
        relay = _SSERelay()
        whole = self._frame("a", [5])
        assert relay.feed(whole[:10]) == b""
        # Upstream dies here: the partial frame must never reach the
        # client, and the ledger covers only fully-relayed frames.
        relay.reset_buffer()
        assert relay.tokens == []
        out = relay.feed(self._frame("a", [5]))
        assert relay.tokens == [5] and b'"text": "a"' in out

    def test_frames_without_ledger_pass_through_byte_identical(self):
        from kubernetes_gpu_cluster_tpu.serving.router import _SSERelay
        relay = _SSERelay()
        plain = self._frame("x")
        assert relay.feed(plain) == plain
        weird = b"data: not json\n\n"
        assert relay.feed(weird) == weird
        assert relay.tokens == []


# ---------------------------------------------------------------------------
# Router failover ladder over stub replicas (engine-free, chaos)
# ---------------------------------------------------------------------------

TOKENS = [11, 22, 33, 44, 55, 66]


async def _stub_replica(resumes, resume_status=200, chunk_gap_s=0.03):
    """A stand-in survivable replica: /v1/completions streams one frame
    per token (with the kgct_token_ids ledger the MIGRATE_URL_HEADER opts
    into), /internal/resume continues after the relayed prefix (or fails
    with ``resume_status``). ``chunk_gap_s`` forces one TCP chunk per
    frame so the router's per-chunk chaos check is deterministic."""
    from aiohttp import web as aioweb

    async def health(request):
        return aioweb.json_response({"status": "ok"})

    async def metrics(request):
        return aioweb.Response(text="", content_type="text/plain")

    def frame(i):
        return (b"data: " + json.dumps(
            {"choices": [{"text": f"t{i} "}],
             "kgct_token_ids": [TOKENS[i]]}).encode() + b"\n\n")

    async def completions(request):
        assert request.headers.get(MIGRATE_URL_HEADER), \
            "router must name the drain-push target on survivable streams"
        resp = aioweb.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        for i in range(len(TOKENS)):
            await resp.write(frame(i))
            await asyncio.sleep(chunk_gap_s)
        await resp.write(b"data: [DONE]\n\n")
        return resp

    async def resume(request):
        envelope = await request.json()
        resumes.append({"url": str(request.url),
                        "rid": request.headers.get(REQUEST_ID_HEADER),
                        "envelope": envelope})
        if resume_status != 200:
            return aioweb.json_response(
                {"error": {"message": "no seat"}}, status=resume_status)
        relayed = envelope["relayed_token_ids"]
        assert envelope["body"]["prompt"] == "survive me"
        resp = aioweb.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            RESUME_MODE_HEADER: "import"})
        await resp.prepare(request)
        for i in range(len(relayed), len(TOKENS)):
            await resp.write(frame(i))
        await resp.write(b"data: [DONE]\n\n")
        return resp

    app = aioweb.Application()
    app.router.add_get("/health", health)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/internal/resume", resume)
    runner = aioweb.AppRunner(app)
    await runner.setup()
    site = aioweb.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, f"http://127.0.0.1:{runner.addresses[0][1]}"


async def _start_router(router):
    from aiohttp.test_utils import TestClient, TestServer
    client = TestClient(TestServer(router.build_app()))
    await client.start_server()
    return client


def _client_frames(body: bytes):
    """(data payloads, [DONE] seen) of a client-received SSE byte stream."""
    payloads, done = [], False
    for part in body.split(b"\n\n"):
        for line in part.split(b"\n"):
            if not line.startswith(b"data:"):
                continue
            payload = line[5:].strip()
            if payload == b"[DONE]":
                done = True
            elif payload:
                payloads.append(json.loads(payload))
    return payloads, done


@pytest.mark.chaos
class TestRouterMidstreamFailover:
    def test_kill_midstream_splices_one_complete_stream(self, monkeypatch,
                                                        tmp_path):
        """The acceptance pin at the router: replica_kill_midstream severs
        the upstream socket after 2 relayed chunks, and the client still
        sees ONE complete stream — the relayed prefix from the dying
        replica spliced with the successor's /internal/resume continuation
        — with the failover attributed (counter, trace span, flight dump)
        and the token ledger stripped from every client frame."""
        from kubernetes_gpu_cluster_tpu.serving.router import Router
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))

        async def scenario():
            resumes = []
            r1, u1 = await _stub_replica(resumes)
            r2, u2 = await _stub_replica(resumes)
            router = Router([u1, u2], health_interval_s=9999,
                            fail_threshold=99)
            client = await _start_router(router)
            try:
                configure_faults("replica_kill_midstream:after=2,times=1")
                r = await client.post(
                    "/v1/completions",
                    json={"prompt": "survive me", "max_tokens": 6,
                          "stream": True})
                assert r.status == 200
                body = await r.read()
                payloads, done = _client_frames(body)
                assert done, "client stream must end in [DONE]"
                texts = [p["choices"][0]["text"] for p in payloads]
                assert texts == [f"t{i} " for i in range(6)], texts
                # The replica-embedded ledger never reaches the client.
                assert b"kgct_token_ids" not in body
                # Exactly one resume, on the OTHER replica, carrying the
                # relayed prefix as the replay ledger.
                assert len(resumes) == 1
                assert resumes[0]["envelope"]["relayed_token_ids"] == \
                    TOKENS[:2]
                assert resumes[0]["envelope"]["kind"] == "completion"
                assert router.failovers_total["import"] == 1
                assert router.failovers_total["failed"] == 0
                kinds = [e["kind"] for e in router.flight.export()["events"]]
                assert "failover" in kinds
                dumps = list(tmp_path.glob("flight-midstream_failover-*"))
                assert dumps, "failover must trigger a flight dump"
                # Metrics rows render (pre-seeded outcomes, zeros-safe).
                rm = await client.get("/metrics")
                text = await rm.text()
                assert 'kgct_failovers_total{outcome="import"} 1' in text
                assert 'kgct_failovers_total{outcome="failed"} 0' in text
                assert "kgct_router_failover_seconds" in text
            finally:
                await client.close()
                await r1.cleanup()
                await r2.cleanup()
        asyncio.run(scenario())

    def test_exhausted_ladder_truncates_with_attributed_error(
            self, monkeypatch, tmp_path):
        """Every rung failing (the lone successor 500s its resume) ends the
        stream with a CLEAN error frame carrying the request id, then
        [DONE] — degraded and attributed, never a hang or a silent
        truncation that reads as a finished completion."""
        from kubernetes_gpu_cluster_tpu.serving.router import Router
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))

        async def scenario():
            resumes = []
            r1, u1 = await _stub_replica(resumes, resume_status=500)
            r2, u2 = await _stub_replica(resumes, resume_status=500)
            router = Router([u1, u2], health_interval_s=9999,
                            fail_threshold=99)
            client = await _start_router(router)
            try:
                configure_faults("replica_kill_midstream:after=2,times=1")
                r = await client.post(
                    "/v1/completions",
                    json={"prompt": "survive me", "stream": True},
                    headers={REQUEST_ID_HEADER: "req-truncated1"})
                body = await r.read()
                payloads, done = _client_frames(body)
                assert done, "even the bottom rung ends in a clean [DONE]"
                errors = [p for p in payloads if "error" in p]
                assert len(errors) == 1
                err = errors[0]["error"]
                assert "truncated" in err["message"]
                assert err["request_id"] == "req-truncated1"
                assert router.failovers_total["failed"] == 1
                assert len(resumes) == 1   # the one successor was tried
                dumps = [json.loads(p.read_text()) for p in
                         tmp_path.glob("flight-midstream_failover-*")]
                assert any(d["reason"] == "midstream_failover"
                           and d["info"].get("outcome") == "failed"
                           for d in dumps)
            finally:
                await client.close()
                await r1.cleanup()
                await r2.cleanup()
        asyncio.run(scenario())

    def test_non_survivable_streams_relay_untouched(self):
        """A single-replica fleet has no failover target: the router must
        not enter parse-mode relay (no MIGRATE_URL_HEADER upstream, bytes
        pass through untouched) — the pre-migration contract holds
        byte-for-byte."""
        from aiohttp import web as aioweb

        from kubernetes_gpu_cluster_tpu.serving.router import Router

        async def scenario():
            seen = {}

            async def completions(request):
                seen["migrate_url"] = request.headers.get(MIGRATE_URL_HEADER)
                resp = aioweb.StreamResponse()
                await resp.prepare(request)
                await resp.write(b"data: {\"kgct_token_ids\": [9]}\n\n")
                await resp.write(b"data: [DONE]\n\n")
                return resp

            async def health(request):
                return aioweb.json_response({"status": "ok"})

            app = aioweb.Application()
            app.router.add_get("/health", health)
            app.router.add_post("/v1/completions", completions)
            runner = aioweb.AppRunner(app)
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            url = f"http://127.0.0.1:{runner.addresses[0][1]}"
            router = Router([url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                r = await client.post("/v1/completions",
                                      json={"prompt": "x", "stream": True})
                body = await r.read()
                assert seen["migrate_url"] is None
                # No parse-mode: even a stray ledger field passes through.
                assert b"kgct_token_ids" in body
            finally:
                await client.close()
                await runner.cleanup()
        asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Real-engine topology: drain migration + kill-mid-stream (@slow)
# ---------------------------------------------------------------------------

def _serve(runners, servers):
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server

    async def start():
        srv = build_server(_engine_config(), None, "debug-tiny")
        runner = aioweb.AppRunner(srv.build_app())
        await runner.setup()
        site = aioweb.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        runners.append(runner)
        servers.append(srv)
        return srv, f"http://127.0.0.1:{runner.addresses[0][1]}"
    return start()


@pytest.mark.slow
class TestLiveMigrationServing:
    """End-to-end session survivability over real sockets: 2 colocated
    replicas behind the real router; an in-flight stream outlives its
    replica through drain migration (parked-KV import) and through a
    mid-stream kill (token-replay recompute), byte-identical to the
    uninterrupted run in both cases."""

    PROMPT_TEXT_BODY = {"prompt": "the fleet must survive", "max_tokens": 24,
                        "temperature": 0.0}

    async def _topology(self):
        import aiohttp
        from aiohttp import web as aioweb

        from kubernetes_gpu_cluster_tpu.serving.router import Router
        runners, servers = [], []
        await _serve(runners, servers)
        await _serve(runners, servers)
        urls = []
        for runner in runners:
            urls.append(f"http://127.0.0.1:{runner.addresses[0][1]}")
        router = Router(urls, health_interval_s=9999)
        rrunner = aioweb.AppRunner(router.build_app())
        await rrunner.setup()
        rsite = aioweb.TCPSite(rrunner, "127.0.0.1", 0)
        await rsite.start()
        runners.append(rrunner)
        ru = f"http://127.0.0.1:{rrunner.addresses[0][1]}"
        return runners, servers, router, ru, aiohttp.ClientSession()

    @staticmethod
    def _stream_text(body: bytes):
        payloads, done = _client_frames(body)
        assert not any("error" in p for p in payloads), payloads
        return "".join(p["choices"][0]["text"] for p in payloads), done

    def test_drain_migrates_stream_to_peer_import(self, monkeypatch,
                                                  tmp_path):
        monkeypatch.setenv("KGCT_FLIGHT_DIR", str(tmp_path))

        async def scenario():
            runners, servers, router, ru, sess = await self._topology()
            try:
                async with sess:
                    # Uninterrupted reference (greedy, non-stream).
                    async with sess.post(f"{ru}/v1/completions",
                                         json=self.PROMPT_TEXT_BODY) as r:
                        assert r.status == 200, await r.text()
                        ref = (await r.json())["choices"][0]["text"]
                    body = dict(self.PROMPT_TEXT_BODY, stream=True)
                    async with sess.post(f"{ru}/v1/completions",
                                         json=body) as r:
                        assert r.status == 200
                        it = r.content.__aiter__()
                        first_line = await it.__anext__()   # stream is live
                        src = next(s for s in servers
                                   if s.engine.engine.has_unfinished_requests())
                        dst = next(s for s in servers if s is not src)
                        task = src.begin_drain()
                        assert task is not None
                        chunks = [first_line]
                        async for chunk in r.content:
                            chunks.append(chunk)
                        await asyncio.wait_for(task, timeout=30)
                    text, done = self._stream_text(b"".join(chunks))
                    assert done
                    # One uninterrupted client-visible stream, byte-equal
                    # to the undrained reference run.
                    assert text == ref
                    # Attribution on both sides of the seam + the router.
                    mig_src = src.migration.migrations
                    mig_dst = dst.migration.migrations
                    assert mig_src.get(("push", "ok")) == 1
                    assert mig_dst.get(("recv", "ok")) == 1
                    assert router.failovers_total["import"] == 1
                    src_kinds = [e["kind"] for e in
                                 src.engine.engine.obs.flight.export()
                                 ["events"]]
                    dst_kinds = [e["kind"] for e in
                                 dst.engine.engine.obs.flight.export()
                                 ["events"]]
                    assert "migrate" in src_kinds
                    assert "migrate" in dst_kinds
            finally:
                for runner in reversed(runners):
                    await runner.cleanup()
        asyncio.run(scenario())

    def test_kill_midstream_recomputes_on_successor(self):
        """No drain, no parked KV — the upstream socket is severed by
        chaos and the successor reconstructs the stream by token replay,
        still byte-identical (greedy)."""
        async def scenario():
            runners, servers, router, ru, sess = await self._topology()
            try:
                async with sess:
                    async with sess.post(f"{ru}/v1/completions",
                                         json=self.PROMPT_TEXT_BODY) as r:
                        assert r.status == 200, await r.text()
                        ref = (await r.json())["choices"][0]["text"]
                    configure_faults(
                        "replica_kill_midstream:after=2,times=1")
                    body = dict(self.PROMPT_TEXT_BODY, stream=True)
                    async with sess.post(f"{ru}/v1/completions",
                                         json=body) as r:
                        assert r.status == 200
                        text, done = self._stream_text(await r.read())
                    assert done
                    assert text == ref
                    assert router.failovers_total["recompute"] == 1
                    assert router.failovers_total["failed"] == 0
                    # The dying replica's engine was told to abort its
                    # orphaned sequence eventually (the router closed the
                    # upstream); the resumed side emitted only new tokens.
            finally:
                configure_faults(None)
                for runner in reversed(runners):
                    await runner.cleanup()
        asyncio.run(scenario())
