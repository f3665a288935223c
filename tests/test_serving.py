"""Serving layer: tokenizer, detokenizer stop handling, OpenAI API server,
router — end-to-end over the real engine on the CPU mesh (debug-tiny)."""

import asyncio
import json
import time

import aiohttp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
from kubernetes_gpu_cluster_tpu.serving.router import Router
from kubernetes_gpu_cluster_tpu.serving.tokenizer import (
    ByteTokenizer, IncrementalDetokenizer)


class TestByteTokenizer:
    def test_roundtrip(self):
        tok = ByteTokenizer()
        text = "hello, TPU! héllo é世界"
        ids = tok.encode(text)
        assert ids[0] == tok.BOS
        assert tok.decode(ids) == text

    def test_specials_skipped(self):
        tok = ByteTokenizer()
        assert tok.decode([tok.BOS, ord("h") + 3, tok.EOS]) == "h"


class TestIncrementalDetokenizer:
    def test_streams_deltas(self):
        tok = ByteTokenizer(add_bos=False)
        d = IncrementalDetokenizer(tok)
        out = d.push(tok.encode("hel")) + d.push(tok.encode("lo"))
        out += d.push([], final=True)
        assert out == "hello"

    def test_stop_string_across_pushes(self):
        tok = ByteTokenizer(add_bos=False)
        d = IncrementalDetokenizer(tok, stop=["END"])
        a = d.push(tok.encode("abcE"))
        assert "E" not in a          # held back: could start "END"
        b = d.push(tok.encode("NDxyz"))
        assert d.stopped
        assert a + b == "abc"

    def test_stop_string_not_matched_releases_holdback(self):
        tok = ByteTokenizer(add_bos=False)
        d = IncrementalDetokenizer(tok, stop=["END"])
        a = d.push(tok.encode("abcEN"))
        b = d.push(tok.encode("Q"), final=True)
        assert not d.stopped
        assert a + b == "abcENQ"

    def test_partial_utf8_held_back(self):
        tok = ByteTokenizer(add_bos=False)
        d = IncrementalDetokenizer(tok)
        raw = "é".encode("utf-8")
        a = d.push([raw[0] + 3])
        b = d.push([raw[1] + 3], final=True)
        assert a + b == "é"


def _engine_config():
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=256,
                                  decode_buckets=(1, 2, 4),
                                  prefill_buckets=(128, 256),
                                  decode_window=4))


_SERVER: dict = {}      # module-scope handle to the live APIServer (obs tests)


@pytest.fixture(scope="module")
def api_client():
    """One engine + server shared by the module (compiles once)."""
    loop = asyncio.new_event_loop()
    server = build_server(_engine_config(), tokenizer_path=None,
                          model_name="debug-tiny")
    _SERVER["api"] = server
    client = TestClient(TestServer(server.build_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield loop, client
    loop.run_until_complete(client.close())
    loop.close()


class TestAPIServer:
    def test_health_and_models(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.get("/health")
            assert r.status == 200
            health = await r.json()
            assert health["status"] == "ok"
            # The device, kernels and pool behind every answer — what a
            # launcher (chip_smoke.py) asserts before it believes a result.
            assert health["platform"] == "cpu"
            assert health["device_kind"] and health["device_count"] == 8
            assert health["use_pallas"] is False
            assert health["use_pallas_hist"] is False
            assert (health["num_pages"], health["page_size"]) == (128, 16)
            assert health["hbm_bytes_in_use"] == [0] * 8
            r = await client.get("/v1/models")
            data = await r.json()
            assert data["data"][0]["id"] == "debug-tiny"
        loop.run_until_complete(go())

    def test_completion_non_streaming(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "hello world", "max_tokens": 8, "temperature": 0.0})
            assert r.status == 200
            data = await r.json()
            assert data["object"] == "completion"
            assert data["usage"]["completion_tokens"] > 0
            assert isinstance(data["choices"][0]["text"], str)
            assert data["choices"][0]["finish_reason"] in ("stop", "length")
            return data
        d1 = loop.run_until_complete(go())
        d2 = loop.run_until_complete(go())
        # greedy determinism through the whole HTTP+engine stack
        assert d1["choices"][0]["text"] == d2["choices"][0]["text"]

    def test_completion_streaming_sse(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "stream me", "max_tokens": 8, "temperature": 0.0,
                "stream": True})
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            events = []
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: "):
                    payload = line[len("data: "):]
                    if payload == "[DONE]":
                        break
                    events.append(json.loads(payload))
            assert events, "no SSE events"
            assert events[-1]["choices"][0]["finish_reason"] in ("stop", "length")
            text = "".join(e["choices"][0].get("text", "") for e in events)
            return text
        text = loop.run_until_complete(go())

        async def non_stream():
            r = await client.post("/v1/completions", json={
                "prompt": "stream me", "max_tokens": 8, "temperature": 0.0})
            return (await r.json())["choices"][0]["text"]
        assert text == loop.run_until_complete(non_stream())

    def test_chat_completion(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 6, "temperature": 0.0})
            assert r.status == 200
            data = await r.json()
            assert data["object"] == "chat.completion"
            assert "content" in data["choices"][0]["message"]
        loop.run_until_complete(go())

    def test_token_ids_prompt_and_errors(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": [5, 6, 7], "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            r = await client.post("/v1/completions", json={"max_tokens": 4})
            assert r.status == 400
            r = await client.post("/v1/completions", data=b"not json")
            assert r.status == 400
        loop.run_until_complete(go())

    def test_metrics_endpoint(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.get("/metrics")
            assert r.status == 200
            text = await r.text()
            assert "kgct_tokens_generated_total" in text
            assert "kgct_kv_pages_free" in text
            return text
        text = loop.run_until_complete(go())
        gen = [l for l in text.splitlines()
               if l.startswith("kgct_tokens_generated_total")]
        assert int(gen[0].split()[-1]) > 0   # previous tests generated tokens
        # Real histograms with filled buckets for the north-star latencies
        # (previous tests completed requests), validated structurally.
        _assert_valid_exposition(text)
        for fam in ("kgct_ttft_seconds", "kgct_tpot_seconds",
                    "kgct_queue_wait_seconds", "kgct_step_seconds",
                    "kgct_request_e2e_seconds", "kgct_batch_size_per_step"):
            assert f"# TYPE {fam} histogram" in text, fam
            assert f"{fam}_bucket" in text, f"{fam}: no observations"
        assert 'le="+Inf"' in text
        assert "kgct_step_phase_seconds_total" in text

    def test_prefix_cache_metrics_on_fresh_scrape(self, api_client):
        """ROADMAP item 2's gauge: kgct_prefix_cache_hit_ratio plus the
        hits/misses counters are PRESENT and nan-free even on an engine
        that never enabled prefix caching (zeros, not absent — dashboards
        must not need an existence check). The exposition validity
        (nan-free, contiguous families) is pinned by
        _assert_valid_exposition in test_metrics_endpoint above."""
        loop, client = api_client

        async def go():
            r = await client.get("/metrics")
            return await r.text()
        text = loop.run_until_complete(go())
        for name, typ in (("kgct_prefix_cache_hit_ratio", "gauge"),
                          ("kgct_prefix_cache_hits_total", "counter"),
                          ("kgct_prefix_cache_misses_total", "counter")):
            assert f"# TYPE {name} {typ}" in text, name
            [line] = [l for l in text.splitlines()
                      if l.startswith(name + " ")]
            value = float(line.split()[-1])
            assert value == value and value >= 0.0, line


def _parse_sample(line: str):
    """One exposition sample line -> (base_name, labels_dict, float_value)."""
    import re
    name_part, _, val = line.rpartition(" ")
    base, _, rest = name_part.partition("{")
    labels = dict(re.findall(r'(\w+)="([^"]*)"', rest))
    return base, labels, float(val)


def _assert_valid_exposition(text: str) -> None:
    """Prometheus text-format validity as strict parsers enforce it: at most
    one TYPE line per metric family with all of a family's samples contiguous
    (a family's block ends as soon as another family's line appears); every
    sample value finite (no nan, even on a freshly started server); histogram
    families structurally sound — per labelset, cumulative bucket counts
    monotone non-decreasing, the +Inf bucket equal to ``_count``, and a
    matching ``_sum``/``_count`` pair present."""
    import math

    closed: set[str] = set()
    current = None
    types: dict[str, str] = {}
    by_name: dict[str, list] = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE"):
            parts = line.split()
            fam = parts[2]
            assert fam not in closed and fam != current, (
                f"duplicate TYPE for family {fam}")
            assert fam not in types, f"duplicate TYPE for family {fam}"
            types[fam] = parts[3]
            if current is not None:
                closed.add(current)
            current = fam
            continue
        if line.startswith("#"):
            continue
        base, labels, value = _parse_sample(line)
        assert not math.isnan(value), f"nan in exposition: {line!r}"
        by_name.setdefault(base, []).append((labels, value))
        fam = (current if current is not None and
               (base == current or base.startswith(current + "_"))
               else base)
        if fam != current:
            if current is not None:
                closed.add(current)
            current = fam
        assert fam not in closed, (
            f"samples of family {fam} are not contiguous: {line!r}")

    def cell_key(labels):
        return tuple(sorted((k, v) for k, v in labels.items() if k != "le"))

    for fam, typ in types.items():
        if typ != "histogram":
            continue
        buckets = by_name.get(fam + "_bucket", [])
        sums = {cell_key(l): v for l, v in by_name.get(fam + "_sum", [])}
        counts = {cell_key(l): v for l, v in by_name.get(fam + "_count", [])}
        if not (buckets or sums or counts):
            continue    # labeled histogram with no observations yet: legal
        assert buckets and sums and counts, f"{fam}: incomplete histogram"
        series: dict = {}
        for labels, v in buckets:
            series.setdefault(cell_key(labels), []).append(
                (labels["le"], v))
        assert set(series) == set(sums) == set(counts), (
            f"{fam}: bucket/_sum/_count labelsets disagree")
        for key, bs in series.items():
            def le_val(le):
                return float("inf") if le == "+Inf" else float(le)
            bs = sorted(bs, key=lambda x: le_val(x[0]))
            cums = [v for _, v in bs]
            assert cums == sorted(cums), (
                f"{fam}{dict(key)}: non-monotone buckets {cums}")
            assert bs[-1][0] == "+Inf", f"{fam}{dict(key)}: missing +Inf"
            assert cums[-1] == counts[key], (
                f"{fam}{dict(key)}: +Inf bucket {cums[-1]} != _count "
                f"{counts[key]}")


class TestObservability:
    """The /debug/trace export and the engine's phase-attribution
    bookkeeping, exercised through real API traffic."""

    def test_debug_trace_perfetto_export(self, api_client):
        loop, client = api_client

        async def go():
            # Fresh traffic so the ring holds a complete lifecycle.
            r = await client.post("/v1/completions", json={
                "prompt": "trace me", "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            r = await client.get("/debug/trace")
            assert r.status == 200
            return await r.json()
        doc = loop.run_until_complete(go())
        evs = doc["traceEvents"]
        assert isinstance(evs, list) and evs
        # Perfetto-loadable skeleton: process/thread metadata present.
        assert any(e.get("ph") == "M" for e in evs)
        # Request lifecycle spans: async begin/end pairs keyed by request id,
        # with the instant events (queued/scheduled/first_token) in between.
        reqs = [e for e in evs if e.get("cat") == "request"]
        opens = {e["id"] for e in reqs if e["ph"] == "b"}
        closes = {e["id"] for e in reqs if e["ph"] == "e"}
        assert opens and opens & closes, "no complete request span"
        names = {e["name"] for e in reqs if e["ph"] == "n"}
        assert {"queued", "scheduled", "first_token"} <= names
        # Step-phase attribution slices on the engine.step track.
        slices = [e for e in evs if e.get("ph") == "X"]
        assert {"schedule", "device_dispatch"} <= {s["name"] for s in slices}
        assert all(s["ts"] >= 0 and s["dur"] >= 0 for s in slices)
        json.dumps(doc)     # round-trips to the wire format

    def test_trace_clear_param(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.get("/debug/trace?clear=1")
            assert r.status == 200
            r2 = await client.get("/debug/trace")
            return await r2.json()
        doc = loop.run_until_complete(go())
        assert not [e for e in doc["traceEvents"]
                    if e.get("cat") == "request"]

    def test_frame_delay_and_the_workers_three_states_on_a_live_server(
            self, api_client):
        """Every token-bearing frame of a stream is held against the end of
        the program that made its tokens, whole and in five stages that add
        up to it; and the step loop thread's three states, read at two
        moments, grew by the wall between them (within 1 %): idle on the
        inbox before, blocked and busy through a stream."""
        import time
        from kubernetes_gpu_cluster_tpu.observability.phases import (
            FRAME_STAGES)
        loop, client = api_client
        obs = _SERVER["api"].engine.engine.obs

        def read():
            got, now = obs.phases.worker_seconds(), time.monotonic()
            return got, now

        def stages():
            # stage -> (sum, count), a copy
            return {s: tuple(obs.frame_stage._cells.get((s,), [0, 0.0, 0])[1:])
                    for s in FRAME_STAGES}
        (w0, t0), n0 = read(), obs.frame_delay.count
        sum0, stage0 = obs.frame_delay.sum, stages()

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "frames", "max_tokens": 24, "temperature": 0.0,
                "stream": True})
            assert r.status == 200
            return [line async for line in r.content
                    if line.startswith(b"data: {")]
        frames = loop.run_until_complete(go())
        loop.run_until_complete(asyncio.sleep(0.3))     # the inbox again
        w1, t1 = read()
        assert 0 < len(frames) <= obs.frame_delay.count - n0 + 1
        assert obs.frame_delay.count - n0 >= len(frames) - 1
        assert 0 < obs.frame_delay.sum < 60.0
        # the same frames, the same stamps: every stage counted once a
        # frame, none negative, and the five sums are the delay's sum
        stage1 = stages()
        by_stage = {s: stage1[s][0] - stage0[s][0] for s in FRAME_STAGES}
        assert [stage1[s][1] - stage0[s][1] for s in FRAME_STAGES] \
            == [obs.frame_delay.count - n0] * 5
        assert all(v >= 0 for v in by_stage.values()), by_stage
        assert sum(by_stage.values()) == pytest.approx(
            obs.frame_delay.sum - sum0, rel=1e-6)
        grown = {k: w1[k] - w0[k] for k in w1}
        assert all(v >= 0 for v in grown.values()), grown
        assert sum(grown.values()) == pytest.approx(t1 - t0, rel=0.01)
        assert grown["device_wait"] > 0 and grown["host"] > 0
        assert grown["inbox_wait"] >= 0.25

        async def scrape():
            return await (await client.get("/metrics")).text()
        text = loop.run_until_complete(scrape())
        for fam in ("kgct_step_device_seconds", "kgct_frame_delay_seconds",
                    "kgct_frame_stage_seconds", "kgct_step_lead_seconds"):
            assert f"# TYPE {fam} histogram" in text
        for stage in FRAME_STAGES:
            assert ('kgct_frame_stage_seconds_count{stage="%s"} %d'
                    % (stage, obs.frame_delay.count)) in text
        assert 'kgct_step_lead_seconds_count{kind="decode"}' in text
        assert 'kgct_device_starved_seconds_total{kind="decode"} ' in text
        for fam in ("kgct_steps_retired_total", "kgct_step_slow_total",
                    "kgct_device_starved_seconds_total",
                    "kgct_step_slow_seconds_total", "kgct_step_tokens_total",
                    "kgct_worker_seconds_total"):
            assert f"# TYPE {fam} counter" in text
        _assert_valid_exposition(text)

    def test_phase_attribution_bookkeeping(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "phases", "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
        loop.run_until_complete(go())
        obs = _SERVER["api"].engine.engine.obs
        assert obs.phases.steps_recorded > 0
        for phase in ("schedule", "host_prep", "device_dispatch",
                      "device_fetch", "postproc", "detokenize"):
            assert obs.phases.totals[phase] > 0.0, f"{phase} never recorded"
        assert obs.phases.counts["device_dispatch"] > 0
        # What a TTFT is made of, as /metrics carries it: queue wait and
        # prefill, one observation each a first token.
        assert obs.ttft.count > 0
        assert obs.queue_wait.count > 0 and obs.prefill_latency.count > 0


class TestFleetTelemetry:
    """The device/SLO telemetry layer: new gauges present and nan-free on
    a scrape of the module's live server (exposition validity overall is
    pinned by _assert_valid_exposition in test_metrics_endpoint)."""

    def test_new_gauges_present_and_nan_free(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.get("/metrics")
            return await r.text()
        text = loop.run_until_complete(go())
        _assert_valid_exposition(text)

        def val(prefix):
            [line] = [l for l in text.splitlines() if l.startswith(prefix)]
            return float(line.rpartition(" ")[2])

        # HBM gauges: 0 on CPU (the backend reports nothing), never nan.
        assert val("kgct_hbm_bytes_limit ") >= 0
        assert val("kgct_hbm_bytes_in_use ") >= 0
        # jit-cache entry count: the module's traffic compiled something.
        assert val("kgct_jit_compiles_total ") > 0
        # Per-phase mean step time, promoted from the tracer's breakdown.
        assert "# TYPE kgct_step_phase_mean_seconds gauge" in text
        assert val('kgct_step_phase_mean_seconds{phase="device_dispatch"}'
                   ) > 0
        # Rolling SLO layer: attainment in [0, 1], budget > 0, goodput >= 0.
        att = val("kgct_slo_ttft_attainment_ratio ")
        assert 0.0 <= att <= 1.0
        assert val("kgct_slo_ttft_budget_ms ") > 0
        assert val("kgct_slo_goodput_tokens_per_sec ") >= 0

    def test_flightrecorder_endpoint(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.get("/debug/flightrecorder")
            assert r.status == 200
            return await r.json()
        doc = loop.run_until_complete(go())
        assert doc["enabled"] is True
        kinds = {e["kind"] for e in doc["events"]}
        # Mirrored lifecycle events from the module's traffic plus at
        # least one periodic state snapshot.
        assert "arrival" in kinds and "snapshot" in kinds
        snap = next(e for e in doc["events"] if e["kind"] == "snapshot")
        assert {"waiting", "running", "kv_pages_free"} <= set(snap)


class TestRequestIdPropagation:
    """The x-kgct-request-id contract on the replica side: an inbound id
    (the router's mint) becomes the ENGINE request id — shared with the
    lifecycle trace — and every response echoes an id, success or error."""

    def test_inbound_id_adopted_and_traced(self, api_client):
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)
        loop, client = api_client
        rid = "req-test-correlate-1"

        async def go():
            r = await client.post(
                "/v1/completions",
                json={"prompt": "trace my id", "max_tokens": 4,
                      "temperature": 0.0},
                headers={REQUEST_ID_HEADER: rid})
            assert r.status == 200
            assert r.headers[REQUEST_ID_HEADER] == rid
            data = await r.json()
            assert data["id"] == rid              # engine adopted it
            rt = await client.get("/debug/trace")
            return await rt.json()
        doc = loop.run_until_complete(go())
        spans = [e for e in doc["traceEvents"]
                 if e.get("cat") == "request" and e.get("id") == rid]
        assert {e["ph"] for e in spans} >= {"b", "e"}, \
            "engine lifecycle trace does not carry the inbound id"

    def test_minted_id_on_success_and_errors(self, api_client):
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)
        loop, client = api_client

        async def go():
            # No inbound header: a cmpl- id is minted and echoed.
            r = await client.post("/v1/completions", json={
                "prompt": "mint me", "max_tokens": 2, "temperature": 0.0})
            assert r.headers[REQUEST_ID_HEADER].startswith("cmpl-")
            assert (await r.json())["id"] == r.headers[REQUEST_ID_HEADER]
            # Error responses carry the id too (a 400 in a client log must
            # join the server's records).
            r400 = await client.post("/v1/completions",
                                     json={"max_tokens": 2})
            assert r400.status == 400
            assert REQUEST_ID_HEADER in r400.headers
            # An invalid inbound id (spaces) is ignored, not echoed.
            rbad = await client.post(
                "/v1/completions",
                json={"prompt": "x", "max_tokens": 2, "temperature": 0.0},
                headers={REQUEST_ID_HEADER: "bad id with spaces"})
            assert rbad.headers[REQUEST_ID_HEADER] != "bad id with spaces"
            # Streaming: the header rides the SSE response's headers.
            rs = await client.post("/v1/completions", json={
                "prompt": "s", "max_tokens": 2, "temperature": 0.0,
                "stream": True}, headers={REQUEST_ID_HEADER: "req-sse-7"})
            assert rs.headers[REQUEST_ID_HEADER] == "req-sse-7"
            await rs.read()
        loop.run_until_complete(go())

    def test_recorder_off_byte_identical(self, api_client):
        """The acceptance pin: the recorder only OBSERVES — toggling it
        off must not perturb engine outputs (greedy, same warm engine)."""
        loop, client = api_client
        obs = _SERVER["api"].engine.engine.obs
        body = {"prompt": "identical under observation", "max_tokens": 6,
                "temperature": 0.0}

        async def one():
            r = await client.post("/v1/completions", json=body)
            assert r.status == 200
            return (await r.json())["choices"][0]["text"]
        text_on = loop.run_until_complete(one())
        obs.flight.enabled = False
        try:
            text_off = loop.run_until_complete(one())
        finally:
            obs.flight.enabled = True
        assert text_on == text_off


class TestRouter:
    def test_routes_and_failover(self, api_client):
        loop, client = api_client

        async def go():
            # Two "replicas": one real (the api server), one dead.
            real = f"http://{client.host}:{client.port}"
            router = Router([real, "http://127.0.0.1:1"],
                            health_interval_s=0.1)
            rclient = TestClient(TestServer(router.build_app()))
            await rclient.start_server()
            try:
                await asyncio.sleep(0.35)   # health loop marks dead replica
                r = await rclient.get("/health")
                body = await r.json()
                assert body["replicas"][real]["healthy"] is True
                assert body["replicas"]["http://127.0.0.1:1"]["healthy"] is False
                # Proxied completion end-to-end.
                r = await rclient.post("/v1/completions", json={
                    "prompt": "via router", "max_tokens": 4,
                    "temperature": 0.0})
                assert r.status == 200
                data = await r.json()
                assert data["choices"][0]["text"] is not None
                r = await rclient.get("/metrics")
                text = await r.text()
                assert "kgct_router_replica_healthy" in text
                _assert_valid_exposition(text)
            finally:
                await rclient.close()
        loop.run_until_complete(go())


class TestRouterFailover:
    def test_failover_to_next_replica_before_streaming(self, event_loop=None):
        """An upstream that refuses the connection is retried on the next
        healthy replica; the client sees a single successful response."""
        import asyncio
        import aiohttp
        from aiohttp import web as aioweb
        from kubernetes_gpu_cluster_tpu.serving.router import Router

        async def scenario():
            # live replica
            async def ok(request):
                return aioweb.json_response({"from": "live"})

            async def health(request):
                return aioweb.json_response({"status": "ok"})
            app = aioweb.Application()
            app.router.add_post("/v1/completions", ok)
            app.router.add_get("/health", health)
            runner = aioweb.AppRunner(app)
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]

            # dead replica first in the list (connection refused)
            router = Router([f"http://127.0.0.1:1",      # nothing listens
                             f"http://127.0.0.1:{port}"],
                            health_interval_s=9999)
            rapp = router.build_app()
            rrunner = aioweb.AppRunner(rapp)
            await rrunner.setup()
            rsite = aioweb.TCPSite(rrunner, "127.0.0.1", 0)
            await rsite.start()
            rport = rrunner.addresses[0][1]
            # The startup probe already benched the dead replica; this test
            # is about the harder case — a replica that PASSED its probes and
            # died just before the request — so put it back in rotation.
            router.replicas[0].healthy = True
            router.replicas[0].consecutive_failures = 0
            try:
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                            f"http://127.0.0.1:{rport}/v1/completions",
                            json={"prompt": "x"}) as resp:
                        assert resp.status == 200
                        data = await resp.json()
                        assert data["from"] == "live"
            finally:
                await rrunner.cleanup()
                await runner.cleanup()

        asyncio.run(scenario())


class TestLogprobsAPI:
    def test_completions_logprobs(self, api_client):
        """OpenAI completions logprobs parity: logprobs: 1 returns the
        chosen-token logprobs aligned with tokens; >1 (alternatives) is a
        clean 400."""
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 4, "temperature": 0.0,
                "logprobs": 1})
            assert r.status == 200
            body = await r.json()
            lp = body["choices"][0]["logprobs"]
            assert len(lp["token_logprobs"]) == len(lp["tokens"]) == 4
            assert all(isinstance(x, float) and x <= 0.0
                       for x in lp["token_logprobs"])

            # Determinism: greedy rerun returns identical logprobs.
            r2 = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 4, "temperature": 0.0,
                "logprobs": 1})
            lp2 = (await r2.json())["choices"][0]["logprobs"]
            assert lp2["token_logprobs"] == lp["token_logprobs"]

            r3 = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 2, "logprobs": 5})
            assert r3.status == 200   # alternatives supported since r5
            assert "top_logprobs" in (await r3.json())["choices"][0]["logprobs"]

            # Off by default: no logprobs object.
            r4 = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 2, "temperature": 0.0})
            assert "logprobs" not in (await r4.json())["choices"][0]

            # return_tokens_as_token_ids (vLLM's field): tokens and
            # alternatives render as "token_id:<id>" — the only way to read
            # a generation back where ids do not decode to text.
            r5 = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 4, "temperature": 0.0,
                "logprobs": 1, "return_tokens_as_token_ids": True})
            lp5 = (await r5.json())["choices"][0]["logprobs"]
            assert lp5["token_logprobs"] == lp["token_logprobs"]
            ids = [int(t.removeprefix("token_id:")) for t in lp5["tokens"]]
            assert all(0 <= t < 512 for t in ids)
            for tid, top in zip(ids, lp5["top_logprobs"]):
                assert f"token_id:{tid}" in top
        loop.run_until_complete(go())

    def test_streaming_logprobs_and_chat_rejection(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 4, "temperature": 0.0,
                "logprobs": 1, "stream": True,
                "return_tokens_as_token_ids": True})
            assert r.status == 200
            lps = []
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    ev = json.loads(line[len("data: "):])
                    lp = ev["choices"][0].get("logprobs")
                    if lp:
                        assert len(lp["tokens"]) == len(lp["token_logprobs"])
                        assert all(t.startswith("token_id:")
                                   for t in lp["tokens"])
                        lps.extend(lp["token_logprobs"])
                if line == "data: [DONE]":
                    break
            assert len(lps) == 4 and all(x <= 0 for x in lps)

            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 2, "logprobs": 1})
            assert r.status == 400
        loop.run_until_complete(go())


class TestSamplingTailAPI:
    """OpenAI sampling-surface tail (VERDICT r4 missing #3): presence/
    frequency penalties, per-request seed, echo — against the vLLM API the
    reference exposed (reference old_README.md:1472-1476)."""

    def test_echo_completions(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 3, "temperature": 0.0,
                "echo": True})
            assert r.status == 200
            body = await r.json()
            assert body["choices"][0]["text"].startswith("hi")

            # echo + logprobs: prompt tokens present with null logprobs
            r2 = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 2, "temperature": 0.0,
                "echo": True, "logprobs": 1})
            lp = (await r2.json())["choices"][0]["logprobs"]
            assert len(lp["token_logprobs"]) == 3 + 2
            assert lp["token_logprobs"][:3] == [None, None, None]
            assert all(x <= 0 for x in lp["token_logprobs"][3:])

            # echo on chat is a clean 400
            r3 = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 2, "echo": True})
            assert r3.status == 400
        loop.run_until_complete(go())

    def test_echo_streaming_first_frame_is_prompt(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2, "temperature": 0.0,
                "echo": True, "stream": True})
            assert r.status == 200
            first = None
            # Drain to [DONE]: breaking early aborts the request server-side
            # and leaves a zombie window that changes the next test's batch.
            async for line in r.content:
                line = line.decode().strip()
                if line == "data: [DONE]":
                    break
                if line.startswith("data: ") and first is None:
                    first = json.loads(line[len("data: "):])
            assert first["choices"][0]["text"] == "hi"
        loop.run_until_complete(go())

    def test_seed_reproducible_over_api(self, api_client):
        loop, client = api_client

        async def go():
            req = {"prompt": [2, 8, 4], "max_tokens": 6, "temperature": 1.0,
                   "seed": 1234, "logprobs": 1}
            a = (await (await client.post("/v1/completions", json=req)).json())
            b = (await (await client.post("/v1/completions", json=req)).json())
            la = a["choices"][0]["logprobs"]["token_logprobs"]
            lb = b["choices"][0]["logprobs"]["token_logprobs"]
            assert la == lb
        loop.run_until_complete(go())

    def test_logprobs_alternatives_over_api(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": [1, 5, 9], "max_tokens": 3, "temperature": 0.0,
                "logprobs": 3})
            assert r.status == 200
            lp = (await r.json())["choices"][0]["logprobs"]
            assert len(lp["top_logprobs"]) == 3
            for chosen_lp, tops in zip(lp["token_logprobs"],
                                       lp["top_logprobs"]):
                # OpenAI's dict-of-token-strings format collapses distinct
                # ids that decode identically (the byte tokenizer renders
                # out-of-range ids as "") — so <= 3 keys, not == 3; the
                # engine-level test asserts exact id-level counts.
                assert 1 <= len(tops) <= 3
                assert max(tops.values()) >= chosen_lp - 1e-5

            r2 = await client.post("/v1/completions", json={
                "prompt": [1, 5], "max_tokens": 2, "logprobs": 9})
            assert r2.status == 400

            # echo + alternatives: prompt positions are null
            r3 = await client.post("/v1/completions", json={
                "prompt": [1, 5], "max_tokens": 2, "temperature": 0.0,
                "logprobs": 2, "echo": True})
            lp3 = (await r3.json())["choices"][0]["logprobs"]
            assert lp3["top_logprobs"][:2] == [None, None]
            assert len(lp3["top_logprobs"]) == 4
        loop.run_until_complete(go())

    def test_logit_bias_and_best_of(self, api_client):
        loop, client = api_client

        async def go():
            # logit_bias forces the token end-to-end over the API
            r = await client.post("/v1/completions", json={
                "prompt": [3, 1], "max_tokens": 3, "temperature": 0.0,
                "logit_bias": {"70": 100}, "logprobs": 1})
            assert r.status == 200
            # token id 70 maps to byte 'C' in the byte tokenizer (70-3=67)
            body = await r.json()
            assert body["choices"][0]["text"] == "CCC"

            r2 = await client.post("/v1/completions", json={
                "prompt": [3, 1], "max_tokens": 2, "logit_bias": {"5": 200}})
            assert r2.status == 400

            # best_of: 3 candidates, top-1 by mean logprob returned
            r3 = await client.post("/v1/completions", json={
                "prompt": [2, 8], "max_tokens": 4, "temperature": 1.0,
                "seed": 9, "best_of": 3})
            assert r3.status == 200
            assert len((await r3.json())["choices"]) == 1

            r4 = await client.post("/v1/completions", json={
                "prompt": [2, 8], "max_tokens": 2, "n": 3, "best_of": 2})
            assert r4.status == 400
        loop.run_until_complete(go())

    def test_penalties_accepted_and_validated(self, api_client):
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": [3, 1], "max_tokens": 4, "temperature": 0.5,
                "presence_penalty": 1.0, "frequency_penalty": 0.5})
            assert r.status == 200
            assert len((await r.json())["choices"]) == 1

            r2 = await client.post("/v1/completions", json={
                "prompt": [3, 1], "max_tokens": 2, "presence_penalty": 9.0})
            assert r2.status == 400
            msg = (await r2.json())["error"]["message"]
            assert "presence_penalty" in msg
        loop.run_until_complete(go())


class TestClientDisconnectAborts:
    """A client that goes away must not leave device work running: every
    handler exit path calls engine.abort (previously asserted only by
    comments). Requests here ask for FAR more tokens than the poll deadline
    allows, so a missing abort fails the test instead of passing slowly."""

    async def _wait_engine_idle(self, eng, deadline_s=8.0):
        deadline = time.monotonic() + deadline_s
        while eng.has_unfinished_requests():
            assert time.monotonic() < deadline, (
                "engine still has unfinished requests after client "
                "disconnect — abort path leaked device work")
            await asyncio.sleep(0.02)

    def test_streaming_disconnect_aborts_engine_request(self, api_client):
        loop, client = api_client

        async def go():
            eng = _SERVER["api"].engine.engine
            r = await client.post("/v1/completions", json={
                "prompt": "run forever", "max_tokens": 400,
                "temperature": 0.0, "stream": True})
            assert r.status == 200
            async for line in r.content:
                if line.decode().strip().startswith("data: "):
                    break       # first token delivered: request is live
            assert eng.has_unfinished_requests()
            r.close()           # client vanishes mid-stream
            await self._wait_engine_idle(eng)
            # The server survives and keeps serving.
            r2 = await client.post("/v1/completions", json={
                "prompt": "still alive", "max_tokens": 4,
                "temperature": 0.0})
            assert r2.status == 200
        loop.run_until_complete(go())

    def test_n_gt_1_disconnect_aborts_all_subrequests(self, api_client):
        loop, client = api_client

        async def go():
            eng = _SERVER["api"].engine.engine
            with pytest.raises(asyncio.TimeoutError):
                await client.post("/v1/completions", json={
                    "prompt": [2, 8, 4], "max_tokens": 400,
                    "temperature": 1.0, "seed": 3, "n": 2},
                    timeout=aiohttp.ClientTimeout(total=0.5))
            await self._wait_engine_idle(eng)
        loop.run_until_complete(go())

    def test_best_of_disconnect_aborts_all_candidates(self, api_client):
        loop, client = api_client

        async def go():
            eng = _SERVER["api"].engine.engine
            with pytest.raises(asyncio.TimeoutError):
                await client.post("/v1/completions", json={
                    "prompt": [2, 8], "max_tokens": 400,
                    "temperature": 1.0, "seed": 7, "best_of": 3},
                    timeout=aiohttp.ClientTimeout(total=0.5))
            await self._wait_engine_idle(eng)
            r = await client.post("/v1/completions", json={
                "prompt": [2, 8], "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
        loop.run_until_complete(go())


class TestSessionAffinityPassthrough:
    def test_session_id_and_user_accepted_and_validated(self, api_client):
        """The prefix-affinity router's stickiness keys pass through the
        engine: scalar session_id/user are accepted (and otherwise
        ignored); non-scalar values are a loud 400 — they would silently
        change the ROUTER's per-request hashing semantics."""
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2, "temperature": 0.0,
                "session_id": "conv-1", "user": "u-9"})
            assert r.status == 200
            r2 = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2,
                "session_id": {"nested": "object"}})
            assert r2.status == 400
            assert "session_id" in (await r2.json())["error"]["message"]
            r3 = await client.post("/v1/completions", json={
                "prompt": "hi", "max_tokens": 2, "user": ["a", "b"]})
            assert r3.status == 400
        loop.run_until_complete(go())


class TestMultipleCompletions:
    def test_n_choices(self, api_client):
        """OpenAI n > 1: n concurrent engine requests gathered into indexed
        choices; greedy n=2 must produce identical texts (deterministic)."""
        loop, client = api_client

        async def go():
            r = await client.post("/v1/completions", json={
                "prompt": [2, 8, 4], "max_tokens": 4, "temperature": 0.0,
                "n": 2})
            assert r.status == 200
            body = await r.json()
            assert [c["index"] for c in body["choices"]] == [0, 1]
            assert body["choices"][0]["text"] == body["choices"][1]["text"]
            assert body["usage"]["completion_tokens"] == 8

            r = await client.post("/v1/completions", json={
                "prompt": [2, 8], "max_tokens": 2, "n": 2, "stream": True})
            assert r.status == 400
            r = await client.post("/v1/completions", json={
                "prompt": [2, 8], "max_tokens": 2, "n": 0})
            assert r.status == 400
        loop.run_until_complete(go())


class TestKVHandoffOnWarmServer:
    """Disaggregated-serving paths that need only the module's warm
    role="both" server: the export endpoint, and the decode-side fallback
    to local recompute (chaos site kv_handoff_fail + dead prefill URL),
    with the flight recorder capturing the fallback trigger."""

    def test_kv_handoff_export_endpoint(self, api_client):
        from kubernetes_gpu_cluster_tpu.serving.handoff import decode_handoff

        loop, client = api_client

        async def go():
            r = await client.post("/internal/kv_handoff", json={
                "prompt_token_ids": list(range(2, 40)),
                "temperature": 0.0})
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/octet-stream"
            state = decode_handoff(await r.read())
            assert state["model"] == "debug-tiny"
            assert len(state["output_token_ids"]) == 1   # max_tokens clamp
            assert state["k"].shape[1] > 0
            # Malformed bodies are loud 400s, not engine crashes.
            r = await client.post("/internal/kv_handoff",
                                  json={"prompt_token_ids": []})
            assert r.status == 400
            r = await client.post("/internal/kv_handoff",
                                  json={"prompt_token_ids": ["x"]})
            assert r.status == 400
        loop.run_until_complete(go())

    def test_export_failure_counts_outcome_error(self, api_client):
        """An export that dies AFTER admission (engine-side rejection —
        here an out-of-vocab logit_bias id surfacing through the worker)
        must move kgct_disagg_handoffs_total{side="export",
        outcome="error"}: an operator watching a failing prefill pool
        reads the counter, while the 400 itself only reaches the one
        client (the decode side can only ever count its own fallbacks)."""
        loop, client = api_client
        server = _SERVER["api"]

        async def go():
            before = server.disagg.handoffs.get(("export", "error"), 0)
            r = await client.post("/internal/kv_handoff", json={
                "prompt_token_ids": list(range(2, 10)),
                "temperature": 0.0,
                "logit_bias": {"999999": 5}})
            assert r.status == 400
            assert server.disagg.handoffs[("export", "error")] == before + 1
        loop.run_until_complete(go())

    def test_handoff_pull_failure_falls_back_to_local_recompute(
            self, api_client):
        """A completion carrying a prefill-url header whose pull fails —
        chaos-injected (kv_handoff_fail) or a dead upstream — serves the
        SAME output as a plain request (local recompute), 200, with the
        fallback trigger captured in trace ring + flight recorder and the
        fallback counter on /metrics."""
        from kubernetes_gpu_cluster_tpu.resilience.faults import (
            configure_faults)
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            PREFILL_URL_HEADER)

        loop, client = api_client
        body = {"prompt": "fall back please", "max_tokens": 4,
                "temperature": 0.0}

        async def go():
            r = await client.post("/v1/completions", json=body)
            ref = (await r.json())["choices"][0]["text"]

            configure_faults("kv_handoff_fail")
            try:
                r = await client.post(
                    "/v1/completions", json=body,
                    headers={PREFILL_URL_HEADER: "http://127.0.0.1:9"})
                assert r.status == 200
                assert (await r.json())["choices"][0]["text"] == ref
            finally:
                configure_faults(None)
            # Unarmed but dead upstream: the bounded fetch fails, same
            # graceful fallback.
            r = await client.post(
                "/v1/completions", json=body,
                headers={PREFILL_URL_HEADER: "http://127.0.0.1:9"})
            assert r.status == 200
            assert (await r.json())["choices"][0]["text"] == ref

            flight = _SERVER["api"].engine.engine.obs.flight.export()
            falls = [e for e in flight["events"]
                     if e["kind"] == "handoff"
                     and e.get("outcome") == "fallback"]
            assert len(falls) >= 2       # chaos trigger + dead upstream
            assert any("kv_handoff_fail" in (e.get("error") or "")
                       for e in falls)
            r = await client.get("/metrics")
            text = await r.text()
            _assert_valid_exposition(text)
            assert ('kgct_disagg_handoffs_total{side="import",'
                    'outcome="fallback"} 2') in text
            assert 'kgct_engine_role{role="both"} 1' in text
        loop.run_until_complete(go())

    def test_prefill_pool_allowlist_gates_the_pull(self, api_client):
        """With --prefill-pool set, a header naming an out-of-pool URL is
        NEVER fetched (SSRF guard for direct-to-pod traffic) — the request
        serves by local recompute with the allowlist rejection, not a
        connect error, as the fallback trigger; an in-pool URL still
        reaches the fetch path."""
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            PREFILL_URL_HEADER)

        loop, client = api_client
        server = _SERVER["api"]
        body = {"prompt": "allowlist me", "max_tokens": 4,
                "temperature": 0.0}
        assert server.prefill_pool is None   # warm server: trust-the-net
        server.prefill_pool = frozenset({"http://127.0.0.1:9"})
        try:

            async def go():
                r = await client.post("/v1/completions", json=body)
                ref = (await r.json())["choices"][0]["text"]
                r = await client.post(
                    "/v1/completions", json=body,
                    headers={PREFILL_URL_HEADER: "http://evil.example:80"})
                assert r.status == 200
                assert (await r.json())["choices"][0]["text"] == ref
                flight = server.engine.engine.obs.flight.export()
                rejects = [e for e in flight["events"]
                           if e["kind"] == "handoff"
                           and "not in --prefill-pool"
                           in (e.get("error") or "")]
                assert len(rejects) == 1
                # In-pool URL (trailing slash tolerated) passes the gate:
                # the pull itself then fails on the dead upstream — a
                # CONNECT error, not the allowlist.
                r = await client.post(
                    "/v1/completions", json=body,
                    headers={PREFILL_URL_HEADER: "http://127.0.0.1:9/"})
                assert r.status == 200
                assert (await r.json())["choices"][0]["text"] == ref
                flight = server.engine.engine.obs.flight.export()
                rejects = [e for e in flight["events"]
                           if e["kind"] == "handoff"
                           and "not in --prefill-pool"
                           in (e.get("error") or "")]
                assert len(rejects) == 1   # unchanged
            loop.run_until_complete(go())
        finally:
            server.prefill_pool = None

    def test_engine_side_import_fallback_reports_to_metrics(self, api_client):
        """An ENGINE-side import failure (worker thread, after the pull was
        already counted ok) reports through the on_import_fallback hook the
        server installs — without it /metrics reads 100% successful imports
        on a replica that recomputes everything."""
        loop, client = api_client
        server = _SERVER["api"]
        assert server.engine.on_import_fallback is not None
        before = server.disagg.handoffs.get(("import", "fallback"), 0)
        server.engine.on_import_fallback()
        assert server.disagg.handoffs[("import", "fallback")] == before + 1


class TestWorkerOpShutdownGuard:
    """An op enqueued after the worker thread's final wakeup can never
    drain — run_in_worker must fail the awaiter NOW (a kv_handoff export
    would otherwise hang until the client's own timeout) and
    post_to_worker must drop loudly instead of enqueueing into the void.
    Engine-free: the guard reads only the op-queue fields."""

    def _dead_engine(self):
        import threading

        from kubernetes_gpu_cluster_tpu.serving.async_engine import (
            AsyncLLMEngine)
        eng = AsyncLLMEngine.__new__(AsyncLLMEngine)
        eng._cv = threading.Condition()
        eng._ops = []
        eng._shutdown = True
        eng._thread = threading.Thread()   # never started
        return eng

    def test_run_in_worker_fails_fast_after_shutdown(self):
        eng = self._dead_engine()

        async def go():
            with pytest.raises(RuntimeError, match="shut down"):
                await eng.run_in_worker(lambda e: 1)
        asyncio.run(go())
        assert eng._ops == []            # never enqueued

    def test_post_to_worker_drops_after_shutdown(self):
        eng = self._dead_engine()
        eng.post_to_worker(lambda e: 1)
        assert eng._ops == []
