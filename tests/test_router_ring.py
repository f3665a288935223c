"""Cache-aware fleet routing: hash-ring properties, bounded-load pick
policy, session stickiness, and membership-churn remap — all pure-Python /
aiohttp simulation, no engines.

Correctness here is a DISTRIBUTION property: the ring must be
deterministic across router instances (hashlib, never the salted builtin
``hash``), stable under membership churn (only the dead replica's ~K/N
keys remap), and the least-inflight default must stay byte-identical to
the pre-affinity router so existing deployments see zero behavior change.
"""

import asyncio
from collections import Counter

import pytest

from kubernetes_gpu_cluster_tpu.resilience.faults import configure_faults
from kubernetes_gpu_cluster_tpu.serving.router import HashRing, Router
from test_serving import _assert_valid_exposition


@pytest.fixture(autouse=True)
def _clean_faults():
    configure_faults(None)
    yield
    configure_faults(None)


URLS = [f"http://replica-{i}:8000" for i in range(4)]
KEYS = [f"session-{i}".encode() for i in range(400)]


class TestHashRing:
    def test_deterministic_across_instances(self):
        """Two rings from identical configs agree on every key — the
        process-restart / multi-router-replica contract (builtin ``hash``
        is salted per process and would break this silently)."""
        a, b = HashRing(URLS), HashRing(URLS)
        assert [a.owner(k) for k in KEYS] == [b.owner(k) for k in KEYS]

    def test_vnode_balance_within_bound(self):
        """Raw key-space shares stay within ~1.6x fair at RING_VNODES=64
        (the CHWBL load bound does the rest at pick time)."""
        counts = Counter(HashRing(URLS).owner(k) for k in KEYS)
        assert set(counts) == set(URLS), "some replica owns no keys"
        fair = len(KEYS) / len(URLS)
        assert max(counts.values()) <= 1.6 * fair, counts

    def test_single_removal_remaps_at_most_2k_over_n(self):
        """Consistent-hashing contract: removing 1 of N replicas moves
        ONLY that replica's keys (<= ~K/N, pinned at 2K/N); every other
        key keeps its owner."""
        full = HashRing(URLS)
        shrunk = HashRing(URLS[:-1])
        moved = sum(1 for k in KEYS if full.owner(k) != shrunk.owner(k))
        assert moved <= 2 * len(KEYS) / len(URLS), moved
        survivors_moved = [
            k for k in KEYS
            if full.owner(k) != URLS[-1] and full.owner(k) != shrunk.owner(k)]
        assert survivors_moved == []

    def test_walk_skip_equals_membership_removal(self):
        """Skipping a dead URL while walking the full ring lands exactly
        where a ring built without it would — health churn never needs a
        ring rebuild."""
        full = HashRing(URLS)
        shrunk = HashRing(URLS[:-1])
        for k in KEYS[:100]:
            walked = next(u for u in full.walk(k) if u != URLS[-1])
            assert walked == shrunk.owner(k)

    def test_walk_yields_every_member_once(self):
        walk = list(HashRing(URLS).walk(b"any-key"))
        assert sorted(walk) == sorted(URLS)
        assert walk[0] == HashRing(URLS).owner(b"any-key")


def _router(policy="prefix-affinity", urls=URLS, **kw):
    # Never started: _pick / _affinity_key are pure and need no session.
    return Router(list(urls), routing_policy=policy, **kw)


class TestPickPolicy:
    def test_identical_configs_identical_assignments(self):
        """Acceptance pin: two router instances with the same config route
        K sampled keys identically."""
        r1, r2 = _router(), _router()
        assert ([r1._pick(affinity_key=k).url for k in KEYS]
                == [r2._pick(affinity_key=k).url for k in KEYS])

    def test_session_stickiness(self):
        router = _router()
        first = router._pick(affinity_key=b"sticky")
        for _ in range(5):
            assert router._pick(affinity_key=b"sticky") is first
        assert router.affinity_hits_total == 6
        assert router.affinity_requests_total == 6

    def test_bounded_load_overflow_walks_to_ring_successor(self):
        """An over-bound owner spills to the NEXT under-bound replica in
        ring order (deterministic — not least-inflight scatter), and the
        overflow is charged to the owner's counter."""
        router = _router(balance_factor=1.0)
        key = b"hot-prefix"
        owner_url = router.ring.owner(key)
        owner = next(r for r in router.replicas if r.url == owner_url)
        owner.inflight = 8      # others idle: bound = ceil(9/4) = 3
        picked = router._pick(affinity_key=key)
        successor = next(u for u in router.ring.walk(key)
                         if u != owner_url)
        assert picked.url == successor
        assert router.affinity_overflow_total[owner_url] == 1
        assert router.affinity_hits_total == 0
        # Owner drains below bound: the key comes home.
        owner.inflight = 0
        assert router._pick(affinity_key=key).url == owner_url

    def test_unhealthy_owner_remaps_and_recovers(self):
        router = _router()
        key = b"some-session"
        owner_url = router.ring.owner(key)
        owner = next(r for r in router.replicas if r.url == owner_url)
        owner.healthy = False
        picked = router._pick(affinity_key=key)
        assert picked.url == next(u for u in router.ring.walk(key)
                                  if u != owner_url)
        assert router.ring_remaps_total == 1
        owner.healthy = True
        assert router._pick(affinity_key=key).url == owner_url

    def test_retry_exclude_flows_through_pick_seam(self):
        """The connect-failure retry path (exclude=tried) remaps the SAME
        affinity key deterministically to the ring successor — same seam,
        same walk."""
        router = _router()
        key = b"retry-me"
        first = router._pick(affinity_key=key)
        second = router._pick(affinity_key=key, exclude={first.url})
        assert second is not None and second.url != first.url
        assert second.url == next(u for u in router.ring.walk(key)
                                  if u != first.url)
        third = router._pick(affinity_key=key,
                             exclude={first.url, second.url})
        assert third.url == next(u for u in router.ring.walk(key)
                                 if u not in (first.url, second.url))

    def test_walk_always_places_when_candidates_exist(self):
        """CHWBL never refuses: for any load vector some candidate sits
        under ceil(c*(L+1)/n) (pigeonhole), so an affinity pick with live
        replicas always returns one."""
        import random
        rng = random.Random(7)
        router = _router(balance_factor=1.0)
        for _ in range(200):
            for r in router.replicas:
                r.inflight = rng.randrange(0, 30)
            assert router._pick(affinity_key=b"k") is not None

    def test_no_key_falls_back_to_least_inflight(self):
        router = _router()
        router.replicas[2].inflight = 0
        for r in router.replicas[:2]:
            r.inflight = 5
        router.replicas[3].inflight = 5
        assert router._pick(affinity_key=None) is router.replicas[2]
        assert router.affinity_requests_total == 0

    def test_least_inflight_byte_identical_to_pre_affinity_router(self):
        """Acceptance pin: the default policy reproduces the pre-PR
        algorithm choice-for-choice — min inflight, ties broken by a
        0-based round-robin counter over the tied list in replica order —
        across a scripted sequence of loads, exclusions, and health flips.
        """
        import itertools

        router = _router(policy="least-inflight")
        legacy_rr = itertools.count()

        def legacy_pick(replicas, exclude=None, include_unhealthy=False):
            healthy = [r for r in replicas
                       if (r.healthy or include_unhealthy)
                       and (not exclude or r.url not in exclude)]
            if not healthy:
                return None
            least = min(r.inflight for r in healthy)
            tied = [r for r in healthy if r.inflight == least]
            return tied[next(legacy_rr) % len(tied)]

        script = [
            dict(loads=[0, 0, 0, 0]),
            dict(loads=[0, 0, 0, 0]),
            dict(loads=[2, 0, 1, 0]),
            dict(loads=[2, 0, 1, 0], exclude={URLS[1]}),
            dict(loads=[1, 1, 1, 1], unhealthy={URLS[0]}),
            dict(loads=[3, 3, 3, 3], unhealthy={URLS[0]},
                 include_unhealthy=True),
            dict(loads=[0, 5, 0, 5]),
            dict(loads=[0, 5, 0, 5]),
            dict(loads=[0, 5, 0, 5], exclude={URLS[0], URLS[2]}),
        ]
        for step in script:
            for r, load in zip(router.replicas, step["loads"]):
                r.inflight = load
                r.healthy = r.url not in step.get("unhealthy", ())
            expect = legacy_pick(router.replicas,
                                 exclude=step.get("exclude"),
                                 include_unhealthy=step.get(
                                     "include_unhealthy", False))
            got = router._pick(exclude=step.get("exclude"),
                               include_unhealthy=step.get(
                                   "include_unhealthy", False))
            assert got is expect, step

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="routing_policy"):
            Router(URLS, routing_policy="round-robin")
        with pytest.raises(ValueError, match="balance_factor"):
            Router(URLS, routing_policy="prefix-affinity",
                   balance_factor=0.5)


class TestAffinityKey:
    def test_session_id_beats_user_beats_prompt(self):
        router = _router()
        body = (b'{"prompt": "abc", "user": "u1", "session_id": "s1"}')
        assert router._affinity_key(body) == \
            b"sticky:session_id:s1"
        body = b'{"prompt": "abc", "user": "u1"}'
        assert router._affinity_key(body) == \
            b"sticky:user:u1"

    def test_prompt_prefix_windows(self):
        router = _router(affinity_prefix_len=4)
        # token-array prompt: first N ids
        assert router._affinity_key(
            b'{"prompt": [5, 6, 7, 8, 9, 10]}') == b"tokens:5,6,7,8"
        # text prompt: first 4*N utf-8 bytes
        key = router._affinity_key(b'{"prompt": "abcdefghijklmnopqrstuvwx"}')
        assert key == b"text:abcdefghijklmnop"
        # chat: serialized messages prefix (shared system prompts collide
        # into the same key, unrelated sessions with different prompts
        # diverge once past the boilerplate)
        k1 = router._affinity_key(
            b'{"messages": [{"role": "user", "content": "hi"}]}')
        assert k1 is not None and k1.startswith(b"chat:")

    def test_unparseable_or_keyless_bodies_yield_none(self):
        router = _router()
        assert router._affinity_key(b"") is None
        assert router._affinity_key(b"not json") is None
        assert router._affinity_key(b'[1, 2]') is None
        assert router._affinity_key(b'{"n": 1}') is None
        # bool session_id is not a usable scalar key
        assert router._affinity_key(b'{"session_id": true, "n": 1}') is None

    def test_least_inflight_policy_never_peeks(self):
        router = _router(policy="least-inflight")
        assert router._affinity_key(b'{"session_id": "s"}') is None


# ---------------------------------------------------------------------------
# aiohttp-level: streaming stickiness, churn remap, metrics aggregation
# ---------------------------------------------------------------------------

async def _recording_replica(extra_metrics=""):
    """A stand-in engine replica that records served completion requests
    (body + forwarded ``x-kgct-request-id``) and streams an SSE body (so
    stickiness is proven on the STREAMING proxy path — the body-peek must
    not break passthrough). Its ``/debug/trace`` mimics a real
    api_server: a lifecycle span per served request id, exported through
    the real RequestTracer — what the router's merged fleet trace
    fetches."""
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.observability.trace import RequestTracer
    from kubernetes_gpu_cluster_tpu.serving.errors import REQUEST_ID_HEADER

    served = []
    tracer = RequestTracer()

    async def health(request):
        return aioweb.json_response({"status": "ok"})

    async def metrics(request):
        return aioweb.Response(
            text="# TYPE kgct_requests_total counter\n"
                 f"kgct_requests_total {len(served)}\n" + extra_metrics,
            content_type="text/plain")

    async def completions(request):
        rid = request.headers.get(REQUEST_ID_HEADER, "")
        served.append({"body": await request.json(), "request_id": rid,
                       "headers": {k.lower(): v
                                   for k, v in request.headers.items()}})
        if rid:
            tracer.emit("arrival", rid, prompt_tokens=1)
            tracer.emit("first_token", rid, ttft_ms=1.0)
            tracer.emit("finish", rid, outcome="finished")
        resp = aioweb.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        await resp.write(b'data: {"text": "tok"}\n\n')
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    async def debug_trace(request):
        return aioweb.json_response(tracer.export_perfetto())

    app = aioweb.Application()
    app.router.add_get("/health", health)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/trace", debug_trace)
    app.router.add_post("/v1/completions", completions)
    runner = aioweb.AppRunner(app)
    await runner.setup()
    site = aioweb.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, f"http://127.0.0.1:{runner.addresses[0][1]}", served


async def _start_router(router):
    from aiohttp.test_utils import TestClient, TestServer

    client = TestClient(TestServer(router.build_app()))
    await client.start_server()
    return client


class TestStreamedStickiness:
    def test_session_requests_stream_through_one_replica(self):
        async def scenario():
            a_runner, a_url, a_served = await _recording_replica()
            b_runner, b_url, b_served = await _recording_replica()
            router = Router([a_url, b_url], health_interval_s=9999,
                            routing_policy="prefix-affinity")
            client = await _start_router(router)
            try:
                for i in range(4):
                    r = await client.post(
                        "/v1/completions",
                        json={"prompt": f"turn {i} of this conversation",
                              "session_id": "conv-42", "stream": True})
                    assert r.status == 200
                    body = await r.read()
                    assert b"[DONE]" in body      # stream passed through
                # All four landed on ONE replica (whichever owns the key).
                assert sorted([len(a_served), len(b_served)]) == [0, 4]
                assert router.affinity_hits_total == 4
                # A different session may land elsewhere, but is also
                # sticky to wherever it lands.
                for i in range(2):
                    await client.post(
                        "/v1/completions",
                        json={"prompt": "x", "session_id": "conv-43"})
                assert (len(a_served), len(b_served)) in (
                    (6, 0), (0, 6), (4, 2), (2, 4))
            finally:
                await client.close()
                await a_runner.cleanup()
                await b_runner.cleanup()
        asyncio.run(scenario())


@pytest.mark.chaos
class TestReplicaDownRemap:
    def test_downed_replica_keys_remap_then_return(self):
        """KGCT_FAULT replica_down: the health probe of the ring owner is
        forced down; its keys deterministically remap to the ring
        successor; clearing the fault restores the owner and the keys come
        home. The drain/429 machinery is untouched (other keys never
        move)."""
        async def scenario():
            a_runner, a_url, _ = await _recording_replica()
            b_runner, b_url, _ = await _recording_replica()
            router = Router([a_url, b_url], health_interval_s=9999,
                            routing_policy="prefix-affinity")
            client = await _start_router(router)
            try:
                key = b"sticky:session_id:chaos"
                owner_url = router.ring.owner(key)
                own_idx = [r.url for r in router.replicas].index(owner_url)
                other_url = [u for u in (a_url, b_url) if u != owner_url][0]
                other_key = next(
                    k for k in (f"probe-{i}".encode() for i in range(64))
                    if router.ring.owner(k) == other_url)
                assert router._pick(affinity_key=key).url == owner_url

                configure_faults(f"replica_down:value={own_idx}")
                for r in router.replicas:
                    await router._check(r, startup=True)
                assert not router.replicas[own_idx].healthy
                # Owned keys remap to the survivor...
                assert router._pick(affinity_key=key).url == other_url
                assert router.ring_remaps_total == 1
                # ...other keys never move (only K/N remap on churn).
                assert router._pick(affinity_key=other_key).url == other_url

                configure_faults(None)
                for r in router.replicas:
                    await router._check(r)
                assert router.replicas[own_idx].healthy
                assert router._pick(affinity_key=key).url == owner_url

                # The fire budget is consumed ONLY by the targeted
                # replica's probes: with times=1, probing every OTHER
                # replica first must not burn the single fire.
                router.replicas[own_idx].benched_until = 0.0
                configure_faults(f"replica_down:value={own_idx},times=1")
                for r in router.replicas:
                    if r is not router.replicas[own_idx]:
                        await router._check(r)
                assert all(r.healthy for r in router.replicas)
                await router._check(router.replicas[own_idx], startup=True)
                assert not router.replicas[own_idx].healthy
            finally:
                await client.close()
                await a_runner.cleanup()
                await b_runner.cleanup()
        asyncio.run(scenario())


class TestRouterMetricsAggregation:
    def test_replica_locality_gauges_zero_and_absent_safe(self):
        """The router folds each replica's scraped prefix-cache hit ratio
        and swapped count into router-owned labeled gauges. A replica
        whose engine predates the series (or was skipped) still gets a 0.0
        sample — a fresh scrape is nan-free and needs no existence check —
        and the affinity counters render zeros on a fresh least-inflight
        router."""
        async def scenario():
            a_runner, a_url, _ = await _recording_replica(
                extra_metrics=(
                    "# TYPE kgct_prefix_cache_hit_ratio gauge\n"
                    "kgct_prefix_cache_hit_ratio 0.75\n"
                    "# TYPE kgct_num_swapped gauge\n"
                    "kgct_num_swapped 2\n"))
            b_runner, b_url, _ = await _recording_replica()  # no series
            router = Router([a_url, b_url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                r = await client.get("/metrics")
                text = await r.text()
                _assert_valid_exposition(text)

                def val(name, url):
                    # Per-replica gauges may carry a role label after the
                    # replica label (disaggregated pools).
                    [line] = [l for l in text.splitlines()
                              if l.startswith(f'{name}{{replica="{url}"')]
                    return float(line.rpartition(" ")[2])

                assert val("kgct_router_replica_prefix_cache_hit_ratio",
                           a_url) == 0.75
                assert val("kgct_router_replica_prefix_cache_hit_ratio",
                           b_url) == 0.0
                assert val("kgct_router_replica_num_swapped", a_url) == 2.0
                assert val("kgct_router_replica_num_swapped", b_url) == 0.0
                # Pool-role label: a non-disaggregated router labels every
                # replica gauge role="both" (the pre-disaggregation
                # behavior, one spelling fleet-wide).
                assert (f'kgct_router_replica_healthy{{replica="{a_url}",'
                        'role="both"} 1') in text
                # Affinity accounting: present and zero-safe even on the
                # default policy with zero affinity-keyed traffic.
                assert "kgct_router_affinity_hit_ratio 0.0" in text
                assert "kgct_router_ring_remaps_total 0" in text
                assert val("kgct_router_affinity_overflow_total",
                           a_url) == 0.0
                assert ('kgct_router_policy{policy="least-inflight"} 1'
                        in text)
                # Fleet-trace scrape accounting: present and zero on a
                # fresh router.
                assert "kgct_router_trace_scrape_errors_total 0" in text
            finally:
                await client.close()
                await a_runner.cleanup()
                await b_runner.cleanup()
        asyncio.run(scenario())


class TestRouterRequestId:
    def test_id_minted_forwarded_and_echoed(self):
        """The correlation-id contract (satellite 1): every router response
        carries x-kgct-request-id — minted when absent, honored when the
        inbound header is valid — and the SAME id is forwarded upstream so
        the replica can adopt it as its engine request id."""
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)

        async def scenario():
            a_runner, a_url, a_served = await _recording_replica()
            router = Router([a_url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                # Minted: no inbound header.
                r = await client.post("/v1/completions",
                                      json={"prompt": "x"})
                assert r.status == 200
                minted = r.headers[REQUEST_ID_HEADER]
                assert minted.startswith("req-")
                assert a_served[0]["request_id"] == minted   # forwarded
                # Honored: a valid inbound id passes through end-to-end.
                r2 = await client.post(
                    "/v1/completions", json={"prompt": "y"},
                    headers={REQUEST_ID_HEADER: "req-client-42"})
                assert r2.headers[REQUEST_ID_HEADER] == "req-client-42"
                assert a_served[1]["request_id"] == "req-client-42"
                # Invalid inbound (spaces) is replaced by a fresh mint.
                r3 = await client.post(
                    "/v1/completions", json={"prompt": "z"},
                    headers={REQUEST_ID_HEADER: "bad id"})
                assert r3.headers[REQUEST_ID_HEADER].startswith("req-")
            finally:
                await client.close()
                await a_runner.cleanup()
        asyncio.run(scenario())

    def test_error_responses_carry_id(self):
        """429/503-class rejections are exactly where correlation matters
        most (satellite 1's bugfix): a router with no healthy replicas
        still stamps the id on its 503."""
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)

        async def scenario():
            # Nothing listens on this port: the startup probe benches it.
            router = Router(["http://127.0.0.1:1"], health_interval_s=9999,
                            connect_retries=0)
            client = await _start_router(router)
            try:
                r = await client.post(
                    "/v1/completions", json={"prompt": "x"},
                    headers={REQUEST_ID_HEADER: "req-err-1"})
                assert r.status in (502, 503)
                assert r.headers[REQUEST_ID_HEADER] == "req-err-1"
                r2 = await client.post("/v1/completions",
                                       json={"prompt": "x"})
                assert r2.status in (502, 503)
                assert r2.headers[REQUEST_ID_HEADER].startswith("req-")
            finally:
                await client.close()
        asyncio.run(scenario())


class TestMergedFleetTrace:
    def test_debug_trace_merges_router_and_replica_spans(self):
        """The tentpole's single-download contract: GET /debug/trace on the
        router returns ONE Perfetto doc with the router's spans (pid 1) and
        each replica's lifecycle spans (pid 2..N), correlated on the
        router-minted ids, with per-process name metadata."""
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            REQUEST_ID_HEADER)

        async def scenario():
            a_runner, a_url, _ = await _recording_replica()
            b_runner, b_url, _ = await _recording_replica()
            router = Router([a_url, b_url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                # One request pinned to each replica via least-inflight's
                # deterministic tie-break (inflight 0, seq 0 then 1).
                for rid in ("req-merge-a", "req-merge-b"):
                    r = await client.post(
                        "/v1/completions", json={"prompt": rid},
                        headers={REQUEST_ID_HEADER: rid})
                    assert r.status == 200
                r = await client.get("/debug/trace")
                assert r.status == 200
                doc = await r.json()
            finally:
                await client.close()
                await a_runner.cleanup()
                await b_runner.cleanup()

            evs = doc["traceEvents"]
            # Three processes, labeled: the router + both replicas.
            labels = {e["pid"]: e["args"]["name"] for e in evs
                      if e.get("name") == "process_name"}
            assert labels[1] == "kgct-router"
            assert {f"kgct-engine {a_url}", f"kgct-engine {b_url}"} == {
                labels[2], labels[3]}
            # Router spans AND replica spans share the minted ids.
            by_pid = {}
            for e in evs:
                if e.get("cat") == "request" and e.get("id"):
                    by_pid.setdefault(e["pid"], set()).add(e["id"])
            assert by_pid[1] == {"req-merge-a", "req-merge-b"}
            assert by_pid[2] | by_pid[3] == {"req-merge-a", "req-merge-b"}
            # The router's per-request instants carry pick attribution.
            picks = [e for e in evs if e.get("name") == "pick"]
            assert picks and all(e["pid"] == 1 for e in picks)
            assert {e["args"]["replica"] for e in picks} == {a_url, b_url}
            # Timestamps rebased onto one timeline: all non-meta ts >= 0.
            assert all(e["ts"] >= 0 for e in evs if "ts" in e)
            import json as _json
            _json.dumps(doc)               # wire-serializable

    def test_replica_without_trace_endpoint_is_skipped_and_counted(self):
        """A replica whose /debug/trace is missing (predates the feature)
        or stalls must not break the fleet download: it is skipped and
        counted, and the router's own spans still export."""
        from aiohttp import web as aioweb

        async def scenario():
            # Minimal replica: health only — /debug/trace 404s.
            async def health(request):
                return aioweb.json_response({"status": "ok"})

            app = aioweb.Application()
            app.router.add_get("/health", health)
            runner = aioweb.AppRunner(app)
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            url = f"http://127.0.0.1:{runner.addresses[0][1]}"
            router = Router([url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                r = await client.get("/debug/trace")
                assert r.status == 200
                doc = await r.json()
                assert router.trace_scrape_errors_total == 1
                labels = [e["args"]["name"] for e in doc["traceEvents"]
                          if e.get("name") == "process_name"]
                assert labels == ["kgct-router"]
            finally:
                await client.close()
                await runner.cleanup()
        asyncio.run(scenario())

    def test_flightrecorder_endpoint_exports_spans_and_snapshots(self):
        async def scenario():
            a_runner, a_url, _ = await _recording_replica()
            router = Router([a_url], health_interval_s=9999)
            client = await _start_router(router)
            try:
                await client.post("/v1/completions", json={"prompt": "x"})
                router.flight.maybe_snapshot()   # the health loop's call
                r = await client.get("/debug/flightrecorder")
                assert r.status == 200
                doc = await r.json()
            finally:
                await client.close()
                await a_runner.cleanup()
            kinds = {e["kind"] for e in doc["events"]}
            assert {"arrival", "pick", "finish", "snapshot"} <= kinds
            snap = next(e for e in doc["events"] if e["kind"] == "snapshot")
            assert a_url in snap["inflight"]
            assert snap["healthy"] == [a_url]
        asyncio.run(scenario())


@pytest.mark.slow
@pytest.mark.chaos
class TestRemapContractSoak:
    """Live CHWBL remap-contract soak (ROADMAP 7a, software half): under
    ``replica_down`` churn across several cycles, ONLY the downed
    replica's ~K/N affinity keys remap (each to a deterministic ring
    successor) and every owner returns home on recovery — the contract
    that makes drain migration and failover land where the parked KV
    lives. Engine-free: stub replicas, real router probes + pick seam."""

    def test_churn_cycles_remap_only_owned_keys_and_recover(self):
        N, K, CYCLES = 6, 96, 4

        async def scenario():
            runners, urls = [], []
            for _ in range(N):
                runner, url, _ = await _recording_replica()
                runners.append(runner)
                urls.append(url)
            router = Router(urls, health_interval_s=9999,
                            routing_policy="prefix-affinity")
            client = await _start_router(router)
            keys = [f"soak-session-{i}".encode() for i in range(K)]
            try:
                def owners():
                    return {k: router._pick(affinity_key=k).url
                            for k in keys}

                baseline = owners()
                by_owner: dict = {}
                for k, u in baseline.items():
                    by_owner.setdefault(u, []).append(k)
                # CHWBL spreads the keys: every replica owns some, nobody
                # owns a constant factor more than fair share (the load
                # bound, not vnode luck, is what bounds skew — but vnode
                # placement must not be degenerate either).
                assert len(by_owner) == N
                assert max(len(v) for v in by_owner.values()) <= 3 * K // N

                for cycle in range(CYCLES):
                    down = cycle % N
                    down_url = urls[down]
                    configure_faults(f"replica_down:value={down}")
                    for r in router.replicas:
                        await router._check(r, startup=True)
                    assert not router.replicas[down].healthy
                    churned = owners()
                    moved = {k for k in keys
                             if churned[k] != baseline[k]}
                    # The remap contract: exactly the downed replica's
                    # keys move — ~K/N, never a full reshuffle — and each
                    # lands on ITS key's ring successor (where a drain
                    # push / failover re-dispatch would look for it).
                    assert moved == set(by_owner[down_url]), \
                        f"cycle {cycle}: non-owned keys remapped"
                    assert 0 < len(moved) <= 3 * K // N
                    for k in moved:
                        want = next(
                            u for u in router.ring.walk(k)
                            if u != down_url)
                        assert churned[k] == want
                    # Recovery: the owner returns, every key comes home.
                    configure_faults(None)
                    router.replicas[down].benched_until = 0.0
                    for r in router.replicas:
                        await router._check(r)
                    assert router.replicas[down].healthy
                    assert owners() == baseline, \
                        f"cycle {cycle}: owners did not return on recovery"
            finally:
                configure_faults(None)
                await client.close()
                for runner in runners:
                    await runner.cleanup()
        asyncio.run(scenario())


class TestDisaggRouting:
    """Disaggregated prefill/decode at the ROUTER layer (engine-free):
    prefill-pool picks flow through the one _pick seam on a dedicated
    ring, the forwarded header names the picked prefill replica (and
    client-supplied values are stripped), and one scrape separates the
    pools by role."""

    PF_URLS = [f"http://prefill-{i}:8000" for i in range(2)]

    def test_prefill_pick_is_prefix_affine_even_under_least_inflight(self):
        router = Router(list(URLS), routing_policy="least-inflight",
                        prefill_urls=list(self.PF_URLS))
        key = b"text:some prompt prefix"
        owner = router.prefill_ring.owner(key)
        for _ in range(5):
            picked = router._pick(affinity_key=key,
                                  pool=router.prefill_replicas,
                                  ring=router.prefill_ring)
            assert picked.url == owner
        # Prefill-pool picks never pollute the MAIN pool's affinity
        # accounting.
        assert router.affinity_requests_total == 0
        # Dead owner: keys remap to the ring successor, deterministic.
        dead = next(r for r in router.prefill_replicas if r.url == owner)
        dead.healthy = False
        picked = router._pick(affinity_key=key,
                              pool=router.prefill_replicas,
                              ring=router.prefill_ring)
        assert picked.url == next(u for u in router.prefill_ring.walk(key)
                                  if u != owner)
        assert router.ring_remaps_total == 0   # main-pool counter untouched

    def test_prefill_pool_bounded_load_spills_off_a_hot_owner(self):
        """A prefill replica holding outstanding pull slots overflows the
        CHWBL bound to the ring successor — live only because proxy()
        accounts the pull slot on the picked replica (at permanent
        inflight 0 the bound is never exceeded and a hot prefix would pin
        100% of handoffs to one replica)."""
        router = Router(list(URLS), routing_policy="least-inflight",
                        prefill_urls=list(self.PF_URLS))
        key = b"text:some prompt prefix"
        owner_url = router.prefill_ring.owner(key)
        owner = next(r for r in router.prefill_replicas
                     if r.url == owner_url)
        owner.inflight = 10            # outstanding handoff pull slots
        picked = router._pick(affinity_key=key,
                              pool=router.prefill_replicas,
                              ring=router.prefill_ring)
        assert picked.url != owner_url
        assert router._pick_info["pick"] == "affinity_overflow"

    def test_proxy_accounts_the_prefill_pull_slot(self):
        """proxy() holds one inflight slot on the picked prefill replica
        for the request's lifetime and always drains it."""
        async def scenario():
            pf_runner, pf_url, _ = await _recording_replica()
            dc_runner, dc_url, _ = await _recording_replica()
            router = Router([dc_url], health_interval_s=9999,
                            prefill_urls=[pf_url])
            client = await _start_router(router)
            pf = router.prefill_replicas[0]
            seen = []
            orig = router._session.request

            def spy(method, url, **kw):
                seen.append(pf.inflight)
                return orig(method, url, **kw)

            router._session.request = spy
            try:
                r = await client.post("/v1/completions",
                                      json={"prompt": "x"})
                assert r.status == 200
                assert seen[-1] == 1   # held while forwarding downstream
                await r.read()         # drain the relay to its finally
                await asyncio.sleep(0.05)
                assert pf.inflight == 0            # drained at completion
            finally:
                await client.close()
                await pf_runner.cleanup()
                await dc_runner.cleanup()
        asyncio.run(scenario())

    def test_header_forwarded_and_client_value_stripped(self):
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            PREFILL_URL_HEADER)

        async def scenario():
            pf_runner, pf_url, _ = await _recording_replica()
            dc_runner, dc_url, dc_served = await _recording_replica()

            # Capture the headers the decode replica actually receives.
            router = Router([dc_url], health_interval_s=9999,
                            prefill_urls=[pf_url])
            client = await _start_router(router)
            seen = []
            orig = router._session.request

            def spy(method, url, **kw):
                seen.append(kw.get("headers") or {})
                return orig(method, url, **kw)

            router._session.request = spy
            try:
                r = await client.post(
                    "/v1/completions", json={"prompt": "x"},
                    headers={PREFILL_URL_HEADER: "http://evil:1"})
                assert r.status == 200
                fwd = seen[-1]
                assert fwd[PREFILL_URL_HEADER] == pf_url
                # /v1/models (no body/prompt) never carries the header.
                r = await client.get("/v1/models")
                assert PREFILL_URL_HEADER not in (seen[-1] or {})
                # The pick span carries the pool attribution.
                picks = [e for e in router.tracer.events()
                         if e.kind == "pick"
                         and e.args.get("pool") == "prefill"]
                assert picks and picks[0].args["replica"] == pf_url
            finally:
                await client.close()
                await pf_runner.cleanup()
                await dc_runner.cleanup()
        asyncio.run(scenario())

    def test_metrics_and_health_separate_pools_by_role(self):
        async def scenario():
            pf_runner, pf_url, _ = await _recording_replica()
            dc_runner, dc_url, _ = await _recording_replica()
            router = Router([dc_url], health_interval_s=9999,
                            prefill_urls=[pf_url])
            client = await _start_router(router)
            try:
                r = await client.get("/metrics")
                text = await r.text()
                _assert_valid_exposition(text)
                assert (f'kgct_router_replica_healthy{{replica="{dc_url}",'
                        'role="decode"} 1') in text
                assert (f'kgct_router_replica_healthy{{replica="{pf_url}",'
                        'role="prefill"} 1') in text
                # Locality gauges cover BOTH pools, zero-safe.
                assert (f'kgct_router_replica_prefix_cache_hit_ratio'
                        f'{{replica="{pf_url}",role="prefill"}} 0.0') \
                    in text
                r = await client.get("/health")
                body = await r.json()
                assert body["replicas"][pf_url]["role"] == "prefill"
                assert body["replicas"][dc_url]["role"] == "decode"
            finally:
                await client.close()
                await pf_runner.cleanup()
                await dc_runner.cleanup()
        asyncio.run(scenario())

    def test_no_healthy_prefill_pool_degrades_to_no_header(self):
        from kubernetes_gpu_cluster_tpu.serving.errors import (
            PREFILL_URL_HEADER)

        async def scenario():
            dc_runner, dc_url, dc_served = await _recording_replica()
            # Nothing listens on the prefill URL: the startup probe
            # benches it; completions must still flow, headerless.
            router = Router([dc_url], health_interval_s=9999,
                            prefill_urls=["http://127.0.0.1:1"])
            client = await _start_router(router)
            seen = []
            orig = router._session.request

            def spy(method, url, **kw):
                seen.append(kw.get("headers") or {})
                return orig(method, url, **kw)

            router._session.request = spy
            try:
                r = await client.post("/v1/completions",
                                      json={"prompt": "x"})
                assert r.status == 200
                assert PREFILL_URL_HEADER not in seen[-1]
            finally:
                await client.close()
                await dc_runner.cleanup()
        asyncio.run(scenario())

    def test_multi_sequence_requests_skip_the_prefill_pick(self):
        """n/best_of > 1 requests fan out through the replica's _run_n
        BEFORE its handoff block — a prefill pick would hold a phantom
        pull slot forever. Only positively multi-sequence bodies skip;
        everything else (absent, n=1, unparseable) stays eligible."""
        def ok(body):
            return Router._handoff_eligible(Router._parse_json_dict(body))
        assert not ok(b'{"prompt": "x", "n": 2}')
        assert not ok(b'{"prompt": "x", "best_of": 3}')
        assert ok(b'{"prompt": "x"}')
        assert ok(b'{"prompt": "x", "n": 1}')
        assert ok(b'{"prompt": "x", "n": 1, "best_of": 1}')
        assert ok(b'{"prompt": "x", "n": "zzz"}')   # replica's 400 to give
        assert ok(b'not json at all')
        assert ok(b'[1, 2, 3]')

    def test_flight_snapshot_covers_both_pools(self):
        """Flight-recorder fleet snapshots report inflight/health for the
        prefill pool too, not just the main pool."""
        router = Router(list(URLS), routing_policy="least-inflight",
                        prefill_urls=list(self.PF_URLS))
        router.prefill_replicas[0].inflight = 3
        router.prefill_replicas[1].healthy = False
        snap = router._flight_snapshot()
        for url in (*URLS, *self.PF_URLS):
            assert url in snap["inflight"]
        assert snap["inflight"][self.PF_URLS[0]] == 3
        assert self.PF_URLS[0] in snap["healthy"]
        assert self.PF_URLS[1] not in snap["healthy"]


class TestTierAwarePicks:
    """ROADMAP 3c: interactive-tier picks deprioritize batch-saturated
    replicas using the per-tier /health inflight ledger the health probe
    already scrapes — engine-free, all inside the one _pick seam."""

    def _router(self, n=3):
        from kubernetes_gpu_cluster_tpu.config.qos import parse_qos_tiers
        return _router(policy="least-inflight",
                       urls=[f"http://r{i}:8000" for i in range(n)],
                       qos_tiers=parse_qos_tiers("default"))

    def test_interactive_pick_avoids_batch_saturated_replica(self):
        router = self._router()
        router.replicas[0].tier_inflight = {"interactive": 0, "batch": 5}
        # Total inflight ties at 0 everywhere: the interactive pick must
        # rotate over the two batch-free replicas only.
        urls = {router._pick(pick_tier="interactive").url
                for _ in range(6)}
        assert urls == {"http://r1:8000", "http://r2:8000"}
        assert router._pick_info.get("tier_deprioritized") == 1

    def test_batch_pick_keeps_legacy_rotation(self):
        """A lowest-tier pick has no lower tier to avoid: the legacy
        round-robin covers every replica, batch-saturated included."""
        router = self._router()
        router.replicas[0].tier_inflight = {"batch": 5}
        urls = {router._pick(pick_tier="batch").url for _ in range(3)}
        assert urls == {r.url for r in router.replicas}

    def test_tier_none_byte_identical_rotation(self):
        """QoS-off picks (tier None) ignore the ledger entirely — the
        pre-existing least-inflight behavior, ledger or not."""
        router = self._router()
        router.replicas[0].tier_inflight = {"batch": 99}
        urls = {router._pick().url for _ in range(3)}
        assert urls == {r.url for r in router.replicas}

    def test_total_inflight_stays_primary(self):
        """The tie-break is SECONDARY: a genuinely less-loaded replica
        wins even when its ledger shows batch work (that work is
        engine-preemptible for the interactive request; an extra live
        stream is not)."""
        router = self._router()
        router.replicas[0].tier_inflight = {"batch": 9}
        router.replicas[1].inflight = 1
        router.replicas[2].inflight = 1
        assert router._pick(pick_tier="interactive").url == "http://r0:8000"

    def test_health_probe_scrapes_the_ledger(self):
        """The /health probe body's qos_tiers dict lands on the Replica —
        no extra request, best-effort on replicas without the field."""
        import aiohttp

        async def run():
            from kubernetes_gpu_cluster_tpu.config.qos import parse_qos_tiers
            from aiohttp import web as aioweb

            async def health(request):
                return aioweb.json_response(
                    {"status": "ok", "qos_tiers": {"batch": 7,
                                                   "interactive": 1}})
            app = aioweb.Application()
            app.router.add_get("/health", health)
            runner = aioweb.AppRunner(app)
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            url = f"http://127.0.0.1:{runner.addresses[0][1]}"
            router = Router([url], health_interval_s=9999,
                            qos_tiers=parse_qos_tiers("default"))
            router._session = aiohttp.ClientSession()
            try:
                await router._check(router.replicas[0], startup=True)
                assert router.replicas[0].tier_inflight == {
                    "batch": 7, "interactive": 1}
            finally:
                await router._session.close()
                await runner.cleanup()
        asyncio.run(run())


class TestPrefixSourceHint:
    """Fleet-wide prefix cache, router half: an overflow/remap pick names
    the ring owner in x-kgct-prefix-source so the chosen replica can pull
    the owner's cached prefix — engine-free pins of the hint derivation
    plus one proxied header-forwarding check."""

    def _owner_and_other(self, router, key):
        owner_url = router.ring.owner(key)
        owner = next(r for r in router.replicas if r.url == owner_url)
        other = next(r for r in router.replicas if r.url != owner_url)
        return owner, other

    def test_overflow_pick_names_the_owner(self):
        router = _router(urls=URLS[:2], balance_factor=1.0)
        key = b"hot-prefix"
        owner, other = self._owner_and_other(router, key)
        owner.inflight = 5                      # over the CHWBL bound
        picked = router._pick(affinity_key=key)
        assert picked.url == other.url
        assert router._pick_info["pick"] == "affinity_overflow"
        assert router._prefix_source(dict(router._pick_info),
                                     picked.url) == owner.url

    def test_affinity_hit_carries_no_hint(self):
        router = _router(urls=URLS[:2])
        key = b"cold-prefix"
        picked = router._pick(affinity_key=key)
        assert router._pick_info["pick"] == "affinity_hit"
        assert router._prefix_source(dict(router._pick_info),
                                     picked.url) is None

    def test_downed_owner_is_not_named(self):
        """A remap whose owner is DOWN must not be named: the pull would
        burn a doomed connect before degrading — worse than recomputing."""
        router = _router(urls=URLS[:2])
        key = b"hot-prefix-2"
        owner, other = self._owner_and_other(router, key)
        owner.healthy = False
        picked = router._pick(affinity_key=key)
        assert picked.url == other.url
        assert router._pick_info["pick"] == "affinity_remap"
        assert router._prefix_source(dict(router._pick_info),
                                     picked.url) is None

    def test_excluded_healthy_owner_is_named(self):
        """A remap because the owner was EXCLUDED (this request's retry
        walk) still names it: the owner is alive and its cache is warm."""
        router = _router(urls=URLS[:2])
        key = b"hot-prefix-3"
        owner, other = self._owner_and_other(router, key)
        picked = router._pick(affinity_key=key, exclude={owner.url})
        assert picked.url == other.url
        assert router._pick_info["pick"] == "affinity_remap"
        assert router._prefix_source(dict(router._pick_info),
                                     picked.url) == owner.url

    def test_overflowed_pick_forwards_the_hint_upstream(self):
        """Through the real proxy: the over-bound owner's url rides
        x-kgct-prefix-source to the chosen replica, and a client-supplied
        value is stripped (router-owned header)."""
        async def scenario():
            a_runner, a_url, a_served = await _recording_replica()
            b_runner, b_url, b_served = await _recording_replica()
            router = Router([a_url, b_url], health_interval_s=9999,
                            routing_policy="prefix-affinity",
                            balance_factor=1.0)
            client = await _start_router(router)
            try:
                from kubernetes_gpu_cluster_tpu.serving.errors import \
                    PREFIX_SOURCE_HEADER
                body = {"prompt": "shared prefix body", "stream": False}
                r = await client.post(
                    "/v1/completions", json=body,
                    headers={PREFIX_SOURCE_HEADER: "http://evil:1"})
                assert r.status == 200
                served = (a_served + b_served)[-1]
                # Affinity hit: no hint, and the client's value is gone.
                assert PREFIX_SOURCE_HEADER not in served["headers"]
                # Saturate the owner so the next pick overflows.
                owner_url = router.ring.owner(
                    router._affinity_key_from_obj(body))
                owner = next(rep for rep in router.replicas
                             if rep.url == owner_url)
                other_served = b_served if owner_url == a_url else a_served
                owner.inflight = 5
                r = await client.post("/v1/completions", json=body)
                assert r.status == 200
                served = other_served[-1]      # the overflow target
                assert served["headers"].get(
                    PREFIX_SOURCE_HEADER) == owner_url
            finally:
                await client.close()
                await a_runner.cleanup()
                await b_runner.cleanup()
        asyncio.run(scenario())
