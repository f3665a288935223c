"""Fleet-wide KV reuse: the global prefix cache over the handoff substrate.

Tier-1 keeps the CHEAP pins: one module-scoped debug-tiny engine PAIR
proves the acceptance contract — a prefix pulled from a peer's cache and
streamed into the local cache yields BYTE-IDENTICAL output to recomputing
it (greedy AND seeded) — plus engine-free codec/policy/queue pins and ONE
two-server HTTP scenario (pull ok / roofline skip / allowlist /
kv_pull_fail chaos) on the same tiny engines. The router's control plane
under churn is tests/test_fleet_soak.py's.
"""

import asyncio

import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.resilience.faults import configure_faults
from kubernetes_gpu_cluster_tpu.serving.fleet_cache import (
    PullPolicy, SpillQueue, build_pull_policy, kv_bytes_per_token,
    prefill_flops_per_token)
from kubernetes_gpu_cluster_tpu.serving.handoff import (
    PrefixStreamDecoder, decode_spill_frame, encode_prefix_frames,
    encode_spill_frame)


@pytest.fixture(autouse=True)
def _clean_faults():
    configure_faults(None)
    yield
    configure_faults(None)


def _engine_config(swap_gb: float = 0.0):
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=16, num_pages=96,
                          swap_space_gb=swap_gb),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=128,
                                  decode_buckets=(1, 2),
                                  prefill_buckets=(32, 64, 128),
                                  decode_window=4, mixed_batch_enabled=False,
                                  enable_prefix_caching=True))


@pytest.fixture(scope="module")
def engines():
    """(owner, importer): identical weights by construction (same seed).
    The importer carries a host tier so the remote-spill rung is
    exercisable on the same pair."""
    return LLMEngine(_engine_config()), LLMEngine(_engine_config(0.001))


PROMPT = np.random.default_rng(3).integers(1, 500, 80).tolist()


def _stream_import(dst: LLMEngine, state: dict, chunk_pages: int = 2) -> int:
    """Wire round-trip + streamed import: encode the export as the actual
    prefix frames, feed them through the incremental decoder, and scatter
    each chunk through the begin/chunk/commit seam."""
    dec = PrefixStreamDecoder()
    handle = None
    for part in encode_prefix_frames(state, chunk_pages=chunk_pages):
        chunks = dec.feed(bytes(part))
        if handle is None and dec.header is not None:
            handle = dst.begin_prefix_import(dict(dec.header))
        for ck, cv in chunks:
            dst.import_prefix_chunk(handle, ck, cv)
    assert dec.done
    return dst.commit_prefix_import(handle)


class TestPullPolicy:
    """Engine-free pins of the anti-thrash roofline gate."""

    def _policy(self, link=1e9, flops=1e9, kvb=1000.0, fpt=1000.0, mn=16):
        return PullPolicy(link_bytes_per_s=link, flops_per_s=flops,
                          kv_bytes_per_token=kvb, flops_per_token=fpt,
                          min_tokens=mn)

    def test_fast_link_slow_compute_pulls(self):
        # transfer: 1 KB/tok over 1 GB/s = 1 us/tok; recompute: 1 kFLOP
        # over 1 MFLOP/s = 1 ms/tok -> pull wins.
        p = self._policy(link=1e9, flops=1e6)
        assert p.pull_beats_recompute(64)

    def test_slow_link_fast_compute_skips(self):
        # transfer: 1 KB/tok over 1 KB/s = 1 s/tok; recompute: 1 kFLOP
        # over 1 GFLOP/s = 1 us/tok -> the gate refuses the pull.
        p = self._policy(link=1e3, flops=1e9)
        assert not p.pull_beats_recompute(64)

    def test_sub_page_matches_never_pull(self):
        p = self._policy(link=1e12, flops=1.0, mn=16)
        assert not p.pull_beats_recompute(15)
        assert p.pull_beats_recompute(16)

    def test_build_policy_mirrors_roofline_accounting(self):
        mcfg = get_model_config("debug-tiny")
        pol = build_pull_policy(mcfg, page_size=16, itemsize=4,
                                backend="cpu")
        assert pol.kv_bytes_per_token == kv_bytes_per_token(mcfg, 4)
        assert pol.flops_per_token == prefill_flops_per_token(mcfg)
        assert pol.min_tokens == 16
        # The FLOPs model is prefill's matmul term: 2 FLOPs/MAC
        # over attention projections + MLP, every layer.
        h, inter = mcfg.hidden_size, mcfg.intermediate_size
        attn = (h * mcfg.num_heads * mcfg.head_dim
                + 2 * h * mcfg.num_kv_heads * mcfg.head_dim
                + mcfg.num_heads * mcfg.head_dim * h)
        assert pol.flops_per_token == 2 * mcfg.num_layers * (
            attn + 3 * h * inter)


class TestPrefixStreamCodec:
    """Engine-free pins of the streamed wire format (serving/handoff.py)."""

    def _state(self, n_pages=5, dtype="float32"):
        rng = np.random.default_rng(0)
        k = rng.standard_normal((2, n_pages, 16, 64)).astype(dtype)
        return {"model": "debug-tiny", "page_size": 16, "dtype": dtype,
                "matched_tokens": n_pages * 16,
                "prompt_token_ids": list(range(n_pages * 16)),
                "k": k, "v": k + 1}

    def test_roundtrip_across_dribbled_feeds(self):
        """Chunks must come out correct however the bytes are sliced on
        the wire — feed the frame one 1000-byte dribble at a time."""
        state = self._state()
        blob = b"".join(bytes(p) for p in
                        encode_prefix_frames(state, chunk_pages=2))
        dec = PrefixStreamDecoder()
        got = []
        for i in range(0, len(blob), 1000):
            got.extend(dec.feed(blob[i:i + 1000]))
        assert dec.done and dec.header["matched_tokens"] == 80
        k = np.concatenate([ck for ck, _ in got], axis=1)
        v = np.concatenate([cv for _, cv in got], axis=1)
        np.testing.assert_array_equal(k, state["k"])
        np.testing.assert_array_equal(v, state["v"])
        # chunk sizes: 2 + 2 + 1 (last chunk short)
        assert [ck.shape[1] for ck, _ in got] == [2, 2, 1]

    def test_corrupt_frames_rejected(self):
        blob = b"".join(bytes(p) for p in
                        encode_prefix_frames(self._state()))
        with pytest.raises(ValueError, match="magic"):
            PrefixStreamDecoder().feed(b"NOTAPF1!" + blob[8:])
        with pytest.raises(ValueError, match="trailing"):
            PrefixStreamDecoder().feed(blob + b"x")
        dec = PrefixStreamDecoder()
        dec.feed(blob[:-5])
        assert not dec.done      # truncated: never silently complete

    def test_spill_frame_roundtrip(self):
        rng = np.random.default_rng(1)
        k = rng.standard_normal((2, 1, 16, 64)).astype(np.float32)
        blob = encode_spill_frame("ab" * 16, k, k + 2, "debug-tiny", 16)
        digest, header, k2, v2 = decode_spill_frame(blob)
        assert digest == "ab" * 16
        assert header["model"] == "debug-tiny"
        np.testing.assert_array_equal(k2, k)
        np.testing.assert_array_equal(v2, k + 2)
        with pytest.raises(ValueError):
            decode_spill_frame(blob[:-3])


class TestSpillQueue:
    def test_bounded_drop_oldest(self):
        q = SpillQueue(cap=2)
        assert q.offer("a", None, None)
        assert q.offer("b", None, None)
        assert not q.offer("c", None, None)   # displaced the oldest
        assert q.dropped == 1
        assert q.pop()[0] == "b"
        assert q.pop()[0] == "c"
        assert q.pop() is None


class TestPulledPrefixByteIdentity:
    """The acceptance contract, engine-level: export from the owner's
    cache -> actual wire frames -> streamed import -> the importer's own
    admission reuses the pages — output byte-identical to recomputing."""

    def test_greedy_identical_and_cache_hit(self, engines):
        owner, importer = engines
        params = SamplingParams(max_tokens=8, temperature=0.0)
        ref = owner.generate([PROMPT], params)[0].output_token_ids
        hits0, misses0 = (owner.scheduler.prefix_cache.hits,
                          owner.scheduler.prefix_cache.misses)
        state = owner.export_prefix(PROMPT)
        # Serving a peer's fetch must not skew the owner's own locality
        # stats (the router's per-replica hit-ratio gauge reads them).
        assert (owner.scheduler.prefix_cache.hits,
                owner.scheduler.prefix_cache.misses) == (hits0, misses0)
        assert state["matched_tokens"] == 64      # 80 tokens, 16/page, <80
        tokens = _stream_import(importer, state)
        assert tokens == 64
        assert importer.prefix_peek(PROMPT) == 64
        hits_before = importer.scheduler.prefix_cache.hits
        got = importer.generate([PROMPT], params)[0].output_token_ids
        assert got == ref
        assert importer.scheduler.prefix_cache.hits == hits_before + 1

    def test_seeded_sampled_identical(self, engines):
        owner, importer = engines
        params = SamplingParams(max_tokens=8, temperature=0.9, top_k=30,
                                top_p=0.95, seed=17)
        ref = owner.generate([PROMPT], params)[0].output_token_ids
        got = importer.generate([PROMPT], params)[0].output_token_ids
        assert got == ref

    def test_truncated_import_raises_and_frees(self, engines):
        owner, importer = engines
        state = owner.export_prefix(PROMPT)
        free0 = importer.scheduler.allocator.num_free
        handle = importer.begin_prefix_import(
            {k: v for k, v in state.items() if k not in ("k", "v")})
        importer.import_prefix_chunk(handle, state["k"][:, :2],
                                     state["v"][:, :2])
        with pytest.raises(ValueError, match="truncated"):
            importer.commit_prefix_import(handle)
        assert importer.scheduler.allocator.num_free == free0

    def test_abort_import_frees(self, engines):
        owner, importer = engines
        state = owner.export_prefix(PROMPT)
        free0 = importer.scheduler.allocator.num_free
        handle = importer.begin_prefix_import(
            {k: v for k, v in state.items() if k not in ("k", "v")})
        assert importer.scheduler.allocator.num_free < free0
        importer.abort_prefix_import(handle)
        importer.abort_prefix_import(handle)      # idempotent
        assert importer.scheduler.allocator.num_free == free0

    def test_mismatched_header_rejected_without_pages(self, engines):
        owner, importer = engines
        state = owner.export_prefix(PROMPT)
        free0 = importer.scheduler.allocator.num_free
        hdr = {k: v for k, v in state.items() if k not in ("k", "v")}
        for field, garbage in (("model", "llama-3-8b"), ("page_size", 32),
                               ("dtype", "float16"),
                               ("matched_tokens", 63)):
            with pytest.raises(ValueError):
                importer.begin_prefix_import(dict(hdr, **{field: garbage}))
            assert importer.scheduler.allocator.num_free == free0

    def test_mismatched_chunk_aborts_the_import(self, engines):
        owner, importer = engines
        state = owner.export_prefix(PROMPT)
        free0 = importer.scheduler.allocator.num_free
        handle = importer.begin_prefix_import(
            {k: v for k, v in state.items() if k not in ("k", "v")})
        bad = state["k"][:, :1].astype(np.float16)
        with pytest.raises(ValueError):
            importer.import_prefix_chunk(handle, bad, bad)
        # The failed chunk aborted the whole import: pages back, handle
        # dead.
        assert importer.scheduler.allocator.num_free == free0
        with pytest.raises(ValueError, match="unknown"):
            importer.commit_prefix_import(handle)


class TestDeltaExport:
    """The fetch ships only the DELTA beyond the puller's local coverage
    (the span the roofline gate priced), and the offset import registers
    a tail chain that becomes reachable once its head arrives."""

    P2 = np.random.default_rng(21).integers(1, 500, 80).tolist()

    def test_delta_then_head_compose(self, engines):
        owner, importer = engines
        params = SamplingParams(max_tokens=6, temperature=0.0)
        ref = owner.generate([self.P2], params)[0].output_token_ids
        delta = owner.export_prefix(self.P2, skip_tokens=32)
        assert delta["start_tokens"] == 32
        assert delta["matched_tokens"] == 64
        assert delta["k"].shape[1] == 2          # pages 2..3 only
        # Tail-first: registered but unreachable (chain walks from 0).
        _stream_import(importer, delta)
        assert importer.prefix_peek(self.P2) == 0
        # Head arrives (full export; the tail pages dedupe at commit).
        free0 = importer.scheduler.allocator.num_free
        full = owner.export_prefix(self.P2)
        assert full["start_tokens"] == 0 and full["k"].shape[1] == 4
        _stream_import(importer, full)
        # 2 pages newly registered (head), 2 deduped back to the pool.
        assert importer.scheduler.allocator.num_free == free0 - 2
        assert importer.prefix_peek(self.P2) == 64
        got = importer.generate([self.P2], params)[0].output_token_ids
        assert got == ref

    def test_skip_past_match_is_a_miss(self, engines):
        owner, _ = engines
        with pytest.raises(KeyError, match="beyond"):
            owner.export_prefix(self.P2, skip_tokens=64)

    def test_export_reads_host_tier_in_place(self, engines):
        """A chain sitting in the HOST tier is served without restoring
        it into the device pool, without counters, byte-identical to the
        live-tier export — a peer's fetch must not perturb the owner."""
        _, importer = engines
        pc = importer.scheduler.prefix_cache
        ref_state = importer.export_prefix(self.P2)      # live-tier bytes
        pc.evict(len(pc))                # spills to importer's OWN host tier
        assert len(pc._host_entries) >= 4
        free0 = importer.scheduler.allocator.num_free
        host_hits0 = pc.host_hits
        state = importer.export_prefix(self.P2)
        np.testing.assert_array_equal(state["k"], ref_state["k"])
        np.testing.assert_array_equal(state["v"], ref_state["v"])
        assert importer.scheduler.allocator.num_free == free0
        assert pc.host_hits == host_hits0
        assert len(pc) == 0              # nothing restored to the live tier


class TestRemoteSpill:
    """The eviction ladder's remote rung: pages the local host tier could
    not take move to a PEER's host tier and second-chance back into its
    device pool byte-identically."""

    SPILL_PROMPT = np.random.default_rng(11).integers(1, 500, 80).tolist()

    def test_spill_to_peer_host_tier_and_second_chance(self, engines):
        owner, importer = engines
        params = SamplingParams(max_tokens=6, temperature=0.0)
        ref = owner.generate([self.SPILL_PROMPT], params)[0].output_token_ids
        spills = []
        assert owner.enable_fleet_spill(
            lambda d, k, v: (spills.append((d, k, v)) or True))
        pc = owner.scheduler.prefix_cache
        pc.evict(len(pc))
        # The owner has no host tier: EVERY evicted page took the remote
        # rung (this prompt's chain + whatever earlier tests cached).
        assert len(spills) >= 4
        owner.scheduler.prefix_cache.fleet_spill = None
        accepted = sum(importer.accept_remote_spill(d, k, v)
                       for d, k, v in spills)
        # Digests the importer already holds (earlier tests imported the
        # shared PROMPT chain) are refused; the SPILL chain is new.
        assert accepted >= 4
        assert importer.prefix_peek(self.SPILL_PROMPT) == 64
        host_hits0 = importer.scheduler.prefix_cache.host_hits
        got = importer.generate([self.SPILL_PROMPT],
                                params)[0].output_token_ids
        assert got == ref
        assert importer.scheduler.prefix_cache.host_hits >= host_hits0 + 4

    def test_duplicate_and_malformed_spills_refused(self, engines):
        owner, importer = engines
        k = np.zeros((2, 1, 16, 64), np.float32)
        # wrong geometry
        assert not importer.accept_remote_spill("aa", k[:, :, :8], k[:, :, :8])
        # bad digest spelling
        assert not importer.accept_remote_spill("not-hex", k, k)
        # owner has no host tier at all
        assert not owner.accept_remote_spill("ab" * 16, k, k)


class TestFleetHTTP:
    """ONE two-server scenario over real sockets: pull-on-hint is
    byte-identical and counted; the roofline gate skips; an out-of-pool
    hint and the kv_pull_fail chaos site both degrade to local recompute
    with the trigger in the trace ring and the flight recorder."""

    def test_pull_skip_allowlist_and_chaos(self):
        from aiohttp import web as aioweb

        import aiohttp
        from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
        from kubernetes_gpu_cluster_tpu.serving.errors import \
            PREFIX_SOURCE_HEADER
        from kubernetes_gpu_cluster_tpu.serving.fleet_cache import PullPolicy

        async def scenario():
            runners = []

            async def serve(**kw):
                srv = build_server(_engine_config(), None, "debug-tiny",
                                   **kw)
                runner = aioweb.AppRunner(srv.build_app())
                await runner.setup()
                site = aioweb.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                runners.append(runner)
                return srv, f"http://127.0.0.1:{runner.addresses[0][1]}"

            try:
                sa, ua = await serve(fleet_prefix_cache=True)
                sb, ub = await serve(fleet_prefix_cache=True, peer_pool=[ua])
                assert sa.fleet_on and sb.fleet_on
                pulls = sb.engine.engine.obs.fleet_pulls
                prompt = np.random.default_rng(7).integers(
                    1, 200, 80).tolist()
                body = {"prompt": prompt, "max_tokens": 6,
                        "temperature": 0.0}
                async with aiohttp.ClientSession() as sess:
                    async def comp(base, js, hint=None):
                        headers = ({PREFIX_SOURCE_HEADER: hint}
                                   if hint else {})
                        async with sess.post(f"{base}/v1/completions",
                                             json=js,
                                             headers=headers) as resp:
                            assert resp.status == 200, await resp.text()
                            return (await resp.json())[
                                "choices"][0]["text"]

                    ref = await comp(ua, body)              # warm the owner
                    got = await comp(ub, body, hint=ua)     # pull into B
                    assert got == ref
                    assert pulls["ok"] == 1
                    assert sb.engine.engine.scheduler.prefix_cache.hits >= 1
                    # Same prefix again: already local -> skipped, not
                    # re-pulled (anti-thrash).
                    await comp(ub, dict(body, prompt=prompt[:64] + [9, 9]),
                               hint=ua)
                    assert pulls["skipped"] == 1 and pulls["ok"] == 1
                    # Roofline gate: a policy that prices every pull above
                    # recompute skips BEFORE any socket I/O.
                    sb._pull_policy = PullPolicy(
                        link_bytes_per_s=1.0, flops_per_s=1e15,
                        kv_bytes_per_token=1e6, flops_per_token=1.0,
                        min_tokens=16)
                    p2 = np.random.default_rng(8).integers(
                        1, 200, 80).tolist()
                    await comp(ua, dict(body, prompt=p2))
                    await comp(ub, dict(body, prompt=p2), hint=ua)
                    assert pulls["skipped"] == 2 and pulls["ok"] == 1
                    sb._pull_policy = build_pull_policy(
                        sb.engine.engine.model_config, 16, 4, "cpu")
                    # Out-of-pool hint: never fetched, local recompute.
                    p3 = np.random.default_rng(9).integers(
                        1, 200, 80).tolist()
                    ref3 = await comp(ua, dict(body, prompt=p3))
                    got3 = await comp(ub, dict(body, prompt=p3),
                                      hint="http://169.254.0.1:1")
                    assert got3 == ref3 and pulls["recompute"] == 1
                    # Chaos: kv_pull_fail degrades to recompute with the
                    # trigger recorded in trace ring + flight recorder.
                    configure_faults("kv_pull_fail")
                    p4 = np.random.default_rng(10).integers(
                        1, 200, 80).tolist()
                    ref4 = await comp(ua, dict(body, prompt=p4))
                    got4 = await comp(ub, dict(body, prompt=p4), hint=ua)
                    configure_faults(None)
                    assert got4 == ref4 and pulls["recompute"] == 2
                    events = [e for e in
                              sb.engine.engine.obs.tracer.events()
                              if e.kind == "fleet_prefix"]
                    assert any(e.args.get("outcome") == "recompute"
                               and "kv_pull_fail" in e.args.get("error", "")
                               for e in events)
                    # The flight recorder mirrors the emit (args are
                    # flattened into the event record).
                    flight = sb.engine.engine.obs.flight.export()["events"]
                    assert any(e.get("kind") == "fleet_prefix"
                               and e.get("outcome") == "recompute"
                               for e in flight)
                    # /metrics renders every outcome, zeros included.
                    async with sess.get(f"{ub}/metrics") as resp:
                        text = await resp.text()
                    assert ('kgct_fleet_prefix_pulls_total'
                            '{outcome="ok"} 1') in text
                    assert ('kgct_fleet_prefix_pulls_total'
                            '{outcome="recompute"} 2') in text
                    assert ('kgct_fleet_prefix_pulls_total'
                            '{outcome="skipped"} 2') in text
                    assert ('kgct_fleet_prefix_spills_total'
                            '{outcome="ok"} 0') in text
            finally:
                for runner in reversed(runners):
                    await runner.cleanup()

        asyncio.run(scenario())


class TestFleetOffByteIdentical:
    def test_flag_off_ignores_hint_and_renders_zeros(self):
        """fleet off: the hint header is inert, the fetch endpoint 404s,
        and the metrics render zeros — the byte-identity-with-off half of
        the acceptance contract at the serving layer (engine behavior off
        the fleet path is untouched by construction: no code runs)."""
        from aiohttp import web as aioweb

        import aiohttp
        from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
        from kubernetes_gpu_cluster_tpu.serving.errors import \
            PREFIX_SOURCE_HEADER

        async def scenario():
            srv = build_server(_engine_config(), None, "debug-tiny")
            assert not srv.fleet_on
            runner = aioweb.AppRunner(srv.build_app())
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            url = f"http://127.0.0.1:{runner.addresses[0][1]}"
            try:
                async with aiohttp.ClientSession() as sess:
                    prompt = list(range(1, 40))
                    async with sess.post(
                            f"{url}/v1/completions",
                            json={"prompt": prompt, "max_tokens": 2,
                                  "temperature": 0.0},
                            headers={PREFIX_SOURCE_HEADER:
                                     "http://169.254.0.1:1"}) as resp:
                        assert resp.status == 200
                        await resp.read()
                    async with sess.post(
                            f"{url}/internal/fetch_prefix",
                            json={"prompt_token_ids": prompt}) as resp:
                        assert resp.status == 404
                    async with sess.post(
                            f"{url}/internal/fleet_spill",
                            data=b"x") as resp:
                        assert resp.status == 404
                    async with sess.get(f"{url}/metrics") as resp:
                        text = await resp.text()
                    for oc in ("ok", "recompute", "skipped"):
                        assert (f'kgct_fleet_prefix_pulls_total'
                                f'{{outcome="{oc}"}} 0') in text
            finally:
                await runner.cleanup()

        asyncio.run(scenario())
