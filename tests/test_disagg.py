"""Disaggregated prefill/decode serving: the KV export/import seam, the
handoff wire codec, and the role-split serving topology.

Tier-1 keeps the CHEAP pins: one shared debug-tiny engine proves the
acceptance contract — a disaggregated run (prefill-with-hold -> export ->
wire round-trip -> import -> decode resume) is BYTE-IDENTICAL to a
colocated run for greedy and seeded-sampled decoding — plus engine-free
codec/fetch pins. The multi-engine HTTP topology (role-split replicas
behind the real router) is @slow, per the tier-1 budget guard.
"""

import asyncio
import dataclasses
import time

import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.resilience.faults import configure_faults
from kubernetes_gpu_cluster_tpu.serving.handoff import (
    decode_handoff, encode_handoff, handoff_request_body)


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
def engine():
    """ONE debug-tiny engine serves as colocated reference, prefill
    replica, AND decode replica (identical weights by construction; the
    handoff still crosses the full gather -> host buffer -> wire -> scatter
    path, which is exactly what distinct replicas exchange)."""
    return LLMEngine(_engine_config())


PROMPT = np.random.default_rng(3).integers(1, 500, 40).tolist()


def _run_to_completion(eng, rid):
    out_tokens = None
    while eng.has_unfinished_requests():
        for o in eng.step():
            if o.request_id == rid and o.finished:
                out_tokens = list(o.output_token_ids)
    return out_tokens


def _disagg_roundtrip(eng, rid, prompt, params):
    """prefill(hold, max_tokens=1) -> export -> WIRE round-trip -> import
    -> decode to completion. Returns the final output token ids."""
    eng.add_request(f"{rid}-pf", prompt,
                    dataclasses.replace(params, max_tokens=1), hold_kv=True)
    while eng.has_unfinished_requests():
        eng.step()
    state = eng.export_held(f"{rid}-pf")
    state = decode_handoff(encode_handoff(state))   # the actual wire bytes
    outs = eng.import_request(f"{rid}-dc", prompt, params, state)
    assert outs[0].new_token_ids == state["output_token_ids"]
    if outs[0].finished:
        return list(outs[0].output_token_ids)
    return _run_to_completion(eng, f"{rid}-dc")


class TestHandoffByteIdentity:
    def test_greedy_identical_to_colocated(self, engine):
        params = SamplingParams(max_tokens=12, temperature=0.0)
        ref = engine.generate([PROMPT], params)[0].output_token_ids
        got = _disagg_roundtrip(engine, "g", PROMPT, params)
        assert got == ref

    def test_seeded_sampled_identical_to_colocated(self, engine):
        params = SamplingParams(max_tokens=12, temperature=0.8,
                                top_k=30, top_p=0.95, seed=17)
        ref = engine.generate([PROMPT], params)[0].output_token_ids
        got = _disagg_roundtrip(engine, "s", PROMPT, params)
        assert got == ref

    def test_no_pages_leak_across_the_handoff(self, engine):
        alloc = engine.scheduler.allocator
        free0 = alloc.num_free
        params = SamplingParams(max_tokens=4, temperature=0.0)
        _disagg_roundtrip(engine, "leak", PROMPT, params)
        assert alloc.num_free == free0

    def test_eos_on_first_token_finishes_at_import(self, engine):
        """A prompt whose first sampled token is a stop token finishes the
        imported sequence immediately — no decode step, pages released."""
        params = SamplingParams(max_tokens=8, temperature=0.0)
        ref = engine.generate([PROMPT], params)[0]
        stop_tok = ref.output_token_ids[0]
        params = SamplingParams(max_tokens=8, temperature=0.0,
                                stop_token_ids=(stop_tok,))
        free0 = engine.scheduler.allocator.num_free
        got = _disagg_roundtrip(engine, "eos", PROMPT, params)
        assert got == [stop_tok]
        assert engine.scheduler.allocator.num_free == free0

    def test_discard_held_releases_without_export(self, engine):
        free0 = engine.scheduler.allocator.num_free
        engine.add_request(
            "dis-pf", PROMPT, SamplingParams(max_tokens=1, temperature=0.0),
            hold_kv=True)
        while engine.has_unfinished_requests():
            engine.step()
        assert "dis-pf" in engine.scheduler.held
        engine.discard_held("dis-pf")
        engine.discard_held("dis-pf")   # idempotent
        assert engine.scheduler.allocator.num_free == free0
        with pytest.raises(KeyError):
            engine.export_held("dis-pf")

    def test_abort_releases_held_kv(self, engine):
        """abort_request must scan ``held`` too: a kv_handoff handler
        cancelled between the prefill finishing and the export consuming
        it aborts the request — without this the held pages leak until
        the prefill replica is capacity-dead."""
        free0 = engine.scheduler.allocator.num_free
        engine.add_request(
            "abt-pf", PROMPT, SamplingParams(max_tokens=1, temperature=0.0),
            hold_kv=True)
        while engine.has_unfinished_requests():
            engine.step()
        assert "abt-pf" in engine.scheduler.held
        engine.abort_request("abt-pf")
        assert "abt-pf" not in engine.scheduler.held
        assert engine.scheduler.allocator.num_free == free0

    def test_import_records_decode_side_ttft(self, engine):
        """step() never fires on_first_token for an imported sequence
        (append_token stamps first_token_time at import), so the decode
        side's TTFT sample — remote prefill + transfer + import, measured
        from the serving layer's ``_ttft_t0`` stamp — lands in
        import_request: SLO attainment window AND the goodput gate must
        judge the real span, not the ~0 of first_token - arrival."""
        obs = engine.obs
        params = SamplingParams(max_tokens=4, temperature=0.0)
        engine.add_request("ttft-pf", PROMPT,
                           dataclasses.replace(params, max_tokens=1),
                           hold_kv=True)
        while engine.has_unfinished_requests():
            engine.step()
        state = engine.export_held("ttft-pf")
        obs.slo.clear()
        state["_ttft_t0"] = time.monotonic() - 5.0   # the pull "took" 5 s
        engine.import_request("ttft-dc", PROMPT, params, state)
        ttfts = list(obs.slo._ttfts)
        assert len(ttfts) == 1 and ttfts[0] >= 5.0
        # 5 s against the 1 s default budget: a pure-handoff decode
        # replica must NOT read a pegged-1.0 attainment.
        assert obs.slo.attainment() == 0.0
        _run_to_completion(engine, "ttft-dc")
        # ...and the finish-side goodput gate judged the same 5 s (over
        # budget -> the tokens are not goodput).
        assert len(obs.slo._good) == 0
        obs.slo.clear()

    def test_malformed_output_state_rejected_without_page_leak(self, engine):
        """A peer whose frame passes the shape/dtype/prompt checks but
        carries garbage OUTPUT state (non-int tokens, non-pair
        top-logprobs) must be rejected BEFORE any pages are allocated —
        the conversion used to run post-scatter, so every such handoff
        leaked the imported pages while the broad serving-layer fallback
        swallowed the error."""
        params = SamplingParams(max_tokens=4, temperature=0.0)
        engine.add_request("mal-pf", PROMPT,
                           dataclasses.replace(params, max_tokens=1),
                           hold_kv=True)
        while engine.has_unfinished_requests():
            engine.step()
        state = engine.export_held("mal-pf")
        free0 = engine.scheduler.allocator.num_free
        for field, garbage in (("output_token_ids", ["x"]),
                               ("output_logprobs", ["nope"]),
                               ("output_top_logprobs", [5])):
            bad = dict(state, **{field: garbage})
            with pytest.raises(ValueError, match="malformed handoff"):
                engine.import_request(f"mal-{field}", PROMPT, params, bad)
            assert engine.scheduler.allocator.num_free == free0
        # The untouched state still imports (and is drained clean).
        outs = engine.import_request("mal-ok", PROMPT, params, state)
        assert outs[0].new_token_ids
        _run_to_completion(engine, "mal-ok")

    def test_failed_pull_backdates_arrival(self, engine):
        """A decode replica whose handoff pull FAILED admits the request
        only after the pull burned its wall time (up to the handoff
        timeout). add_request(arrival_t0=) backdates the arrival stamp so
        the client-observed wait reaches the TTFT histogram and the SLO
        attainment window instead of reading a green post-pull arrival."""
        obs = engine.obs
        obs.slo.clear()
        t0 = time.monotonic() - 5.0
        engine.add_request("bkd", PROMPT,
                           SamplingParams(max_tokens=2, temperature=0.0),
                           arrival_t0=t0)
        seq = next(s for s in engine.scheduler.waiting
                   if s.request_id == "bkd")
        assert seq.arrival_time == t0
        _run_to_completion(engine, "bkd")
        ttfts = list(obs.slo._ttfts)
        assert len(ttfts) == 1 and ttfts[0] >= 5.0
        assert obs.slo.attainment() == 0.0
        obs.slo.clear()

    def test_import_rejects_mismatched_state(self, engine):
        params = SamplingParams(max_tokens=4, temperature=0.0)
        engine.add_request("rej-pf", PROMPT,
                           dataclasses.replace(params, max_tokens=1),
                           hold_kv=True)
        while engine.has_unfinished_requests():
            engine.step()
        state = engine.export_held("rej-pf")
        with pytest.raises(ValueError, match="prompt does not match"):
            engine.import_request("rej-a", PROMPT[:-1] + [1], params, state)
        bad = dict(state, page_size=state["page_size"] * 2)
        with pytest.raises(ValueError, match="page_size"):
            engine.import_request("rej-b", PROMPT, params, bad)
        bad = dict(state, model="llama-3-8b")
        with pytest.raises(ValueError, match="model"):
            engine.import_request("rej-c", PROMPT, params, bad)
        # The well-formed state still imports (and is drained clean).
        outs = engine.import_request("rej-d", PROMPT, params, state)
        assert outs[0].new_token_ids
        _run_to_completion(engine, "rej-d")


class TestHandoffWireCodec:
    """Engine-free pins of the binary frame (serving/handoff.py)."""

    def _state(self, dtype="float32"):
        rng = np.random.default_rng(0)
        k = rng.standard_normal((2, 3, 16, 64)).astype(dtype)
        return {"model": "debug-tiny", "page_size": 16, "dtype": dtype,
                "prompt_token_ids": [1, 2, 3], "output_token_ids": [7],
                "output_logprobs": [-0.5], "output_top_logprobs": [],
                "k": k, "v": k + 1}

    def test_roundtrip(self):
        state = self._state()
        out = decode_handoff(encode_handoff(state))
        assert out["prompt_token_ids"] == [1, 2, 3]
        assert out["output_token_ids"] == [7]
        np.testing.assert_array_equal(out["k"], state["k"])
        np.testing.assert_array_equal(out["v"], state["v"])

    def test_bfloat16_roundtrip(self):
        """TPU pools are bf16: tobytes/frombuffer must round-trip the
        ml_dtypes family without pickle."""
        import ml_dtypes
        state = self._state()
        state["k"] = state["k"].astype(ml_dtypes.bfloat16)
        state["v"] = state["v"].astype(ml_dtypes.bfloat16)
        state["dtype"] = "bfloat16"
        out = decode_handoff(encode_handoff(state))
        assert out["k"].dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(out["k"], state["k"])

    def test_corrupt_frames_rejected(self):
        state = self._state()
        data = encode_handoff(state)
        with pytest.raises(ValueError, match="magic"):
            decode_handoff(b"NOTAKV" + data[6:])
        with pytest.raises(ValueError, match="!= 2 x"):
            decode_handoff(data[:-7])          # truncated payload
        with pytest.raises(ValueError):
            decode_handoff(data[:10])          # truncated header

    def test_request_body_forwards_sampling_and_tenant_fields_only(self):
        """Forwarded: the sampling fields that shape the first token plus
        the QoS tenant keys (user/session_id — the prefill replica
        resolves the request's tier from them, since the pull carries no
        client headers). Never forwarded: text prompt (the prefill side
        must not re-tokenize), stream, max_tokens (clamped to 1 by the
        handoff handler)."""
        body = {"prompt": "ignored", "temperature": 0.5, "seed": 3,
                "stream": True, "max_tokens": 99, "user": "u"}
        fwd = handoff_request_body([1, 2], body)
        assert fwd == {"prompt_token_ids": [1, 2], "temperature": 0.5,
                       "seed": 3, "user": "u"}


class TestBoundedFetch:
    """The decode side's pull is bounded in bytes and never trusts an
    oversized response (engine-free aiohttp stub)."""

    def test_oversized_blob_rejected(self):
        from aiohttp import web as aioweb

        import aiohttp
        from kubernetes_gpu_cluster_tpu.serving.handoff import fetch_handoff

        async def scenario():
            async def kv(request):
                return aioweb.Response(body=b"x" * 4096)

            app = aioweb.Application()
            app.router.add_post("/internal/kv_handoff", kv)
            runner = aioweb.AppRunner(app)
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            url = f"http://127.0.0.1:{runner.addresses[0][1]}"
            try:
                async with aiohttp.ClientSession() as sess:
                    with pytest.raises(RuntimeError, match="bound"):
                        await fetch_handoff(sess, url, {}, "rid",
                                            max_bytes=1024, timeout_s=5)
                    data = await fetch_handoff(sess, url, {}, "rid",
                                               max_bytes=8192, timeout_s=5)
                    assert len(data) == 4096
                    # Non-200 raises with a bounded error peek.
                    with pytest.raises(RuntimeError, match="404"):
                        await fetch_handoff(sess, url + "/nope", {}, "rid",
                                            max_bytes=8192, timeout_s=5)
            finally:
                await runner.cleanup()
        asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Role-split serving topology over real sockets (multi-engine: @slow)
# ---------------------------------------------------------------------------

def _serve(role, runners):
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server

    async def start():
        srv = build_server(_engine_config(), None, "debug-tiny", role=role)
        runner = aioweb.AppRunner(srv.build_app())
        await runner.setup()
        site = aioweb.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        runners.append(runner)
        return srv, f"http://127.0.0.1:{runner.addresses[0][1]}"
    return start()


@pytest.mark.slow
class TestDisaggServing:
    def test_role_split_pools_byte_identical_to_colocated(self):
        """The acceptance topology end-to-end: 1 prefill + 1 decode
        replica behind the real router (distinct engines, identical
        seeds) produce the same greedy AND seeded-sampled completions as
        a single role="both" replica, with handoff metrics/trace evidence
        on both sides."""
        import aiohttp
        from aiohttp import web as aioweb

        from kubernetes_gpu_cluster_tpu.serving.router import Router

        async def scenario():
            runners = []
            prompt = np.random.default_rng(5).integers(1, 200, 40).tolist()
            greedy = {"prompt": prompt, "max_tokens": 8, "temperature": 0.0}
            seeded = {"prompt": prompt, "max_tokens": 8, "temperature": 0.9,
                      "top_k": 30, "seed": 11}
            try:
                _, u0 = await _serve("both", runners)
                async with aiohttp.ClientSession() as sess:
                    async def text_of(base, body):
                        async with sess.post(f"{base}/v1/completions",
                                             json=body) as resp:
                            assert resp.status == 200, await resp.text()
                            return (await resp.json())["choices"][0]["text"]

                    ref_g = await text_of(u0, greedy)
                    ref_s = await text_of(u0, seeded)

                    pf_srv, pf_url = await _serve("prefill", runners)
                    dc_srv, dc_url = await _serve("decode", runners)
                    router = Router([dc_url], health_interval_s=9999,
                                    prefill_urls=[pf_url])
                    rrunner = aioweb.AppRunner(router.build_app())
                    await rrunner.setup()
                    rsite = aioweb.TCPSite(rrunner, "127.0.0.1", 0)
                    await rsite.start()
                    runners.append(rrunner)
                    ru = f"http://127.0.0.1:{rrunner.addresses[0][1]}"

                    assert await text_of(ru, greedy) == ref_g
                    assert await text_of(ru, seeded) == ref_s

                    async with sess.get(f"{dc_url}/metrics") as resp:
                        dc_text = await resp.text()
                    async with sess.get(f"{pf_url}/metrics") as resp:
                        pf_text = await resp.text()
                    assert ('kgct_disagg_handoffs_total{side="import",'
                            'outcome="ok"} 2') in dc_text
                    assert ('kgct_disagg_handoffs_total{side="export",'
                            'outcome="ok"} 2') in pf_text
                    assert 'kgct_engine_role{role="decode"} 1' in dc_text
                    assert 'kgct_engine_role{role="prefill"} 1' in pf_text
                    # Handoff spans on both sides of the seam.
                    dc_kinds = [e["kind"] for e in
                                dc_srv.engine.engine.obs.flight.export()
                                ["events"]]
                    pf_kinds = [e["kind"] for e in
                                pf_srv.engine.engine.obs.flight.export()
                                ["events"]]
                    assert "handoff" in dc_kinds
                    assert "handoff" in pf_kinds
            finally:
                for runner in reversed(runners):
                    await runner.cleanup()
        asyncio.run(scenario())
