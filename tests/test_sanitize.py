"""Runtime sanitizers (KGCT_SANITIZE=1) under the KGCT_FAULT chaos harness.

The acceptance bars, in order:

1. NO-OP WHEN OFF: with KGCT_SANITIZE unset the engine holds no sanitizer
   and outputs are byte-identical to a sanitized run (the guard observes,
   never perturbs).
2. A seeded NaN fault (``nan_step_output``) in the step fetch path raises
   SanitizerError at the step that produced it.
3. A seeded committed-slot KV write (``kv_commit_stomp``) — a REAL
   corruption of a spec-verify slot_mapping — is refused pre-dispatch by
   the KV shadow.
4. The shadow's stale-slot machine enforces the rollback contract
   (rejected-draft slots overwritten before any read) — unit-level, since
   a correct engine never produces the violation.
"""

import numpy as np
import pytest

import jax

from kubernetes_gpu_cluster_tpu.analysis.sanitize import (SanitizerError,
                                                          StepSanitizer,
                                                          build_step_sanitizer)
from kubernetes_gpu_cluster_tpu.config import (CacheConfig, EngineConfig,
                                               SchedulerConfig,
                                               get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine.spec import DraftProposer
from kubernetes_gpu_cluster_tpu.models import llama as model_lib
from kubernetes_gpu_cluster_tpu.resilience import configure_faults

pytestmark = pytest.mark.chaos

_MODEL = get_model_config("debug-tiny")
_PARAMS = model_lib.init_params(_MODEL, jax.random.key(7))

REPETITIVE = [7, 3, 9, 11] * 8   # n-gram structure -> spec steps engage


@pytest.fixture(autouse=True)
def _clear_faults():
    configure_faults(None)
    yield
    configure_faults(None)


class _AlwaysDraft(DraftProposer):
    """Drafts a constant token every step: guarantees spec steps engage
    (and, rejecting almost always, guarantees real rollbacks for the KV
    shadow to watch) independent of what the random-weight model emits."""

    def __init__(self, k, token=1):
        super().__init__(k)
        self.token = token

    def propose(self, token_ids):
        return [self.token] * self.k


def make_engine(spec: bool = False):
    cfg = EngineConfig(
        model=_MODEL,
        cache=CacheConfig(page_size=8, num_pages=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=256,
            decode_buckets=(1, 2, 4), prefill_buckets=(32, 64, 128, 256),
            decode_window=8,
            spec_decode_enabled=spec, num_speculative_tokens=4))
    engine = LLMEngine(cfg, params=_PARAMS)
    if spec:
        engine.scheduler.spec_proposer = _AlwaysDraft(4)
    return engine


class TestNoOpWhenOff:
    def test_outputs_byte_identical_with_and_without_sanitizer(
            self, monkeypatch):
        monkeypatch.delenv("KGCT_SANITIZE", raising=False)
        off = make_engine()
        assert off._sanitizer is None
        base = off.generate([REPETITIVE],
                            SamplingParams(max_tokens=12, temperature=0.0))
        monkeypatch.setenv("KGCT_SANITIZE", "1")
        on = make_engine()
        assert on._sanitizer is not None
        sane = on.generate([REPETITIVE],
                           SamplingParams(max_tokens=12, temperature=0.0))
        assert base[0].output_token_ids == sane[0].output_token_ids
        # the hooks actually ran (not vacuously clean)
        assert on._sanitizer.checks > 0

    def test_build_seam_reads_env(self, monkeypatch):
        monkeypatch.delenv("KGCT_SANITIZE", raising=False)
        assert build_step_sanitizer(8) is None
        monkeypatch.setenv("KGCT_SANITIZE", "0")
        assert build_step_sanitizer(8) is None
        monkeypatch.setenv("KGCT_SANITIZE", "1")
        assert isinstance(build_step_sanitizer(8), StepSanitizer)


class TestSeededFaults:
    def test_nan_step_output_caught(self, monkeypatch):
        monkeypatch.setenv("KGCT_SANITIZE", "1")
        engine = make_engine()
        configure_faults("nan_step_output:times=1")
        with pytest.raises(SanitizerError, match="non-finite logprob"):
            engine.generate([REPETITIVE],
                            SamplingParams(max_tokens=8, temperature=0.0))

    def test_spec_rollbacks_clean_then_seeded_stomp_caught(self, monkeypatch):
        """One spec engine, both sides of the contract. First a clean run:
        spec decode's REAL rollbacks (garbage drafts reject constantly)
        must not trip the shadow — rejected slots are overwritten before
        any read, which is exactly what it watches. Then the seeded
        committed-slot KV write (a genuine slot_mapping corruption — with
        the sanitizer off it would poison served context silently) is
        refused before the upload."""
        monkeypatch.setenv("KGCT_SANITIZE", "1")
        engine = make_engine(spec=True)
        out = engine.generate([REPETITIVE],
                              SamplingParams(max_tokens=12, temperature=0.0))
        assert engine.obs.step_kind_counts["spec"] > 0
        assert len(out[0].output_token_ids) == 12
        assert engine._sanitizer.checks > 0
        # Recycled request id (generate() numbers from zero per call): the
        # previous request's rollbacks left stale shadow entries under
        # "req-0"; a fresh sequence wearing the same id must not inherit
        # them and false-positive on a healthy engine.
        out2 = engine.generate([list(REPETITIVE) + [7, 3]],
                               SamplingParams(max_tokens=8, temperature=0.0))
        assert len(out2[0].output_token_ids) == 8
        configure_faults("kv_commit_stomp:times=1")
        with pytest.raises(SanitizerError, match="COMMITTED slot"):
            engine.generate([REPETITIVE],
                            SamplingParams(max_tokens=12, temperature=0.0))


class _FakeSeq:
    def __init__(self, rid, num_tokens, pages, finished=False):
        self.request_id = rid
        self.num_tokens = num_tokens
        self.pages = pages
        self.is_finished = finished


class _FakeSpecBatch:
    def __init__(self, seqs, seg_ids, positions, slot_mapping):
        self.seqs = seqs
        self.seg_ids = np.asarray(seg_ids, np.int32)
        self.positions = np.asarray(positions, np.int32)
        self.slot_mapping = np.asarray(slot_mapping, np.int32)


class TestKVShadowUnit:
    """The stale-slot machine, driven directly (a correct engine never
    produces these traces)."""

    PS = 8

    def _spec_step(self, san, seq, k=2):
        # writes positions n-1 .. n-1+k with matching slots
        n = seq.num_tokens
        poss = [n - 1 + i for i in range(k + 1)]
        slots = [seq.pages[p // self.PS] * self.PS + p % self.PS
                 for p in poss]
        batch = _FakeSpecBatch([seq], [0] * (k + 1), poss, slots)
        san.on_spec_dispatch(batch)
        return batch

    def test_rejected_slots_go_stale_and_overwrite_clears(self):
        san = StepSanitizer(self.PS)
        seq = _FakeSeq("r1", num_tokens=9, pages=[3, 4])   # committed KV: 8
        batch = self._spec_step(san, seq, k=2)     # writes pos 8, 9, 10
        san.on_spec_commit(batch, np.asarray([1]))  # emit 1 -> 9, 10 stale
        assert set(san._stale["r1"]) == {9, 10}
        # next decode window starts at the first stale position: clears it
        seq.num_tokens = 10
        san.on_decode_dispatch([seq], np.asarray([9]), window=8)
        assert san._stale["r1"] == {}

    def test_stale_read_detected(self):
        san = StepSanitizer(self.PS)
        seq = _FakeSeq("r1", num_tokens=9, pages=[3, 4])
        batch = self._spec_step(san, seq, k=2)
        san.on_spec_commit(batch, np.asarray([1]))  # 9, 10 stale
        # BUG trace: committed length advances past the stale slots with
        # no overwrite — the next window would read garbage as context.
        seq.num_tokens = 13
        with pytest.raises(SanitizerError, match="stale"):
            san.on_decode_dispatch([seq], np.asarray([12]), window=8)

    def test_decode_window_inside_committed_history_detected(self):
        san = StepSanitizer(self.PS)
        seq = _FakeSeq("r1", num_tokens=9, pages=[3, 4])
        with pytest.raises(SanitizerError, match="committed history"):
            san.on_decode_dispatch([seq], np.asarray([3]), window=8)

    def test_cross_sequence_committed_stomp_detected(self):
        """A slot mis-aimed into ANOTHER sequence's committed page must be
        refused too — the writing row's own page index can't see it, the
        batch-wide ownership map can."""
        san = StepSanitizer(self.PS)
        a = _FakeSeq("a", num_tokens=9, pages=[3, 4])
        b = _FakeSeq("b", num_tokens=9, pages=[6, 7])
        # row 0 (seq a) claims a legal position but its write slot lands in
        # seq b's page 6, position 0 — committed history of b.
        batch = _FakeSpecBatch([a, b], [0], [8], [6 * self.PS])
        with pytest.raises(SanitizerError, match="owned by 'b'|owned by b"):
            san.on_spec_dispatch(batch)

    def test_recycled_request_id_does_not_inherit_stale_state(self):
        san = StepSanitizer(self.PS)
        old = _FakeSeq("r1", num_tokens=9, pages=[3, 4])
        batch = self._spec_step(san, old, k=2)
        san.on_spec_commit(batch, np.asarray([1]))
        assert san._stale["r1"]
        # a NEW sequence object reuses the id with fresh pages: the old
        # stale map must be dropped, not raised over
        fresh = _FakeSeq("r1", num_tokens=13, pages=[5, 6])
        san.on_decode_dispatch([fresh], np.asarray([12]), window=8)
        assert san._stale.get("r1", {}) == {}

    def test_scrap_page_writes_are_ignored(self):
        san = StepSanitizer(self.PS)
        seq = _FakeSeq("r1", num_tokens=9, pages=[3, 4])
        # slot < page_size -> scrap page routing, never an error
        batch = _FakeSpecBatch([seq], [0], [8], [5])
        san.on_spec_dispatch(batch)

    def test_finished_seqs_pruned(self):
        san = StepSanitizer(self.PS)
        seq = _FakeSeq("r1", num_tokens=9, pages=[3, 4])
        batch = self._spec_step(san, seq, k=2)
        san.on_spec_commit(batch, np.asarray([1]))
        assert "r1" in san._stale
        other = _FakeSeq("r2", num_tokens=5, pages=[5])
        san.on_decode_dispatch([other], np.asarray([4]), window=8)
        assert "r1" not in san._stale   # absent from a full batch = gone


class TestOutputGuardUnit:
    def test_out_of_vocab_token(self):
        san = StepSanitizer(8)
        with pytest.raises(SanitizerError, match="out of vocab"):
            san.check_outputs(np.asarray([[5, 900]]),
                              np.zeros((1, 2)), None, 512, 1)

    def test_inf_logprob(self):
        san = StepSanitizer(8)
        with pytest.raises(SanitizerError, match="non-finite"):
            san.check_outputs(np.asarray([[5, 6]]),
                              np.asarray([[0.0, np.inf]]), None, 512, 1)

    def test_emit_mask_ignores_rejected_columns(self):
        """Spec rows carry garbage past the accepted prefix — the guard
        must only check what the host consumes."""
        san = StepSanitizer(8)
        san.check_outputs(np.asarray([[5, -1, 99999]]),
                          np.asarray([[0.0, np.nan, np.inf]]),
                          np.asarray([1]), 512, 1)

    def test_padding_rows_ignored(self):
        san = StepSanitizer(8)
        san.check_outputs(np.asarray([[5], [-7]]),
                          np.asarray([[0.0], [np.nan]]), None, 512,
                          num_seqs=1)


# -- interleave sanitizer (KGCT_SANITIZE_INTERLEAVE) ---------------------------

import asyncio
import itertools
import threading
import types

from kubernetes_gpu_cluster_tpu.analysis.sanitize import (
    InterleaveSanitizer, build_interleave_sanitizer)
from kubernetes_gpu_cluster_tpu.engine import SamplingParams as _SP
from kubernetes_gpu_cluster_tpu.serving.async_engine import AsyncLLMEngine


class _ScriptedEngine:
    """Deterministic engine stand-in: emits ``n`` fixed tokens per request,
    one per step. The interleave sanitizer perturbs WHERE the loop and
    worker interleave, never WHAT the engine computes — a scripted engine
    makes that separation testable in milliseconds (no device, no jit)."""

    def __init__(self, n: int = 4):
        from kubernetes_gpu_cluster_tpu.observability.phases import (
            StepPhaseStats)
        self.n = n
        self._live: dict = {}
        # the worker brackets its turn in obs.phases spans (no-ops here:
        # no profiler capture runs)
        self.obs = types.SimpleNamespace(phases=StepPhaseStats())

    def has_unfinished_requests(self):
        return bool(self._live)

    def add_request(self, rid, ids, params, **kw):
        self._live[rid] = []

    def abort_request(self, rid):
        self._live.pop(rid, None)

    def export_held(self, rid):          # run_in_worker target in the test
        return f"held:{rid}"

    def step(self):
        outs = []
        for rid in list(self._live):
            toks = self._live[rid]
            toks.append(100 + len(toks))
            fin = len(toks) >= self.n
            outs.append(types.SimpleNamespace(
                request_id=rid, new_token_ids=[toks[-1]],
                output_token_ids=list(toks), finished=fin,
                finish_reason="length" if fin else None,
                new_logprobs=[], new_top_logprobs=[], clock=None))
            if fin:
                del self._live[rid]
        return outs


def _make_async_engine() -> AsyncLLMEngine:
    """Engine-free AsyncLLMEngine (the __new__ pattern): real worker
    thread, real _cv handshake, real interleave hooks — scripted steps."""
    a = AsyncLLMEngine.__new__(AsyncLLMEngine)
    a.engine = _ScriptedEngine()
    a.leader = None
    a.watchdog = None
    a._loop = None
    a._queues = {}
    a._reserved = set()
    a._inbox = []
    a._aborts = []
    a._handoffs = {}
    a._holds = set()
    a._resumes = {}
    a._arrival_t0s = {}
    a.on_import_fallback = None
    a._ops = []
    a._interleave = build_interleave_sanitizer()
    a._cv = threading.Condition()
    a._shutdown = False
    a._counter = itertools.count()
    a._thread = threading.Thread(target=a._worker, daemon=True,
                                 name="kgct-test-step-loop")
    return a


def _serve(n_requests: int = 3):
    """Run a small concurrent workload through the async engine; returns
    ({request_id: output tokens}, the engine's InterleaveSanitizer)."""
    a = _make_async_engine()
    loop = asyncio.new_event_loop()
    try:
        async def consume(rid):
            assert a.reserve_request_id(rid)
            toks = []
            async for chunk in a.generate(rid, [1, 2, 3], _SP(max_tokens=4)):
                toks = list(chunk.output_token_ids)
            # One worker-op crossing per request: the export seam path.
            held = await a.run_in_worker(lambda e: e.export_held(rid))
            assert held == f"held:{rid}"
            return toks

        async def go():
            a.start()
            outs = await asyncio.gather(
                *[consume(f"r{i}") for i in range(n_requests)])
            return {f"r{i}": outs[i] for i in range(n_requests)}

        return loop.run_until_complete(go()), a._interleave
    finally:
        a.shutdown()
        loop.close()


def _by_site(trace):
    sites: dict = {}
    for site, n, yielded in trace:
        sites.setdefault(site, []).append((n, yielded))
    return sites


class TestInterleaveSanitizer:
    def test_build_seam_reads_env(self, monkeypatch):
        monkeypatch.delenv("KGCT_SANITIZE_INTERLEAVE", raising=False)
        assert build_interleave_sanitizer() is None
        monkeypatch.setenv("KGCT_SANITIZE_INTERLEAVE", "0")
        assert build_interleave_sanitizer() is None
        monkeypatch.setenv("KGCT_SANITIZE_INTERLEAVE", "1")
        monkeypatch.setenv("KGCT_INTERLEAVE_SEED", "7")
        izer = build_interleave_sanitizer()
        assert isinstance(izer, InterleaveSanitizer) and izer.seed == 7

    def test_decisions_are_a_pure_function_of_seed_site_counter(self):
        a, b = InterleaveSanitizer(3), InterleaveSanitizer(3)
        sa = [a.decide("worker.wake") for _ in range(64)]
        assert sa == [b.decide("worker.wake") for _ in range(64)]
        sc = [InterleaveSanitizer(4).decide("worker.wake")
              for _ in range(64)]
        assert sa != sc                        # seed picks the schedule
        yielded = [y for y, _ in sa]
        assert any(yielded) and not all(yielded)   # perturbs, some sites

    def test_off_engine_holds_none_and_outputs_byte_identical(
            self, monkeypatch):
        monkeypatch.delenv("KGCT_SANITIZE_INTERLEAVE", raising=False)
        base, izer = _serve()
        assert izer is None                    # zero-cost hooks when off
        monkeypatch.setenv("KGCT_SANITIZE_INTERLEAVE", "1")
        monkeypatch.setenv("KGCT_INTERLEAVE_SEED", "3")
        perturbed, izer_on = _serve()
        assert izer_on is not None and izer_on.trace
        # Interleaving changed, outputs did not: the sanitizer perturbs
        # scheduling only — any output divergence IS a found race.
        assert perturbed == base

    def test_same_seed_replays_the_interleaving(self, monkeypatch):
        monkeypatch.setenv("KGCT_SANITIZE_INTERLEAVE", "1")
        monkeypatch.setenv("KGCT_INTERLEAVE_SEED", "3")
        out1, iz1 = _serve()
        out2, iz2 = _serve()
        assert out1 == out2
        s1, s2 = _by_site(iz1.trace), _by_site(iz2.trace)
        # Loop-side sites have workload-determined counts: exact replay.
        for site in ("generate.submit", "generate.stream"):
            assert s1[site] == s2[site], site
        # Worker-side wakeup counts depend on OS thread timing, but the
        # decision SEQUENCE is seed-deterministic: common prefix matches.
        for site in ("worker.wake", "worker.step"):
            k = min(len(s1[site]), len(s2[site]))
            assert k > 0 and s1[site][:k] == s2[site][:k], site
        # At least one sanctioned seam crossing actually yielded.
        assert any(y for _, _, y in iz1.trace)
        # A different seed drives a different schedule.
        monkeypatch.setenv("KGCT_INTERLEAVE_SEED", "11")
        out3, iz3 = _serve()
        assert out3 == out1                    # still race-free
        s3 = _by_site(iz3.trace)
        assert s3["generate.stream"] != s1["generate.stream"]


# -- device-queue shadow: nothing a dispatched, unfetched step program can
# still write goes back to the allocator (engine._step, _drain_deferred) ----

def _queue_cfg(model="debug-ssm-hybrid"):
    return EngineConfig(
        model=get_model_config(model),
        cache=CacheConfig(page_size=8, num_pages=64),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32, decode_buckets=(1, 2, 4),
            prefill_buckets=(16, 32), decode_window=4))


_QUEUE_PROMPTS = [[5, 9, 2, 7, 11], [3, 1, 4, 1, 5, 9, 2, 6], [8, 8, 2]]


def _eos_mid_window(cfg):
    """An id the first prompt's greedy decode emits inside a decode window
    (not as its prefill's token) and not before: declared EOS it ends the
    request in a step whose successor is already queued with a row for
    it. Returns the id and how many tokens the request then has."""
    out = LLMEngine(cfg).generate(
        [_QUEUE_PROMPTS[0]], SamplingParams(max_tokens=10, temperature=0.0))
    ids = out[0].output_token_ids
    n = next(j for j in range(2, len(ids)) if ids[j] not in ids[:j])
    return ids[n], n + 1


def _staged(eng, abort=None):
    """Three staggered requests; ``abort`` (call, request id) cuts one
    while a step that has a row for it is in flight."""
    sp = SamplingParams(max_tokens=24, temperature=0.0)
    done, call = {}, 0
    pending = [(2 * i, f"q{i}", p, sp) for i, p in enumerate(_QUEUE_PROMPTS)]
    while pending or eng.has_unfinished_requests():
        for item in [a for a in pending if a[0] <= call]:
            pending.remove(item)
            eng.add_request(*item[1:])
        if abort is not None and abort[0] == call:
            assert eng._inflight is not None
            assert eng.abort_request(abort[1])
        for o in eng.step():
            done[o.request_id] = (list(o.output_token_ids), o.finish_reason)
        call += 1
    return done


class TestDeviceQueueShadow:
    def test_shadow_unit(self):
        san = StepSanitizer(8)
        a, b = _FakeSeq("a", 9, [1, 2]), _FakeSeq("b", 9, [3])
        a.state_slot = b.state_slot = None
        san.on_step_dispatch([a, b])
        san.on_step_dispatch([b])
        with pytest.raises(SanitizerError, match="device-queue shadow"):
            san.on_release(a)
        san.on_step_retire()                  # the oldest is fetched
        san.on_release(a)                     # no program has a row for it
        with pytest.raises(SanitizerError, match="b released"):
            san.on_release(b)
        b.pages, b.state_slot = [], 3         # a slot alone counts too
        with pytest.raises(SanitizerError, match="device-queue shadow"):
            san.on_release(b)
        b.state_slot = None                   # nothing held: nothing to say
        san.on_release(b)
        san.on_step_retire()
        san.on_step_retire()                  # an empty queue stays empty

    def test_clean_run_under_both_sanitizers(self, monkeypatch):
        """A finish by EOS inside a window whose successor is queued and an
        abort of a row in flight, on the state model (pages AND slots):
        identical with the sanitizers off and on, every page and slot back.
        Then the same engine behind the real worker thread with the
        interleave sanitizer widening every seam."""
        monkeypatch.delenv("KGCT_SANITIZE", raising=False)
        monkeypatch.delenv("KGCT_SANITIZE_INTERLEAVE", raising=False)
        cfg = _queue_cfg()
        eos, n_out = _eos_mid_window(cfg)
        base = _staged(LLMEngine(cfg, eos_token_id=eos), abort=(5, "q1"))
        assert base["q0"][1] == "stop" and len(base["q0"][0]) == n_out
        monkeypatch.setenv("KGCT_SANITIZE", "1")
        monkeypatch.setenv("KGCT_SANITIZE_INTERLEAVE", "1")
        monkeypatch.setenv("KGCT_INTERLEAVE_SEED", "5")
        eng = LLMEngine(cfg, eos_token_id=eos)
        assert eng.scheduler.release_guard is not None
        assert _staged(eng, abort=(5, "q1")) == base
        alloc = eng.scheduler.allocator
        assert alloc.num_free == alloc.num_pages - 1
        assert alloc.num_free_slots == 4
        assert not eng._sanitizer._dispatched and eng._sanitizer.checks > 0

        a = AsyncLLMEngine(cfg, eos_token_id=eos)
        assert a._interleave is not None and a.engine._sanitizer is not None
        loop = asyncio.new_event_loop()
        try:
            async def consume(rid, prompt, cut=None):
                toks = []
                async for chunk in a.generate(
                        rid, prompt,
                        SamplingParams(max_tokens=24, temperature=0.0)):
                    toks = list(chunk.output_token_ids)
                    if cut is not None and len(toks) >= cut:
                        a.abort(rid)
                        break
                return toks

            async def go():
                a.start(loop)
                return await asyncio.gather(
                    consume("q0", _QUEUE_PROMPTS[0]),
                    consume("q1", _QUEUE_PROMPTS[1], cut=6),
                    consume("q2", _QUEUE_PROMPTS[2]))
            served = loop.run_until_complete(go())
        finally:
            a.shutdown()
            loop.close()
        assert served[0] == base["q0"][0] and served[2] == base["q2"][0]
        assert any(y for _, _, y in a._interleave.trace)

    def test_planted_early_release_is_caught(self, monkeypatch):
        """The fault itself: the loop forgets that the successor, already
        dispatched, has a row for a sequence that finishes now, and hands
        its pages and slot back at once."""
        monkeypatch.delenv("KGCT_SANITIZE", raising=False)
        cfg = _queue_cfg()
        eos, _ = _eos_mid_window(cfg)
        monkeypatch.setenv("KGCT_SANITIZE", "1")
        eng = LLMEngine(cfg, eos_token_id=eos)
        commit = eng._process_window
        eng._process_window = (
            lambda rec, toks, lps, carried=frozenset(), **kw:
            commit(rec, toks, lps, frozenset(), **kw))
        with pytest.raises(SanitizerError, match="device-queue shadow"):
            _staged(eng)
