"""Scheduler admission/preemption policy regression tests."""

import pytest

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine.scheduler import CannotChain, Scheduler
from kubernetes_gpu_cluster_tpu.engine.sampling_params import SamplingParams
from kubernetes_gpu_cluster_tpu.engine.sequence import (
    FinishReason, Sequence, SequenceStatus)


def _cfg(num_pages=8, page_size=4, max_num_seqs=4, decode_window=1):
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=page_size, num_pages=num_pages),
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs,
                                  max_prefill_tokens=64,
                                  decode_buckets=(1, 2, 4),
                                  prefill_buckets=(16, 32, 64),
                                  decode_window=decode_window))


def _seq(rid, n_prompt, max_tokens=64):
    return Sequence(rid, list(range(1, n_prompt + 1)),
                    SamplingParams(max_tokens=max_tokens))


class TestAdmission:
    def test_oversized_prompt_rejected_up_front(self):
        """A prompt needing more pages than the whole pool must raise, not
        busy-spin forever (review finding: schedule() returned None while
        has_work() stayed True)."""
        cfg = _cfg(num_pages=4, page_size=4)   # 3 usable pages = 12 tokens
        sched = Scheduler(cfg, 4)
        with pytest.raises(ValueError, match="KV pages"):
            sched.add(_seq("big", 13))
        # A fitting prompt is accepted and schedulable.
        sched.add(_seq("ok", 12))
        assert sched.schedule() is not None

    def test_no_preemption_for_waiting_sequences(self):
        """Admitting a waiting sequence must never evict running ones (review
        finding: preempt-at-admission churned full recomputes)."""
        cfg = _cfg(num_pages=9, page_size=4, max_num_seqs=8)  # 8 usable pages
        sched = Scheduler(cfg, 9)
        for i in range(4):
            sched.add(_seq(f"run-{i}", 8))     # 2 pages each -> pool full
        batch = sched.schedule()
        assert batch.kind == "prefill" and len(batch.seqs) == 4
        sched.add(_seq("late", 8))
        # Pool is full: the late arrival must wait; the step must be a decode
        # of the 4 running sequences, with nobody preempted.
        batch = sched.schedule()
        assert batch.kind == "decode" and len(batch.seqs) == 4
        assert sched.num_preemptions == 0
        assert [s.request_id for s in sched.running] == [f"run-{i}" for i in range(4)]

    def test_grown_sequence_at_pool_capacity_finishes(self):
        """A recomputed sequence grown past total pool capacity terminates at
        LENGTH instead of hanging the engine."""
        cfg = _cfg(num_pages=3, page_size=4)   # 2 usable pages = 8 tokens
        sched = Scheduler(cfg, 3)
        seq = _seq("grown", 6)
        sched.add(seq)
        assert sched.schedule() is not None    # prefill at 6 tokens (2 pages)
        # Simulate preempt-recompute growth past capacity: 9 tokens > 8.
        for t in (7, 8, 9):
            seq.append_token(t)
        sched.running.remove(seq)
        sched.allocator.free(seq.pages)
        seq.pages = []
        seq.status = SequenceStatus.PREEMPTED
        sched.waiting.appendleft(seq)
        assert sched.schedule() is None
        assert seq.status == SequenceStatus.FINISHED
        assert seq.finish_reason == FinishReason.LENGTH
        assert not sched.has_work()
        # The engine must be able to surface a finished event for it (review
        # finding: generate() raised KeyError / server clients hung).
        assert sched.terminally_finished == [seq]

    def test_engine_emits_output_for_capacity_terminated_seq(self):
        """End-to-end: a scheduler-terminated sequence still produces a
        finished RequestOutput through LLMEngine.step()."""
        from kubernetes_gpu_cluster_tpu.engine import LLMEngine

        cfg = _cfg(num_pages=3, page_size=4)   # 2 usable pages = 8 tokens
        eng = LLMEngine(cfg)
        seq = _seq("grown", 6)
        eng.scheduler.add(seq)
        for t in (7, 8, 9):                    # grown past 8-token capacity
            seq.append_token(t)
        outs = eng.step()
        assert [o.request_id for o in outs] == ["grown"]
        assert outs[0].finished and outs[0].finish_reason == "length"
        assert not eng.has_unfinished_requests()


class TestAbort:
    def test_abort_waiting_sets_finish_reason(self):
        sched = Scheduler(_cfg(), 8)
        seq = _seq("a", 4)
        sched.add(seq)
        assert sched.abort("a")
        assert seq.status == SequenceStatus.FINISHED
        assert seq.finish_reason == FinishReason.ABORT
        assert not sched.has_work()

    def test_abort_running_frees_pages_and_finishes(self):
        sched = Scheduler(_cfg(num_pages=8, page_size=4), 8)
        seq = _seq("r", 8)
        sched.add(seq)
        sched.schedule()
        free_before = sched.allocator.num_free
        assert sched.abort("r")
        assert seq.finish_reason == FinishReason.ABORT
        assert sched.allocator.num_free == free_before + 2
        assert not sched.has_work()

    def test_abort_unknown_returns_false(self):
        sched = Scheduler(_cfg(), 8)
        assert not sched.abort("nope")


class TestPreemptionInDecode:
    def test_decode_preempts_youngest_when_pool_exhausted(self):
        """Decode-path preemption (the legitimate one) still works: when a
        running sequence needs a new page and none is free, the youngest is
        evicted and re-queued."""
        cfg = _cfg(num_pages=3, page_size=2, max_num_seqs=4)  # 2 usable pages
        sched = Scheduler(cfg, 3)
        a, b = _seq("a", 2), _seq("b", 2)
        sched.add(a)
        sched.add(b)
        assert sched.schedule().kind == "prefill"   # each takes 1 page
        a.append_token(5)
        b.append_token(6)
        # Next decode: both need a second page; only 0 free -> preempt b.
        batch = sched.schedule()
        assert batch.kind == "decode"
        assert [s.request_id for s in batch.seqs] == ["a"]
        assert sched.num_preemptions == 1
        assert b.status == SequenceStatus.PREEMPTED
        assert sched.waiting[0] is b


class TestScheduleBehind:
    """``schedule(behind=True)``: a step is in flight, so what the
    scheduler's queues show is not all there is."""

    def test_no_capacity_termination_while_a_step_is_in_flight(self):
        """A sequence that ended in the step in flight has left ``running``
        and still holds its pages until that step is fetched: ``running``
        empty is then no empty pool, and the waiting head that does not
        fit yet waits, it is not finished at LENGTH."""
        cfg = _cfg(num_pages=5, page_size=4)   # 4 usable pages = 16 tokens
        sched = Scheduler(cfg, 5)
        a = _seq("a", 10)                      # 3 pages
        sched.add(a)
        assert sched.schedule().kind == "prefill"
        # "a" found finished (EOS, abort) while its successor is in flight:
        # the engine takes it out of running and defers the release.
        sched.running.remove(a)
        a.status = SequenceStatus.FINISHED
        b = _seq("b", 8, max_tokens=4)         # 2 pages: 1 is free
        sched.add(b)
        assert sched.schedule(behind=True) is None
        assert b.status != SequenceStatus.FINISHED and sched.waiting[0] is b
        assert not sched.terminally_finished
        sched._release(a)                      # the step was fetched
        batch = sched.schedule()
        assert batch.kind == "prefill" and batch.seqs == [b]

    def test_head_beyond_the_pool_is_still_finished_once_nothing_flies(self):
        cfg = _cfg(num_pages=3, page_size=4)   # 2 usable pages = 8 tokens
        sched = Scheduler(cfg, 3)
        seq = _seq("grown", 6)
        sched.add(seq)
        for t in (7, 8, 9):
            seq.append_token(t)
        assert sched.schedule(behind=True) is None
        assert seq.status != SequenceStatus.FINISHED
        assert sched.schedule() is None
        assert seq.finish_reason == FinishReason.LENGTH

    def test_growth_that_needs_a_victim_raises_instead_of_preempting(self):
        cfg = _cfg(num_pages=3, page_size=2, max_num_seqs=4)  # 2 usable pages
        sched = Scheduler(cfg, 3)
        a, b = _seq("a", 2), _seq("b", 2)
        sched.add(a)
        sched.add(b)
        assert sched.schedule().kind == "prefill"
        a.append_token(5)
        b.append_token(6)
        with pytest.raises(CannotChain) as e:
            sched.schedule(behind=True)
        assert e.value.reason == "no_pages"
        assert sched.num_preemptions == 0 and sched.running == [a, b]
        assert sched.schedule().seqs == [a]    # nothing in flight: as ever
        assert sched.num_preemptions == 1


class TestDecodeWindow:
    def test_window_preallocates_pages(self):
        """With decode_window=W the decode schedule must grow each sequence's
        page list to cover all W on-device KV writes up front."""
        cfg = _cfg(num_pages=9, page_size=4, decode_window=6)
        sched = Scheduler(cfg, 9)
        seq = _seq("w", 4)       # 1 page for the prompt
        sched.add(seq)
        assert sched.schedule().kind == "prefill"
        seq.append_token(5)
        batch = sched.schedule()
        assert batch.kind == "decode"
        # positions 4..9 -> 10 slots -> 3 pages of 4
        assert len(seq.pages) == 3


class TestChunkLadders:
    """The default grid at a server's size: the mixed step's chunk has a
    rung at 1536 tokens (``SchedulerConfig.mixed_chunk_buckets``); a packed
    prefill's total and a solo chunk keep ``prefill_buckets``."""

    @staticmethod
    def _sched():
        cfg = EngineConfig(
            model=get_model_config("debug-tiny"), max_model_len=4096,
            cache=CacheConfig(page_size=16, num_pages=1024),
            scheduler=SchedulerConfig())
        return Scheduler(cfg, 1024)

    @pytest.mark.parametrize("tokens,want", [
        (1024, 1024), (1025, 1536), (1536, 1536), (1537, 2048), (1920, 2048)])
    def test_mixed_chunk_beside_63_rows(self, tokens, want):
        sched = self._sched()
        rows = [_seq(f"r{i}", 4) for i in range(63)]
        for seq in rows:
            sched.add(seq)
        assert sched.schedule().kind == "prefill"
        for seq in rows:
            seq.append_token(7)
        sched.add(_seq("head", tokens))
        batch = sched.schedule()
        assert batch.kind == "mixed" and batch.prefill_token_count == tokens
        assert len(batch.tokens) == want + 64
        assert len(batch.context_lens) == 64

    @pytest.mark.parametrize("tokens,want", [
        (1024, 1024), (1025, 2048), (1536, 2048), (1537, 2048), (1920, 2048)])
    def test_packed_prefill_total_keeps_its_ladder(self, tokens, want):
        sched = self._sched()
        sched.add(_seq("a", tokens - 500))
        sched.add(_seq("b", 500))
        batch = sched.schedule()
        assert batch.kind == "prefill" and len(batch.seqs) == 2
        assert len(batch.tokens) == want

    @pytest.mark.parametrize("tokens,want", [
        (1024, 1024), (1025, 2048), (1536, 2048), (1537, 2048), (1920, 2048)])
    def test_solo_chunk_keeps_its_ladder(self, tokens, want):
        """A prompt over the step's budget with nothing decoding beside it:
        a chunk of 2048 tokens, then one of ``tokens`` with history."""
        sched = self._sched()
        sched.add(_seq("long", 2048 + tokens))
        assert len(sched.schedule().tokens) == 2048
        batch = sched.schedule()
        assert batch.kind == "prefill" and batch.hist_len == 2048
        assert len(batch.tokens) == want

    @pytest.mark.parametrize("buckets,want", [
        ((128, 256, 512, 1024, 2048), (128, 256, 512, 1024, 1536, 2048)),
        ((1024, 4096), (1024, 2560, 4096)),
        ((16, 32, 64), (16, 32, 64)),        # under 1024: as it was
        ((512, 1024), (512, 1024)),
        ((2048,), (2048,))])
    def test_the_rung_comes_from_the_top_two_buckets(self, buckets, want):
        sc = SchedulerConfig(prefill_buckets=buckets)
        assert sc.mixed_chunk_buckets == want
