"""Chunked prefill + prefill admission fairness.

Chunked prefill: a prompt longer than max_prefill_tokens streams through in
solo chunks that attend to the sequence's committed pool history
(ops.attention.prefill_history_attention_xla). The bar: IDENTICAL greedy
output to an engine with a budget big enough to prefill in one step.

Fairness: a blocked large prompt at the queue head must not stall small
prompts behind it (bounded lookahead, no reordering).
"""

import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (CacheConfig, EngineConfig,
                                               SchedulerConfig,
                                               get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams


def _engine(max_prefill_tokens, max_num_seqs=4, num_pages=129, mixed=False):
    # These are LEGACY-policy pins (solo-chunk admission, lookahead,
    # preemption ordering), so mixing is pinned off explicitly now that
    # mixed batching is the SchedulerConfig default; the mixed-policy
    # equivalents live in tests/test_mixed_batch.py.
    cfg = EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=8, num_pages=num_pages),
        scheduler=SchedulerConfig(
            max_num_seqs=max_num_seqs, max_prefill_tokens=max_prefill_tokens,
            decode_buckets=(1, 2, 4), prefill_buckets=(32, 64, 128, 256),
            mixed_batch_enabled=mixed))
    return LLMEngine(cfg)


def test_long_prompt_chunks_and_matches_unchunked():
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 500, 150).tolist()   # 150 > budget 32
    params = SamplingParams(max_tokens=8, temperature=0.0)

    ref_eng = _engine(max_prefill_tokens=256)
    ref = ref_eng.generate([prompt], params)[0].output_token_ids

    eng = _engine(max_prefill_tokens=32)
    out = eng.generate([prompt], params)[0].output_token_ids
    assert out == ref, (out, ref)
    # it actually chunked: 150 tokens / 32-budget => ceil = 5 prefill steps
    assert eng.scheduler.num_preemptions == 0


def test_chunk_progress_and_solo_admission():
    eng = _engine(max_prefill_tokens=32)
    eng.add_request("long", list(range(1, 81)), SamplingParams(max_tokens=4))
    eng.add_request("short", [1, 2, 3], SamplingParams(max_tokens=4))
    sched = eng.scheduler

    b1 = sched.schedule()
    assert b1.kind == "prefill" and b1.hist_len == 0 and b1.partial
    assert b1.seqs[0].request_id == "long"
    assert b1.num_seqs == 1                      # solo
    assert b1.seqs[0].num_prefilled == 32
    np.testing.assert_array_equal(b1.positions[:32], np.arange(32))

    b2 = sched.schedule()
    assert b2.hist_len == 32 and b2.partial
    np.testing.assert_array_equal(b2.positions[:32], np.arange(32, 64))

    b3 = sched.schedule()
    assert b3.hist_len == 64 and not b3.partial  # final chunk: 80 - 64 = 16
    assert b3.seqs[0].status.value == "running"
    # the short request is next (was behind the chunking head, not starved)
    b4 = sched.schedule()
    assert b4.kind == "prefill" and b4.seqs[0].request_id == "short"


def test_multiple_long_prompts_e2e():
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 500, n).tolist() for n in (100, 40, 70)]
    params = SamplingParams(max_tokens=6, temperature=0.0)
    ref = [o.output_token_ids for o in
           _engine(max_prefill_tokens=256).generate(prompts, params)]
    got = [o.output_token_ids for o in
           _engine(max_prefill_tokens=32).generate(prompts, params)]
    assert got == ref


def test_abort_mid_chunk_releases_pages():
    eng = _engine(max_prefill_tokens=32)
    eng.add_request("long", list(range(1, 101)), SamplingParams(max_tokens=4))
    free0 = eng.scheduler.allocator.num_free
    eng.step()                                   # first chunk: pages held
    assert eng.scheduler.allocator.num_free < free0
    assert eng.abort_request("long")
    # The next chunk is already queued on the device behind the first and
    # writes those pages: they come back when it has been fetched.
    eng.step()
    assert eng.scheduler.allocator.num_free == free0
    assert not eng.has_unfinished_requests()


def test_lookahead_admits_small_behind_blocked_large():
    """Pool sized so the large head prompt cannot get pages while small ones
    can: the small ones must still be admitted (no head-of-line blocking),
    and the queue order must be preserved for the head."""
    eng = _engine(max_prefill_tokens=64, num_pages=9)  # 8 usable pages
    sched = eng.scheduler
    # head needs 8 pages; can_allocate(8) is True only when pool empty —
    # admit a small seq first to occupy pages.
    eng.add_request("small-0", [1, 2, 3], SamplingParams(max_tokens=2))
    b = sched.schedule()
    assert b.seqs[0].request_id == "small-0"     # takes 1 page
    eng2_prompt = list(range(1, 62))             # needs 8 pages > 7 free
    eng.add_request("big", eng2_prompt, SamplingParams(max_tokens=2))
    eng.add_request("small-1", [4, 5], SamplingParams(max_tokens=2))
    b2 = sched.schedule()
    assert b2 is not None, "small-1 was starved behind the blocked big prompt"
    assert [s.request_id for s in b2.seqs] == ["small-1"]
    # big is still at the queue head, unreordered
    assert sched.waiting[0].request_id == "big"


def test_blocked_chunk_head_does_not_starve_small():
    """A chunkable head that cannot get pages falls through to lookahead
    admission; once pages free, the head gets first claim."""
    eng = _engine(max_prefill_tokens=32, num_pages=9)   # 8 usable pages
    sched = eng.scheduler
    eng.add_request("small-0", [1, 2, 3], SamplingParams(max_tokens=2))
    assert sched.schedule().seqs[0].request_id == "small-0"  # holds 1 page
    # chunkable head: first chunk needs 4 pages; only fits while <=4 free...
    # fill more pages so the chunk is blocked
    eng.add_request("eater", list(range(1, 30)), SamplingParams(max_tokens=2))
    b = sched.schedule()
    assert b.seqs[0].request_id == "eater"               # 4 more pages
    eng.add_request("big", list(range(1, 60)), SamplingParams(max_tokens=2))
    eng.add_request("small-1", [7, 8], SamplingParams(max_tokens=2))
    # big's first chunk needs 4 pages, 3 free -> blocked; small-1 (1 page) goes
    b2 = sched.schedule()
    assert b2 is not None and b2.seqs[0].request_id == "small-1"
    assert sched.waiting[0].request_id == "big"          # still the head


def test_preemption_never_displaces_mid_chunk_head():
    """A preempted victim must slot in BEHIND a mid-chunk head — displacing
    it would strand its held pages (scheduler deadlock)."""
    eng = _engine(max_prefill_tokens=32, num_pages=17)
    sched = eng.scheduler
    eng.add_request("victim", [1, 2], SamplingParams(max_tokens=2))
    assert sched.schedule().seqs[0].request_id == "victim"   # now running
    eng.add_request("big", list(range(1, 70)), SamplingParams(max_tokens=2))
    b = sched.schedule()
    assert b.partial and sched.waiting[0].request_id == "big"  # mid-chunk head
    assert sched._preempt_youngest()
    # the mid-chunk head must still be first; victim slots in behind it
    assert sched.waiting[0].request_id == "big"
    assert sched.waiting[1].request_id == "victim"
