"""End-to-end engine tests: continuous batching, stops, determinism,
preemption — automated versions of the reference's manual serving smoke
checks (SURVEY §4)."""

import time

import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams


def make_engine(eos=None, num_pages=128, max_seqs=8, **model_over):
    cfg = EngineConfig(
        model=get_model_config("debug-tiny", **model_over),
        cache=CacheConfig(page_size=8, num_pages=num_pages),
        scheduler=SchedulerConfig(
            max_num_seqs=max_seqs, max_prefill_tokens=256,
            decode_buckets=(1, 2, 4, 8), prefill_buckets=(32, 64, 128, 256)))
    return LLMEngine(cfg, eos_token_id=eos)


def test_greedy_matches_teacher_forcing():
    """Engine greedy output must equal the oracle: repeatedly full-prefill the
    growing sequence and take argmax — validates paged decode against dense
    attention through the whole engine path."""
    import jax.numpy as jnp
    from tests.test_model import _prefill_whole

    eng = make_engine()
    prompt = [5, 99, 23, 44, 17]
    n_gen = 10
    out = eng.generate([prompt], SamplingParams(max_tokens=n_gen, temperature=0.0))[0]

    cfg = eng.model_config
    seq = list(prompt)
    expected = []
    for _ in range(n_gen):
        logits, _, _ = _prefill_whole(cfg, eng.params, seq)
        nxt = int(np.argmax(np.asarray(logits)))
        expected.append(nxt)
        seq.append(nxt)
    assert out.output_token_ids == expected


def test_multiple_requests_interleaved():
    eng = make_engine()
    prompts = [[1, 2, 3], [10, 11, 12, 13, 14, 15, 16], [7]]
    outs = eng.generate(prompts, SamplingParams(max_tokens=6, temperature=0.0))
    assert all(len(o.output_token_ids) == 6 for o in outs)
    assert all(o.finish_reason == "length" for o in outs)
    # All KV pages returned after completion.
    assert eng.scheduler.allocator.num_free == eng.scheduler.allocator.num_pages - 1


def test_eos_stop():
    eng = make_engine()
    # Find which token greedy decoding emits first, then declare it EOS.
    probe = eng.generate([[3, 1, 4]], SamplingParams(max_tokens=1, temperature=0.0))[0]
    eos = probe.output_token_ids[0]
    eng2 = make_engine(eos=eos)
    out = eng2.generate([[3, 1, 4]], SamplingParams(max_tokens=50, temperature=0.0))[0]
    assert out.finish_reason == "stop"
    assert out.output_token_ids[-1] == eos and len(out.output_token_ids) == 1
    out = eng2.generate([[3, 1, 4]], SamplingParams(max_tokens=5, temperature=0.0,
                                                    ignore_eos=True))[0]
    assert out.finish_reason == "length" and len(out.output_token_ids) == 5


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(max_tokens=0)
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)


def test_seed_reproducible_across_engines_and_batchmates():
    """vLLM per-request seed semantics: same prompt + same seed => same
    tokens, independent of the engine's global PRNG state, batch position,
    or window boundaries. Different seeds diverge."""
    prompt = [5, 9, 2, 7]
    p42 = SamplingParams(max_tokens=12, temperature=1.0, seed=42)
    eng = make_engine()
    outs = eng.generate([prompt, prompt, prompt],
                        [p42, p42, SamplingParams(max_tokens=12,
                                                  temperature=1.0, seed=7)])
    assert outs[0].output_token_ids == outs[1].output_token_ids
    assert outs[0].output_token_ids != outs[2].output_token_ids

    eng2 = make_engine()       # fresh engine, different global key state
    eng2.generate([[1, 2]], SamplingParams(max_tokens=3, temperature=1.0))
    again = eng2.generate([prompt], p42)[0]
    assert again.output_token_ids == outs[0].output_token_ids


def test_frequency_penalty_suppresses_repeats():
    """Near-greedy sampling with a strong frequency penalty: every
    repetition costs 2.0 logits, far above debug-tiny's logit gaps, so the
    output cannot dwell on one token; counts must persist across chained
    decode windows (window=4 < max_tokens=16)."""
    eng = make_engine()
    prompt = [3, 1, 4]
    base = eng.generate([prompt], SamplingParams(
        max_tokens=16, temperature=0.01, seed=0))[0]
    pen = eng.generate([prompt], SamplingParams(
        max_tokens=16, temperature=0.01, seed=0,
        frequency_penalty=2.0))[0]
    counts = {}
    for t in pen.output_token_ids:
        counts[t] = counts.get(t, 0) + 1
    assert max(counts.values()) <= 3, (pen.output_token_ids, counts)
    assert len(set(pen.output_token_ids)) > len(set(base.output_token_ids)) \
        or base.output_token_ids == pen.output_token_ids


def test_preempted_seeded_penalized_output_unchanged():
    """Recompute-preemption must not change seeded+penalized results: the
    re-prefill's sampling point applies the same output-token penalties
    (built on-device from the re-prefilled batch) and the same seeded keys
    as the uninterrupted run (regression: penalties were skipped at the
    prefill sampling point)."""
    prompts = [[9, 8, 7, 6], [1, 2, 3, 4], [5, 5, 5, 5]]
    params = [SamplingParams(max_tokens=16, temperature=0.8, seed=11,
                             frequency_penalty=1.5, presence_penalty=0.5),
              SamplingParams(max_tokens=16, temperature=0.8, seed=22,
                             frequency_penalty=1.5),
              SamplingParams(max_tokens=16, temperature=0.0)]
    big = make_engine(num_pages=128, max_seqs=4)
    small = make_engine(num_pages=8, max_seqs=4)
    outs_big = big.generate(prompts, params)
    outs_small = small.generate(prompts, params)
    assert small.scheduler.num_preemptions > 0
    for a, b in zip(outs_big, outs_small):
        assert a.output_token_ids == b.output_token_ids


def test_preempted_penalized_chunked_reprefill_exact():
    """When a preempted penalized+seeded sequence's prompt+outputs exceed
    the prefill budget, the re-prefill takes the CHUNKED path — whose
    penalty histogram comes from a host resync of the full output history,
    so outputs must still match the unpressured run exactly (regression:
    the chunked path used to count only the final chunk's in-batch
    tokens)."""
    from kubernetes_gpu_cluster_tpu.config import (
        CacheConfig, EngineConfig, SchedulerConfig, get_model_config)

    def engine(num_pages):
        return LLMEngine(EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(page_size=8, num_pages=num_pages),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_prefill_tokens=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(16,))))

    prompts = [[9, 8, 7, 6], [1, 2, 3, 4], [5, 5, 5, 5]]
    params = [SamplingParams(max_tokens=20, temperature=0.8, seed=11,
                             frequency_penalty=1.5, presence_penalty=0.5),
              SamplingParams(max_tokens=20, temperature=0.8, seed=22,
                             frequency_penalty=1.5),
              SamplingParams(max_tokens=20, temperature=0.0)]
    big, small = engine(128), engine(9)
    outs_big = big.generate(prompts, params)
    outs_small = small.generate(prompts, params)
    assert small.scheduler.num_preemptions > 0
    for a, b in zip(outs_big, outs_small):
        assert a.output_token_ids == b.output_token_ids


def test_penalty_params_validated():
    with pytest.raises(ValueError):
        SamplingParams(presence_penalty=3.0)
    with pytest.raises(ValueError):
        SamplingParams(frequency_penalty=-2.5)
    with pytest.raises(ValueError):
        SamplingParams(seed="abc")
    # OpenAI accepts any integer seed (negative/64-bit are folded to 31
    # bits at batch-assembly time).
    assert SamplingParams(seed=-1).seed == -1


def test_logit_bias_forces_and_bans_tokens():
    """OpenAI logit_bias semantics: +100 effectively forces a token, -100
    bans it — across prefill (first token) AND decode windows, greedy and
    sampled dispatch paths."""
    eng = make_engine()
    prompt = [3, 1, 4]
    forced = eng.generate([prompt], SamplingParams(
        max_tokens=6, temperature=0.0, logit_bias={7: 100.0}))[0]
    assert forced.output_token_ids == [7] * 6

    greedy = eng.generate([prompt], SamplingParams(
        max_tokens=4, temperature=0.0))[0]
    banned_tok = greedy.output_token_ids[0]
    banned = eng.generate([prompt], SamplingParams(
        max_tokens=4, temperature=0.0, logit_bias={banned_tok: -100.0}))[0]
    assert banned.output_token_ids[0] != banned_tok

    sampled = eng.generate([prompt], SamplingParams(
        max_tokens=6, temperature=1.0, seed=1, logit_bias={9: 100.0}))[0]
    assert sampled.output_token_ids == [9] * 6

    # out-of-vocab ids are rejected at submission, not silently dropped
    with pytest.raises(ValueError, match="out of range"):
        eng.add_request("bad", prompt, SamplingParams(
            logit_bias={10 ** 6: -100.0}))


def test_top_logprobs_alternatives():
    """logprobs=N alternatives: the top list contains the chosen token for
    greedy rows (argmax == top-1), logprobs are sorted descending, and the
    record spans prefill + chained decode windows."""
    import math
    eng = make_engine()
    out = eng.generate([[3, 1, 4]], SamplingParams(
        max_tokens=6, temperature=0.0, logprobs=True, top_logprobs=3))[0]
    tops = out.output_top_logprobs
    assert len(tops) == 6
    for token, top, lp in zip(out.output_token_ids, tops,
                              out.output_logprobs):
        assert len(top) == 3
        ids = [t for t, _ in top]
        lps = [v for _, v in top]
        assert token == ids[0]          # greedy chose the argmax
        assert lps == sorted(lps, reverse=True)
        assert math.isclose(lps[0], lp, rel_tol=1e-5)

    with pytest.raises(ValueError):
        SamplingParams(top_logprobs=6)
    with pytest.raises(ValueError):
        SamplingParams(top_logprobs=2)   # requires logprobs


def test_logit_bias_validation():
    with pytest.raises(ValueError):
        SamplingParams(logit_bias=[1, 2])
    with pytest.raises(ValueError):
        SamplingParams(logit_bias="abc")
    with pytest.raises(ValueError):
        SamplingParams(logit_bias={5: 101.0})
    with pytest.raises(ValueError):
        SamplingParams(logit_bias={-2: 1.0})
    with pytest.raises(ValueError):
        SamplingParams(logit_bias={"x": 1.0})
    with pytest.raises(ValueError):
        SamplingParams(logit_bias={i: 1.0 for i in range(301)})
    # string keys (json) are coerced
    assert SamplingParams(logit_bias={"5": 1}).logit_bias == {5: 1.0}


def test_stochastic_sampling_runs():
    eng = make_engine()
    outs = eng.generate([[1, 2, 3]] * 2,
                        SamplingParams(max_tokens=8, temperature=0.9, top_k=20, top_p=0.9))
    assert all(len(o.output_token_ids) == 8 for o in outs)


def test_preemption_under_memory_pressure():
    """Tiny page pool forces recompute-preemption; all sequences must still
    finish correctly (the engine-level reset-then-converge property)."""
    eng = make_engine(num_pages=12, max_seqs=4)  # 11 usable pages of 8 tokens
    prompts = [[i, i + 1, i + 2, i + 3] for i in range(4)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=24, temperature=0.0))
    assert all(len(o.output_token_ids) == 24 for o in outs)
    assert eng.scheduler.num_preemptions > 0
    assert eng.scheduler.allocator.num_free == eng.scheduler.allocator.num_pages - 1


def test_preempted_greedy_output_unchanged():
    """Recompute-preemption must not change greedy results vs an unpressured
    run of the same request."""
    prompts = [[9, 8, 7, 6], [1, 2, 3, 4], [5, 5, 5, 5]]
    big = make_engine(num_pages=128, max_seqs=4)
    small = make_engine(num_pages=8, max_seqs=4)  # 7 usable pages for 3 seqs
    outs_big = big.generate(prompts, SamplingParams(max_tokens=16, temperature=0.0))
    outs_small = small.generate(prompts, SamplingParams(max_tokens=16, temperature=0.0))
    assert small.scheduler.num_preemptions > 0
    for a, b in zip(outs_big, outs_small):
        assert a.output_token_ids == b.output_token_ids


def test_abort():
    eng = make_engine()
    eng.add_request("keep", [1, 2, 3], SamplingParams(max_tokens=4, temperature=0.0))
    eng.add_request("kill", [4, 5, 6], SamplingParams(max_tokens=4, temperature=0.0))
    assert eng.abort_request("kill")
    assert not eng.abort_request("missing")
    done = []
    while eng.has_unfinished_requests():
        done += [o.request_id for o in eng.step() if o.finished]
    assert done == ["keep"]


def test_prompt_too_long_rejected():
    eng = make_engine()
    with pytest.raises(ValueError, match="exceeds"):
        eng.add_request("x", list(range(1000)))
    # The rejected request's trace span must be CLOSED (arrival + abort) —
    # an unpaired open would render as running forever in /debug/trace.
    kinds = [e.kind for e in eng.obs.tracer.events() if e.request_id == "x"]
    assert kinds == ["arrival", "abort"]


class TestDecodeWindowEquivalence:
    def test_windowed_decode_matches_single_step(self):
        """Greedy generation must be identical for decode_window=1 and =4:
        the on-device autoregressive scan is semantically the same loop."""
        import jax
        from kubernetes_gpu_cluster_tpu.models import llama as model_lib
        base = dict(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(page_size=4, num_pages=64))
        params = model_lib.init_params(base["model"], jax.random.key(7))
        prompts = [[1, 5, 9, 2], [3, 3, 7]]
        sp = SamplingParams(temperature=0.0, max_tokens=10)

        outs = {}
        for w in (1, 4):
            cfg = EngineConfig(
                scheduler=SchedulerConfig(
                    max_num_seqs=4, max_prefill_tokens=64,
                    decode_buckets=(2, 4), prefill_buckets=(16, 32),
                    decode_window=w),
                **base)
            eng = LLMEngine(cfg, params=params)
            outs[w] = [o.output_token_ids for o in eng.generate(prompts, sp)]
        assert outs[1] == outs[4]


class TestLogprobs:
    def test_greedy_logprobs_match_forward(self):
        """The engine's per-token logprob record must match the log-softmax
        of an independent forward pass for the first sampled token, align
        1:1 with output tokens, and be non-positive throughout."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from kubernetes_gpu_cluster_tpu.config import (CacheConfig,
                                                       EngineConfig,
                                                       SchedulerConfig,
                                                       get_model_config)
        from kubernetes_gpu_cluster_tpu.engine.kv_cache import allocate_kv_cache
        from kubernetes_gpu_cluster_tpu.models import llama as model_lib

        cfg = EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(page_size=16, num_pages=33),
            scheduler=SchedulerConfig(max_num_seqs=2, max_prefill_tokens=64,
                                      decode_buckets=(1, 2),
                                      prefill_buckets=(64,)))
        params = model_lib.init_params(cfg.model, jax.random.key(0))
        eng = LLMEngine(cfg, params=params)
        prompt = [1, 5, 9, 2]
        out = eng.generate([prompt], SamplingParams(
            temperature=0.0, max_tokens=4, logprobs=True))[0]
        assert len(out.output_logprobs) == len(out.output_token_ids)
        assert all(lp <= 0.0 for lp in out.output_logprobs)

        # Manual prefill forward -> log-softmax at the sampled token.
        T = 64
        toks = np.zeros(T, np.int32)
        toks[:len(prompt)] = prompt
        seg = np.where(np.arange(T) < len(prompt), 0, -1).astype(np.int32)
        pos = np.where(np.arange(T) < len(prompt),
                       np.arange(T), 0).astype(np.int32)
        slots = np.where(np.arange(T) < len(prompt),
                         16 + np.arange(T), np.arange(T) % 16).astype(np.int32)
        meta = model_lib.StepMeta(
            seg_ids=jnp.asarray(seg), positions=jnp.asarray(pos),
            slot_mapping=jnp.asarray(slots),
            logits_indices=jnp.asarray([len(prompt) - 1], jnp.int32))
        kv = allocate_kv_cache(cfg.model, cfg.cache, 33)
        hidden, _, _ = model_lib.forward(params, cfg.model,
                                         jnp.asarray(toks), meta, kv)
        logits = model_lib.compute_logits(params, cfg.model, hidden)[0]
        assert out.output_token_ids[0] == int(jnp.argmax(logits))
        ref_lp = float(jax.nn.log_softmax(logits)[out.output_token_ids[0]])
        np.testing.assert_allclose(out.output_logprobs[0], ref_lp,
                                   rtol=1e-4, atol=1e-4)


# -- the one-deep device queue: every step kind dispatched behind the one in
# flight must give what the same engine gives when it schedules each step
# only after the one before it was fetched ----------------------------------

def _queue_engine(model="debug-tiny", eos=None, num_pages=128,
                  max_model_len=None, **sched):
    kw = dict(max_num_seqs=4, max_prefill_tokens=32, decode_buckets=(1, 2, 4),
              prefill_buckets=(16, 32), decode_window=4)
    kw.update(sched)
    return LLMEngine(EngineConfig(
        model=get_model_config(model), max_model_len=max_model_len,
        cache=CacheConfig(page_size=8, num_pages=num_pages),
        scheduler=SchedulerConfig(**kw)), eos_token_id=eos)


def _drive(eng, arrivals, aborts=()):
    """Step ``eng`` through a script: ``arrivals`` are (call, request id,
    prompt, params), ``aborts`` (call, request id); ``call`` counts the
    calls of ``step()``. Returns request id -> (tokens, finish reason,
    logprobs, the alternatives' ids, their values)."""
    arrivals, aborts = list(arrivals), list(aborts)
    seen, call = {}, 0
    while arrivals or aborts or eng.has_unfinished_requests():
        for item in [a for a in arrivals if a[0] <= call]:
            arrivals.remove(item)
            eng.add_request(*item[1:])
        for item in [a for a in aborts if a[0] <= call]:
            aborts.remove(item)
            eng.abort_request(item[1])
        if eng.has_unfinished_requests():
            for o in eng.step():
                seen[o.request_id] = (
                    list(o.output_token_ids), o.finish_reason,
                    list(o.output_logprobs or []),
                    [[t for t, _ in row]
                     for row in o.output_top_logprobs or []],
                    [[v for _, v in row]
                     for row in o.output_top_logprobs or []])
        call += 1
        assert call < 2000
    return seen


_RNG = np.random.default_rng(7)
_P = [[int(t) for t in _RNG.integers(1, 200, n)]
      for n in (5, 12, 9, 80, 20, 7, 30)]
_GREEDY = SamplingParams(max_tokens=21, temperature=0.0)
_BLOCK = "debug-block-moe"      # block_length 4: a pass yields 0-4 tokens


def _staggered(params, prompts=_P[:3] + _P[4:6], every=2):
    params = params if isinstance(params, list) else [params] * len(prompts)
    return [(i * every, f"r{i}", p, sp)
            for i, (p, sp) in enumerate(zip(prompts, params))]


def _eos_of(model="debug-tiny"):
    """An id the greedy decode of ``_P[0]`` emits a few tokens in: declared
    EOS it ends that request inside a decode window."""
    out = _queue_engine(model).generate(
        [_P[0]], SamplingParams(max_tokens=12, temperature=0.0))[0]
    return out.output_token_ids[6]


QUEUE_CASES = {
    "greedy": lambda: dict(arrivals=_staggered(_GREEDY)),
    "seeded_penalties": lambda: dict(arrivals=_staggered(
        [SamplingParams(max_tokens=18 + i, temperature=0.8, top_k=20,
                        seed=11 + i, presence_penalty=0.3 * (i % 2),
                        frequency_penalty=0.2, logprobs=True)
         for i in range(5)])),
    "top_logprobs_5": lambda: dict(arrivals=_staggered(
        SamplingParams(max_tokens=13, temperature=0.0, logprobs=True,
                       top_logprobs=5))),
    # 80 tokens at a budget of 32 beside decode rows: three mixed steps
    "three_chunks_mixed": lambda: dict(
        arrivals=[(0, "r0", _P[0], _GREEDY), (0, "r1", _P[1], _GREEDY),
                  (3, "long", _P[3], _GREEDY), (5, "r2", _P[2], _GREEDY)],
        kinds={"mixed": 3}),
    "packed_prefill_of_three": lambda: dict(
        arrivals=[(0, "r0", _P[0], _GREEDY)]
        + [(2, f"p{i}", p, _GREEDY) for i, p in enumerate(_P[:3])],
        sched=dict(mixed_batch_enabled=False)),
    "max_tokens_and_eos_in_window": lambda: dict(
        arrivals=[(0, "r0", _P[0], SamplingParams(max_tokens=40,
                                                  temperature=0.0)),
                  (0, "r1", _P[1], SamplingParams(max_tokens=6,
                                                  temperature=0.0,
                                                  ignore_eos=True)),
                  (1, "r2", _P[2], SamplingParams(max_tokens=15,
                                                  temperature=0.0,
                                                  ignore_eos=True)),
                  (7, "r3", _P[5], _GREEDY)],
        eos=_eos_of(), reasons={"r0": "stop", "r1": "length"}),
    "abort_in_flight": lambda: dict(
        arrivals=_staggered(SamplingParams(max_tokens=30, temperature=0.0)),
        aborts=[(4, "r1"), (9, "r3")]),
    "preemption": lambda: dict(
        arrivals=_staggered(SamplingParams(max_tokens=40, temperature=0.0),
                            every=1),
        num_pages=14, preempts=True),
    # A pool of 5 pages: "a" holds 2-5 of them and "b" (4 to be admitted)
    # waits; "a" ends in the step in flight and leaves ``running`` with its
    # pages still held, which is no empty pool: "b" is served a step later,
    # not finished at capacity.
    "eos_frees_tight_pool": lambda: dict(
        arrivals=[(0, "a", _P[0], SamplingParams(max_tokens=30,
                                                 temperature=0.0)),
                  (1, "b", _P[6], SamplingParams(max_tokens=8,
                                                 temperature=0.0,
                                                 ignore_eos=True))],
        eos=_eos_of(), num_pages=6, reasons={"a": "stop", "b": "length"},
        tokens={"b": 8}),
    "abort_frees_tight_pool": lambda: dict(
        arrivals=[(0, "a", _P[0], SamplingParams(max_tokens=30,
                                                 temperature=0.0)),
                  (1, "b", _P[6], SamplingParams(max_tokens=8,
                                                 temperature=0.0))],
        aborts=[(4, "a")], num_pages=6, reasons={"b": "length"},
        tokens={"b": 8}),
    # One packed prefill of three, then windows over the same three rows
    # to the same last token: both loops build the very same batches, so
    # here the values are held bit for bit.
    "same_batches_bitwise": lambda: dict(
        arrivals=[(0, f"r{i}", p, SamplingParams(
            max_tokens=21, temperature=0.0, logprobs=True, top_logprobs=5))
            for i, p in enumerate(_P[:3])],
        bitwise=True),
    "latent_page_experts": lambda: dict(
        model="debug-mla-moe",
        arrivals=_staggered(_GREEDY) + [(4, "long", _P[3], _GREEDY)]),
    "state_slots": lambda: dict(
        model="debug-ssm-hybrid",
        arrivals=_staggered(_GREEDY) + [(4, "long", _P[3], _GREEDY)]),
    # A block model (engine/block.py): a program's rows read their open
    # blocks from the final state of the one in flight, and the host
    # replays each program one program late. 80 tokens at a budget of 32
    # beside decode rows: three mixed steps, each one pass.
    "block_chunks_mixed": lambda: dict(
        model=_BLOCK,
        arrivals=_staggered(_GREEDY) + [(4, "long", _P[3], _GREEDY)],
        kinds={"mixed": 3}),
    # a stop id and two ``max_tokens`` (one off a block's edge, one on it)
    # fall inside windows: each row rides the program behind as a zombie
    "block_stop_and_max_tokens_in_window": lambda: dict(
        model=_BLOCK,
        arrivals=[(0, "r0", _P[0], SamplingParams(max_tokens=40,
                                                  temperature=0.0)),
                  (0, "r1", _P[1], SamplingParams(max_tokens=6,
                                                  temperature=0.0,
                                                  ignore_eos=True)),
                  (1, "r2", _P[2], SamplingParams(max_tokens=16,
                                                  temperature=0.0,
                                                  ignore_eos=True)),
                  (7, "r3", _P[5], _GREEDY)],
        eos=_eos_of(_BLOCK), reasons={"r0": "stop", "r1": "length",
                                      "r2": "length"},
        tokens={"r0": 5, "r1": 6, "r2": 16}),
    # (a call later than the token models': a block model's prefill samples
    # nothing, its first tokens leave with the next program)
    "block_abort_in_flight": lambda: dict(
        model=_BLOCK,
        arrivals=_staggered(SamplingParams(max_tokens=30, temperature=0.0)),
        aborts=[(5, "r1"), (10, "r3")]),
    # the victim's open block is on the chip: fetched first, preempted then
    # (``redrawn`` in the test: what a victim's ids are held to)
    "block_preemption": lambda: dict(
        model=_BLOCK,
        arrivals=_staggered(SamplingParams(max_tokens=40, temperature=0.0),
                            every=1),
        num_pages=14, preempts=True),
    # the sampler's keys take the DEVICE's count of a block's passes
    "block_seeded_top_logprobs_5": lambda: dict(
        model=_BLOCK,
        arrivals=_staggered(
            [SamplingParams(max_tokens=18 + i, temperature=0.8, top_k=20,
                            seed=11 + i, logprobs=True, top_logprobs=5)
             for i in range(5)])),
    # prompts on a block's edge, a position a pass, a window of block_length
    # passes: every row's block turns whole (PENDING: its K/V in no page) in
    # the LAST pass of a program and is written by the FIRST pass of the
    # next, which reads the flag and the ids from the final state handed on
    "block_pending_across_programs": lambda: dict(
        model=_BLOCK,
        arrivals=[(0, f"r{i}", p, SamplingParams(
            max_tokens=20, temperature=0.0, logprobs=True, top_logprobs=5))
            for i, p in enumerate((_P[1], _P[4]))],
        pending_across=True, bitwise=True),
    # prompts of one length modulo the block, a position a pass, outputs
    # that end on a block's edge (whatever the order of the last block's
    # transfers): the rows end in one pass, and both loops build the very
    # same batches
    "block_same_batches_bitwise": lambda: dict(
        model=_BLOCK,
        arrivals=[(0, f"r{i}", p, SamplingParams(
            max_tokens=19, temperature=0.0, logprobs=True, top_logprobs=5))
            for i, p in enumerate((_P[0], _P[2]))],
        bitwise=True),
}


@pytest.mark.parametrize("case", sorted(QUEUE_CASES))
def test_device_queue_matches_chain_broken_every_step(case):
    """Token ids of every request bit for bit, logprobs to float32
    rounding (bit for bit where both loops build the same batches): the loop that dispatches each step behind the one in flight
    against the same engine made to fetch every step before it schedules
    the next."""
    spec = QUEUE_CASES[case]()
    kw = dict(model=spec.get("model", "debug-tiny"), eos=spec.get("eos"),
              num_pages=spec.get("num_pages", 128), **spec.get("sched", {}))
    results = {}
    # A block model's victim drops its open block and denoises it again
    # from the tokens that had left: positions it had transferred ahead of
    # a masked one are drawn anew, beside other neighbours, so from there
    # on its ids are those of WHEN it was preempted (either loop's are a
    # generation of the model; tests/test_block_diffusion.py). Such a row
    # is held to its finish reason and length; every other row, preempted
    # or not, to every id.
    redrawn, pending, commits = set(), {}, {}
    for mode in ("queued", "broken"):
        eng = _queue_engine(**kw)
        if mode == "broken":
            eng._chain_break = lambda pred: "forced"

        def fill(seqs, R, _fill=eng.scheduler.fill_block_rows, mode=mode):
            pending[mode] = pending.get(mode, 0) + sum(
                s.block_pending for s in seqs)
            return _fill(seqs, R)
        eng.scheduler.fill_block_rows = fill

        def requeue(seq, _requeue=eng.scheduler._requeue_for_recompute,
                    **how):
            if not all(seq.block_masked[seq.num_tokens - seq.num_committed:]):
                redrawn.add(seq.request_id)
            return _requeue(seq, **how)
        eng.scheduler._requeue_for_recompute = requeue
        results[mode] = _drive(eng, spec["arrivals"], spec.get("aborts", ()))
        commits[mode] = eng.obs.block_commits
        alloc = eng.scheduler.allocator
        assert alloc.num_free == alloc.num_pages - 1, (mode, "pages leaked")
        assert not eng._deferred_release and eng._inflight is None
        behind = sum(n for (_, b), n in eng.obs.steps_dispatched.items() if b)
        assert "block" not in eng.obs.chain_breaks
        if mode == "queued":
            assert behind > 0 and "forced" not in eng.obs.chain_breaks
            if spec.get("preempts"):
                assert eng.scheduler.num_preemptions > 0
                assert eng.obs.chain_breaks.get("no_pages", 0) > 0
            for kind, n in spec.get("kinds", {}).items():
                assert eng.obs.step_kind_counts[kind] >= n
        else:
            assert behind == 0 and eng.obs.chain_breaks["forced"] > 0
    queued, broken = results["queued"], results["broken"]
    assert queued.keys() == broken.keys()
    if spec.get("pending_across"):
        # fetched first, the host sees every program start on pending rows;
        # chained, the device hands the flag on: the same blocks are written
        assert pending["broken"] >= commits["broken"] - 1 > 0
        assert commits["queued"] == commits["broken"]
    aborted = {rid for _, rid in spec.get("aborts", ())}
    for rid in queued:
        if rid in aborted:
            # cut at another token by the call it fell in: one a prefix
            a, b = queued[rid][0], broken[rid][0]
            n = min(len(a), len(b))
            assert a[:n] == b[:n]
            continue
        if rid in redrawn:
            assert queued[rid][1] == broken[rid][1], rid
            assert len(queued[rid][0]) == len(broken[rid][0]), rid
            continue
        # ids, finish reason and the alternatives' ids: equal. The values:
        # to float32 rounding, for a row's logits come from programs of
        # other row counts where a prompt rides a window later.
        assert queued[rid][:2] == broken[rid][:2], rid
        assert queued[rid][3] == broken[rid][3], rid
        if spec.get("bitwise"):
            assert queued[rid][2] and queued[rid] == broken[rid], rid
        np.testing.assert_allclose(queued[rid][2], broken[rid][2],
                                   rtol=0, atol=2e-5, err_msg=rid)
        for a, b in zip(queued[rid][4], broken[rid][4]):
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-5, err_msg=rid)
    for rid, reason in spec.get("reasons", {}).items():
        assert queued[rid][1] == reason
    for rid, n in spec.get("tokens", {}).items():
        assert len(queued[rid][0]) == n
    # (only the block model's tight pool meets it, and not on every row)
    assert len(redrawn) < len(queued)
    assert not redrawn or (spec.get("preempts") and kw["model"] == _BLOCK)


def test_warm_full_window_meets_the_full_seat_program_and_nothing_else():
    """``warm_full_window`` runs the greedy window at the largest row
    bucket over padding rows alone: one step program more has met its
    shape, no page, step or key of the engine is used up, and what is
    served afterwards (greedy, and sampled from the engine's own key) is
    what an engine that was never warmed serves."""
    prompts = _P[:3] + _P[4:6]
    cold, warm = _queue_engine(), _queue_engine()
    before = warm.compiled_step_variants()
    warm.warm_full_window()
    assert warm.compiled_step_variants() == before + 1
    assert warm.step_count == 0
    alloc = warm.scheduler.allocator
    assert alloc.num_free == alloc.num_pages - 1
    for temperature in (0.0, 0.8):
        p = SamplingParams(max_tokens=9, temperature=temperature)
        want = [o.output_token_ids for o in cold.generate(prompts, p)]
        assert [o.output_token_ids
                for o in warm.generate(prompts, p)] == want
    # what was warmed IS the program full seats run: the unwarmed engine
    # met it in its load, and holds no shape fewer
    assert warm.compiled_step_variants() == cold.compiled_step_variants()


def test_warm_mixed_steps_meets_a_short_prompts_step_beside_full_seats():
    """``warm_mixed_steps`` runs the mixed step of the smallest prefill
    bucket at the largest row bucket over padding alone: one step program
    more has met its shape, no page, step or key of the engine is used up,
    what is served afterwards is what an engine that was never warmed
    serves, and what was warmed IS the program that a prompt inside a page
    runs beside the rows in their seats: the unwarmed engine met it in its
    load, and holds no shape fewer."""
    long = SamplingParams(max_tokens=30, temperature=0.0)
    arrivals = [(0, f"r{i}", _P[i], long) for i in (1, 2, 4)] + [
        (4, "short", _P[0], SamplingParams(max_tokens=6, temperature=0.8)),
        (9, "short2", _P[5], _GREEDY)]
    cold, warm = _queue_engine(), _queue_engine()
    before = warm.compiled_step_variants()
    warm.warm_mixed_steps()
    assert warm.compiled_step_variants() == before + 1
    assert warm.step_count == 0 and warm.stats.prefill_tokens == 0
    alloc = warm.scheduler.allocator
    assert alloc.num_free == alloc.num_pages - 1
    met = warm.compiled_step_variants()
    want = _drive(cold, arrivals)
    assert _drive(warm, arrivals) == want
    assert ("mixed", True) in cold.obs.steps_dispatched
    assert warm.compiled_step_variants() == cold.compiled_step_variants()
    assert warm._mixed_fn._cache_size() == cold._mixed_fn._cache_size()
    assert met < warm.compiled_step_variants()      # the load met others


def test_warm_mixed_steps_meets_every_step_of_a_long_prompt_it_was_named():
    """``warm_prompt_lens`` names a prompt of several chunks: what is warmed
    is one step program a (chunk rung, history width) such a prompt passes
    through beside full seats, beside the short prompt's, and those ARE the
    programs its chunks run: the load compiles no mixed step more, where
    the unwarmed engine compiles at first use each one it meets."""
    long = SamplingParams(max_tokens=40, temperature=0.0)
    arrivals = [(0, f"r{i}", _P[i], long) for i in (1, 2, 4)] + [
        (4, "doc", _P[3], _GREEDY), (30, "short", _P[0], _GREEDY)]
    cold, warm = _queue_engine(), _queue_engine(warm_prompt_lens=(1, 80))
    assert cold.config.scheduler.warm_prompt_lens == (1,)
    before = warm.compiled_step_variants()
    warm.warm_mixed_steps()
    # 80 tokens beside 3 rows: chunks of 29, 29 and 22 tokens, all on the
    # rung of 32, over 4, 8 and 10 pages: widths 4, 8, 16; and (16, 1)
    assert warm.compiled_step_variants() == before + 4
    assert warm.step_count == 0 and warm.stats.prefill_tokens == 0
    met = warm._mixed_fn._cache_size()
    want = _drive(cold, arrivals)
    assert _drive(warm, arrivals) == want
    # (the unwarmed engine met the two full chunks' and the short one's)
    assert warm._mixed_fn._cache_size() == met
    assert 3 <= cold._mixed_fn._cache_size() <= met


def _rung_engine(monkeypatch, kind, **sched):
    """An engine on a server's top chunk buckets (the rung of
    ``mixed_chunk_buckets`` between them), and the token widths of the
    ``kind`` steps its scheduler hands out."""
    eng = _queue_engine(sched.pop("model", "debug-tiny"), num_pages=400,
                        max_model_len=2048, max_prefill_tokens=2048,
                        prefill_buckets=(32, 1024, 2048), decode_window=2,
                        **sched)
    widths = []
    schedule = eng.scheduler.schedule

    def spy(*args, **kw):
        batch = schedule(*args, **kw)
        if batch is not None and batch.kind == kind:
            widths.append(len(batch.tokens))
        return batch
    monkeypatch.setattr(eng.scheduler, "schedule", spy)
    return eng, widths


def _take_the_rung_away(monkeypatch):
    monkeypatch.setattr(SchedulerConfig, "mixed_chunk_buckets",
                        property(lambda self: self.prefill_buckets))


_LONG = [int(t) for t in np.random.default_rng(3).integers(1, 200, 1100)]


@pytest.mark.parametrize("model", ["debug-tiny", "debug-mla-moe",
                                   "debug-ssm-hybrid", "debug-kda-hybrid"])
def test_a_chunk_in_the_rung_is_served_as_in_the_bucket_above(model,
                                                              monkeypatch):
    """A prompt of 1100 tokens rides its mixed step beside a decoding row in
    the 1536-token rung of ``mixed_chunk_buckets``; with the rung taken away
    the same engine runs it in the 2048-token program. Padding tokens carry
    segment -1 and write the scrap page, and every matmul, scan and
    attention row is a row's own: both requests get the same tokens
    whichever program served them, and the decoding row the same logprobs
    bit for bit. The prompt's own agree to float32 rounding only: the XLA
    reference attention (what the CPU runs) sums a query's softmax over the
    step's whole key axis, masked padding included, and that sum's tiling
    follows the axis' width (0.7e-6 to 1.9e-6 apart in these four presets)."""
    eng, widths = _rung_engine(monkeypatch, "mixed", model=model)
    p = SamplingParams(max_tokens=12, temperature=0.0, logprobs=True)
    arrivals = [(0, "held", _P[1], p), (2, "long", _LONG, p)]
    with_rung = _drive(eng, arrivals)
    assert widths == [1536 + 4]
    _take_the_rung_away(monkeypatch)
    without = _drive(eng, arrivals)
    assert widths == [1536 + 4, 2048 + 4]
    assert without["held"] == with_rung["held"]
    assert without["long"][:2] == with_rung["long"][:2]
    assert len(with_rung["long"][2]) == 12
    np.testing.assert_allclose(without["long"][2], with_rung["long"][2],
                               rtol=0, atol=1e-5)


def test_a_speculative_mixed_step_takes_the_rung_too(monkeypatch):
    """The chunk of a mixed step that carries verify slices buckets on the
    same ladder: 1100 tokens beside one speculating row ride 1536 + (k + 1)
    tokens, and the same tokens come out as from the 2048-token program."""
    eng, widths = _rung_engine(monkeypatch, "spec_mixed",
                               spec_decode_enabled=True,
                               num_speculative_tokens=3)
    p = SamplingParams(max_tokens=40, temperature=0.0)
    arrivals = [(0, "held", _P[0][:4] * 7, p), (2, "long", _LONG, p)]
    with_rung = _drive(eng, arrivals)
    assert widths == [1536 + 4]
    _take_the_rung_away(monkeypatch)
    without = _drive(eng, arrivals)
    assert widths == [1536 + 4, 2048 + 4]
    assert {k: v[:2] for k, v in without.items()} == {
        k: v[:2] for k, v in with_rung.items()}


# -- the step record: one a dispatched program, its own number and clock ------

@pytest.fixture(scope="module")
def recorded_run():
    """A staged run (a long prompt in three mixed steps beside decode rows,
    an arrival riding behind a window) with every record ``on_step`` was
    handed, in order, and what /debug/trace exports afterwards."""
    eng = _queue_engine()
    records = []
    on_step = eng.obs.on_step
    eng.obs.on_step = lambda rec: (on_step(rec), records.append(rec))[0]
    _drive(eng, QUEUE_CASES["three_chunks_mixed"]()["arrivals"])
    return eng, records, eng.obs.export_perfetto()["traceEvents"]


def test_every_retired_program_has_one_record_and_the_times_add_up(
        recorded_run):
    eng, records, _ = recorded_run
    steps = [r["step"] for r in records]
    assert steps == list(range(1, eng.step_count + 1))      # each once
    assert {r["kind"] for r in records} == {"prefill", "decode", "mixed"}
    for r in records:
        assert (r["t_launch"] <= r["t_dispatched"] <= r["t_wait"]
                <= r["t_ready"] <= r["t_retired"]), r["step"]
        assert r["wait_s"] == r["t_ready"] - r["t_wait"]
        assert r["found_ready"] == (r["wait_s"] < 100e-6)
        assert 0 < r["tokens"] <= r["padded_tokens"]
        assert r["rows"] == r["batch"].num_seqs
    # a chain of programs each queued behind the one before it: device time
    # (exact or not) plus the time the chip stood waiting for a dispatch
    # tile the span from the first dispatch to the last end
    chains, by_step = [], {r["step"]: r for r in records}
    for r in records:
        if r["pred"] is None:
            chains.append([r])
        else:
            assert r["behind"] and r["pred"] == r["step"] - 1
            assert r["ready_gap_s"] == r["t_ready"] - by_step[
                r["pred"]]["t_ready"]
            chains[-1].append(r)
    assert max(map(len, chains)) > 3
    for chain in chains:
        assert chain[0]["ready_gap_s"] is None and not chain[0]["behind"]
        busy = sum(r["device_s"] for r in chain)
        stood = sum(max(-r["lead_s"], 0.0) for r in chain[1:])
        span = chain[-1]["t_ready"] - chain[0]["t_dispatched"]
        assert busy + stood == pytest.approx(span, abs=1e-9)
        for prev, r in zip(chain, chain[1:]):
            assert r["exact"] == (not r["found_ready"]
                                  and not prev["found_ready"])
    # the histogram holds the exact ones and nothing else
    exact = [r for r in records if r["exact"]]
    assert eng.obs.step_device.count == len(exact)
    assert eng.obs.step_device.sum == pytest.approx(
        sum(r["device_s"] for r in exact))
    assert sum(eng.obs.steps_retired.values()) == len(records)
    assert eng.obs.step_tokens[("mixed", True)] == sum(
        r["tokens"] for r in records if r["kind"] == "mixed")
    # the lead: a chained program is dispatched before its predecessor's
    # fetch begins, so it is never negative here; observed where that
    # predecessor was waited for. The starved seconds: what lies between
    # two chains (a program launched with nothing in flight, behind one
    # already retired), by the late program's kind; every output of a
    # program shares ONE clock, closed before it left ``step()``
    for chain in chains:
        for prev, r in zip(chain, chain[1:]):
            assert r["lead_s"] >= 0 and r["starved_s"] == 0.0
            assert r["lead_exact"] == (not prev["found_ready"])
    led = [r for r in records if r["lead_exact"]]
    assert eng.obs.step_lead.count == len(led) > 3
    assert eng.obs.step_lead.sum == pytest.approx(
        sum(r["lead_s"] for r in led))
    for prev, r in zip(records, records[1:]):
        if r["pred"] is None:
            assert r["starved_s"] == r["t_dispatched"] - prev["t_ready"] > 0
    assert records[0]["starved_s"] == 0.0
    assert sum(eng.obs.device_starved.values()) == pytest.approx(
        sum(r["starved_s"] for r in records))
    for r in records:
        clock = r["clock"]
        assert (clock.step, clock.t_ready, clock.t_retired) == (
            r["step"], r["t_ready"], r["t_retired"])


@pytest.mark.parametrize("model", ["debug-tiny", "debug-block-moe"])
def test_the_outputs_of_one_program_share_its_clock(model):
    """Both output paths (``_process_window``; a block program's replay):
    what one call of ``step()`` returns with a clock holds the SAME object,
    that of the program it retired, closed; a drained output has none."""
    eng = _queue_engine(model)
    sp = SamplingParams(max_tokens=9, temperature=0.0)
    for i in range(3):
        eng.add_request(f"r{i}", _P[i], sp)
    retired = []
    on_step = eng.obs.on_step
    eng.obs.on_step = lambda rec: (on_step(rec), retired.append(rec))[0]
    shared = 0
    while eng.has_unfinished_requests():
        n = len(retired)
        outs = eng.step()
        clocks = {id(o.clock): o.clock for o in outs if o.clock is not None}
        assert len(clocks) <= 1
        for clock in clocks.values():
            [rec] = retired[n:]
            assert clock is rec["clock"] and clock.step == rec["step"]
            assert clock.t_ready <= clock.t_retired <= time.monotonic()
            shared += sum(o.clock is clock for o in outs) > 1
    assert shared > 0


def test_trace_slices_carry_the_step_and_kind_of_the_program_they_served(
        recorded_run):
    """An iteration dispatches program n+1 and fetches program n: the
    dispatch's slices are filed under n+1 and its kind, the fetch's under
    n and ITS kind (the parent filed both under the number of n+1 and the
    kind of n)."""
    _, records, events = recorded_run
    kinds = {r["step"]: r["kind"] for r in records}
    slices = [e for e in events if e.get("ph") == "X"]
    seen: dict = {}
    for e in slices:
        a = e["args"]
        assert a["kind"] == kinds[a["step"]], e
        assert {"device_ms", "wait_ms", "lead_ms", "exact"} <= set(a)
        seen.setdefault(a["step"], []).append(e["name"])
    for step, kind in kinds.items():
        names = seen[step]
        assert names.count("device_dispatch") == 1, (step, names)
        assert names.count("device_fetch") == 1
        assert names.count("postproc") == 1
        assert names.index("schedule") < names.index("device_dispatch") \
            < names.index("device_fetch") < names.index("postproc")
    # a mixed step queued behind a window: its dispatch lies BEFORE the end
    # of the window's fetch on the timeline, and is still the mixed step's
    by = {(e["args"]["step"], e["name"]): e for e in slices}
    mixed = next(r for r in records
                 if r["kind"] == "mixed" and kinds[r["pred"]] == "decode")
    d, f = by[(mixed["step"], "device_dispatch")], \
        by[(mixed["pred"], "device_fetch")]
    assert d["ts"] < f["ts"] + f["dur"]
    assert d["args"]["kind"] == "mixed" and f["args"]["kind"] == "decode"
    # the step events of the flight recorder carry the same numbers
    evs = [e for e in events if e.get("cat") == "engine"]
    assert [e["args"]["step"] for e in evs] == sorted(kinds)
    assert all(e["name"] == kinds[e["args"]["step"]] for e in evs)


def test_request_events_name_the_steps_that_served_them(recorded_run):
    _, records, events = recorded_run
    kinds = {r["step"]: r["kind"] for r in records}
    by_req: dict = {}
    for e in events:
        if e.get("cat") == "request" and e.get("ph") == "n":
            by_req.setdefault(e["id"], {}).setdefault(
                e["name"], []).append(e["args"])
    assert set(by_req) == {"r0", "r1", "long", "r2"}
    for rid, ev in by_req.items():
        [sched] = ev["scheduled"]
        [first] = ev["first_token"]
        assert sched["step"] in kinds and first["step"] in kinds
        assert sched["step"] <= first["step"]
    # the long prompt: three chunks in three mixed steps, the first token
    # out of the last of them
    chunks = [a["step"] for a in by_req["long"]["prefill_chunk"]]
    assert len(chunks) == 3 and all(kinds[s] == "mixed" for s in chunks)
    assert by_req["long"]["scheduled"][0]["step"] == chunks[0]
    assert by_req["long"]["first_token"][0]["step"] == chunks[-1]
    # it arrived while a window was in flight: the program that served it
    # was queued BEHIND that one (two step numbers: S5(iii))
    served = next(r for r in records if r["step"] == chunks[0])
    assert served["behind"] and kinds[served["pred"]] == "decode"


def test_spec_steps_get_the_same_record_through_the_same_helper():
    eng = _queue_engine(spec_decode_enabled=True, num_speculative_tokens=3)
    records = []
    on_step = eng.obs.on_step
    eng.obs.on_step = lambda rec: (on_step(rec), records.append(rec))[0]
    eng.generate([[7, 3, 9, 11] * 4],
                 SamplingParams(max_tokens=16, temperature=0.0))
    spec = [r for r in records if r["kind"] == "spec"]
    assert spec and [r["step"] for r in records] == list(
        range(1, eng.step_count + 1))
    for r in spec:
        assert not r["behind"] and r["pred"] is None
        assert r["t_launch"] <= r["t_dispatched"] <= r["t_wait"] \
            <= r["t_ready"] <= r["t_retired"]
        assert r["device_s"] == r["t_ready"] - r["t_dispatched"]
        assert r["tokens"] == r["rows"] * 4 <= r["padded_tokens"]
        assert r["drafted_tokens"] >= r["accepted_tokens"] >= 0
    assert eng.obs.steps_retired[("spec", True)] \
        + eng.obs.steps_retired.get(("spec", False), 0) == len(spec)
