"""Generation by diffusion over blocks on the serving path (sdar_moe: a step
that yields 0 to ``block_length`` tokens a row, block-causal attention, pages
written at a block's commit, which rides the next block's first denoising
pass), held to the plain float32 reference
(perfbench/reference/sdar_moe.py: the ONE copy, the benchmark's, which also
writes the cell's goldens) on ``debug-block-moe`` with seeded weights, B = 4.

Tolerances, with their reasons: the served path and the reference are both
float32 here, so they differ only by the ORDER of float32 sums (paged
attention against a materialised mask, grouped against per-expert dispatch,
XLA's default CPU matmul against "highest"). Log-probabilities are O(1);
2e-4 absolute is ~80x the 2.6e-6 seen and far under what a planted fault
moves them by (``test_planted_faults_read_over_the_limit``: each changes an
id or reads over 50x the limit). The ORDER of two masked positions'
confidences could flip on such a difference; none of these seeds meets a
tie that close (a flip transfers another position: ids differ, a failure,
not a tolerance)."""

import dataclasses

import jax
import numpy as np
import pytest

import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine.mixed_batch import (
    mixed_steps_of_prompt)
from kubernetes_gpu_cluster_tpu.models import llama
from perfbench.reference import sdar_moe as ref

LOGIT_TOL = 2e-4
CFG = get_model_config("debug-block-moe")
PS = 16
B = CFG.block_length


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def _engine(params, model=CFG, pages=64, **sched):
    kw = dict(max_num_seqs=4, max_prefill_tokens=64, decode_buckets=(1, 2, 4),
              prefill_buckets=(16, 32, 64))
    kw.update(sched)
    return LLMEngine(EngineConfig(
        model=model, cache=CacheConfig(page_size=PS, num_pages=pages),
        scheduler=SchedulerConfig(**kw)), params=params)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(3, 500, n).tolist()


def _distance(out, want) -> float:
    """How far a served output is from a generation of the reference: the
    largest |log-probability difference| over the tokens, infinite where an
    id differs (another position was transferred, or another candidate)."""
    if list(out.output_token_ids) != want["tokens"]:
        return float("inf")
    return float(np.abs(np.asarray(out.output_logprobs)
                        - np.asarray(want["logprobs"])).max())


def _serve(eng, prompts, max_tokens, **kw):
    return eng.generate(prompts, [SamplingParams(
        max_tokens=m, temperature=0.0, logprobs=True, top_logprobs=5, **kw)
        for m in max_tokens])


def _held_to_reference(eng, params, prompts, max_tokens, cfg=CFG, **kw):
    outs = _serve(eng, prompts, max_tokens, **kw)
    for p, m, o in zip(prompts, max_tokens, outs):
        want = ref.generate(params, cfg, p, m,
                            stop_ids=kw.get("stop_token_ids", ()))
        assert _distance(o, want) < LOGIT_TOL, (len(p), m)
        # the top-5 of the pass that transferred each token, at its position
        for got, top in zip(o.output_top_logprobs, want["top"]):
            got = dict(got)
            assert set(map(str, list(got)[:5])) == set(top)
            assert max(abs(got[int(t)] - v) for t, v in top.items()) \
                < LOGIT_TOL
    return outs


# -- (a) the served path against the reference's generation -------------------

@pytest.mark.parametrize("steps", [4, 2, 1])
def test_served_ids_and_logprobs_are_the_reference_s(params, steps):
    """Prompts of every ``len % 4`` (one shorter than a block), for every
    number of denoising steps, ``max_tokens`` off and on a block edge: four
    sequences beside each other, their rows in different phases of their
    blocks."""
    cfg = CFG.replace(denoising_steps=steps)
    prompts = [_prompt(8, 10 * steps), _prompt(21, 7), _prompt(3, 3),
               _prompt(38, 11)]
    eng = _engine(params, cfg)
    _held_to_reference(eng, params, prompts, [10, 7, 5, 12], cfg)
    obs = eng.obs
    assert obs.block_tokens_transferred >= 34
    # two blocks a row-pass: [the block awaiting its commit | the open one]
    assert obs.block_positions_computed == 2 * B * obs.block_passes
    # every commit rode a denoising pass; no pass was a commit alone
    assert 0 < obs.block_commits == obs.block_commit_passes \
        < obs.block_passes


@pytest.mark.parametrize("threshold", [0.0036, 0.0042])
def test_a_low_threshold_transfers_several_positions_a_pass(params, threshold):
    """Confidences here are ~0.004: a threshold among them makes some
    passes transfer two or three positions and others one, so blocks take
    different numbers of passes and rows of one program sit in different
    phases."""
    cfg = CFG.replace(confidence_threshold=threshold)
    prompts = [_prompt(n, n) for n in (9, 14, 23, 32)]
    eng = _engine(params, cfg)
    _held_to_reference(eng, params, prompts, [12, 9, 11, 6], cfg)
    per_pass = eng.obs.block_tokens_transferred / eng.obs.block_passes
    assert 1.0 < per_pass < B, per_pass


def test_a_stop_inside_a_block_drops_what_follows_it(params):
    prompts = [_prompt(13, 5)]
    free = ref.generate(params, CFG, prompts[0], 12)["tokens"]
    stop = free[5]                  # second block's second position
    cut = free.index(stop) + 1
    outs = _held_to_reference(_engine(params), params, prompts, [12],
                              stop_token_ids=[stop])
    assert outs[0].output_token_ids == free[:cut]
    assert outs[0].finish_reason == "stop"


def test_a_prompt_that_holds_the_mask_token_id_is_not_masked(params):
    """MASKED is a flag the engine holds, never ``id == mask_token_id``."""
    prompts = [[CFG.mask_token_id] * 3 + _prompt(8, 1)
               + [CFG.mask_token_id] * 2]
    _held_to_reference(_engine(params), params, prompts, [9])


@pytest.mark.parametrize("budget", [32, 30, 18])
def test_chunked_prefill_ends_its_chunks_on_block_edges(params, budget):
    """A 100-token prompt through chunks under a budget that is (32) and is
    not (30, 18) a multiple of the block: every chunk is whole blocks, alone
    and beside decoding rows."""
    eng = _engine(params, max_prefill_tokens=budget,
                  prefill_buckets=(16, 32))
    chunks, orig = [], eng.obs.on_step
    eng.obs.on_step = lambda rec: (
        chunks.append(rec["batch"].prefill_token_count
                      or (rec["kind"] == "prefill"
                          and int((rec["batch"].seg_ids >= 0).sum()))),
        orig(rec))[1]
    prompts = [_prompt(14, 2), _prompt(101, 9), _prompt(57, 4)]
    _held_to_reference(eng, params, prompts, [14, 6, 9])
    assert all(c % B == 0 for c in chunks if c)
    steps = mixed_steps_of_prompt(eng.scheduler, 101)
    assert steps and sum(1 for _ in steps) >= 100 // budget


def test_preemption_drops_the_open_block_and_keeps_what_left(params):
    """A pool too small for four sequences: the youngest is preempted with
    an open block, recomputes its committed prefix AND the tokens that had
    left, and still ends on the reference's ids."""
    eng = _engine(params, pages=9)
    prompts = [_prompt(30, s) for s in (1, 2, 3, 4)]
    _held_to_reference(eng, params, prompts, [40, 40, 40, 40])
    assert eng.scheduler.num_preemptions > 0


def test_frames_carry_tokens_in_position_order(params):
    """Every step's ``new_token_ids`` are the next tokens of the output, in
    position order, and a frame may carry several or none."""
    eng = _engine(params, CFG.replace(confidence_threshold=0.0038))
    prompts = [_prompt(11, 3), _prompt(18, 4)]
    for i, p in enumerate(prompts):
        eng.add_request(str(i), p, SamplingParams(
            max_tokens=13, temperature=0.0, logprobs=True))
    seen, sizes = {"0": [], "1": []}, set()
    while eng.has_unfinished_requests():
        for o in eng.step():
            assert o.output_token_ids[:len(seen[o.request_id])] \
                == seen[o.request_id]
            seen[o.request_id] += o.new_token_ids
            assert o.output_token_ids == seen[o.request_id]
            sizes.add(len(o.new_token_ids))
    for i, p in enumerate(prompts):
        assert seen[str(i)] == ref.generate(
            params, CFG.replace(confidence_threshold=0.0038), p,
            13)["tokens"]
    assert max(sizes) > 1


def test_sampled_requests_are_reproducible_by_seed(params):
    eng = _engine(params)
    sp = SamplingParams(max_tokens=9, temperature=0.8, top_k=50, top_p=0.9,
                        seed=7)
    a = eng.generate([_prompt(10, 1)], sp)[0].output_token_ids
    b = eng.generate([_prompt(17, 2), _prompt(10, 1)], sp)[1].output_token_ids
    assert a == b and len(a) == 9
    c = eng.generate([_prompt(10, 1)], dataclasses.replace(sp, seed=8))
    assert c[0].output_token_ids != a


# -- (h) the commit rides the next block's first denoising pass ---------------

@pytest.mark.parametrize("steps,n_prompt,max_tokens,window", [
    (1, 8, 16, 4),      # a block whole in ONE pass: every pass completes one
    (1, 20, 24, 8),     # ... six of them, in one window
    (4, 8, 8, 4),       # the floor, a request that ends on a block's edge
    (4, 12, 16, 4),     # ... whose blocks turn whole in a window's LAST pass
    (2, 8, 12, 8)])     # two positions a pass
def test_a_commit_rides_the_pass_behind_it_and_the_last_block_has_none(
        params, steps, n_prompt, max_tokens, window):
    """One request from a block's edge to a block's edge, at the sampler's
    floor of each ``denoising_steps``: every pass transfers
    ``B / steps`` positions, so ``block_tokens_transferred / block_passes``
    is exactly that (1.0 at ``steps`` 4, where a commit as a pass of its
    own read 0.8), every whole block but the LAST is written, each beside
    the pass behind the one that made it whole, and the last, pending at
    the request's end, is never written: its pages are freed with the
    rest."""
    cfg = CFG.replace(denoising_steps=steps)
    eng = _engine(params, cfg, decode_window=window)
    recs, orig = [], eng.obs.on_step
    eng.obs.on_step = lambda rec: (recs.append(rec), orig(rec))[1]
    _held_to_reference(eng, params, [_prompt(n_prompt, 5)], [max_tokens], cfg)
    obs, per_pass = eng.obs, B // steps
    assert obs.block_tokens_transferred == max_tokens
    assert obs.block_passes == -(-max_tokens // per_pass)
    written = max_tokens // B - 1       # every block it filled but the last
    assert obs.block_commits == obs.block_commit_passes == written
    assert obs.block_positions_computed == 2 * B * obs.block_passes
    assert obs.block_passes_per_block.count == written
    assert obs.block_passes_per_block.sum == written * steps
    alloc = eng.scheduler.allocator
    assert alloc.num_free == alloc.num_pages - 1
    # no pass of any program was a commit alone: each yielded its tokens
    assert all(r["tokens"] == r["passes"] * per_pass for r in recs
               if r.get("mode") == "block")


@pytest.mark.parametrize("window", [4, 8])
def test_a_preemption_with_a_block_pending_serves_the_same_generation(
        params, window):
    """Prompts on a block's edge at the floor and a window of a multiple of
    B passes: every program ends with each row's block just made whole and
    PENDING (its K/V in no page). A pool too small for four sequences
    preempts some of them so: the pending block's ids are tokens that have
    left, they are re-prefilled like any other, and the generation is the
    unpreempted one's (the reference's), token for token."""
    eng = _engine(params, pages=9, decode_window=window)
    pending, requeue = [], eng.scheduler._requeue_for_recompute
    eng.scheduler._requeue_for_recompute = lambda seq, **how: (
        pending.append(seq.block_pending), requeue(seq, **how))[1]
    prompts = [_prompt(32, s) for s in (1, 2, 3, 4)]
    _held_to_reference(eng, params, prompts, [40, 40, 40, 40])
    assert eng.scheduler.num_preemptions > 0 and any(pending)
    alloc = eng.scheduler.allocator
    assert alloc.num_free == alloc.num_pages - 1
    assert not any(s.block_pending for s in eng.scheduler.waiting)


# -- (g) programs behind one another: pages for what is in flight -------------

def test_an_exactly_sized_pool_serves_with_passes_in_flight(
        params, monkeypatch):
    """A pool of just the pages its requests can ever hold (``admit_tokens``
    at admission, then the last block ``max_tokens`` reaches), arrivals
    spread so that windows and mixed steps queue behind one another: pages
    grown for what the passes in flight may commit never ask for one more
    (no chain break for pages, no preemption), no page is released under a
    dispatched program (the sanitizer's device-queue shadow), and no commit
    went to the scrap page for want of one (the ids are the reference's)."""
    monkeypatch.setenv("KGCT_SANITIZE", "1")
    lens, max_tokens = (30, 21, 13, 38), (40, 23, 30, 26)
    prompts = [_prompt(n, n) for n in lens]
    pages = 1 + sum(
        -(-max(n - n % B + B, -(-(n + m) // B) * B) // PS)
        for n, m in zip(lens, max_tokens))
    eng = _engine(params, pages=pages, decode_window=4)
    assert eng._sanitizer is not None
    outs, call = {}, 0
    while call < len(prompts) * 3 or eng.has_unfinished_requests():
        if call % 3 == 0 and call // 3 < len(prompts):
            i = call // 3
            eng.add_request(str(i), prompts[i], SamplingParams(
                max_tokens=max_tokens[i], temperature=0.0, logprobs=True))
        for o in eng.step():
            outs[o.request_id] = o
        call += 1
    for i, (p, m) in enumerate(zip(prompts, max_tokens)):
        assert outs[str(i)].finish_reason == "length"
        assert _distance(outs[str(i)], ref.generate(params, CFG, p, m)) \
            < LOGIT_TOL, i
    sched = eng.scheduler
    assert sched.num_preemptions == 0 and not eng.obs.chain_breaks
    assert sched.allocator.num_free == pages - 1
    behind = {k for (k, b), n in eng.obs.steps_dispatched.items() if b and n}
    assert behind >= {"decode", "mixed"}


@pytest.mark.parametrize("inflight,passes,committed,max_tokens,want", [
    # every pass may make a block whole (and the pass behind it writes it)
    (0, 8, 8, 64, 8 + 4 * 8 - 1),       # nothing in flight: as scheduled
    (8, 8, 8, 64, 8 + 4 * 16 - 1),      # a window behind a window
    (1, 8, 8, 64, 8 + 4 * 9 - 1),       # a window behind a mixed step
    (8, 1, 8, 64, 8 + 4 * 9 - 1),       # a mixed step behind a window
    (8, 8, 8, 5, 15),                   # the last block the request reaches
    (8, 8, 496, 1000, 511)])            # the model's length
def test_pages_are_held_for_what_the_passes_in_flight_may_commit(
        inflight, passes, committed, max_tokens, want):
    from kubernetes_gpu_cluster_tpu.engine.sequence import Sequence

    seq = Sequence("r", list(range(10)),
                   SamplingParams(max_tokens=max_tokens), block_length=B)
    seq.num_committed, seq.inflight_passes = committed, inflight
    assert seq.window_last_pos(passes, 512) == want


@pytest.mark.parametrize("outputs,inflight,window,max_tokens,want", [
    (0, 0, 8, 64, 16), (5, 0, 8, 64, 21), (5, 8, 8, 64, 29),
    (5, 1, 8, 64, 22), (5, 8, 1, 64, 22), (5, 8, 8, 10, 19),
    (0, 0, 1, 64, 9), (200, 8, 8, 400, 99)])
def test_a_token_models_window_reaches_where_it_always_did(
        outputs, inflight, window, max_tokens, want):
    """``window_last_pos`` at B = 1 (a prompt of 10, the model's length
    100): the numbers of the parent commit, whatever passes a block
    model's field would count."""
    from kubernetes_gpu_cluster_tpu.engine.sequence import Sequence

    seq = Sequence("r", list(range(10)), SamplingParams(max_tokens=max_tokens))
    for t in range(outputs):
        seq.append_token(t)
    seq.inflight_tokens = inflight
    assert seq.window_last_pos(window, 100) == want
    assert want == min(10 + outputs + inflight + window - 2, 99,
                       10 + max_tokens - 1)


# -- (b) planted faults -------------------------------------------------------

@pytest.fixture(scope="module")
def clean(params):
    prompts = [_prompt(21, 21), _prompt(40, 22)]
    outs = _serve(_engine(params), prompts, [12, 12])
    for p, o in zip(prompts, outs):
        assert _distance(o, ref.generate(params, CFG, p, 12)) < LOGIT_TOL
    return prompts, outs


@pytest.mark.parametrize("fault", [
    "causal_inside", "stale_kv", "skip_commit", "pick_second", "shift"])
def test_planted_faults_read_over_the_limit(params, clean, fault):
    """The reference with one fault of the mechanism planted in it (a causal
    mask inside the block; K/V written from a denoising pass; a commit that
    never wrote; a transfer that picks the second most confident; a shifted
    logit) is further from the served path than the tolerance, each time,
    by 50x at the least: the comparison can tell them."""
    prompts, outs = clean
    far = [_distance(o, ref.generate(params, CFG, p, 12, **{fault: True}))
           for p, o in zip(prompts, outs)]
    assert min(far) > 50 * LOGIT_TOL, far


# -- (f) what the server shows ------------------------------------------------

def test_health_and_metrics_name_the_block_mechanism(params):
    eng = _engine(params)
    info = eng.runtime_info()
    assert (info["block_length"], info["denoising_steps"],
            info["remasking"]) == (4, 4, "low_confidence_dynamic")
    assert "kgct_block_passes_total" not in "\n".join(
        eng.obs.render_prometheus())
    recs, orig = [], eng.obs.on_step
    eng.obs.on_step = lambda rec: (recs.append(rec), orig(rec))[1]
    eng.generate([_prompt(9, 1), _prompt(16, 2)],
                 SamplingParams(max_tokens=8, temperature=0.0))
    text = "\n".join(eng.obs.render_prometheus())
    for name in ("kgct_block_passes_total", "kgct_block_commit_passes_total",
                 "kgct_block_commits_total",
                 "kgct_block_tokens_transferred_total",
                 "kgct_block_positions_computed_total",
                 "kgct_block_passes_per_block_bucket"):
        assert f"\n{name}" in text, name
    # random weights: one position a pass, a block is 4 passes, and its
    # commit rides the fifth (the next block's first): every commit rode
    assert 16 <= eng.obs.block_tokens_transferred <= 16 + 2 * (B - 1)
    assert eng.obs.block_tokens_transferred == eng.obs.block_passes
    assert 0 < eng.obs.block_commits == eng.obs.block_commit_passes
    windows = [r for r in recs if r["kind"] == "decode"]
    assert windows and all(r["mode"] == "block" for r in windows)
    assert sum(r["passes"] for r in recs if "passes" in r) \
        == eng.obs.block_passes
    assert all(r["positions"] == 2 * B * r["passes"] for r in recs
               if "passes" in r)
    assert sum(r["commit_passes"] for r in recs if "passes" in r) \
        == eng.obs.block_commits
    assert sum(r["tokens"] for r in windows) <= eng.obs.block_tokens_transferred
    # a prefill of a block model samples nothing
    assert all(r["new_tokens"] == 0 for r in recs if r["kind"] == "prefill")


def test_warm_up_meets_the_block_programs(params):
    eng = _engine(params)
    eng.warm_full_window()
    eng.warm_mixed_steps()
    n = eng.compiled_step_variants()
    assert n >= 2
    eng.generate([_prompt(9, 1)], SamplingParams(max_tokens=4))
    assert eng.obs.block_passes > 0
