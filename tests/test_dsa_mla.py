"""DeepSeek Sparse Attention on the serving path (an indexer with a key cache
of its own in front of the latent pages, its top-k choice shared by the
layers behind it), held to the plain float32 reference
(perfbench/reference/glm5_2.py: the ONE copy, the benchmark's, which also
writes the cell's goldens) on ``debug-dsa-mla-moe`` with seeded weights:
``index_topk`` 16, so every form chooses, a fresh chunk included.

Tolerances, with their reasons: the served path and the reference are both
float32 here, so they differ only by the ORDER of float32 sums (absorbed
against materialised attention, grouped against per-expert dispatch, XLA's
default CPU matmul against "highest"). Log-probabilities are O(1); 2e-4
absolute is 30x the 6e-6 seen and far under what a planted fault moves
them by (``test_planted_faults_read_over_the_limit``: the smallest, a
'shared' layer choosing for itself, reads 0.19; the choice dropped 4.4).
Index scores near a tie could flip a choice between the two; none of these
seeds meets one (a flip would read ~1e-2: a failure, not a tolerance)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    apply_hf_overrides, cache_kind_refusal, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine import kv_cache as kvc
from kubernetes_gpu_cluster_tpu.engine import weights
from kubernetes_gpu_cluster_tpu.engine.engine import step_workspace_bytes
from kubernetes_gpu_cluster_tpu.engine.mixed_batch import (
    mixed_row_bucket, mixed_steps_of_prompt)
from kubernetes_gpu_cluster_tpu.engine.scheduler import Scheduler
from kubernetes_gpu_cluster_tpu.engine.sequence import Sequence
from kubernetes_gpu_cluster_tpu.models import llama
from kubernetes_gpu_cluster_tpu.ops import dsa
from perfbench.reference import glm5_2 as ref

LOGIT_TOL = 2e-4
CFG = get_model_config("debug-dsa-mla-moe")
PS = 16

# The catalog's row, as its config.json reads (the two long lists by rule).
GLM_HF = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32,
    "index_share_for_mtp_iteration": True, "index_skip_topk_offset": 3,
    "index_topk": 2048, "index_topk_freq": 4, "index_topk_pattern": None,
    "indexer_rope_interleave": True,
    "indexer_types": ["full" if i < 3 or (i - 3) % 4 == 3 else "shared"
                      for i in range(78)],
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75,
    "model_type": "glm_moe_dsa", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880}
CUT = {"num_hidden_layers": 6, "layers_from": 2,
       "experts_held": 16, "vocab_size": 19360}


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def _engine(params, model=CFG, pages=64, prefix=False, **sched):
    kw = dict(max_num_seqs=4, max_prefill_tokens=64, decode_buckets=(1, 2, 4),
              prefill_buckets=(32, 64), enable_prefix_caching=prefix)
    kw.update(sched)
    return LLMEngine(EngineConfig(
        model=model, cache=CacheConfig(page_size=PS, num_pages=pages),
        scheduler=SchedulerConfig(**kw)), params=params)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(3, CFG.vocab_size, n).tolist()


def _gap(outs, params, prompts, cfg=CFG):
    """Largest |log-probability difference| of the engine's emitted tokens
    to the reference's full forward pass over prompt + emitted tokens, and
    whether every emitted token is the reference's greedy one."""
    worst, same = 0.0, True
    for p, o in zip(prompts, outs):
        ids = list(o.output_token_ids)
        lp = jax.nn.log_softmax(ref.forward(params, cfg, p + ids), axis=-1)
        rows = np.asarray(lp[len(p) - 1:len(p) - 1 + len(ids)])
        same = same and ids == rows.argmax(-1).tolist()
        worst = max(worst, float(np.abs(
            np.asarray(o.output_logprobs, np.float32)
            - rows[np.arange(len(ids)), ids]).max()))
    return worst, same


def _served_vs_reference(eng, params, prompts, max_tokens=5, cfg=CFG):
    outs = eng.generate(prompts, SamplingParams(
        max_tokens=max_tokens, temperature=0.0, logprobs=1))
    worst, same = _gap(outs, params, prompts, cfg)
    assert same and worst < LOGIT_TOL, worst
    return outs


def _step_kinds(eng):
    kinds, orig = [], eng.obs.on_step
    eng.obs.on_step = lambda rec: (kinds.append(rec["kind"]), orig(rec))[1]
    return kinds


# -- (a) every serving form against the reference, on log-probabilities ------

@pytest.fixture(scope="module")
def served(params):
    return _engine(params)


class TestServedAgainstReference:
    def test_alone_a_fresh_chunk_then_decode_windows(self, served, params):
        """40 tokens in a 64-token step choose 16 of up to 40 in the fresh
        chunk; 12 decode rows choose 16 of 41-52 from the pages."""
        kinds = _step_kinds(served)
        _served_vs_reference(served, params, [_prompt(40, 1)], max_tokens=12)
        assert "prefill" in kinds and kinds.count("decode") >= 1

    def test_three_chunks_and_a_mixed_step_beside_decode_rows(self, served,
                                                              params):
        """150 tokens > the 64-token budget: three chunks, the later two
        choosing among their history's index keys in the pool, beside the
        40-token prompt's decode row; on the engine the test above left:
        pages and their index keys are reused after a finish."""
        alloc = served.scheduler.allocator
        assert served.obs.dsa_visible_tokens > 0      # it has served before
        assert alloc.num_free == alloc.num_pages - 1
        kinds = _step_kinds(served)
        _served_vs_reference(served, params, [_prompt(40, 2), _prompt(150, 3)])
        assert kinds.count("mixed") >= 2 and "decode" in kinds
        assert alloc.num_free == alloc.num_pages - 1

    def test_packed_prefill_and_the_eight_step_window(self, params):
        eng = _engine(params, mixed_batch_enabled=False)
        assert eng.config.scheduler.decode_window == 8
        kinds = _step_kinds(eng)
        # three prompts side by side in one 64-token prefill, then windows
        _served_vs_reference(eng, params,
                             [_prompt(n, n) for n in (9, 21, 30)],
                             max_tokens=18)
        assert kinds.count("prefill") == 1 and kinds.count("decode") >= 2
        # ... and the counters over those decode rows, from host lengths
        assert 0 < eng.obs.dsa_chosen_tokens < eng.obs.dsa_visible_tokens
        text = "\n".join(eng.obs.render_prometheus())
        assert "kgct_dsa_chosen_tokens_total %d" % eng.obs.dsa_chosen_tokens \
            in text and "kgct_dsa_visible_tokens_total" in text

    def test_preemption_by_recompute(self, params):
        """Two seats and a pool that cannot hold both sequences to their
        end: a sequence is preempted by recompute (latent pages AND index
        keys prefilled again from zero); outputs stay the reference's."""
        eng = _engine(params, pages=9, max_num_seqs=2, decode_buckets=(1, 2))
        prompts = [_prompt(50, 6), _prompt(45, 7)]
        _served_vs_reference(eng, params, prompts, max_tokens=40)
        assert eng.scheduler.num_preemptions_by_kind["recompute"] > 0
        assert eng.scheduler.allocator.num_free == 8

    def test_a_cached_prefix_brings_its_index_keys(self, params):
        """Prefix caching works with the index-key pool (a page id names
        the same tokens in both pools): the second request finds 48 tokens
        cached and chooses among their keys."""
        eng = _engine(params, prefix=True)
        head = _prompt(48, 8)
        _served_vs_reference(eng, params, [head + _prompt(30, 9)])
        _served_vs_reference(eng, params, [head + _prompt(50, 10)])
        assert eng.scheduler.allocator.prefix_cache.hits > 0

    def test_a_share_of_the_experts_and_grouped_dispatch(self, params):
        """Experts 2-5 of 8 held, steps wide enough for the grouped path."""
        cfg = apply_hf_overrides(CFG, {"experts_held": 4, "experts_first": 2})
        share = llama.init_params(cfg, jax.random.key(0))
        eng = _engine(share, model=cfg, prefill_buckets=(192,),
                      max_prefill_tokens=192)
        _served_vs_reference(eng, share, [_prompt(40, 11), _prompt(250, 12)],
                             cfg=cfg)


# -- (b) planted faults ---------------------------------------------------------

def _index_key_unrotated(ip, cfg, x, positions):
    return ref._layer_norm(x @ ip["wk"], ip["k_norm"], ip["k_norm_b"],
                           ref.K_NORM_EPS)


def _mask_over_future_positions(scores, positions, topk):
    causal = positions[:, None] >= positions[None, :]
    _, idx = jax.lax.top_k(scores, topk)        # the future scored too
    T = scores.shape[0]
    kept = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(True)
    # (a token whose best 16 all lie ahead of it keeps itself: no NaN)
    return (kept & causal) | jnp.eye(T, dtype=bool)


@pytest.fixture(scope="module")
def clean(params):
    """One 150-token prompt through three chunks and 6 decode rows, and the
    engine's log-probabilities of it."""
    prompts = [_prompt(150, 21)]
    outs = _engine(params).generate(prompts, SamplingParams(
        max_tokens=6, temperature=0.0, logprobs=1))
    assert _gap(outs, params, prompts)[0] < LOGIT_TOL
    return prompts, outs


@pytest.mark.parametrize("fault", [
    "a_shared_layer_chooses_for_itself", "a_full_layer_reuses_the_last_choice",
    "scores_over_future_positions", "an_unrotated_index_key",
    "the_choice_dropped"])
def test_planted_faults_read_over_the_limit(params, clean, fault, monkeypatch):
    """The reference with one fault of the mechanism planted in it is
    further from the served path than the tolerance, each time: the
    comparison can tell them."""
    prompts, outs = clean
    cfg, tree = CFG, params
    if fault == "a_shared_layer_chooses_for_itself":
        # layer 1 chooses from its own hidden state, with layer 0's indexer
        cfg = CFG.replace(indexer_types=("full", "full", "shared", "full",
                                         "shared"))
        tree = dict(params, indexer=jax.tree.map(
            lambda a: a[jnp.array([0, 0, 1])], params["indexer"]))
    elif fault == "a_full_layer_reuses_the_last_choice":
        cfg = CFG.replace(indexer_types=("full",) + ("shared",) * 4)
    elif fault == "scores_over_future_positions":
        monkeypatch.setattr(ref, "choice_mask", _mask_over_future_positions)
    elif fault == "an_unrotated_index_key":
        monkeypatch.setattr(ref, "index_key", _index_key_unrotated)
    kw = {"dense": True} if fault == "the_choice_dropped" else {}
    forward = ref.forward
    monkeypatch.setattr(ref, "forward", lambda p, c, t, *a: forward(
        tree, cfg, t, *a, **kw))
    assert _gap(outs, params, prompts)[0] > 50 * LOGIT_TOL


def _chunk_meta(tokens, start, table, T=64):
    n = len(tokens)
    ar = np.arange(T)
    pos = start + ar % max(n, 1)
    return jnp.asarray(np.pad(tokens, (0, T - n))), llama.StepMeta(
        seg_ids=jnp.asarray(np.where(ar < n, 0, -1)),
        positions=jnp.asarray(pos),
        slot_mapping=jnp.asarray(np.where(
            ar < n, np.asarray(table)[pos // PS] * PS + pos % PS, 0)),
        logits_indices=jnp.array([n - 1]),
        chunk_page_table=jnp.asarray(table, jnp.int32),
        hist_len=jnp.int32(start))


@pytest.mark.parametrize("stale", [False, True])
def test_a_stale_index_key_page_reads_over_the_limit(params, stale):
    """Two chunks through ``forward`` itself over a pool full of junk; the
    second chooses among the first's index keys. With the first page's
    keys left as they were (a write that did not happen), the last logits
    leave the reference by more than the tolerance; written, they agree."""
    kv = kvc.allocate_kv_cache(CFG, CacheConfig(page_size=PS), 16)
    kv = kvc.KVCache(*(None if a is None else jnp.full_like(a, 0.7)
                       for a in kv))
    toks, table = _prompt(100, 31), [3, 5, 2, 9, 4, 7, 1, 6]
    fwd = jax.jit(lambda t, m, kv: llama.forward(params, CFG, t, m, kv))
    _, kv, _ = fwd(*_chunk_meta(toks[:60], 0, table), kv)
    if stale:
        kv = kv._replace(idx=kv.idx.at[:, table[0]].set(0.7))
    hid, kv, _ = fwd(*_chunk_meta(toks[60:], 60, table), kv)
    gap = float(jnp.abs(llama.compute_logits(params, CFG, hid)[0]
                        - ref.forward(params, CFG, toks)[-1]).max())
    assert (gap > 50 * LOGIT_TOL) if stale else (gap < LOGIT_TOL), gap


# -- (c) the choice itself -------------------------------------------------------

def test_the_mask_is_the_set_top_k_names_ties_to_the_lower_position():
    """Scores full of exact ties (two index heads: a quarter of all scores
    are exactly 0): the mask keeps what ``lax.top_k`` over positions in
    order keeps, and the rows' indices are those positions."""
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.integers(-2, 3, (12, 40)).astype(np.float32))
    allowed = jnp.asarray(rng.random((12, 40)) < 0.8).at[7].set(False)
    k = 9
    masked = jnp.where(allowed, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    want = np.zeros((12, 40), bool)
    for r in range(12):
        want[r, np.asarray(idx[r])[np.asarray(vals[r]) > -np.inf]] = True
    np.testing.assert_array_equal(dsa.topk_mask(scores, allowed, k), want)
    got, exists = dsa.topk_indices(scores, allowed, k)
    np.testing.assert_array_equal(got, idx)
    np.testing.assert_array_equal(exists, vals > -jnp.inf)
    # no more candidates than k: everything allowed is kept
    np.testing.assert_array_equal(dsa.topk_mask(scores, allowed, 40), allowed)


def test_index_scores_are_the_reference_s(params):
    ip = jax.tree.map(lambda a: a[0], params["indexer"])
    T = 128                                  # two blocks of queries
    x = jax.random.normal(jax.random.key(1), (T, CFG.hidden_size))
    c_q = jax.random.normal(jax.random.key(2), (T, CFG.q_lora_rank))
    pos = jnp.arange(T) + 5
    q, w, k = dsa.project(ip, CFG, c_q, x, pos)
    with jax.default_matmul_precision("highest"):
        want = ref.index_scores(ip, CFG, c_q, x, pos)
    np.testing.assert_allclose(dsa.index_scores(q, w, k), want, atol=2e-5)
    np.testing.assert_allclose(
        dsa.row_scores(q[:4], w[:4], jnp.stack([k] * 4)), want[:4],
        atol=2e-5)


# -- (d) configuration -----------------------------------------------------------

def _hf_dir(tmp_path, **edits):
    hf = {k: v for k, v in {**GLM_HF, **edits}.items() if v != "<absent>"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_config_from_hf_reads_the_catalog_row_into_the_preset(tmp_path):
    got = weights.config_from_hf(_hf_dir(tmp_path), name="glm-5.2")
    assert got == get_model_config("glm-5.2").replace(max_model_len=8192)
    assert got.index_layers[:5] == (0, 1, 2, 6, 10)
    assert len(got.index_layers) == 21


def test_the_cut_is_a_window_of_the_published_layers():
    cut = apply_hf_overrides(get_model_config("glm-5.2"), CUT)
    assert cut.indexer_types == ("full", "shared", "shared", "shared",
                                 "full", "shared")
    assert cut.index_layers == (0, 4) and cut.num_dense_layers == 1
    assert cut.layer_sections == ((("attention",), 1, True),
                                  (("attention",), 5, False))
    # 6 latent rows of 640 and 2 index keys of 128, bf16: 8192 B a token
    assert kvc.kv_cache_bytes_per_token(cut, CacheConfig(page_size=128)) \
        == 8192
    shapes = jax.eval_shape(lambda: llama.init_params(cut, jax.random.key(0)))
    assert shapes["indexer"]["wq_b"].shape == (2, 2048, 32 * 128)
    assert shapes["layers"]["w_gate"].shape == (5, 16, 6144, 2048)
    n = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert abs(n / 9.38e9 - 1) < 0.01
    for bad, message in (
            ({"layers_from": 76, "num_hidden_layers": 3}, "not among"),
            ({"layers_from": 2, "first_k_dense_replace": 1},
             "first_k_dense_replace beside it is refused")):
        with pytest.raises(ValueError, match=message):
            apply_hf_overrides(get_model_config("glm-5.2"), bad)
    # without the key the window starts at layer 0: three dense layers, and
    # first_k_dense_replace counts dense layers as for every other model
    whole = apply_hf_overrides(get_model_config("glm-5.2"),
                               {"num_hidden_layers": 6})
    assert whole.indexer_types == ("full",) * 3 + ("shared",) * 3
    assert whole.num_dense_layers == 3
    assert apply_hf_overrides(get_model_config("glm-5.2"), {
        "num_hidden_layers": 6, "first_k_dense_replace": 1}
        ).indexer_types == whole.indexer_types


def test_the_cells_flags_warm_every_mixed_step_its_chunks_can_ride():
    """``--warm-prompt-lens`` of the benchmark's configuration, walked as
    the scheduler plans chunks beside 15 rows: six step programs, every
    (chunk rung, history width) a prompt of 7168-8064 tokens or the
    4416-token probe passes through, and none it cannot."""
    from pathlib import Path

    doc = json.loads((Path(__file__).parent.parent / "perfbench" / "configs"
                      / "glm-5.2-bf16.json").read_text())
    flags = doc["server_flags"]
    lens = [int(n) for n in
            flags[flags.index("--warm-prompt-lens") + 1].split(",")]
    assert lens == doc["warmup"]["warm_prompt_lens"]
    assert json.loads(flags[flags.index("--hf-overrides") + 1]) == CUT
    cfg = EngineConfig(
        model=apply_hf_overrides(get_model_config("glm-5.2"), CUT),
        max_model_len=12288, cache=CacheConfig(page_size=128, num_pages=1537),
        scheduler=SchedulerConfig(max_num_seqs=16,
                                  warm_prompt_lens=tuple(lens)))
    sched = Scheduler(cfg, 1537)
    warmed = {s for n in lens for s in mixed_steps_of_prompt(sched, n)}
    assert warmed == {(128, 1), (2048, 16), (2048, 32), (512, 64),
                      (2048, 64), (1536, 64)}
    # every prompt length of the traffic, beside 15 rows, rides these
    for n in range(7168, 8065, 7):
        assert set(mixed_steps_of_prompt(sched, n)) <= warmed, n
    # "beside a chunk of 1024 tokens and more the engine pads the rows to 16
    # whatever their count" (the configuration's ``prefill_buckets_why``):
    # the server's seats, not the ladder's 64: every row of the bucket is
    # chosen for, gathered for and attended for in every layer
    sc = cfg.scheduler
    assert sc.decode_buckets[-1] == 64 and sc.seat_bucket == 16
    assert sc.seat_bucket == doc["warmup"]["decode_buckets"][-1]
    for rung, _ in warmed - {(128, 1)}:
        assert {mixed_row_bucket(rows, rung, sc)
                for rows in range(1, 17)} == {16}, rung


@pytest.mark.parametrize("seats", [16, 64])
def test_warm_up_meets_the_programs_the_scheduler_builds(params, seats):
    """``warm_mixed_steps`` and ``build_mixed_batch`` take a mixed step's
    row bucket from one place (``mixed_row_bucket`` over the scheduler's
    configuration): the (chunk rung, row bucket, history width) the warm-up
    dispatches for ``warm_prompt_lens`` ARE the ones the scheduler builds
    for prompts of those lengths beside full seats (one seat left for the
    prompt, or every seat decoding and the prompt waiting), at 16 seats
    (the floor stops at the seats' bucket, and a chunk beside 16 decoding
    rows has no row of its own) as at 64 (the ladder's top): no mixed step
    of such a prompt compiles under open streams."""
    lens = (1, 300, 511)
    eng = _engine(params, pages=160, max_num_seqs=seats,
                  max_prefill_tokens=512, prefill_buckets=(128, 256, 512),
                  decode_buckets=(1, 2, 4, 8, 16, 32, 64),
                  warm_prompt_lens=lens)
    def shape(b):
        return (len(b.tokens) - len(b.context_lens), len(b.context_lens),
                b.chunk_page_table.shape[1])
    warmed = set()

    def spy(rec, prev, float_b, key):
        warmed.add(shape(rec["batch"]))
        rec["last"] = prev
    eng._dispatch_prefill = spy
    eng.warm_mixed_steps()

    built = set()
    for n, running in [(n, r) for n in lens for r in (seats - 1, seats)]:
        sched = Scheduler(eng.config, 160)
        rows = [Sequence(f"r{i}", [5 + i], SamplingParams(max_tokens=64))
                for i in range(running)]
        for seq in rows:
            sched.add(seq)
        assert sched.schedule().kind == "prefill"
        head = Sequence("head", _prompt(n, n), SamplingParams(max_tokens=4))
        sched.add(head)
        while head in sched.waiting:
            for seq in rows:
                seq.append_token(9)
            batch = sched.schedule()
            if running == seats and batch.kind == "decode":
                # every seat decodes: a last chunk waits for a seat, and
                # at the ladder's top so does every chunk
                assert seats == 64 or head.num_prefilled > n - 512
                break
            assert batch.kind == "mixed" and len(batch.seqs) == running + 1
            built.add(shape(batch))
    assert built == warmed
    # a prompt inside a page; 300 and 497 tokens on the rung of 512 over
    # 19 and 32 pages; the 14 tokens left of 511 behind them
    assert warmed == {(128, seats, 1), (512, seats, 32), (128, seats, 32)}
    assert {r for _, r, _ in warmed} == {eng.config.scheduler.seat_bucket}


def test_layers_from_slices_every_per_layer_list_of_any_model():
    """``layers_from`` is one override for every model: the dense count of
    an expert model, a hybrid's ``layer_types``, a sparse model's
    ``indexer_types`` are all the published layers' from there on."""
    kimi = apply_hf_overrides(get_model_config("kimi-vl-a3b"),
                              {"layers_from": 1, "num_hidden_layers": 4})
    assert (kimi.num_layers, kimi.num_dense_layers) == (4, 0)
    hybrid = get_model_config("debug-kda-hybrid")
    cut = apply_hf_overrides(hybrid, {"layers_from": 2,
                                      "num_hidden_layers": 4})
    assert cut.layer_types == hybrid.layer_types[2:6]
    dsa = apply_hf_overrides(CFG, {"layers_from": 3})
    assert (dsa.num_layers, dsa.num_dense_layers) == (2, 0)
    assert dsa.indexer_types == ("full", "shared")


@pytest.mark.parametrize("edits,message", [
    ({"index_kpool": 4}, "index_kpool is not implemented"),
    ({"index_topk_pattern": "FFS"}, "index_topk_pattern is not implemented"),
    ({"indexer_types": "<absent>"}, "index_topk 2048 without indexer_types"),
    ({"q_lora_rank": None}, "index_topk 2048 without q_lora_rank"),
    ({"index_topk": "<absent>"}, "without index_topk"),
    ({"index_topk_freq": 3}, "indexer_types is not what index_topk_freq 3"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type='yarn' is not implemented"),
    ({"mlp_layer_types": ["sparse"] * 78},
     "mlp_layer_types is not first_k_dense_replace=3"),
])
def test_what_the_indexer_does_not_implement_refuses_the_load(
        tmp_path, edits, message):
    """Nothing named ``index_*`` is accepted and ignored: a config the
    decoder would serve with dense attention under a sparse model's name
    refuses the load, by the key."""
    with pytest.raises(ValueError, match=message):
        weights.config_from_hf(_hf_dir(tmp_path, **edits), name="glm-x")


def test_a_first_layer_that_shares_is_refused():
    with pytest.raises(ValueError, match="the first of them 'full'"):
        CFG.replace(indexer_types=("shared", "full", "shared", "full",
                                   "shared"))
    with pytest.raises(ValueError, match="reads the query latent"):
        CFG.replace(q_lora_rank=0)


# -- (e) what works with an index-key pool and what refuses at start ---------

def _config(**kw):
    base = dict(model=CFG, cache=CacheConfig(page_size=PS, num_pages=32),
                scheduler=SchedulerConfig(max_num_seqs=2))
    base.update(kw)
    return EngineConfig(**base)


@pytest.mark.parametrize("config,extra,flag", [
    (dict(parallel=ParallelConfig(tp=2)), {}, "--tensor-parallel-size 2"),
    (dict(parallel=ParallelConfig(pp=2)), {}, "--pipeline-parallel-size 2"),
    (dict(parallel=ParallelConfig(sp=2)), {}, "--sequence-parallel-size 2"),
    (dict(parallel=ParallelConfig(ep=2)), {}, "--expert-parallel-size 2"),
    (dict(scheduler=SchedulerConfig(max_num_seqs=2,
                                    spec_decode_enabled=True)), {},
     "--enable-spec-decode"),
    (dict(cache=CacheConfig(page_size=PS, num_pages=32, swap_space_gb=0.1)),
     {}, "--swap-space-gb"),
    (dict(model=CFG.replace(quantization="int8")), {}, "--quantization int8"),
    ({}, dict(role="prefill"), "--role prefill"),
    ({}, dict(fleet_prefix_cache=True), "--fleet-prefix-cache"),
    ({}, dict(peer_pool=["http://x"]), "--peer-pool"),
    ({}, dict(mesh_shape={"tp": 4}), "--tensor-parallel-size 4"),
])
def test_what_cannot_carry_index_keys_refuses_at_start(config, extra, flag):
    """A model with an index-key pool is a latent-attention model: every
    flag whose mechanism frames, shards or moves K and V pages refuses at
    start, by name; none would leave the index keys behind in silence."""
    msg = cache_kind_refusal(_config(**config), **extra)
    assert msg is not None and msg.startswith(flag) and CFG.name in msg


def test_what_works_is_not_refused():
    assert cache_kind_refusal(_config()) is None
    assert cache_kind_refusal(_config(scheduler=SchedulerConfig(
        max_num_seqs=2, enable_prefix_caching=True))) is None
    assert cache_kind_refusal(_config(), mesh_shape={"tp": 1}) is None


# -- (f) what the engine reports ---------------------------------------------------

def test_health_reports_the_index_key_pool(served):
    info = served.runtime_info()
    assert info["index_topk"] == 16 and info["indexer_layers"] == 2
    # 2 layers x 64 pages x 16 tokens x 32 lanes, float32
    assert info["index_cache_bytes"] == 2 * 64 * PS * 32 * 4
    # 5 latent rows of 128 lanes (80 padded) and 2 index keys of 32, float32
    assert info["kv_bytes_per_token"] == (5 * 128 + 2 * 32) * 4
    kv = served.kv_cache
    assert kv.idx.shape == (2, 64, PS, 32) and kv.v is None
    # the indexer's workspace is counted on top of the model's without it
    plain = dataclasses.replace(served.config, model=CFG.replace(
        index_topk=0, indexer_types=None))
    assert step_workspace_bytes(served.config) > step_workspace_bytes(plain)
