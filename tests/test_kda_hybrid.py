"""A delta-rule / latent-attention expert model on the serving path
(kimi-linear's block at the size of ``debug-kda-hybrid``: one leading dense
KDA layer, two periods of [KDA, KDA, MLA, KDA], a ragged [KDA, MLA] tail, 16
experts top-4 + 1 shared, chunk 32 = two sub-chunks, float32, seeded
weights), held to the plain reference ``perfbench/reference/kimi_linear.py``
(the repository's one copy: the delta rule token by token, no chunks, no
cache, no slots, experts by a loop), on LOGITS.

What is held: the forward's segment part (a whole prefill; packed prompts
whose boundaries fall inside chunks and sub-chunks; a prompt in two chunks
with history), its row part (a decode through the slots AND the latent
pages), both in one mixed step; the slots (zero at a sequence's start
whatever they held); the engine (chained decode windows, preemption by
recompute); the chunked form (the XLA einsums and the Pallas kernel in interpret mode)
against the recurrence, under a strong gate and over one repeated token, and
the kernel against the einsums; the Pallas update in interpret mode; a model that holds a SHARE of its
experts (four shares add up to the whole layer on both dispatch paths, the
load and the dispatch rule over the experts held); every flag a
latent-and-stateful model is refused, by its message; ``config_from_hf``.
"""

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
from kubernetes_gpu_cluster_tpu.engine.kv_cache import (
    KVCache, allocate_kv_cache, kv_cache_bytes_per_token,
    state_bytes_per_seq)
from kubernetes_gpu_cluster_tpu.models import llama
from kubernetes_gpu_cluster_tpu.observability import Observability
from kubernetes_gpu_cluster_tpu.ops import kda as kda_ops
from kubernetes_gpu_cluster_tpu.ops.attention import NO_KERNELS, Kernels
from perfbench.reference import glm5_2 as glm_ref
from perfbench.reference import kimi_linear as ref

CFG = get_model_config("debug-kda-hybrid")
PS, PAGES, SLOTS = 16, 24, 6
# Float32 against float32: the served path and the reference differ in the
# order of sums only (chunked against token-by-token, the solve against the
# recursion, fused matmuls). Their logits (of size ~4) agree to ~3e-5 here;
# a state rounded to bfloat16 at every token reads over 2e-3 after 90 tokens
# (test_a_bf16_state_would_fail_the_tolerance), so this limit has several
# times of room above the one and an order of magnitude below the other.
TOL = 2e-4
# A slot's contents two ways (packed or alone, one chunk or two): the same
# sums in another order through up to 11 layers, on values of size ~2; read
# up to 1.2e-5.
SLOT_TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def _pool(fill=0.0):
    """A pool whose every slot and page holds ``fill``: what a fresh
    sequence finds must not matter."""
    kv = allocate_kv_cache(CFG, CacheConfig(page_size=PS), PAGES,
                           num_state_slots=SLOTS)
    return KVCache(*(None if a is None else jnp.full_like(a, fill)
                     for a in kv))


def _tokens(n, seed):
    return np.random.RandomState(seed).randint(3, CFG.vocab_size, n)


_fwd = jax.jit(lambda p, t, m, kv: llama.forward(p, CFG, t, m, kv))


def _logits(params, hidden):
    return llama.compute_logits(params, CFG, hidden)


def _segments(prompts, pages, slots, T, starts=None, n_slots=None,
              table=None):
    """StepMeta + tokens of a segment part: ``prompts`` packed side by
    side (or, with ``starts``, ONE sequence's chunk at position
    starts[0], with its page table)."""
    tokens = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    slot_map = np.zeros(T, np.int32)
    i, ends = 0, []
    for s, t in enumerate(prompts):
        n, p0 = len(t), (starts[s] if starts else 0)
        tokens[i:i + n], seg[i:i + n] = t, s
        pos[i:i + n] = p0 + np.arange(n)
        slot_map[i:i + n] = (np.asarray(pages[s])[pos[i:i + n] // PS] * PS
                             + pos[i:i + n] % PS)
        i += n
        ends.append(i - 1)
    seg_slots = np.zeros(n_slots or len(prompts), np.int32)
    seg_slots[:len(slots)] = slots
    extra = {}
    if starts:
        extra = dict(chunk_page_table=jnp.asarray(table, jnp.int32),
                     hist_len=jnp.int32(starts[0]))
    meta = llama.StepMeta(
        seg_ids=jnp.asarray(seg), positions=jnp.asarray(pos),
        slot_mapping=jnp.asarray(slot_map),
        logits_indices=jnp.asarray(ends, jnp.int32),
        seg_slots=jnp.asarray(seg_slots), **extra)
    return jnp.asarray(tokens), meta


def _rows(last_tokens, positions, tables, slots, R):
    """StepMeta + tokens of a row part: one token a running sequence, at
    ``positions``, padded to R rows on the scrap page and slot."""
    n = len(last_tokens)
    tok = np.zeros(R, np.int32)
    pos = np.zeros(R, np.int32)
    slot_map = np.zeros(R, np.int32)
    pt = np.zeros((R, 8), np.int32)
    ctx = np.zeros(R, np.int32)
    row_slots = np.zeros(R, np.int32)
    for r in range(n):
        tok[r], pos[r], ctx[r] = last_tokens[r], positions[r], positions[r] + 1
        pt[r, :len(tables[r])] = tables[r]
        slot_map[r] = tables[r][pos[r] // PS] * PS + pos[r] % PS
        row_slots[r] = slots[r]
    return jnp.asarray(tok), llama.StepMeta(
        positions=jnp.asarray(pos), slot_mapping=jnp.asarray(slot_map),
        page_tables=jnp.asarray(pt), context_lens=jnp.asarray(ctx),
        row_slots=jnp.asarray(row_slots))


# -- the pattern ---------------------------------------------------------------

def test_the_pattern_is_a_dense_head_whole_periods_and_a_ragged_tail():
    period = ("kda", "kda", "attention", "kda")
    assert CFG.layer_sections == ((("kda",), 1, True), (period, 2, False),
                                  (("kda", "attention"), 1, False))
    assert llama.layer_stacks(CFG) == {"dense_ssm_layers": 1,
                                       "ssm_layers": 7, "layers": 3}
    full = get_model_config("kimi-linear-48b-a3b")
    assert full.layer_sections == ((("kda",), 1, True), (period, 6, False),
                                   (("kda", "attention"), 1, False))
    assert (full.num_state_layers, full.num_kv_layers) == (20, 7)
    assert [i + 1 for i, t in enumerate(full.layer_types)
            if t == "attention"] == [4, 8, 12, 16, 20, 24, 27]
    # the slot's layout is the model's: 32 heads x 128 x 128 float32, and
    # the last 3 rows of the three convs' inputs
    assert full.state_shape == (32 * 128, 128)
    assert full.state_conv_shape == (3, 3 * 32 * 128)


def test_depth_overrides_take_the_dense_head_and_whole_periods():
    full = get_model_config("kimi-linear-48b-a3b")
    cut = apply_hf_overrides(full, {"num_hidden_layers": 9,
                                    "experts_held": 64, "vocab_size": 40960})
    assert cut.layer_types == ("kda",) + ("kda", "kda", "attention",
                                          "kda") * 2
    assert (cut.num_local_experts, cut.num_experts) == (64, 256)
    assert apply_hf_overrides(full, {"num_hidden_layers": 27}) == full
    with pytest.raises(ValueError, match="1 leading dense layers and whole "
                       "periods of kimi-linear-48b-a3b's layer pattern"):
        apply_hf_overrides(full, {"num_hidden_layers": 10})
    with pytest.raises(ValueError, match="experts 200 to 263 held, of 256"):
        apply_hf_overrides(full, {"experts_held": 64, "experts_first": 200})


# -- the forward against the reference -----------------------------------------

def test_prefill_then_70_decode_steps_match_the_reference(params):
    """Teacher-forced: one reference pass over 20 + 70 tokens gives every
    position's logits; the served path prefills 20 and decodes 70 through
    the slots and the latent pages."""
    seq = _tokens(90, 1)
    want = ref.forward(params, CFG, seq)
    pages, slot = [1, 2, 3, 4, 5, 6], 3
    tok, meta = _segments([seq[:20]], [pages], [slot], 32)
    hid, kv, _ = _fwd(params, tok, meta, _pool(9.0))
    assert kv.v is None and kv.ssm is not None      # latent pages AND slots
    worst = float(jnp.max(jnp.abs(_logits(params, hid)[0] - want[19])))
    for t in range(20, 90):
        tok, meta = _rows([seq[t]], [t], [pages], [slot], 2)
        hid, kv, _ = _fwd(params, tok, meta, kv)
        worst = max(worst, float(jnp.max(jnp.abs(
            _logits(params, hid)[0] - want[t]))))
    assert worst < TOL


def test_a_bf16_state_would_fail_the_tolerance(params):
    """What TOL guards: the reference with S rounded to bfloat16 after every
    token is far outside it after 90 tokens."""
    seq = _tokens(90, 1)
    gap = jnp.max(jnp.abs(
        ref.forward(params, CFG, seq, state_dtype=jnp.bfloat16)
        - ref.forward(params, CFG, seq)))
    assert float(gap) > 10 * TOL


def test_whole_prefill_matches_the_reference_at_every_position(params):
    """70 tokens in one step: three chunks of 32, the last one padded; every
    token's logits, not the last one's only."""
    seq = _tokens(70, 2)
    tok, meta = _segments([seq], [[1, 2, 3, 4, 5]], [2], 96)
    hid, _, _ = _fwd(params, tok, meta._replace(logits_indices=None),
                     _pool(3.0))
    assert float(jnp.max(jnp.abs(
        _logits(params, hid)[:70] - ref.forward(params, CFG, seq)))) < TOL


def test_packed_prefill_with_boundaries_inside_chunks(params):
    """Three prompts of 37, 5 and 21 tokens side by side: with a chunk of 32
    in sub-chunks of 16 the boundaries (37, 42) fall inside chunk 1, in its
    first sub-chunk."""
    prompts = [_tokens(n, 10 + n) for n in (37, 5, 21)]
    tok, meta = _segments(prompts, [[1, 2, 3], [4], [5, 6]], [1, 2, 3], 64,
                          n_slots=4)
    hid, kv, _ = _fwd(params, tok, meta, _pool(7.0))
    logits = _logits(params, hid)
    for s, p in enumerate(prompts):
        assert float(jnp.max(jnp.abs(
            logits[s] - ref.forward(params, CFG, p)[-1]))) < TOL
    # ... and each one's slot holds what a prompt alone leaves there.
    for s, p in enumerate(prompts):
        tok1, meta1 = _segments([p], [[7, 8, 9]], [5], 48)
        _, alone, _ = _fwd(params, tok1, meta1, _pool())
        np.testing.assert_allclose(kv.ssm[:, s + 1], alone.ssm[:, 5],
                                   atol=SLOT_TOL)
        np.testing.assert_allclose(kv.conv[:, s + 1], alone.conv[:, 5],
                                   atol=SLOT_TOL)


@pytest.mark.parametrize("split", [21, 3, 38])
def test_two_chunks_with_history_equal_one(params, split):
    """40 tokens at once, and as [0:split) then [split:40) continuing from
    the slot and the latent pages (the second chunk alone is 19, 37 or 2
    tokens: the conv reaches back into the slot's rows)."""
    p = _tokens(40, 5)
    pages = [2, 3, 4]
    tok, meta = _segments([p], [pages], [2], 48)
    hid1, kv1, _ = _fwd(params, tok, meta, _pool(4.0))
    tok, meta = _segments([p[:split]], [pages], [2], 48, starts=[0],
                          table=[2, 3, 4, 0])
    _, kv2, _ = _fwd(params, tok, meta, _pool(4.0))
    tok, meta = _segments([p[split:]], [pages], [2], 48, starts=[split],
                          table=[2, 3, 4, 0])
    hid2, kv2, _ = _fwd(params, tok, meta, kv2)
    assert float(jnp.max(jnp.abs(
        _logits(params, hid2)[0] - ref.forward(params, CFG, p)[-1]))) < TOL
    np.testing.assert_allclose(_logits(params, hid1), _logits(params, hid2),
                               atol=TOL)
    np.testing.assert_allclose(kv1.ssm[:, 2], kv2.ssm[:, 2], atol=SLOT_TOL)
    np.testing.assert_allclose(kv1.conv[:, 2], kv2.conv[:, 2],
                               atol=SLOT_TOL)


def _mixed_step(params):
    """A chunk with history beside two decode rows, [segment tokens | row
    tokens]: (the three sequences, tokens, meta, the pool before it, the
    chunk step's and the decode step's hidden states, the pool behind the
    two)."""
    a, b, c = _tokens(12, 21), _tokens(9, 22), _tokens(30, 23)
    tok, meta = _segments([a, b], [[1], [2]], [1, 2], 32)
    _, kv0, _ = _fwd(params, tok, meta, _pool())
    tok, meta = _segments([c[:17]], [[3, 4]], [3], 32, starts=[0],
                          table=[3, 4])
    _, kv0, _ = _fwd(params, tok, meta, kv0)
    # pure: the rest of the chunk, then the rows
    tok_s, meta_s = _segments([c[17:]], [[3, 4]], [3], 16, starts=[17],
                              table=[3, 4])
    hid_s, kv_p, _ = _fwd(params, tok_s, meta_s, kv0)
    tok_r, meta_r = _rows([5, 6], [12, 9], [[1], [2]], [1, 2], 4)
    hid_r, kv_p, _ = _fwd(params, tok_r, meta_r, kv_p)
    # mixed: [segment tokens | row tokens]
    meta_m = meta_s._replace(
        seg_ids=jnp.concatenate([meta_s.seg_ids, jnp.full(4, -1)]),
        positions=jnp.concatenate([meta_s.positions, meta_r.positions]),
        slot_mapping=jnp.concatenate([meta_s.slot_mapping,
                                      meta_r.slot_mapping]),
        logits_indices=jnp.asarray([16, 17, 12], jnp.int32),
        page_tables=meta_r.page_tables, context_lens=meta_r.context_lens,
        row_slots=meta_r.row_slots)
    return ((a, b, c), jnp.concatenate([tok_s, tok_r]), meta_m, kv0, hid_s,
            hid_r, kv_p)


def test_a_mixed_step_matches_the_reference_and_the_two_pure_steps(params):
    """A chunk with history beside two decode rows in ONE program, against
    the reference and against the chunk step and the decode step alone."""
    (a, b, c), tok, meta_m, kv0, hid_s, hid_r, kv_p = _mixed_step(params)
    hid_m, kv_m, _ = _fwd(params, tok, meta_m, kv0)
    logits = _logits(params, hid_m)
    for row, seq in ((0, list(a) + [5]), (1, list(b) + [6]), (2, c)):
        assert float(jnp.max(jnp.abs(
            logits[row] - ref.forward(params, CFG, seq)[-1]))) < TOL
    np.testing.assert_allclose(hid_m[:2], hid_r[:2], atol=SLOT_TOL)
    np.testing.assert_allclose(hid_m[2], hid_s[0], atol=SLOT_TOL)
    for got, want in zip(kv_m, kv_p):
        if got is not None:
            np.testing.assert_allclose(got[:, 1:], want[:, 1:],
                                       atol=SLOT_TOL)


class ConvKernel(Kernels):
    """The XLA references, but for the conv stage: the chip's kernel in
    interpret mode."""

    def conv_segments(self, *args):
        from kubernetes_gpu_cluster_tpu.ops.pallas.conv_segments import (
            conv_segments)
        return conv_segments(*args, interpret=True)


def test_a_mixed_step_through_the_conv_kernel_equals_the_xla_form(params):
    """The same mixed step with the conv stage of its segment part as
    ``ops/pallas/conv_segments.py`` (q, k and v leave it as the ``[T, H,
    d]`` heads the chunked form reads; a chunk of 16 tokens with history:
    the slot's rows before token 0): logits, slots and conv rows as the XLA
    form's, to the order of the sums."""
    _, tok, meta_m, kv0, *_ = _mixed_step(params)
    hid_x, kv_x, _ = _fwd(params, tok, meta_m, kv0)
    hid_k, kv_k, _ = jax.jit(lambda p, t, m, kv: llama.forward(
        p, CFG, t, m, kv, ConvKernel()))(params, tok, meta_m, kv0)
    want = _logits(params, hid_x)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(_logits(params, hid_k), want,
                               atol=2e-5 * scale)
    np.testing.assert_allclose(kv_k.ssm, kv_x.ssm, atol=1e-4)
    np.testing.assert_allclose(kv_k.conv, kv_x.conv, atol=1e-5)


def test_a_stale_slot_does_not_leak_into_a_new_sequence(params):
    """A prompt prefilled over a pool full of another sequence's leavings
    (every slot and page 9.0) gives the logits and the slot it gives over
    zeros: a segment starts from zero WHATEVER its slot held; padding rows
    touch the scrap slot only."""
    p = _tokens(25, 31)
    tok, meta = _segments([p], [[1, 2]], [4], 32)
    hid_a, kv_a, _ = _fwd(params, tok, meta, _pool())
    hid_b, kv_b, _ = _fwd(params, tok, meta, _pool(9.0))
    np.testing.assert_array_equal(np.asarray(hid_a), np.asarray(hid_b))
    np.testing.assert_array_equal(np.asarray(kv_a.ssm[:, 4]),
                                  np.asarray(kv_b.ssm[:, 4]))
    tok, meta = _rows([7], [0], [[1]], [4], 4)      # 1 real row, 3 padding
    before = _pool(2.0)
    _, after, _ = _fwd(params, tok, meta, _pool(2.0))
    for b, a in zip((before.ssm, before.conv), (after.ssm, after.conv)):
        changed = np.any(np.asarray(a != b).reshape(
            a.shape[0], a.shape[1], -1), axis=(0, 2))
        assert list(np.nonzero(changed)[0]) in ([0, 4], [4])


# -- the chunked form and the update, alone ----------------------------------------

def _kda_inputs(T, H, d, key, gate_bias, A=None):
    k = jax.random.split(key, 6)
    q = kda_ops.l2_normalise(jax.random.normal(k[0], (T, H, d))) * d ** -0.5
    kk = kda_ops.l2_normalise(jax.random.normal(k[1], (T, H, d)))
    v = jax.random.normal(k[2], (T, H, d))
    if A is None:
        A = jnp.exp(jax.random.uniform(k[3], (H, 1), minval=0.0,
                                       maxval=np.log(16.0)))
    g = -A * jax.nn.softplus(jax.random.normal(k[4], (T, H, d)) + gate_bias)
    return q, kk, v, g, jax.nn.sigmoid(jax.random.normal(k[5], (T, H)))


def _pallas_chunk(*args):
    from kubernetes_gpu_cluster_tpu.ops.pallas.kda_chunk import kda_chunk
    return kda_chunk(*args, interpret=True)


# The chunked form's two implementations: what the CPU and NO_KERNELS run,
# and the chip's kernel in interpret mode.
_CHUNK_FORMS = [pytest.param(kda_ops.kda_chunk_scan_xla, id="xla"),
                pytest.param(_pallas_chunk, id="pallas")]


@pytest.mark.parametrize("form", _CHUNK_FORMS)
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_form_equals_the_recurrence(chunk, form):
    """Three segments in 300 tokens (boundaries at 70 and 201: inside chunks
    and sub-chunks at both sizes), the first continuing from a state."""
    H, d = 3, 32
    q, k, v, g, beta = _kda_inputs(300, H, d, jax.random.key(chunk), -3.0)
    init = jax.random.normal(jax.random.key(5), (H * d, d))
    seg = jnp.asarray([0] * 70 + [1] * 131 + [2] * 79 + [-1] * 20)
    ends = jnp.asarray([69, 200, 279, -1])
    o, final = form(q, k, v, g, beta, seg, ends, init, 0, chunk)
    for s, (a, b) in enumerate(((0, 70), (70, 201), (201, 280))):
        o_s, f_s = kda_ops.kda_recurrence(
            q[a:b], k[a:b], v[a:b], g[a:b], beta[a:b],
            init if s == 0 else jnp.zeros_like(init))
        # float32 both: the order of sums only
        np.testing.assert_allclose(o[a:b], o_s, atol=2e-5)
        np.testing.assert_allclose(final[s], f_s, atol=2e-5)


@pytest.mark.parametrize("form", _CHUNK_FORMS)
def test_chunked_form_under_a_strong_gate_is_finite_and_right(form):
    """A = 16 and a large dt_bias (softplus(x + 4) ~ 4: g ~ -64 a token, a
    chunk's running sum ~ -4000): exp(-G_s) alone overflows float32 after
    two tokens; the decay differences are formed as G_t - G_s and the
    result is finite and the recurrence's, over 256 tokens."""
    H, d = 2, 32
    q, k, v, g, beta = _kda_inputs(256, H, d, jax.random.key(9), 4.0, A=16.0)
    assert float(jnp.min(jnp.cumsum(g[:64], axis=0))) < -3000
    init = jax.random.normal(jax.random.key(6), (H * d, d))
    seg, ends = jnp.zeros(256, jnp.int32), jnp.asarray([255])
    o, final = form(q, k, v, g, beta, seg, ends, init, 0, 64)
    o_r, f_r = kda_ops.kda_recurrence(q, k, v, g, beta, init)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(final).all())
    # nothing survives a token under this gate, so o is one rank-one term:
    # both sides compute it with the same few products
    np.testing.assert_allclose(o, o_r, atol=1e-6)
    np.testing.assert_allclose(final[0], f_r, atol=1e-6)


@pytest.mark.parametrize("form", _CHUNK_FORMS)
def test_the_solve_survives_a_prompt_of_one_repeated_token(form):
    """Every key the same and no decay: A is beta times the strictly lower
    ones, whose powers grow like binomials; the sub-chunk inverses and the
    forward substitution keep the digits a 64-row series would lose."""
    T, H, d = 64, 1, 32
    k = jnp.broadcast_to(kda_ops.l2_normalise(
        jax.random.normal(jax.random.key(1), (d,))), (T, H, d))
    q = k * d ** -0.5
    v = jax.random.normal(jax.random.key(2), (T, H, d))
    g, beta = jnp.zeros((T, H, d)), jnp.full((T, H), 0.5)
    init = jnp.zeros((H * d, d))
    o, final = form(
        q, k, v, g, beta, jnp.zeros(T, jnp.int32), jnp.asarray([T - 1]),
        init, -2, 64)
    o_r, f_r = kda_ops.kda_recurrence(q, k, v, g, beta, init)
    np.testing.assert_allclose(o, o_r, atol=1e-5)
    np.testing.assert_allclose(final[0], f_r, atol=1e-5)


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("bounds,history", [
    pytest.param([(0, 150)], False, id="fresh"),
    pytest.param([(0, 150)], True, id="history"),
    pytest.param([(0, 70), (70, 180)], False, id="two-packed"),
    pytest.param([(0, 37), (37, 42), (42, 150)], True, id="three-packed"),
])
def test_the_chunk_kernel_in_interpret_mode_equals_the_xla_form(
        chunk, bounds, history):
    """``ops/pallas/kda_chunk.py`` against ``kda_chunk_scan_xla`` over 256
    tokens of two heads: one prompt from nothing and from a slot's state
    (``init_seg`` 0); two and three prompts packed, their boundaries (70;
    37 and 42) inside chunks and sub-chunks at both chunk sizes; behind
    them a padding tail of whole chunks (from 192 at the latest), where o
    is zero; absent segments (``seg_ends`` -1) beside the present ones."""
    T, H, d, S = 256, 2, 32, 3
    q, k, v, g, beta = _kda_inputs(T, H, d, jax.random.key(7), -3.0)
    init = jax.random.normal(jax.random.key(8), (H * d, d))
    seg = np.full(T, -1, np.int32)
    for s, (a, b) in enumerate(bounds):
        seg[a:b] = s
    ends = jnp.asarray([b - 1 for _, b in bounds]
                       + [-1] * (S - len(bounds)), jnp.int32)
    args = (q, k, v, g, beta, jnp.asarray(seg), ends, init,
            0 if history else -2, chunk)
    want_o, want_f = kda_ops.kda_chunk_scan_xla(*args)
    got_o, got_f = _pallas_chunk(*args)
    n = bounds[-1][1]
    # float32 both: the order of sums only
    np.testing.assert_allclose(got_o[:n], want_o[:n], atol=1e-5)
    np.testing.assert_allclose(got_f[:len(bounds)], want_f[:len(bounds)],
                               atol=1e-5)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_f).all())
    whole = -(-n // chunk) * chunk          # the first chunk of padding only
    assert whole < T and not np.asarray(got_o[whole:]).any()


def test_pallas_update_in_interpret_mode_equals_its_xla_twin():
    from kubernetes_gpu_cluster_tpu.ops.pallas.kda_update import kda_update
    H, d, R = 4, 128, 4
    pool = jax.random.normal(jax.random.key(3), (3, 5, H * d, d), jnp.float32)
    slots = jnp.asarray([2, 4, 0, 0], jnp.int32)
    q, k, v, g, beta = _kda_inputs(R, H, d, jax.random.key(4), -1.0)
    want_pool, want_o = kda_ops.kda_update_xla(pool, jnp.int32(1), slots, g,
                                               beta, q, k, v)
    got_pool, got_o = kda_update(pool, jnp.int32(1), slots, g, beta, q, k, v,
                                 head_block=2, interpret=True)
    # the rows' slots to a rounding (the two order the 128-term sums
    # otherwise), every other slot of every layer untouched: bitwise
    np.testing.assert_allclose(got_o[:2], want_o[:2], atol=1e-5)
    np.testing.assert_allclose(got_pool[1, [2, 4]], want_pool[1, [2, 4]],
                               atol=1e-5)
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [0, 2, 4]] = False
    np.testing.assert_array_equal(np.asarray(got_pool)[untouched],
                                  np.asarray(pool)[untouched])


def _chain_gate():
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "tpu_kernel_check", Path(__file__).resolve().parents[1]
        / "benchmarks" / "tpu_kernel_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_chip_gate_of_the_delta_state_tells_its_planted_faults():
    """``tpu_kernel_check.py --kernels kda-chain`` (what the chip runs over
    the Pallas update at the published widths), here over the XLA update at
    the debug widths."""
    gate = _chain_gate()
    got = gate.check_kda_chain(CFG, NO_KERNELS, steps=96)
    assert max(got["served"]) < gate.KDA_CHAIN_LIMIT < min(
        got["bf16"] + got["stale"])


# -- a share of the experts ---------------------------------------------------------

def _expert_layer(key, cfg, T):
    """One expert layer's weights with EVERY expert, and T tokens."""
    keys = iter(jax.random.split(key, 12))

    def w(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
    whole = cfg.replace(experts_held=0, experts_first=0)
    lp = {k: a[0] for k, a in llama._init_experts(
        whole, 1, keys, w, jnp.float32).items()}
    return lp, jax.random.normal(next(keys), (T, cfg.hidden_size))


def _share_of(lp, first, held):
    return {k: (a[first:first + held]
                if k in ("w_gate", "w_up", "w_down") else a)
            for k, a in lp.items()}


# The deployments that hold a share of their experts: kimi-linear's four
# chips (16 debug experts in shares of 4), glm-5.2's sixteen (its debug
# block widened to 32 experts, top-4, in shares of 2), each against its own
# plain reference.
_SHARED_OUT = {
    "kimi-linear": (CFG, ref, 4),
    "glm-5.2": (get_model_config("debug-dsa-mla-moe").replace(
        num_experts=32, num_experts_per_tok=4), glm_ref, 16)}


@pytest.mark.parametrize("model", list(_SHARED_OUT))
@pytest.mark.parametrize("kernels", [NO_KERNELS,
                                     Kernels(grouped_experts=True)],
                         ids=["dense", "grouped"])
def test_four_shares_add_up_to_the_whole_layer(kernels, model):
    """The routed parts the shares give (four of kimi-linear's, all sixteen
    of glm-5.2's) plus the shared expert counted ONCE equal the uncut
    reference's whole layer, on either dispatch path (200 tokens: over the
    dense rule's 128, so ``grouped`` is grouped); padding tokens add nothing
    and are in no expert's load."""
    base, ref, shares = _SHARED_OUT[model]
    held = base.num_experts // shares
    whole = base.replace(experts_held=0)
    lp, x = _expert_layer(jax.random.key(7), base, 200)
    valid = jnp.arange(200) < 190
    want = ref._experts(lp, whole, x)
    shared = ref._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, loads = shared, []
    for first in range(0, base.num_experts, held):
        cfg = base.replace(experts_first=first, experts_held=held)
        assert llama.grouped_dispatch(200, cfg, kernels) == \
            kernels.grouped_experts
        load = []
        out = llama._moe_mlp(_share_of(lp, first, held), x, cfg,
                             kernels=kernels, load_out=load, valid=valid)
        # the program's share is the reference's, given the same share
        # (float32 sums in another order: terms of size ~1)
        np.testing.assert_allclose(
            out[:190],
            ref._experts(_share_of(lp, first, held), cfg, x)[:190],
            atol=2e-5)
        total = total + (out - shared)
        loads.append(load[0])
    # float32 sums in another order: every expert's terms of size ~1
    np.testing.assert_allclose(total[:190], want[:190], atol=5e-5)
    # every share counts the SAME load, over all experts, padding left out
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert int(loads[0].sum()) == 190 * base.num_experts_per_tok


def test_the_dispatch_rule_and_the_load_are_over_the_experts_held():
    full = apply_hf_overrides(get_model_config("kimi-linear-48b-a3b"),
                              {"experts_held": 64})
    # 64 rows x 8 of 256: 128 pairs reach the 64 held experts, 2 each
    assert llama.held_pairs(64, full) == 128.0
    assert not llama.dense_dispatch_pays(64, full)      # 2 < 4 a held expert
    assert llama.dense_dispatch_pays(128, full)         # 4 a held expert
    assert not llama.dense_dispatch_pays(129, full)     # over the balance
    # ... the same rule as with every expert held: pairs a HELD expert sees
    whole = get_model_config("kimi-linear-48b-a3b")
    assert [llama.dense_dispatch_pays(t, whole) for t in (64, 128)] == \
        [False, True]
    # the host's gauges: the held experts' balance, and the share of the
    # step's real pairs that reached them
    obs = Observability()
    load = np.zeros((2, 16), np.int32)
    load[:, 4:8] = [[30, 10, 10, 10], [10, 10, 10, 30]]     # held: 4..7
    load[:, 12] = 60                                        # absent, busy
    obs.moe_held = (4, 4)
    obs.on_expert_load((load,))
    assert obs.moe_pairs_held_share == pytest.approx(50.0)
    assert obs.moe_expert_load_max_ratio == pytest.approx(40 / 30)
    obs.moe_routed_pairs["mixed"] = 240
    assert "kgct_moe_pairs_held_share 50.0000" in obs.render_prometheus()


# -- the engine: slots and latent pages through the scheduler -------------------------

def _engine(**sched):
    kw = dict(max_num_seqs=4, max_prefill_tokens=32, decode_buckets=(1, 2, 4),
              prefill_buckets=(16, 32))
    kw.update(sched)
    pages = kw.pop("num_pages", 64)
    model = kw.pop("model", CFG)
    return LLMEngine(EngineConfig(
        model=model, cache=CacheConfig(page_size=PS, num_pages=pages),
        scheduler=SchedulerConfig(**kw)))


PROMPTS = [[int(t) for t in _tokens(n, 40 + n)] for n in (7, 50, 20, 90, 11, 33)]
GREEDY = SamplingParams(max_tokens=20, temperature=0.0)


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    outs = eng.generate(PROMPTS, GREEDY)
    return eng, [o.output_token_ids for o in outs]


def test_engine_greedy_equals_the_reference(served):
    """Six prompts over four seats: packed prefill, prompts over the step
    budget in chunks (solo and beside decode rows), chained decode windows
    dispatched behind, slots handed on from finished sequences."""
    eng, outs = served
    for prompt, out in zip(PROMPTS, outs):
        # one reference pass over prompt + output: row t is the next token
        want = jnp.argmax(ref.forward(eng.params, CFG, prompt + out), axis=-1)
        assert out == [int(t) for t in want[len(prompt) - 1:-1]]
    alloc = eng.scheduler.allocator
    assert alloc.num_free_slots == alloc.num_state_slots - 1 == 4
    assert alloc.num_free == alloc.num_pages - 1
    kinds = {kind for kind, _ in eng.obs.steps_dispatched}
    assert {"prefill", "mixed", "decode"} <= kinds
    assert any(behind for _, behind in eng.obs.steps_dispatched)


@pytest.mark.parametrize("warm", ["warm_full_window", "warm_mixed_steps"])
def test_a_warmed_step_program_leaves_slots_and_pages_as_they_were(
        served, warm):
    """``warm_full_window`` and ``warm_mixed_steps`` (the serving CLI's,
    before it listens) run a window, a mixed step, of padding alone: they
    write the scrap slot and the scrap page, so the same prompts are served
    as before them."""
    eng, want = served
    getattr(eng, warm)()
    alloc = eng.scheduler.allocator
    assert alloc.num_free_slots == alloc.num_state_slots - 1
    assert alloc.num_free == alloc.num_pages - 1
    outs = eng.generate(PROMPTS, GREEDY)
    assert [o.output_token_ids for o in outs] == want


def test_preemption_by_recompute_frees_pages_and_slot(served):
    """Two seats and a page pool that cannot hold both sequences to their
    end: sequences are preempted by recompute (slot AND latent pages freed,
    prompt and output so far prefilled again from zero); every output
    equals the unpressured engine's."""
    _, want = served
    eng = _engine(max_num_seqs=2, decode_buckets=(1, 2), num_pages=9)
    outs = eng.generate(PROMPTS, GREEDY)
    assert eng.scheduler.num_preemptions_by_kind["recompute"] > 0
    assert [o.output_token_ids for o in outs] == want
    assert eng.scheduler.allocator.num_free_slots == 2
    assert eng.scheduler.allocator.num_free == 8


def test_an_engine_with_a_share_of_the_experts_equals_the_reference():
    """Experts 8-11 of 16 held (``--hf-overrides``' keys): the engine and
    the reference leave the same routed parts out; sampling runs too."""
    cfg = apply_hf_overrides(CFG, {"experts_held": 4, "experts_first": 8})
    eng = _engine(model=cfg)
    assert eng.params["ssm_layers"]["w_gate"].shape[:2] == (7, 4)
    assert eng.params["ssm_layers"]["router"].shape == (7, 128, 16)
    prompts = PROMPTS[:3]
    for prompt, out in zip(prompts, eng.generate(prompts, GREEDY)):
        out = out.output_token_ids
        want = jnp.argmax(ref.forward(eng.params, cfg, prompt + out), axis=-1)
        assert out == [int(t) for t in want[len(prompt) - 1:-1]]
    assert 0 < eng.obs.moe_pairs_held_share < 100
    sampled = SamplingParams(max_tokens=8, temperature=0.8, top_k=20, seed=3)
    a = eng.generate(prompts[:1], sampled)[0].output_token_ids
    assert a == eng.generate(prompts[:1], sampled)[0].output_token_ids
    info = eng.runtime_info()
    assert (info["experts_held"], info["experts_first"],
            info["experts_published"]) == (4, 8, 16)


def test_health_and_metrics_report_both_kinds(served):
    from kubernetes_gpu_cluster_tpu.serving.metrics import Metrics
    eng, _ = served
    info = eng.runtime_info()
    per_seq = state_bytes_per_seq(CFG)
    # 8 KDA layers x (4 heads x 32 x 32 float32 + 3 rows x 3 x 128 float32)
    assert per_seq == 8 * (4 * 32 * 32 * 4 + 3 * 384 * 4)
    assert info["kv_layout"] == "latent+state"
    assert (info["kv_layers"], info["state_layers"]) == (3, 8)
    assert info["state_bytes"] == 5 * per_seq
    # one pool of rows [c | k_pe] padded to 128 lanes, over 3 latent layers
    assert info["kv_bytes_per_token"] == kv_cache_bytes_per_token(
        CFG, eng.config.cache) == 3 * 128 * 4
    assert "experts_held" not in info           # every expert is held
    text = Metrics(eng).render()
    for line in ("kgct_state_slots_total 4", "kgct_state_slots_free 4",
                 f"kgct_state_bytes_per_seq {per_seq}"):
        assert line in text
    assert "kgct_moe_pairs_held_share" not in text


# -- refusals: exact, one a flag ------------------------------------------------------

def _config(**kw):
    parallel = kw.pop("parallel", ParallelConfig())
    cache = kw.pop("cache", CacheConfig(page_size=PS, num_pages=64))
    model = kw.pop("model", CFG)
    return EngineConfig(model=model, cache=cache, parallel=parallel,
                        scheduler=SchedulerConfig(
                            max_num_seqs=4, max_prefill_tokens=32,
                            decode_buckets=(1, 2, 4),
                            prefill_buckets=(16, 32), **kw))


@pytest.mark.parametrize("config,extra,flag,mechanism", [
    (dict(parallel=ParallelConfig(tp=2)), {}, "--tensor-parallel-size 2",
     "the latent row is one shared head"),
    (dict(parallel=ParallelConfig(pp=2)), {}, "--pipeline-parallel-size 2",
     "not leading dense layers beside expert layers"),
    (dict(parallel=ParallelConfig(sp=2)), {}, "--sequence-parallel-size 2",
     "not for the latent row"),
    (dict(parallel=ParallelConfig(ep=2)), {}, "--expert-parallel-size 2",
     "no all-to-all dispatch exists"),
    (dict(enable_prefix_caching=True), {}, "--enable-prefix-caching",
     "the recurrent state at the prefix's end is not kept"),
    (dict(spec_decode_enabled=True), {}, "--enable-spec-decode",
     "no latent-page variant"),
    (dict(cache=CacheConfig(page_size=PS, num_pages=64, swap_space_gb=1.0)),
     {}, "--swap-space-gb", "K and V page pairs, not latent pages"),
    (dict(model=CFG.replace(quantization="int8")), {}, "--quantization int8",
     "no int8/int4 path"),
    ({}, dict(role="prefill"), "--role prefill", "handoff frames K and V"),
    ({}, dict(fleet_prefix_cache=True), "--fleet-prefix-cache",
     "prefix export and spill frame"),
    ({}, dict(peer_pool=["http://a"]), "--peer-pool",
     "live migration frames"),
])
def test_a_latent_and_stateful_model_is_refused_by_flag_and_mechanism(
        config, extra, flag, mechanism):
    """The first applicable reason of either kind, by name: one line naming
    the flag and the mechanism."""
    got = cache_kind_refusal(_config(**config), **extra)
    assert got is not None and "\n" not in got
    assert got.startswith(f"{flag} with {CFG.name}: ") and mechanism in got


def test_what_works_is_not_refused_and_a_mesh_refuses_at_start():
    assert cache_kind_refusal(_config()) is None
    assert cache_kind_refusal(_config(), mesh_shape={"tp": 1}) is None
    got = cache_kind_refusal(_config(), mesh_shape={"tp": 4})
    assert got.startswith("--tensor-parallel-size 4 with debug-kda-hybrid")
    with pytest.raises(ValueError, match="--enable-prefix-caching with "
                       "debug-kda-hybrid"):
        LLMEngine(_config(enable_prefix_caching=True))


# -- config.json ---------------------------------------------------------------------

def _catalog_row():
    return {
        "model_type": "kimi_linear", "first_k_dense_replace": 1,
        "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}


def _from_hf(tmp_path, row):
    from kubernetes_gpu_cluster_tpu.engine.weights import config_from_hf
    (tmp_path / "config.json").write_text(json.dumps(row))
    return config_from_hf(str(tmp_path), "kimi-linear-48b-a3b")


def test_config_from_hf_reads_the_catalog_row_into_the_preset(tmp_path):
    assert _from_hf(tmp_path, _catalog_row()) == get_model_config(
        "kimi-linear-48b-a3b")
    # the benchmark's file: the published group whole beside a depth of 9
    row = dict(_catalog_row(), num_hidden_layers=9)
    assert _from_hf(tmp_path, row).layer_types == (
        "kda",) + ("kda", "kda", "attention", "kda") * 2


@pytest.mark.parametrize("edit,message", [
    (lambda lin: lin["kda_layers"].remove(13),
     "names layer 13 in neither of kda_layers and full_attn_layers"),
    (lambda lin: lin["full_attn_layers"].append(2),
     "names layer 2 in both of kda_layers and full_attn_layers"),
])
def test_a_pattern_the_two_lists_do_not_cover_is_refused_by_name(
        tmp_path, edit, message):
    row = _catalog_row()
    edit(row["linear_attn_config"])
    with pytest.raises(ValueError, match=message):
        _from_hf(tmp_path, row)


def test_what_the_decoder_does_not_implement_refuses_the_load(tmp_path):
    with pytest.raises(ValueError, match="num_expert_group=4 is not "
                       "implemented by the delta-rule decoder"):
        _from_hf(tmp_path, dict(_catalog_row(), num_expert_group=4))
    from safetensors.numpy import save_file

    from kubernetes_gpu_cluster_tpu.engine.weights import load_weights
    save_file({"model.embed_tokens.weight": np.zeros((4, 4), np.float32)},
              str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="no loader for a kimi_linear "
                       "checkpoint"):
        load_weights(str(tmp_path), CFG)
