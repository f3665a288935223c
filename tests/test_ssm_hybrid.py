"""A state model on the serving path (granite-4.0-h-micro's block at the
size of ``debug-ssm-hybrid``: 2 periods of [2 state, 1 attention, 1 state],
scan chunk 8, float32, seeded weights), held to the plain reference
``perfbench/reference/granite_4_0_h.py`` (the repository's one copy: the
recurrence token by token, no chunks, no cache, no slots).

What is held: the forward's segment part (packed prompts whose boundaries
fall inside scan chunks; a prompt in two chunks with history), its row part
(a decode through the slots), both in one mixed step; the slots (zero at a
sequence's start whatever they held, padding on the scrap slot only, reuse
after finish and after preemption); the manager (admission waits for a slot
though pages are free); the Pallas update in interpret mode; the checkpoint
loader; every flag a state model is refused, by its message; and that a
model without state layers carries nothing of all this.
"""

import dataclasses
import functools
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
    KVCache, PageAllocator, allocate_kv_cache, kv_cache_bytes_per_token,
    state_bytes_per_seq)
from kubernetes_gpu_cluster_tpu.engine.scheduler import Scheduler
from kubernetes_gpu_cluster_tpu.engine.sequence import Sequence
from kubernetes_gpu_cluster_tpu.models import llama
from kubernetes_gpu_cluster_tpu.ops import ssm as ssm_ops
from kubernetes_gpu_cluster_tpu.ops.attention import Kernels
from perfbench.reference import granite_4_0_h as ref

CFG = get_model_config("debug-ssm-hybrid")
PS, PAGES, SLOTS = 16, 24, 6
# Float32 against float32: the served path and the reference differ in the
# order of sums only (chunked against token-by-token, fused matmuls). Their
# logits agree to ~3e-6 here; a recurrent state rounded to bfloat16 at every
# token reads 2.3e-3 after 90 tokens
# (test_a_bf16_state_would_fail_the_tolerance), so this limit has an order
# of magnitude of room above the one and 40x below the other.
TOL = 5e-5


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


# -- the forward against the reference -----------------------------------------

def test_prefill_then_64_decode_steps_match_the_reference(params):
    """Teacher-forced: one reference pass over 20 + 70 tokens gives every
    position's logits; the served path prefills 20 and decodes 70 through
    the slot and the pages."""
    seq = _tokens(90, 1)
    want = ref.forward(params, CFG, seq)
    pages, slot = [1, 2, 3, 4, 5, 6], 3
    tok, meta = _segments([seq[:20]], [pages], [slot], 32)
    hid, kv, _ = _fwd(params, tok, meta, _pool(9.0))
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


def test_packed_prefill_with_boundaries_inside_chunks(params):
    """Three prompts of 13, 5 and 21 tokens side by side: with a scan chunk
    of 8 the boundaries (13, 18) fall inside chunks 1 and 2."""
    prompts = [_tokens(n, 10 + n) for n in (13, 5, 21)]
    tok, meta = _segments(prompts, [[1], [2], [3, 4]], [1, 2, 3], 48,
                          n_slots=4)
    hid, kv, _ = _fwd(params, tok, meta, _pool(7.0))
    logits = _logits(params, hid)
    for s, p in enumerate(prompts):
        assert float(jnp.max(jnp.abs(
            logits[s] - ref.forward(params, CFG, p)[-1]))) < TOL
    # ... and each one's slot holds what a prompt alone leaves there.
    for s, p in enumerate(prompts):
        tok1, meta1 = _segments([p], [[5, 6]], [5], 32)
        _, alone, _ = _fwd(params, tok1, meta1, _pool())
        np.testing.assert_allclose(kv.ssm[:, s + 1], alone.ssm[:, 5],
                                   atol=1e-5)
        np.testing.assert_allclose(kv.conv[:, s + 1], alone.conv[:, 5],
                                   atol=1e-5)


@pytest.mark.parametrize("split", [21, 3, 38])
def test_two_chunks_with_history_equal_one(params, split):
    """40 tokens at once, and as [0:split) then [split:40) continuing from
    the slot (the second chunk alone is 19, 37 or 2 tokens: the conv reaches
    back into the slot's rows)."""
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
    np.testing.assert_allclose(_logits(params, hid1), _logits(params, hid2),
                               atol=TOL)
    np.testing.assert_allclose(kv1.ssm[:, 2], kv2.ssm[:, 2], atol=1e-5)
    np.testing.assert_allclose(kv1.conv[:, 2], kv2.conv[:, 2], atol=1e-5)


def _mixed_step(params):
    """A chunk with history beside two decode rows, [segment tokens | row
    tokens]: (tokens, meta, the pool before it, the chunk step's and the
    decode step's hidden states, the pool behind the two)."""
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
    meta_m = meta_s._replace(
        seg_ids=jnp.concatenate([meta_s.seg_ids, jnp.full(4, -1)]),
        positions=jnp.concatenate([meta_s.positions, meta_r.positions]),
        slot_mapping=jnp.concatenate([meta_s.slot_mapping,
                                      meta_r.slot_mapping]),
        logits_indices=jnp.asarray([16, 17, 12], jnp.int32),
        page_tables=meta_r.page_tables, context_lens=meta_r.context_lens,
        row_slots=meta_r.row_slots)
    return jnp.concatenate([tok_s, tok_r]), meta_m, kv0, hid_s, hid_r, kv_p


def test_a_mixed_step_equals_the_two_pure_steps(params):
    """A chunk with history beside two decode rows in ONE program, against
    the chunk step and the decode step each alone."""
    tok, meta_m, kv0, hid_s, hid_r, kv_p = _mixed_step(params)
    hid_m, kv_m, _ = _fwd(params, tok, meta_m, kv0)
    np.testing.assert_allclose(hid_m[:2], hid_r[:2], atol=1e-5)
    np.testing.assert_allclose(hid_m[2], hid_s[0], atol=1e-5)
    for got, want in zip(kv_m, kv_p):
        if got is not None:     # (no index keys: this model has no indexer)
            np.testing.assert_allclose(got[:, 1:], want[:, 1:], atol=1e-5)


class ConvKernel(Kernels):
    """The XLA references, but for the conv stage: the chip's kernel in
    interpret mode."""

    def conv_segments(self, *args):
        from kubernetes_gpu_cluster_tpu.ops.pallas.conv_segments import (
            conv_segments)
        return conv_segments(*args, interpret=True)


def test_a_mixed_step_through_the_conv_kernel_equals_the_xla_form(params):
    """The same mixed step with the conv stage of its segment part as
    ``ops/pallas/conv_segments.py`` (a chunk of 16 tokens with history: the
    slot's rows before token 0, a token block that ends behind the chunk):
    logits, slots and conv rows as the XLA form's, to the order of the
    sums."""
    tok, meta_m, kv0, *_ = _mixed_step(params)
    hid_x, kv_x, _ = _fwd(params, tok, meta_m, kv0)
    hid_k, kv_k, _ = jax.jit(lambda p, t, m, kv: llama.forward(
        p, CFG, t, m, kv, ConvKernel()))(params, tok, meta_m, kv0)
    want = _logits(params, hid_x)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(_logits(params, hid_k), want,
                               atol=2e-5 * scale)
    np.testing.assert_allclose(kv_k.ssm, kv_x.ssm, atol=1e-4)
    np.testing.assert_allclose(kv_k.conv, kv_x.conv, atol=1e-5)


def test_padding_rows_touch_only_the_scrap_slot(params):
    tok, meta = _rows([7], [0], [[1]], [4], 4)      # 1 real row, 3 padding
    before = _pool(2.0)
    _, after, _ = _fwd(params, tok, meta, _pool(2.0))
    for b, a in zip((before.ssm, before.conv), (after.ssm, after.conv)):
        changed = np.any(np.asarray(a != b).reshape(
            a.shape[0], a.shape[1], -1), axis=(0, 2))
        assert list(np.nonzero(changed)[0]) in ([0, 4], [4])


# -- the scan and the update, alone ----------------------------------------------

def _pallas_scan(*args):
    from kubernetes_gpu_cluster_tpu.ops.pallas.ssm_chunk import ssm_chunk
    return ssm_chunk(*args, interpret=True)


# The chunked scan's two implementations: what the CPU and NO_KERNELS run,
# and the chip's kernel in interpret mode.
_SCAN_FORMS = [pytest.param(ssm_ops.ssm_chunk_scan_xla, id="xla"),
               pytest.param(_pallas_scan, id="pallas")]


def _scan_inputs(T, H, P, N, key, dtype=jnp.float32):
    k = jax.random.split(key, 6)
    x = jax.random.normal(k[0], (T, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    dA = dt * -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B, C = (jax.random.normal(k[i], (T, N)).astype(dtype) for i in (3, 4))
    return (x, dt, dA, B, C), jax.random.normal(k[5], (N, H * P))


@pytest.mark.parametrize("form", _SCAN_FORMS)
@pytest.mark.parametrize("chunk", [8, 256])
def test_chunked_scan_equals_the_recurrence(chunk, form):
    """Three segments in 300 tokens (boundaries at 70 and 201: inside
    chunks at both sizes), the first continuing from a state."""
    T, H, P, N = 300, 4, 8, 16
    (x, dt, dA, B, C), init = _scan_inputs(T, H, P, N, jax.random.key(chunk))
    seg = jnp.asarray([0] * 70 + [1] * 131 + [2] * 79 + [-1] * 20)
    ends = jnp.asarray([69, 200, 279, -1])
    y, final = form(x, dt, dA, B, C, seg, ends, init, 0, chunk)
    for s, (a, b) in enumerate(((0, 70), (70, 201), (201, 280))):
        y_s, f_s = ssm_ops.ssm_recurrence(
            x[a:b], dt[a:b], dA[a:b], B[a:b], C[a:b],
            init if s == 0 else jnp.zeros_like(init))
        np.testing.assert_allclose(y[a:b], y_s, atol=2e-4, rtol=1e-4)
        np.testing.assert_allclose(final[s], f_s, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("bounds,history", [
    pytest.param([(0, 300)], False, id="fresh"),
    pytest.param([(0, 300)], True, id="history"),
    pytest.param([(0, 70), (70, 201), (201, 300)], True, id="three-packed"),
    pytest.param([], False, id="no-real-token"),
])
def test_the_scan_kernel_in_interpret_mode_equals_the_xla_form(bounds,
                                                               history):
    """``ops/pallas/ssm_chunk.py`` against ``ssm_chunk_scan_xla`` at the
    served heads' lane tiling (64 heads of 64, N = 128: two heads a 128-lane
    tile, two blocks of 2048 lanes), float32, over 512 tokens: one prompt
    from nothing and from a slot's state (``init_seg`` 0), a padding tail
    behind it that ends in a chunk with no real token (zeros there); three
    prompts packed, both boundaries inside chunks of both forms; no real
    token at all; absent segments (``seg_ends`` -1) beside the present
    ones. y and every final, to the order of the sums (the kernel's chunks
    are 128 tokens, the XLA form's 256)."""
    T, H, P, N, S = 512, 64, 64, 128, 3
    inputs, init = _scan_inputs(T, H, P, N, jax.random.key(5))
    seg = np.full(T, -1, np.int32)
    for s, (a, b) in enumerate(bounds):
        seg[a:b] = s
    ends = jnp.asarray([b - 1 for _, b in bounds]
                       + [-1] * (S - len(bounds)), jnp.int32)
    args = (*inputs, jnp.asarray(seg), ends, init, 0 if history else -2, 256)
    want_y, want_f = ssm_ops.ssm_chunk_scan_xla(*args)
    got_y, got_f = _pallas_scan(*args)
    n = bounds[-1][1] if bounds else 0
    scale = float(jnp.max(jnp.abs(want_y[:n]))) if n else 1.0
    np.testing.assert_allclose(got_y[:n], want_y[:n], atol=2e-5 * scale)
    np.testing.assert_allclose(got_f[:len(bounds)], want_f[:len(bounds)],
                               atol=1e-4)
    assert bool(jnp.isfinite(got_y).all()) and bool(jnp.isfinite(got_f).all())
    whole = -(-n // 128) * 128              # the kernel's chunks of padding
    assert whole < T and not np.asarray(got_y[whole:]).any()


def test_pallas_update_in_interpret_mode_equals_the_reference():
    from kubernetes_gpu_cluster_tpu.ops.pallas.ssm_update import ssm_update
    k = jax.random.split(jax.random.key(3), 5)
    pool = jax.random.normal(k[0], (3, 5, 128, 256), jnp.float32)
    slots = jnp.asarray([2, 4, 0, 0], jnp.int32)
    decay = jax.random.uniform(k[1], (4, 256))
    dtx, B, C = (jax.random.normal(k[i], s) for i, s in
                 ((2, (4, 256)), (3, (4, 128)), (4, (4, 128))))
    want_pool, want_y = ssm_ops.ssm_update_xla(pool, jnp.int32(1), slots,
                                               decay, dtx, B, C)
    got_pool, got_y = ssm_update(pool, jnp.int32(1), slots, decay, dtx, B, C,
                                 interpret=True)
    np.testing.assert_allclose(got_y[:2], want_y[:2], atol=1e-4)
    # the rows' slots to a rounding (the two fuse the multiply-adds
    # otherwise), every other slot of every layer untouched: bitwise
    np.testing.assert_allclose(got_pool[1, [2, 4]], want_pool[1, [2, 4]],
                               atol=1e-5)
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [0, 2, 4]] = False
    np.testing.assert_array_equal(np.asarray(got_pool)[untouched],
                                  np.asarray(pool)[untouched])


# -- the conv stage, alone ------------------------------------------------------

# The two kinds' pieces at widths of whole tiles of their own: [x | B | C]
# in the model's dtype, and unit q (scaled), unit k, v as float32 heads.
CONV_SPLITS = {
    "mamba": (288, ssm_ops.ConvSplit((256, 16, 16))),
    "kda": (384, ssm_ops.ConvSplit((128,) * 3, jnp.float32, 32,
                                   (32 ** -0.5, 1.0, None))),
}
CONV_SEGMENTS = {
    "one": lambda T: [(0, T)],
    "three-packed": lambda T: [(0, T // 2), (T // 2, T // 2 + 2),
                               (T // 2 + 2, T)],
    "padding-tail": lambda T: [(0, T // 3), (T // 3, T - 45)],
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("history", [False, True], ids=["zeros", "slot-rows"])
@pytest.mark.parametrize("segments", list(CONV_SEGMENTS))
@pytest.mark.parametrize("T", [512, 328], ids=["whole-blocks", "ragged"])
@pytest.mark.parametrize("kind", list(CONV_SPLITS))
def test_the_conv_kernel_in_interpret_mode_equals_the_xla_form(
        kind, T, segments, history, bias, dtype):
    """``ops/pallas/conv_segments.py`` against ``conv_operands_xla``: two
    token blocks and one and a part (the rows behind the segment part are
    the step's row tokens), one segment, three packed of which one is
    shorter than the taps reach, a padding tail; from zeros and from a
    slot's rows; with and without bias; both dtypes. The taps' sum BITWISE
    (the same products in the same order; the inputs are whole bfloat16
    values in either dtype, so that a product is exact and the CPU's fused
    multiply-adds, which the two programs place differently, round as the
    plain ones do), the activated pieces within 2 units in the last place
    of float32 (unit q and k within 4: the lane sums' order differs), an
    output in bfloat16 equal or its neighbour; and the new conv rows both
    forms share (``segment_conv_rows``) bitwise a segment's last three
    inputs, the slot's rows or zeros where it is shorter."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.conv_segments import (
        conv_segments)
    C, split = CONV_SPLITS[kind]
    k = jax.random.split(jax.random.key(T), 4)
    draw = lambda key, shape: jax.random.normal(key, shape).astype(
        jnp.bfloat16).astype(dtype)
    xbc, w = draw(k[0], (T + 8, C)), draw(k[1], (4, C))
    b = draw(k[2], (C,)) if bias else None
    init = draw(k[3], (3, C)) if history else jnp.zeros((3, C), dtype)
    bounds = CONV_SEGMENTS[segments](T)
    seg = np.full(T, -1, np.int32)
    for s, (lo, hi) in enumerate(bounds):
        seg[lo:hi] = s
    args = (xbc, jnp.asarray(seg), init, w, b)

    got_sum = conv_segments(*args, split, activate=False, interpret=True)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p).reshape(T, -1) for p in got_sum], 1),
        np.asarray(ssm_ops.conv_segments(xbc[:T], *args[1:])))

    ends = jnp.asarray([hi - 1 for _, hi in bounds]
                       + [-1] * (4 - len(bounds)), jnp.int32)
    rows = ssm_ops.segment_conv_rows(xbc, args[1], ends, init)
    assert rows.dtype == xbc.dtype and rows.shape == (4, 3, C)
    ext = np.concatenate([np.asarray(init.astype(jnp.float32)),
                          np.asarray(xbc.astype(jnp.float32))])
    for s, (lo, hi) in enumerate(bounds):       # ext row r is token r - 3
        own = ext[hi:hi + 3] * (np.arange(hi - 3, hi) >= (lo and lo))[:, None]
        own[:max(lo + 3 - hi, 0)] *= lo == 0    # before token 0: the slot's
        np.testing.assert_array_equal(
            np.asarray(rows[s].astype(jnp.float32)), own)

    want = ssm_ops.conv_operands_xla(*args, split)
    got = conv_segments(*args, split, interpret=True)
    for i, (g, w_) in enumerate(zip(got, want)):
        assert g.shape == w_.shape and g.dtype == w_.dtype
        unit = bool(split.unit) and split.unit[i] is not None
        limit = 1 if g.dtype == jnp.bfloat16 else 4 if unit else 2
        assert _chain_gate().ulps(g, w_) <= limit, i


@functools.lru_cache(maxsize=None)
def _chain_gate():
    """``benchmarks/tpu_kernel_check.py``'s gate of the state's precision
    (what the chip runs over the Pallas update at the published widths),
    here over the XLA update at the debug widths."""
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "tpu_kernel_check", Path(__file__).resolve().parents[1]
        / "benchmarks" / "tpu_kernel_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_chip_gate_of_the_state_tells_its_planted_faults():
    gate = _chain_gate()
    from kubernetes_gpu_cluster_tpu.ops.attention import NO_KERNELS
    got = gate.check_ssm_chain(CFG, NO_KERNELS, steps=96)
    assert max(got["served"]) < gate.STATE_CHAIN_LIMIT < min(
        got["bf16"] + got["stale"])


def test_the_chip_gate_of_the_state_refuses_a_bf16_pool(monkeypatch):
    """The gate takes its pool from the engine's own allocation: a program
    whose slots are bfloat16 fails it, whatever its arithmetic."""
    gate = _chain_gate()
    from kubernetes_gpu_cluster_tpu.engine import kv_cache
    from kubernetes_gpu_cluster_tpu.ops.attention import NO_KERNELS
    monkeypatch.setattr(kv_cache, "STATE_DTYPE", jnp.bfloat16)
    with pytest.raises(AssertionError, match="the state as served"):
        gate.check_ssm_chain(CFG, NO_KERNELS, steps=96)


# -- the engine: slots through the scheduler ---------------------------------------

def _engine(**sched):
    kw = dict(max_num_seqs=4, max_prefill_tokens=32, decode_buckets=(1, 2, 4),
              prefill_buckets=(16, 32))
    kw.update(sched)
    pages = kw.pop("num_pages", 64)
    return LLMEngine(EngineConfig(
        model=CFG, cache=CacheConfig(page_size=PS, num_pages=pages),
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
    budget in chunks (solo and beside decode rows), chained decode windows,
    slots handed on from finished sequences."""
    eng, outs = served
    for prompt, out in zip(PROMPTS, outs):
        # one reference pass over prompt + output: row t is the next token
        want = jnp.argmax(ref.forward(eng.params, CFG, prompt + out), axis=-1)
        assert out == [int(t) for t in want[len(prompt) - 1:-1]]
    alloc = eng.scheduler.allocator
    assert alloc.num_free_slots == alloc.num_state_slots - 1 == 4
    assert alloc.num_free == alloc.num_pages - 1


def test_the_warmed_step_programs_leave_slots_and_pages_as_they_were(served):
    """``warm_full_window`` and ``warm_mixed_steps`` (the serving CLI's,
    before it listens) run a window and a mixed step of padding alone: they
    write the scrap slot and the scrap page, so the same prompts are served
    as before them."""
    eng, want = served
    eng.warm_full_window()
    eng.warm_mixed_steps()
    alloc = eng.scheduler.allocator
    assert alloc.num_free_slots == alloc.num_state_slots - 1
    assert alloc.num_free == alloc.num_pages - 1
    outs = eng.generate(PROMPTS, GREEDY)
    assert [o.output_token_ids for o in outs] == want


def test_slots_are_reused_after_finish_and_preemption(served):
    """Two seats and a page pool that cannot hold both sequences to their
    end: sequences are preempted by recompute (slot freed, prompt and
    output so far prefilled again from zero) and slots pass from finished
    to waiting sequences; every output equals the unpressured engine's."""
    _, want = served
    eng = _engine(max_num_seqs=2, decode_buckets=(1, 2), num_pages=9)
    outs = eng.generate(PROMPTS, GREEDY)
    assert eng.scheduler.num_preemptions_by_kind["recompute"] > 0
    assert [o.output_token_ids for o in outs] == want
    assert eng.scheduler.allocator.num_free_slots == 2


def test_admission_waits_for_a_slot_though_pages_are_free():
    config = EngineConfig(
        model=CFG, cache=CacheConfig(page_size=PS, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=4, max_prefill_tokens=32,
                                  decode_buckets=(1, 2, 4),
                                  prefill_buckets=(16, 32)))
    sched = Scheduler(config, 64, num_state_slots=3)    # two seats' worth
    seqs = [Sequence(f"r{i}", [5] * 6, GREEDY) for i in range(3)]
    for s in seqs:
        sched.add(s)
    batch = sched.schedule()
    assert [s.request_id for s in batch.seqs] == ["r0", "r1"]
    assert sorted(batch.seg_slots[:2]) == [1, 2] and len(sched.waiting) == 1
    assert sched.allocator.num_free > 50 and not sched.allocator.num_free_slots
    for s in batch.seqs:
        s.append_token(9)
    sched.mixed_enabled = False
    assert sched._schedule_prefills() is None           # still waiting
    sched.finish(seqs[0], None)
    batch = sched._schedule_prefills()
    assert [s.request_id for s in batch.seqs] == ["r2"]
    assert seqs[2].state_slot == batch.seg_slots[0] and seqs[0].state_slot is None


def test_the_allocator_hands_out_both_kinds():
    alloc = PageAllocator(8, PS, num_state_slots=3)
    assert alloc.can_admit(7) and not alloc.can_admit(8)
    a, b = alloc.allocate_slot(), alloc.allocate_slot()
    assert {a, b} == {1, 2} and not alloc.can_admit(1)
    alloc.free_slot(a)
    with pytest.raises(RuntimeError, match="double free"):
        alloc.free_slot(a)
    assert PageAllocator(8, PS).allocate_slot() is None     # no state: none


def test_health_and_metrics_report_both_kinds(served):
    from kubernetes_gpu_cluster_tpu.serving.metrics import Metrics
    eng, _ = served
    info = eng.runtime_info()
    per_seq = state_bytes_per_seq(CFG)
    assert per_seq == 6 * (16 * 256 * 4 + 3 * (256 + 32) * 4)
    assert info["kv_layout"] == "k|v+state"
    assert (info["kv_layers"], info["state_layers"]) == (2, 6)
    assert info["state_bytes"] == 5 * per_seq
    assert info["kv_bytes_per_token"] == kv_cache_bytes_per_token(
        CFG, eng.config.cache) == 2 * 2 * 64 * 4
    text = Metrics(eng).render()
    for line in ("kgct_state_slots_total 4", "kgct_state_slots_free 4",
                 f"kgct_state_bytes_per_seq {per_seq}"):
        assert line in text.splitlines()


# -- what a state model is refused, and what it is not ------------------------------

def _cfg(**kw):
    return EngineConfig(model=kw.pop("model", CFG), **kw)


@pytest.mark.parametrize("flag,config,extra,mechanism", [
    ("--enable-prefix-caching",
     _cfg(scheduler=SchedulerConfig(enable_prefix_caching=True)), {},
     "state at the prefix's end is not kept"),
    ("--swap-space-gb", _cfg(cache=CacheConfig(swap_space_gb=1.0)), {},
     "not a sequence's recurrent state"),
    ("--enable-spec-decode",
     _cfg(scheduler=SchedulerConfig(spec_decode_enabled=True)), {},
     "no snapshot exists to roll it back"),
    ("--role prefill", _cfg(), {"role": "prefill"}, "handoff"),
    ("--role decode", _cfg(), {"role": "decode"}, "handoff"),
    ("--fleet-prefix-cache", _cfg(), {"fleet_prefix_cache": True},
     "prefix export"),
    ("--peer-pool", _cfg(), {"peer_pool": "http://peer:8000"},
     "live migration"),
    ("--tensor-parallel-size 2", _cfg(parallel=ParallelConfig(tp=2)), {},
     "no sharded form"),
    ("--pipeline-parallel-size 2", _cfg(parallel=ParallelConfig(pp=2)), {},
     "period of typed layers"),
    ("--sequence-parallel-size 2", _cfg(parallel=ParallelConfig(sp=2)), {},
     "in order on one"),
    ("--expert-parallel-size 2", _cfg(parallel=ParallelConfig(ep=2)), {},
     "no experts"),
    ("--quantization int8", _cfg(model=CFG.replace(quantization="int8")), {},
     "no int8/int4 layout"),
])
def test_refused_flag_is_named_with_its_mechanism(flag, config, extra,
                                                  mechanism):
    msg = cache_kind_refusal(config, **extra)
    assert msg.startswith(flag) and CFG.name in msg and mechanism in msg
    assert "\n" not in msg
    if not extra:   # the engine refuses too, before it builds anything
        with pytest.raises(ValueError, match=flag.split()[0]):
            LLMEngine(config)


def test_default_flags_are_not_refused():
    assert cache_kind_refusal(_cfg()) is None


def test_the_kv_wire_paths_refuse_a_state_model(served):
    eng, _ = served
    with pytest.raises(ValueError, match="recurrent state"):
        eng._require_kv_wire("export")


# -- the configuration ----------------------------------------------------------------

def test_the_period_is_derived_from_layer_types():
    micro = get_model_config("granite-4.0-h-micro")
    assert micro.layer_period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (micro.num_kv_layers, micro.num_state_layers) == (4, 36)
    assert state_bytes_per_seq(micro) == 36 * (128 * 4096 * 4 + 3 * 4352 * 2)
    assert kv_cache_bytes_per_token(micro, CacheConfig()) == 8192
    assert CFG.layer_period == ("mamba", "mamba", "attention", "mamba")
    assert get_model_config("qwen3-4b").layer_period == ("attention",)
    half = apply_hf_overrides(micro, {"num_hidden_layers": 20})
    assert half.num_state_layers == 18 and half.layer_period == micro.layer_period


def test_a_depth_that_is_not_whole_periods_is_refused_by_name():
    with pytest.raises(ValueError, match="not whole periods of "
                       "granite-4.0-h-micro's layer pattern"):
        apply_hf_overrides(get_model_config("granite-4.0-h-micro"),
                           {"num_hidden_layers": 15})
    with pytest.raises(ValueError, match="layer_types names 8 layers"):
        CFG.replace(num_layers=6)
    with pytest.raises(ValueError, match="mamba_n_groups 2"):
        CFG.replace(mamba_n_groups=2)


def test_a_model_without_state_layers_carries_nothing_of_it():
    """Its cache has two leaves, its packed int buffer five columns, its
    decode window's no slot column, and its forward no state operation: the
    step programs of the existing presets are the parent's
    (scripts/step_program_jaxprs.py compare, handed in with PERF.md)."""
    from kubernetes_gpu_cluster_tpu.engine.engine import (_pack_int_b,
                                                          _slot_columns)
    tiny = get_model_config("debug-tiny")
    eng = LLMEngine(EngineConfig(
        model=tiny, cache=CacheConfig(page_size=PS, num_pages=16),
        scheduler=SchedulerConfig(max_num_seqs=2, decode_buckets=(1, 2),
                                  prefill_buckets=(16,))))
    assert len(jax.tree.leaves(eng.kv_cache)) == 2
    assert eng.scheduler.allocator.num_state_slots == 0
    eng.add_request("a", [3, 4, 5], GREEDY)
    batch = eng.scheduler.schedule()
    assert batch.seg_slots is None and _pack_int_b(batch).shape == (1, 5)
    assert _slot_columns(tiny, None, "seg_slots") == {}
    assert "kv_layers" not in eng.runtime_info()
    tok = jnp.zeros(16, jnp.int32)
    meta = llama.StepMeta(seg_ids=tok, positions=tok, slot_mapping=tok)
    text = str(jax.make_jaxpr(lambda p, kv: llama.forward(
        p, tiny, tok, meta, kv))(eng.params, eng.kv_cache))
    assert "softplus" not in text and "cumsum" not in text


# -- a synthetic granitemoehybrid checkpoint round-trips ----------------------------

def test_checkpoint_loads_into_the_tree(tmp_path, params):
    from safetensors.numpy import save_file

    from kubernetes_gpu_cluster_tpu.engine.weights import (config_from_hf,
                                                           load_weights)
    t = {"model.embed_tokens.weight": params["embed"],
         "model.norm.weight": params["final_norm"]}
    at = {"attention": 0, "mamba": 0}
    for l, kind in enumerate(CFG.layer_types):
        p, i = f"model.layers.{l}.", at[kind]
        at[kind] += 1
        lp = jax.tree.map(lambda a: np.asarray(a[i]), params[
            "layers" if kind == "attention" else "ssm_layers"])
        t[p + "input_layernorm.weight"] = lp["input_norm"]
        t[p + "post_attention_layernorm.weight"] = lp["post_attn_norm"]
        t[p + "shared_mlp.input_linear.weight"] = np.concatenate(
            [lp["w_gate"], lp["w_up"]], axis=1).T
        t[p + "shared_mlp.output_linear.weight"] = lp["w_down"].T
        if kind == "attention":
            for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                                 ("wo", "o")):
                t[f"{p}self_attn.{theirs}_proj.weight"] = lp[ours].T
            continue
        m = p + "mamba."
        t[m + "in_proj.weight"] = np.concatenate(
            [lp["w_z"], lp["w_xbc"], lp["w_dt"]], axis=1).T
        t[m + "conv1d.weight"] = lp["conv_w"].T[:, None, :]
        t[m + "conv1d.bias"] = lp["conv_b"]
        for name in ("dt_bias", "A_log", "D"):
            t[m + name] = lp[name]
        t[m + "norm.weight"] = lp["ssm_norm"]
        t[m + "out_proj.weight"] = lp["w_out"].T
    save_file({k: np.ascontiguousarray(v) for k, v in t.items()},
              str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "granitemoehybrid",
        "architectures": ["GraniteMoeHybridForCausalLM"],
        "vocab_size": CFG.vocab_size, "hidden_size": CFG.hidden_size,
        "intermediate_size": CFG.intermediate_size,
        "shared_intermediate_size": CFG.intermediate_size,
        "num_hidden_layers": CFG.num_layers,
        "num_attention_heads": CFG.num_heads,
        "num_key_value_heads": CFG.num_kv_heads,
        "layer_types": list(CFG.layer_types), "num_local_experts": 0,
        "mamba_n_heads": 8, "mamba_d_head": 32, "mamba_d_state": 16,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
        "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "position_embedding_type": "nope", "embedding_multiplier": 3.0,
        "residual_multiplier": 0.5, "attention_multiplier": 0.25,
        "logits_scaling": 2.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "max_position_embeddings": 512}))
    cfg = dataclasses.replace(config_from_hf(str(tmp_path), name=CFG.name),
                              dtype="float32")
    assert cfg == CFG
    loaded = load_weights(str(tmp_path), cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
