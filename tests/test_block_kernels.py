"""A block model's kernels against their XLA twins in interpret mode, the
transfer rule against the reference's loop, the host's block arithmetic, and
what a block model refuses at start (``tests/test_block_diffusion.py`` holds
the served path to the reference's generation; the two are two files so that
the driver's per-file workers share them)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    cache_kind_refusal, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine.sequence import Sequence
from kubernetes_gpu_cluster_tpu.models import llama
from kubernetes_gpu_cluster_tpu.ops import attention as att
from kubernetes_gpu_cluster_tpu.ops.pallas.block_attend import block_attend
from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import (
    flash_ragged_prefill)
from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
    flash_prefill_history)
from kubernetes_gpu_cluster_tpu.ops.sampling import block_transfer
from perfbench.reference import sdar_moe as ref

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


# -- (c) the kernels against their XLA twins ----------------------------------

@pytest.mark.parametrize("rows,ctx", [
    (3, (1, 37, 0)), (4, (17, 16, 65, 2)), (2, (90, 33)), (1, (1,))])
@pytest.mark.parametrize("causal", [False, True])
def test_block_attend_interpreted_is_its_xla_twin(rows, ctx, causal):
    nh, nkv, hd, P, pps, L = 4, 2, 32, 24, 6, 2
    k = jax.random.split(jax.random.key(rows), 6)
    q = jax.random.normal(k[0], (rows * B, nh, hd))
    kk = jax.random.normal(k[1], (rows * B, nkv, hd))
    vv = jax.random.normal(k[2], (rows * B, nkv, hd))
    kp = jax.random.normal(k[3], (L, P, PS, nkv * hd))
    vp = jax.random.normal(k[4], (L, P, PS, nkv * hd))
    pt = jnp.asarray(np.random.default_rng(0).integers(1, P, (rows, pps)),
                     jnp.int32)
    args = (q, kk, vv, kp, vp, pt, jnp.asarray(ctx, jnp.int32), hd ** -0.5)
    want = att.spec_verify_attention_xla(*args, layer=jnp.int32(1),
                                         causal=causal)
    got = block_attend(*args, layer=jnp.int32(1), interpret=True,
                       own="causal" if causal else "block")
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    if not causal and rows > 1:
        # the planted fault (a causal mask inside the block) is not a
        # rounding: a row with little history moves by O(0.1)
        wrong = block_attend(*args, layer=jnp.int32(1), interpret=True,
                             own="causal")
        assert float(jnp.max(jnp.abs(wrong - want))) > 1e-2


def _materialised(q, kk, vv, kp, vp, pt, ctx, scale, layer, block):
    """A sequence's S queries over [its pool history | its own S keys]
    under a mask written out whole, in float64 on the host: key j of the
    own ones visible to query i iff ``j // block <= i // block``."""
    rows, S = pt.shape[0], q.shape[0] // pt.shape[0]
    nh, nkv, hd = q.shape[1], kk.shape[1], q.shape[2]
    q, kk, vv = (np.asarray(a, np.float64).reshape(rows, S, -1, hd)
                 for a in (q, kk, vv))
    kp, vp = (np.asarray(a[layer], np.float64) for a in (kp, vp))
    out = np.zeros((rows, S, nh, hd))
    for r in range(rows):
        n = max(int(ctx[r]) - 1, 0)
        keys = np.concatenate(
            [kp[np.asarray(pt[r])].reshape(-1, nkv, hd)[:n], kk[r]])
        vals = np.concatenate(
            [vp[np.asarray(pt[r])].reshape(-1, nkv, hd)[:n], vv[r]])
        mask = np.ones((S, n + S), bool)
        mask[:, n:] = (np.arange(S)[None, :] // block
                       <= np.arange(S)[:, None] // block)
        for h in range(nh):
            sc = np.einsum("sd,td->st", q[r, :, h],
                           keys[:, h // (nh // nkv)]) * scale
            sc = np.where(mask, sc, -np.inf)
            p = np.exp(sc - sc.max(axis=-1, keepdims=True))
            out[r, :, h] = (p / p.sum(-1, keepdims=True)) \
                @ vals[:, h // (nh // nkv)]
    return out.reshape(rows * S, nh, hd)


@pytest.mark.parametrize("rows,ctx,wide", [
    (3, (1, 37, 0), (1, 0, 1)), (4, (17, 16, 65, 2), (0, 1, 1, 0)),
    (2, (90, 33), (1, 1)), (1, (1,), (0,))])
@pytest.mark.parametrize("width", [B, 2 * B])
def test_a_row_of_two_blocks_is_the_materialised_block_causal_mask(
        rows, ctx, wide, width):
    """The XLA twin's ``block=`` and the kernel (interpreted) at one block
    a sequence and at two, against a mask written out whole: at two, the
    block awaiting its commit never sees the open block behind it; a
    sequence without a second block (``wide`` 0) gets zeros there from the
    kernel and its first block is untouched by that; and the planted fault
    (the first block sees the second) is no rounding."""
    nh, nkv, hd, P, pps, L = 4, 2, 32, 24, 6, 2
    k = jax.random.split(jax.random.key(rows + width), 6)
    q = jax.random.normal(k[0], (rows * width, nh, hd))
    kk = jax.random.normal(k[1], (rows * width, nkv, hd))
    vv = jax.random.normal(k[2], (rows * width, nkv, hd))
    kp = jax.random.normal(k[3], (L, P, PS, nkv * hd))
    vp = jax.random.normal(k[4], (L, P, PS, nkv * hd))
    pt = jnp.asarray(np.random.default_rng(0).integers(1, P, (rows, pps)),
                     jnp.int32)
    ctx = jnp.asarray(ctx, jnp.int32)
    args = (q, kk, vv, kp, vp, pt, ctx, hd ** -0.5)
    want = _materialised(*args, 1, B)
    twin = att.spec_verify_attention_xla(*args, layer=jnp.int32(1),
                                         causal=False, block=B)
    assert float(np.max(np.abs(np.asarray(twin) - want))) < 1e-5
    got = np.asarray(block_attend(*args, layer=jnp.int32(1), interpret=True,
                                  block=B))
    assert float(np.max(np.abs(got - want))) < 1e-5
    if width == B:
        return
    skipped = np.asarray(block_attend(
        *args, layer=jnp.int32(1), interpret=True, block=B,
        wide=jnp.asarray(wide))).reshape(rows, 2, B, nh, hd)
    got = got.reshape(skipped.shape)
    for r, w in enumerate(wide):
        assert (skipped[r, 0] == got[r, 0]).all()
        assert (skipped[r, 1] == (got[r, 1] if w else 0)).all()
    wrong = np.asarray(block_attend(*args, layer=jnp.int32(1),
                                    interpret=True, block=B, own="all"))
    wrong, want = (a.reshape(rows, 2, -1) for a in (wrong, want))
    assert float(np.max(np.abs(wrong - want)[:, 0])) > 1e-2
    assert float(np.max(np.abs(wrong - want)[:, 1])) < 1e-5


@pytest.mark.parametrize("T,segs", [(64, (24, 40)), (32, (32,)),
                                    (128, (60, 8, 44))])
def test_block_causal_flash_prefill_interpreted_is_its_xla_twin(T, segs):
    nh, nkv, hd = 4, 2, 32
    k = jax.random.split(jax.random.key(T), 3)
    q = jax.random.normal(k[0], (T, nh, hd))
    kk = jax.random.normal(k[1], (T, nkv, hd))
    vv = jax.random.normal(k[2], (T, nkv, hd))
    seg = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    at = 0
    for s, n in enumerate(segs):
        seg[at:at + n], pos[at:at + n] = s, np.arange(n)
        at += n
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    want = att.ragged_prefill_attention_xla(q, kk, vv, seg, pos, 0.2,
                                            block=B)
    got = flash_ragged_prefill(q, kk, vv, seg, pos, 0.2, interpret=True,
                               block=B, block_q=16, block_k=16)
    real = np.asarray(seg) >= 0
    assert float(jnp.max(jnp.abs(got - want)[real])) < 2e-6
    causal = att.ragged_prefill_attention_xla(q, kk, vv, seg, pos, 0.2)
    assert float(jnp.max(jnp.abs(causal - want)[real])) > 1e-2


@pytest.mark.parametrize("hist,n", [(0, 32), (48, 28), (16, 64)])
def test_block_causal_chunk_with_history_interpreted_is_its_xla_twin(hist, n):
    nh, nkv, hd, P, T = 4, 2, 32, 12, 64
    k = jax.random.split(jax.random.key(hist + n), 5)
    q = jax.random.normal(k[0], (T, nh, hd))
    kk = jax.random.normal(k[1], (T, nkv, hd))
    vv = jax.random.normal(k[2], (T, nkv, hd))
    kp = jax.random.normal(k[3], (P, PS, nkv * hd))
    vp = jax.random.normal(k[4], (P, PS, nkv * hd))
    seg = jnp.asarray(np.where(np.arange(T) < n, 0, -1), jnp.int32)
    pos = jnp.asarray(hist + np.arange(T), jnp.int32)
    table = jnp.asarray([3, 5, 7, 9], jnp.int32)
    args = (q, kk, vv, seg, pos, kp, vp, table, jnp.int32(hist), 0.2)
    want = att.prefill_history_attention_xla(*args, block=B)
    got = flash_prefill_history(*args, interpret=True, block=B, block_q=16,
                                block_k=16)
    assert float(jnp.max(jnp.abs(got - want)[:n])) < 2e-6
    causal = att.prefill_history_attention_xla(*args)
    assert float(jnp.max(jnp.abs(causal - want)[:n])) > 1e-3


def test_at_block_length_one_the_flash_kernels_lower_to_what_they_did():
    """``block=1`` is no argument at all: the jaxpr of both flash kernels is
    the one of a call without it (the accepted presets' programs)."""
    T, nh, nkv, hd = 64, 4, 2, 32
    a = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    qkv = (a((T, nh, hd), f32), a((T, nkv, hd), f32), a((T, nkv, hd), f32),
           a((T,), i32), a((T,), i32))
    one = jax.make_jaxpr(lambda *x: flash_ragged_prefill(
        *x, 0.2, interpret=True, block=1))(*qkv)
    none = jax.make_jaxpr(lambda *x: flash_ragged_prefill(
        *x, 0.2, interpret=True))(*qkv)
    assert str(one) == str(none)
    pool = a((8, PS, nkv * hd), f32)
    hist = qkv + (pool, pool, a((4,), i32), a((), i32))
    one = jax.make_jaxpr(lambda *x: flash_prefill_history(
        *x, 0.2, interpret=True, block=1))(*hist)
    none = jax.make_jaxpr(lambda *x: flash_prefill_history(
        *x, 0.2, interpret=True))(*hist)
    assert str(one) == str(none)
    # ... and the dispatcher hands an autoregressive model none
    k = att.Kernels()
    x = [jnp.zeros(s.shape, s.dtype) for s in qkv]
    assert str(jax.make_jaxpr(lambda *y: k.prefill_attention(*y, 0.2))(*x)) \
        == str(jax.make_jaxpr(lambda *y: att.ragged_prefill_attention_xla(
            *y, 0.2))(*x))


# -- (d) the transfer rule and the host's arithmetic --------------------------

@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("threshold", [0.9, 0.5, 0.2])
def test_the_transfer_rule_is_the_reference_s_loop(n, threshold):
    rng = np.random.default_rng(n)
    conf = rng.random((64, B)).astype(np.float32)
    conf[:8] = np.round(conf[:8], 1)            # ties: the lower position
    masked = rng.random((64, B)) < 0.6
    got = np.asarray(block_transfer(jnp.asarray(conf), jnp.asarray(masked),
                                    n, threshold)) & masked
    for r in range(64):
        want = ref.transfer(conf[r], masked[r], n, threshold)
        if masked[r].sum() <= n and (conf[r][masked[r]] <= threshold).any():
            want = masked[r]        # fewer masked than n: all of them
        assert (got[r] == want).all(), (r, conf[r], masked[r])


@pytest.mark.parametrize("n_prompt,outputs,want", [
    (9, 0, 8), (8, 0, 8), (3, 0, 0), (11, 2, 12), (11, 5, 16)])
def test_a_prefill_computes_whole_blocks(n_prompt, outputs, want):
    seq = Sequence("r", list(range(n_prompt)), SamplingParams(max_tokens=64),
                   block_length=B)
    for t in range(outputs):
        seq.append_token(100 + t)
    assert seq.prefill_len == want
    seq.num_committed = want
    seq.open_block()
    held = n_prompt + outputs - want
    assert seq.block_masked == [False] * held + [True] * (B - held)
    assert seq.block_ids[:held] == seq.all_token_ids[want:]
    # an autoregressive sequence is block_length 1 of the same code
    ar = Sequence("r", list(range(n_prompt)), SamplingParams(max_tokens=64))
    assert ar.prefill_len == n_prompt and ar.block_length == 1


@pytest.mark.parametrize("passes,committed,max_tokens,want", [
    (8, 8, 64, 8 + 4 * 8 - 1),      # every pass may make a block whole
    (1, 8, 64, 8 + 4 * 1 - 1),      # a mixed step: one pass
    (8, 8, 5, 15),                  # the last block this request reaches
    (8, 504, 1000, 511)])           # the model's length
def test_pages_are_held_for_every_block_a_program_can_commit(
        passes, committed, max_tokens, want):
    seq = Sequence("r", list(range(10)),
                   SamplingParams(max_tokens=max_tokens), block_length=B)
    seq.num_committed = committed
    assert seq.window_last_pos(passes, 512) == want


# -- (e) what a block model refuses at start, by flag and mechanism ------------

def _config(**kw):
    sched = {k: kw.pop(k) for k in list(kw)
             if k in ("enable_prefix_caching", "spec_decode_enabled")}
    cache = {k: kw.pop(k) for k in list(kw) if k == "swap_space_gb"}
    model = CFG.replace(**{k: kw.pop(k) for k in list(kw)
                           if k == "quantization"})
    return EngineConfig(model=model, cache=CacheConfig(page_size=PS, **cache),
                        scheduler=SchedulerConfig(**sched),
                        parallel=ParallelConfig(**kw))


@pytest.mark.parametrize("config,extra,flag", [
    (dict(tp=2), {}, "--tensor-parallel-size 2"),
    (dict(pp=2), {}, "--pipeline-parallel-size 2"),
    (dict(sp=2), {}, "--sequence-parallel-size 2"),
    (dict(ep=2), {}, "--expert-parallel-size 2"),
    (dict(enable_prefix_caching=True), {}, "--enable-prefix-caching"),
    (dict(spec_decode_enabled=True), {}, "--enable-spec-decode"),
    (dict(swap_space_gb=1.0), {}, "--swap-space-gb"),
    (dict(quantization="int8"), {}, "--quantization int8"),
    ({}, dict(role="prefill"), "--role prefill"),
    ({}, dict(role="decode"), "--role decode"),
    ({}, dict(fleet_prefix_cache=True), "--fleet-prefix-cache"),
    ({}, dict(peer_pool=["http://x"]), "--peer-pool")])
def test_what_cannot_carry_an_open_block_refuses_at_start(config, extra,
                                                          flag):
    why = cache_kind_refusal(_config(**config), **extra)
    assert why is not None and why.startswith(flag + " with debug-block-moe")
    assert "\n" not in why and len(why) > len(flag) + 40    # ... and says why


def test_what_works_is_not_refused_and_an_autoregressive_model_is_untouched():
    assert cache_kind_refusal(_config()) is None
    assert cache_kind_refusal(EngineConfig(
        model=get_model_config("debug-moe"),
        scheduler=SchedulerConfig(enable_prefix_caching=True,
                                  spec_decode_enabled=True))) is None


@pytest.mark.parametrize("kw", [dict(presence_penalty=0.5),
                                dict(frequency_penalty=0.5),
                                dict(logit_bias={5: 1.0})])
def test_a_request_the_sampler_cannot_carry_is_refused_by_name(params, kw):
    eng = _engine(params)
    with pytest.raises(ValueError, match="block model"):
        eng.add_request("r", _prompt(9, 1), SamplingParams(max_tokens=4, **kw))
    assert not eng.has_unfinished_requests()


@pytest.mark.parametrize("bad", [dict(block_length=3), dict(denoising_steps=3),
                                 dict(remasking="sequential"),
                                 dict(mask_token_id=512),
                                 dict(max_model_len=510)])
def test_a_block_model_s_numbers_are_checked(bad):
    with pytest.raises(ValueError, match="debug-block-moe"):
        CFG.replace(**bad)
