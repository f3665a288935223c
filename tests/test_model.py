"""Model correctness: paged decode must agree with dense prefill (the
numerical oracle for the whole paged-attention path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import CacheConfig, get_model_config
from kubernetes_gpu_cluster_tpu.engine.kv_cache import allocate_kv_cache
from kubernetes_gpu_cluster_tpu.models import llama as M

PAGE = 8


def _prefill_whole(cfg, params, token_ids, num_pages=64):
    """Run a single-sequence dense prefill; returns last-position logits."""
    kv = allocate_kv_cache(cfg, CacheConfig(page_size=PAGE), num_pages)
    n = len(token_ids)
    pages = list(range(1, 1 + (n + PAGE - 1) // PAGE))
    pos = np.arange(n)
    slots = np.array([pages[p // PAGE] * PAGE + p % PAGE for p in pos], np.int32)
    meta = M.StepMeta(
        seg_ids=jnp.zeros(n, jnp.int32),
        positions=jnp.asarray(pos, jnp.int32),
        slot_mapping=jnp.asarray(slots),
        logits_indices=jnp.array([n - 1], jnp.int32))
    hidden, kv, _ = M.forward(params, cfg, jnp.asarray(token_ids, jnp.int32),
                              meta, kv)
    return M.compute_logits(params, cfg, hidden)[0], kv, pages


@pytest.mark.parametrize("model_name", ["debug-tiny", "debug-moe"])
def test_decode_matches_prefill(model_name):
    """Teacher-forcing oracle: next-token logits from an incremental paged
    decode must match the logits from a full dense prefill of the same
    sequence."""
    cfg = get_model_config(model_name)
    params = M.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    seq = rng.randint(1, cfg.vocab_size, size=13).tolist()

    # Oracle: full prefill of seq -> logits for next token.
    oracle_logits, _, _ = _prefill_whole(cfg, params, seq)

    # Paged path: prefill seq[:-1], then decode seq[-1] against the cache.
    prefix = seq[:-1]
    _, kv, pages = _prefill_whole(cfg, params, prefix)
    n = len(prefix)
    if n % PAGE == 0:
        pages = pages + [max(pages) + 1]
    dmeta = M.StepMeta(
        positions=jnp.array([n], jnp.int32),
        slot_mapping=jnp.array([pages[n // PAGE] * PAGE + n % PAGE], jnp.int32),
        page_tables=jnp.asarray([pages], jnp.int32),
        context_lens=jnp.array([n + 1], jnp.int32))
    hidden, kv, _ = M.forward(params, cfg, jnp.array([seq[-1]], jnp.int32),
                              dmeta, kv)
    decode_logits = M.compute_logits(params, cfg, hidden)[0]

    np.testing.assert_allclose(np.asarray(decode_logits), np.asarray(oracle_logits),
                               rtol=2e-4, atol=2e-4)


def test_ragged_prefill_isolation():
    """Tokens in one segment must not attend across segment boundaries: a
    two-sequence ragged batch must produce the same last-token logits as each
    sequence prefilled alone."""
    cfg = get_model_config("debug-tiny")
    params = M.init_params(cfg, jax.random.key(1))
    rng = np.random.RandomState(1)
    s0 = rng.randint(1, cfg.vocab_size, size=6).tolist()
    s1 = rng.randint(1, cfg.vocab_size, size=9).tolist()

    solo0, _, _ = _prefill_whole(cfg, params, s0)
    solo1, _, _ = _prefill_whole(cfg, params, s1)

    kv = allocate_kv_cache(cfg, CacheConfig(page_size=PAGE), 64)
    T = 16  # padded ragged batch
    toks = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    slots = np.zeros(T, np.int32)
    i = 0
    logits_idx = []
    for s, sq in enumerate([s0, s1]):
        for p, t in enumerate(sq):
            toks[i] = t; seg[i] = s; pos[i] = p
            slots[i] = (1 + s * 4 + p // PAGE) * PAGE + p % PAGE
            i += 1
        logits_idx.append(i - 1)
    meta = M.StepMeta(jnp.asarray(seg), jnp.asarray(pos), jnp.asarray(slots),
                         jnp.asarray(logits_idx, jnp.int32))
    hidden, _, _ = M.forward(params, cfg, jnp.asarray(toks), meta, kv)
    logits = M.compute_logits(params, cfg, hidden)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(solo0), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(solo1), rtol=2e-4, atol=2e-4)


def test_qwen_variants_forward():
    """attention_bias (qwen2) and qk_norm+tied-embeddings (qwen3) paths run."""
    for variant in [dict(attention_bias=True), dict(qk_norm=True, tie_word_embeddings=True)]:
        cfg = get_model_config("debug-tiny").replace(**variant)
        params = M.init_params(cfg, jax.random.key(2))
        logits, _, _ = _prefill_whole(cfg, params, [1, 2, 3, 4])
        assert logits.shape == (cfg.vocab_size,)
        assert np.isfinite(np.asarray(logits)).all()
