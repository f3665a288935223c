"""DeepSeek Sparse Attention (glm_moe_dsa): a lightning indexer in front of
the latent pages. Plain XLA; ``models.llama.forward`` wires it to the pools.

A layer whose ``indexer_types`` entry is "full" scores every earlier token of
the query's sequence and keeps the ``index_topk`` best; attention (its own and
that of the "shared" layers behind it) runs over the kept rows only. For
token t with normed hidden x_t and query latent c^Q_t (``_mla_qkv``'s):

    q^I_t = c^Q_t W_qb  -> [H, D];   k^I_s = LayerNorm(x_s W_k) -> [D]
    rope at the token's position on the FIRST ``qk_rope_head_dim`` dims of both
    w_t = x_t W_w * H^-1/2 * D^-1/2  -> [H]
    I[t, s] = sum_h w[t, h] relu(q^I[t, h] . k^I[s]),   s <= t
    S_t = the min(index_topk, t + 1) positions of largest I[t, .]
          (ties to the lower position, as ``lax.top_k``)

k^I is ONE key a token for all H heads; it is written to the index-key pool
(``KVCache.idx``, a layer a "full" layer, the latent pool's page table) when
the latent row is and never recomputed. The choice is exact: scores are
float32, and every form takes the SAME set ``lax.top_k`` over positions in
order would (``topk_mask`` and ``topk_indices`` below).

The choice takes one of two shapes, by where the candidates lie:

- the segment part (a chunk with its history, packed prompts) shares its
  candidates among all its queries, [history rows | fresh rows]: the choice
  is a mask [n, m] and attention is masked (``attend_masked``, over the
  candidates' materialised keys and values);
- a running row has candidates of its own, its pages: the choice is the
  positions [R, k] themselves, the chosen rows are gathered and attended
  (``attend_gathered``): 2048 rows of the 7-9 k a long context holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .rope import apply_rope, rope_cos_sin

# eps of the index key's LayerNorm (DeepSeek-V3.2's ``LayerNorm``; not a key
# of the config: ``assumed`` in perfbench/configs/glm-5.2-bf16.json).
K_NORM_EPS = 1e-6
# Masked scores of the attention softmax: finite, so a padding query (nothing
# allowed) averages instead of dividing 0 by 0.
_NEG = -1e30
# Queries a block of ``index_scores``: bounds the float32 [block, index
# heads, candidates] scores (32 heads x 14 k candidates: 0.12 GB).
_Q_BLOCK = 64


def project(ip, cfg, c_q: jax.Array, x: jax.Array, positions: jax.Array):
    """One "full" layer's indexer over the step's tokens. ``ip``: the
    layer's entry of the ``indexer`` stack; c_q [T, q_lora_rank]; x [T, d].
    Returns (q [T, H, D], w [T, H] float32, k [T, D]), q and k rotated and in
    the model's dtype (k as the pool holds it)."""
    T = x.shape[0]
    H, D, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    f32 = jnp.float32
    q = jnp.dot(c_q, ip["wq_b"], preferred_element_type=f32
                ).astype(x.dtype).reshape(T, H, D)
    k = jnp.dot(x, ip["wk"], preferred_element_type=f32)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean((k - mu) * (k - mu), axis=-1, keepdims=True)
    k = ((k - mu) * jax.lax.rsqrt(var + K_NORM_EPS)).astype(x.dtype)
    k = k * ip["k_norm"] + ip["k_norm_b"]
    cos, sin = rope_cos_sin(positions, rope, cfg.rope_theta,
                            scaling=cfg.rope_scaling_dict)
    q = jnp.concatenate([apply_rope(q[..., :rope], cos, sin), q[..., rope:]],
                        axis=-1)
    k = jnp.concatenate([apply_rope(k[:, None, :rope], cos, sin)[:, 0],
                         k[:, rope:]], axis=-1)
    w = jnp.dot(x, ip["w_w"], preferred_element_type=f32) * (H * D) ** -0.5
    return q, w, k


def _blocks(n: int) -> int:
    return n // _Q_BLOCK if n % _Q_BLOCK == 0 and n > _Q_BLOCK else 1


def index_scores(q: jax.Array, w: jax.Array, k: jax.Array) -> jax.Array:
    """I[t, s] over shared candidates. q [n, H, D], w [n, H], k [m, D] ->
    [n, m] float32, a block of queries at a time."""
    def block(args):
        qb, wb = args
        s = jnp.einsum("nhd,md->nhm", qb, k,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("nhm,nh->nm", jax.nn.relu(s), wb)

    n, b = q.shape[0], _blocks(q.shape[0])
    out = jax.lax.map(block, (q.reshape((b, n // b) + q.shape[1:]),
                              w.reshape(b, n // b, -1)))
    return out.reshape(n, -1)


def row_scores(q: jax.Array, w: jax.Array, k: jax.Array) -> jax.Array:
    """I[r, s] over each row's own candidates. q [R, H, D], w [R, H],
    k [R, m, D] -> [R, m] float32."""
    s = jnp.einsum("rhd,rmd->rhm", q, k, preferred_element_type=jnp.float32)
    return jnp.einsum("rhm,rh->rm", jax.nn.relu(s), w)


def _ordered(s: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' own."""
    b = jax.lax.bitcast_convert_type(s, jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of ``keys`` [n, m] uint32, found by
    COUNTING: its 32 bits from the top, each kept where k keys at least
    reach the candidate. Exact, 32 passes over the scores; a sort of 10 k
    scores a query costs several times as much on the chip."""
    def bit(i, kth):
        cand = kth | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, kth)
    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def topk_mask(scores: jax.Array, allowed: jax.Array, k: int) -> jax.Array:
    """[n, m] bool: the set ``lax.top_k(where(allowed, scores, -inf), k)``
    names, without its sort and its scatter: everything above the k-th
    value (``kth_largest``), and of the candidates that tie with it the
    first few in order."""
    if scores.shape[-1] <= k:
        return allowed
    s = _ordered(jnp.where(allowed, scores, -jnp.inf))
    kth = kth_largest(s, k)
    above, tie = s > kth, s == kth
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(tie.astype(jnp.int32), axis=-1) <= need
    return (above | (tie & first)) & allowed


def topk_indices(scores: jax.Array, allowed: jax.Array, k: int):
    """([R, k'] int32 candidates, [R, k'] bool which of them exist), k' =
    min(k, m)."""
    s = jnp.where(allowed, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(s, min(k, s.shape[-1]))
    return idx, vals > -jnp.inf


def _softmax(s, mask):
    s = jnp.where(mask, s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _times_values(spec: str, p: jax.Array, v: jax.Array) -> jax.Array:
    """einsum(spec, p, v) in float32. The MXU takes p in v's dtype: a
    bfloat16 v meets p as TWO bfloat16 terms (hi + lo, as
    ``flash_prefill_hist`` has it), which keeps the product within the
    output's own rounding of a float32 one."""
    f32 = jnp.float32
    if v.dtype != jnp.bfloat16:
        return jnp.einsum(spec, p, v, preferred_element_type=f32)
    # (``reduce_precision``: XLA folds a convert to bfloat16 and back away,
    # and lo would be 0.)
    hi = jax.lax.reduce_precision(p, exponent_bits=8, mantissa_bits=7)
    return (jnp.einsum(spec, hi.astype(v.dtype), v,
                       preferred_element_type=f32)
            + jnp.einsum(spec, (p - hi).astype(v.dtype), v,
                         preferred_element_type=f32))


def attend_masked(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: jax.Array, scale: float) -> jax.Array:
    """Attention of the segment part over its chosen candidates, in the
    MATERIALISED form: q [n, nh, hd], k [m, nh, hd], v [m, nh, vd] (the
    candidates' rows through ``mla_materialise``), mask [n, m] -> [n, nh,
    vd] in q's dtype, ONE HEAD at a time: every query of the part against
    every candidate ([n, hd] x [hd, m]: a block of 64 queries a head left
    the MXU three quarters idle), and the mask decides. Per head that is hd
    + vd (512) multiply-adds a pair where the absorbed form has 1152; p is
    ONE bfloat16 term beside a bfloat16 v, as ``flash_prefill`` has it."""
    def head(args):
        qh, kh, vh = args
        s = jnp.einsum("nd,md->nm", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        return jnp.einsum("nm,mc->nc", _softmax(s, mask).astype(vh.dtype), vh,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    out = jax.lax.map(head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(out, 0, 1)


def attend_gathered(q_abs: jax.Array, rows: jax.Array, valid: jax.Array,
                    scale: float, r: int) -> jax.Array:
    """A running row's attention over ITS chosen rows. q_abs [R, nh, W],
    rows [R, k, W] (gathered), valid [R, k] -> [R, nh, r]."""
    s = jnp.einsum("rhd,rkd->rhk", q_abs, rows,
                   preferred_element_type=jnp.float32) * scale
    return _times_values("rhk,rkc->rhc", _softmax(s, valid[:, None, :]),
                         rows[..., :r]).astype(q_abs.dtype)
