"""Ragged (segment-causal) flash prefill attention as a Pallas TPU kernel.

The prefill batch is T flattened prompt tokens with segment ids; attention is
causal within each segment. Because segments are contiguous and positions
increase with the flat index, the mask is exactly

    attend(a, b) <=> seg[a] == seg[b]  and  b <= a

so a standard flash-attention sweep over lower-triangular KV blocks with a
segment-equality mask computes it in O(T) memory — the XLA fallback
materializes the full [heads, T, T] score tensor (it OOMs one v5e chip at
T=8192 on a 1.1B model; this kernel replaces it as the north-star
"ragged-prefill custom call", BASELINE.json).

Grid: (n_heads, T/BQ, T/BK), KV-block index fastest so the fp32 accumulators
live in VMEM scratch across the j sweep. GQA maps each q head to its kv head
via the BlockSpec index maps; upper-triangular blocks are skipped.

SEGMENT-AWARE K WINDOWS (r4): in a ragged batch of short segments, most
lower-triangular blocks are fully cross-segment-masked, and an in-kernel
skip cannot help — the BlockSpec pipeline has already scheduled the block's
DMA (measured: one 8192-token step ran ~2x slower than 4x2048 with ~all of
the extra blocks masked). The fix at the right depth: q block i can only
attend k blocks in [seg_start(first token of i) // BK, last_row(i) // BK] —
a contiguous window, because segments are contiguous and ascending. The
window start comes in as a scalar-prefetched array feeding the k/v/kseg
index maps, the j axis walks the window RELATIVE to it, and steps past the
window clamp to its last block so the pipeline dedups the fetch (same block
index => no DMA) while ``pl.when`` skips the compute. Masked blocks outside
the window are never fetched at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # python scalar: jnp constants captured by kernels are rejected


def _prefill_kernel(
    kbmin_ref,    # [nq] int32 scalar prefetch: first k block of q block i
    q_ref,        # [1, BQ, hd] VMEM (one head; arrays are head-major so the
                  #  trailing block dims satisfy Mosaic's (8, 128) tiling)
    k_ref,        # [1, BK, hd] VMEM (matching kv head, absolute block kb)
    v_ref,        # [1, BK, hv] (hv = hd, or narrower: latent attention's
                  #  materialised form has 192-wide q/k and 128-wide v)
    qseg_ref,     # [BQ, 1] int32
    kseg_ref,     # [BK, 1] int32 (absolute block kb)
    out_ref,      # [1, BQ, hv]
    m_scr,        # [BQ, 1] f32
    l_scr,        # [BQ, 1] f32
    acc_scr,      # [BQ, hv] f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    t_total: int,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, jnp.float32(NEG))
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Absolute k block this step handles; past the causal end of the window
    # the index maps clamped (no fetch) and compute is skipped.
    kb = kbmin_ref[i] + j
    kb_hi = jnp.minimum(i * block_q + block_q - 1, t_total - 1) // block_k

    @pl.when(kb <= kb_hi)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale            # [BQ, hd]
        k = k_ref[0].astype(jnp.float32)                    # [BK, hd]
        v = v_ref[0].astype(jnp.float32)
        # A partial final block (T % BK != 0) carries out-of-bounds padding
        # whose bytes are undefined (NaN in interpret mode): 0*NaN in the
        # p@v contraction would poison every real row, so zero the padded
        # V rows and mask the padded columns out of the scores.
        kcols = (kb * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0))
        v = jnp.where(kcols < t_total, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        rows = (i * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
        cols = (kb * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
        mask = (cols <= rows) & (cols < t_total)
        mask &= qseg_ref[:] == kseg_ref[:].reshape(1, block_k)
        mask &= qseg_ref[:] >= 0                            # padding rows
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        # Fully-masked rows (padding) have l == 0 -> emit zeros.
        l = l_scr[:]
        safe = jnp.where(l > 0, l, 1.0)
        out_ref[0] = (acc_scr[:] / safe).astype(out_ref.dtype)


def flash_ragged_prefill(q, k, v, seg_ids, positions, scale, *,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q: [T, nh, hd]; k: [T, n_kv, hd]; v: [T, n_kv, hv]; seg_ids: [T]
    (-1 = padding). positions are implied by the flat order (causal within
    segment) and are accepted only for dispatcher signature parity. Returns
    [T, nh, hv]."""
    T, nh, hd = q.shape
    hv = v.shape[-1]
    n_kv = k.shape[1]
    g = nh // n_kv
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    nq = pl.cdiv(T, block_q)
    nk = pl.cdiv(T, block_k)

    seg2d = seg_ids.astype(jnp.int32).reshape(T, 1)
    # Head-major so trailing block dims are (tokens, hd) — Mosaic-tileable.
    q_hm = q.transpose(1, 0, 2)
    k_hm = k.transpose(1, 0, 2)
    v_hm = v.transpose(1, 0, 2)

    # Segment-aware k-window starts: the first token of q block i belongs to
    # the block's EARLIEST segment (ids ascend along the flat index), so its
    # segment's start index floors the attendable k range. cummax of
    # change-point indices gives each token's segment start in O(T).
    seg32 = seg_ids.astype(jnp.int32)
    idx = jnp.arange(T, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((1,), bool), seg32[1:] != seg32[:-1]])
    starts = jax.lax.cummax(jnp.where(change, idx, 0))
    first_tok = jnp.minimum(jnp.arange(nq, dtype=jnp.int32) * block_q, T - 1)
    kb_min = starts[first_tok] // block_k                   # [nq]

    kernel = functools.partial(_prefill_kernel, scale=float(scale),
                               block_q=block_q, block_k=block_k, t_total=T)

    def _kb(i, j, kb_ref):
        # MUST mirror the kernel body's kb/kb_hi exactly: the fetched block
        # and the compute guard desynchronize otherwise.
        kb_hi = jnp.minimum(i * block_q + block_q - 1, T - 1) // block_k
        return jnp.minimum(kb_ref[i] + j, kb_hi)

    def kmap(h, i, j, kb_ref):
        return (h // g, _kb(i, j, kb_ref), 0)

    def ksegmap(h, i, j, kb_ref):
        return kmap(h, i, j, kb_ref)[1:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, i, j, kb: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, hd), kmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, hv), kmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((block_q, 1), lambda h, i, j, kb: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, 1), ksegmap, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, hv),
                               lambda h, i, j, kb: (h, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nh, T, hv), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="flash_prefill",
    )(kb_min, q_hm, k_hm, v_hm, seg2d, seg2d)
    return out.transpose(1, 0, 2)
