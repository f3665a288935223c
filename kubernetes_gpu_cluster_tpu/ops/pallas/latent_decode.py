"""Paged decode attention over ONE pool of shared rows, as a Pallas TPU kernel.

Latent attention (MLA) in its absorbed form is multi-query attention whose
value is the key: every head's query [q_lat | q_pe | 0] meets the same cache
row [c | k_pe | 0] of R lanes (``ModelConfig.kv_row_padded``), and the output
is the probabilities times the SAME rows (its first ``kv_lora_rank`` lanes
are the latent output; the caller slices them off). So a page is streamed
HBM -> VMEM **once** a layer a step and used twice, where ``paged_decode``
streams a K page and a V page: handing it this pool twice would double the
cache traffic that latent attention exists to cut.

The stream is ``paged_decode``'s: one grid step a sequence, the valid pages
in chunks of ``chunk_pages``, all pages of a chunk in flight at once, chunks
forming one global stream over the batch so that a sequence's first pages
load during the previous sequence's compute. With one kv head there is no
block-diagonal embedding: scores are ``q [nh, R] x rows^T`` and the output
``p [nh, C*ps] x rows``, both on the MXU in the pool's dtype with float32
accumulation; max, exp and the running sums stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NUM_BUFS = 2   # paged_decode's measured best: one chunk ahead of compute


def _latent_decode_kernel(
    # scalar prefetch
    page_tables_ref,   # [B*pps] int32 (flattened)
    context_lens_ref,  # [B] int32 (incl. current token)
    layer_ref,         # [1] int32 layer index into the pool
    offsets_ref,       # [B+1] int32 cumulative chunk counts (global stream)
    # blocked inputs
    q_ref,             # [1, nh, R] VMEM
    pool_hbm,          # [L, P, ps, R] ANY/HBM
    cur_ref,           # [1, 1, R] VMEM: the current token's row
    # output
    out_ref,           # [1, nh, R] VMEM
    # scratch
    buf,               # [NBUF, C, ps, R] VMEM
    sems,              # DMA sems [NBUF, C]
    *,
    scale: float,
    pages_per_seq: int,
    page_size: int,
    chunk_pages: int,
    num_seqs: int,
):
    NBUF = _NUM_BUFS
    b = pl.program_id(0)
    C, ps = chunk_pages, page_size
    nh, R = q_ref.shape[1], q_ref.shape[2]
    ctx_pool = jnp.maximum(context_lens_ref[b] - 1, 0)  # tokens in the pool
    n_chunks = pl.cdiv(pl.cdiv(ctx_pool, ps), C)
    g0 = offsets_ref[b]

    def copies(s, lc, slot):
        """The C page DMAs of sequence s's chunk lc (pages past the
        sequence's own read the table's padding, scrap page 0: masked)."""
        out = []
        for j in range(C):
            idx = jnp.minimum(lc * C + j, pages_per_seq - 1)
            page = page_tables_ref[s * pages_per_seq + idx]
            out.append(pltpu.make_async_copy(
                pool_hbm.at[layer_ref[0], page], buf.at[slot, j],
                sems.at[slot, j]))
        return out

    def start_global(gid):
        @pl.when(gid < offsets_ref[num_seqs])
        def _():
            s = jax.lax.while_loop(
                lambda s: offsets_ref[s + 1] <= gid, lambda s: s + 1, b)
            for c in copies(s, gid - offsets_ref[s], jax.lax.rem(gid, NBUF)):
                c.start()

    @pl.when(b == 0)
    def _():
        for d in range(NBUF - 1):
            start_global(jnp.int32(d))

    q = q_ref[0]                                              # [nh, R]
    neg = jnp.float32(-1e30)
    m0 = jnp.full((nh, 1), neg, jnp.float32)
    l0 = jnp.zeros((nh, 1), jnp.float32)
    acc0 = jnp.zeros((nh, R), jnp.float32)

    def body(c, carry):
        m, l, acc = carry
        gid = g0 + c
        slot = jax.lax.rem(gid, NBUF)
        start_global(gid + NBUF - 1)
        for cp in copies(b, c, slot):
            cp.wait()
        rows = buf[slot].reshape(C * ps, R)                   # key AND value
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, C * ps), 1)
                 < (ctx_pool - c * (C * ps)))
        s = jnp.where(valid, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [nh, R]
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))

    # The current token (always valid; its row is not in the pool yet).
    cur = cur_ref[0].astype(jnp.float32)                      # [1, R]
    s_cur = jnp.sum(q.astype(jnp.float32) * cur, axis=-1,
                    keepdims=True) * scale                    # [nh, 1]
    m_new = jnp.maximum(m, s_cur)
    alpha = jnp.exp(m - m_new)
    p_cur = jnp.exp(s_cur - m_new)
    l = l * alpha + p_cur
    acc = acc * alpha + p_cur * cur
    out_ref[0] = (acc / l).astype(out_ref.dtype)


def latent_paged_decode(q, pool, page_tables, context_lens, cur, scale, *,
                        layer=None, interpret=False, chunk_pages=None):
    """q: [B, nh, R]; pool: [P, ps, R] (one layer) or [L, P, ps, R] with
    ``layer`` the dynamic layer index; page_tables: [B, pages_per_seq];
    context_lens: [B] (incl. the current token); cur: [B, 1, R], the current
    tokens' rows. Returns [B, nh, R] = softmax(q rows^T * scale) rows over
    each sequence's pool rows and its current row."""
    if pool.shape[-1] % 128 != 0 and not interpret:
        raise ValueError(
            f"latent pool row {pool.shape[-1]} must be a multiple of 128 "
            f"lanes for the Pallas latent decode kernel")
    if pool.ndim == 3:
        pool = pool[None]
        layer = jnp.zeros((1,), jnp.int32)
    elif layer is None:
        raise ValueError("layer index required for stacked pool")
    else:
        layer = jnp.asarray(layer, jnp.int32).reshape(1)
    B, nh, R = q.shape
    ps = pool.shape[2]
    pps = page_tables.shape[1]
    if chunk_pages is None:
        # 512 tokens a chunk: at 128-token pages the v5e read 31 % of its
        # bandwidth in rows with one page a chunk, 45 % with two, 56 % with
        # four (64 rows of 1-2.7 k tokens; PERF.md, PR 26). A row is a third
        # of a dense model's K|V token, so the chunk still is 640 KB.
        chunk_pages = max(1, 512 // ps)
    C = max(1, min(chunk_pages, pps))
    n_chunks_per_seq = jnp.ceil(
        jnp.maximum(context_lens - 1, 0) / (C * ps)).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(n_chunks_per_seq)])
    kernel = functools.partial(
        _latent_decode_kernel, scale=float(scale), pages_per_seq=pps,
        page_size=ps, chunk_pages=C, num_seqs=B)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, nh, R), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, R), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, nh, R), lambda b, *_: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((_NUM_BUFS, C, ps, R), pool.dtype),
            pltpu.SemaphoreType.DMA((_NUM_BUFS, C)),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, nh, R), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="latent_paged_decode",
    )(page_tables.reshape(-1), context_lens, layer, offsets,
      q.astype(pool.dtype), pool, cur.astype(pool.dtype))
