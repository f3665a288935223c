"""A block model's pass over its pages as a Pallas TPU kernel.

``paged_decode``'s structure (one grid step a sequence, the sequence's valid
pages streamed HBM->VMEM in chunks of whole pages, every chunk of the batch
one global stream that runs ``_NUM_BUFS - 1`` chunks ahead of the compute,
flash-style online softmax in float32, the block-diagonal query layout that
scores every kv head in one contraction) with S query positions a sequence
instead of one: the rows of a grid step are (position s, q head h) ->
``s * nh + h``, S x nh of them, so a sequence's pages cross the bus ONCE for
its S queries. That is the point of the kernel: S calls of ``paged_decode``
would read the history S times, and the XLA twin
(``ops.attention.spec_verify_attention_xla(causal=False)``) gathers the whole
padded table in float32.

S is ``block`` (= ``block_length`` B: one block a sequence) or ``2 * block``:
[the block awaiting its commit | the open block] (engine/block.py: a block's
commit rides the next block's first denoising pass; sdar_moe: 8 x 32 = 256
rows over a 512-lane pool row). After the history the sequence's own S keys
and values, which are in no page yet (the pages are written after the
scan), arrive as ``[S, n_kv*hd]`` rows, under the block-causal mask among
themselves: key j visible to query i iff ``j // block <= i // block``. One
block sees all of itself; of two, the first never sees the second and the
second sees both. ``own="causal"`` keeps the lower triangle and ``own="all"``
every key instead; no step program asks for either, the kernel check's
planted faults do.

``wide`` [B] says which sequences HAVE a second block. The MXU's work is
the kernel's second bound beside the pages' DMA (sdar_moe: 128 rows a block),
so a sequence without one runs the grid step over its first block's rows
alone (its output there is zeros), and one with two scores all its rows in
one product a chunk. At the sampler's floor three sequences in four have
none.

``paged_decode`` stays a kernel of its own: it is S = 1 of this one in
mathematics, but its lowered text is in every accepted cell's decode window
and is not touched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_decode import _NUM_BUFS, _VMEM_BUDGET, chunk_tokens


def _block_kernel(
    # scalar prefetch
    page_tables_ref,   # [B*pps] int32 (flattened)
    context_lens_ref,  # [B] int32: tokens in the pool + 1
    layer_ref,         # [1] int32 layer index into the pool
    offsets_ref,       # [B+1] int32 cumulative chunk counts (global stream)
    wide_ref,          # [B] int32: the sequence has a second block
    # blocked inputs
    q_ref,             # [1, S*nh, hd] VMEM, row s * nh + h
    k_hbm,             # [L, P, ps, n_kv*hd] ANY/HBM
    v_hbm,             # [L, P, ps, n_kv*hd]
    k_cur_ref,         # [1, S, n_kv*hd] VMEM: the sequence's own keys
    v_cur_ref,         # [1, S, n_kv*hd]
    # output
    out_ref,           # [1, S*nh, hd] VMEM
    # scratch
    k_buf,             # [NBUF, C, ps, n_kv*hd] VMEM
    v_buf,             # [NBUF, C, ps, n_kv*hd]
    sems,              # DMA sems [NBUF, 2, C]
    *,
    scale: float,
    pages_per_seq: int,
    page_size: int,
    num_kv: int,
    q_per_kv: int,
    head_dim: int,
    width: int,        # S
    block: int,        # B: S is B or 2 B
    chunk_pages: int,
    num_bufs: int,
    num_seqs: int,
    own: str,
):
    NBUF = num_bufs
    b = pl.program_id(0)
    C = chunk_pages
    ps = page_size
    S = width
    nh = num_kv * q_per_kv
    kd = num_kv * head_dim
    ctx_pool = jnp.maximum(context_lens_ref[b] - 1, 0)  # tokens in the pool
    n_pages = pl.cdiv(ctx_pool, ps)
    n_chunks = pl.cdiv(n_pages, C)
    g0 = offsets_ref[b]

    def _start(s, lc, slot):
        for j in range(C):
            idx = jnp.minimum(lc * C + j, pages_per_seq - 1)
            page = page_tables_ref[s * pages_per_seq + idx]
            pltpu.make_async_copy(
                k_hbm.at[layer_ref[0], page], k_buf.at[slot, j],
                sems.at[slot, 0, j]).start()
            pltpu.make_async_copy(
                v_hbm.at[layer_ref[0], page], v_buf.at[slot, j],
                sems.at[slot, 1, j]).start()

    def start_global(gid):
        @pl.when(gid < offsets_ref[num_seqs])
        def _():
            s = jax.lax.while_loop(
                lambda s: offsets_ref[s + 1] <= gid, lambda s: s + 1, b)
            _start(s, gid - offsets_ref[s], jax.lax.rem(gid, NBUF))

    def wait_chunk(c, slot):
        for j in range(C):
            idx = jnp.minimum(c * C + j, pages_per_seq - 1)
            page = page_tables_ref[b * pages_per_seq + idx]
            pltpu.make_async_copy(
                k_hbm.at[layer_ref[0], page], k_buf.at[slot, j],
                sems.at[slot, 0, j]).wait()
            pltpu.make_async_copy(
                v_hbm.at[layer_ref[0], page], v_buf.at[slot, j],
                sems.at[slot, 1, j]).wait()

    @pl.when(b == 0)
    def _():
        for d in range(NBUF - 1):
            start_global(jnp.int32(d))

    neg = jnp.float32(-1e30)

    def run(blocks):
        """The grid step over the sequence's first ``blocks`` blocks of
        queries: ``blocks * B * nh`` rows in ONE pair of products a chunk
        (a chunk's keys are loaded into the MXU once for all of them)."""
        rows = blocks * block * nh
        # Block-diagonal queries, reshape-free as in ``paged_decode``: row
        # r is q head r % nh, whose kv head's lanes alone are kept.
        q = q_ref[0, :rows].astype(jnp.float32) * scale       # [rows, hd]
        lane_d = jax.lax.broadcasted_iota(
            jnp.int32, (head_dim, kd), 1) % head_dim
        row_d = jax.lax.broadcasted_iota(jnp.int32, (head_dim, kd), 0)
        tiler = (lane_d == row_d).astype(jnp.float32)         # [hd, kd]
        lane_kv = jax.lax.broadcasted_iota(
            jnp.int32, (rows, kd), 1) // head_dim
        row_kv = (jax.lax.broadcasted_iota(jnp.int32, (rows, kd), 0) % nh
                  ) // q_per_kv
        bdmask = (lane_kv == row_kv).astype(jnp.float32)      # [rows, kd]
        qbd = jax.lax.dot_general(q, tiler, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) * bdmask

        def attend(kk, vv, valid, m, l, acc):
            """One online-softmax update over keys ``kk`` (``valid``:
            [rows or 1, keys] bool)."""
            s = jax.lax.dot_general(qbd, kk, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p, vv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        def body(c, carry):
            gid = g0 + c
            slot = jax.lax.rem(gid, NBUF)
            start_global(gid + NBUF - 1)
            wait_chunk(c, slot)
            kk = k_buf[slot].reshape(C * ps, kd).astype(jnp.float32)
            vv = v_buf[slot].reshape(C * ps, kd).astype(jnp.float32)
            valid = (jax.lax.broadcasted_iota(jnp.int32, (1, C * ps), 1)
                     < (ctx_pool - c * (C * ps)))
            return attend(kk, vv, valid, *carry)

        m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (
            jnp.full((rows, 1), neg, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, kd), jnp.float32)))

        # The sequence's own S keys under ``own``'s mask (a row always
        # meets its own position, so l > 0 on padding rows too).
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1)
        pos = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 0) // nh
        keep = {"block": col // block <= pos // block, "causal": col <= pos,
                "all": col >= 0}[own]
        m, l, acc = attend(k_cur_ref[0].astype(jnp.float32),
                           v_cur_ref[0].astype(jnp.float32), keep, m, l, acc)
        out = jax.lax.dot_general(acc * bdmask, tiler,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) / l
        out_ref[0, :rows] = out.astype(out_ref.dtype)         # [rows, hd]

    if S == block:
        run(1)
    else:
        has_second = wide_ref[b] > 0

        @pl.when(has_second)
        def _():
            run(2)

        @pl.when(jnp.logical_not(has_second))
        def _():
            run(1)
            out_ref[0, block * nh:] = jnp.zeros((block * nh, head_dim),
                                                out_ref.dtype)


def block_attend(q, k, v, k_pool, v_pool, page_tables, context_lens, scale,
                 *, layer=None, interpret=False, block=None, wide=None,
                 own="block"):
    """q: [B*S, nh, hd], row-major rows of S positions; k/v: [B*S, n_kv, hd]
    the sequences' own keys and values; k_pool/v_pool: [P, ps, n_kv*hd] or
    [L, P, ps, n_kv*hd] with ``layer``; page_tables: [B, pages_per_seq];
    context_lens: [B], the tokens a row has in the pool + 1 (the contract of
    every row part). ``block``: the model's block length, S (default) or
    S / 2; ``wide``: [B], which sequences have a second block (default:
    every one); a sequence without computes none of it and gets zeros
    there. Returns [B*S, nh, hd]."""
    if k_pool.shape[-1] % 128 != 0 and not interpret:
        raise ValueError(
            f"paged pool lane dim {k_pool.shape[-1]} (n_kv*head_dim) must be "
            f"a multiple of 128 for the Pallas block-attend kernel")
    if k_pool.ndim == 3:
        k_pool = k_pool[None]
        v_pool = v_pool[None]
        layer = jnp.zeros((1,), jnp.int32)
    elif layer is None:
        raise ValueError("layer index required for stacked pool")
    else:
        layer = jnp.asarray(layer, jnp.int32).reshape(1)

    B = page_tables.shape[0]
    T, nh, hd = q.shape
    S = T // B
    block = S if block is None else block
    if S not in (block, 2 * block):
        raise ValueError(f"{S} positions a sequence are neither one block "
                         f"of {block} nor two")
    wide = (jnp.ones((B,), jnp.int32) if wide is None
            else jnp.asarray(wide).astype(jnp.int32))
    L, P, ps, _ = k_pool.shape
    n_kv = k.shape[1]
    pps = page_tables.shape[1]
    g = nh // n_kv
    kd = n_kv * hd
    C = max(1, min(max(1, chunk_tokens(kd, k_pool.dtype.itemsize) // ps),
                   pps))
    slot_bytes = 2 * C * ps * kd * k_pool.dtype.itemsize
    if _NUM_BUFS * slot_bytes > _VMEM_BUDGET:
        raise ValueError(
            f"{_NUM_BUFS} slots of {slot_bytes} bytes need more than the "
            f"{_VMEM_BUDGET}-byte VMEM scratch budget")
    n_chunks_per_seq = jnp.ceil(
        jnp.maximum(context_lens - 1, 0) / (C * ps)).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(n_chunks_per_seq)])
    kernel = functools.partial(
        _block_kernel, scale=float(scale), pages_per_seq=pps, page_size=ps,
        num_kv=n_kv, q_per_kv=g, head_dim=hd, width=S, block=block,
        chunk_pages=C, num_bufs=_NUM_BUFS, num_seqs=B, own=own)
    rows = S * nh
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, rows, hd), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, S, kd), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, kd), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, rows, hd), lambda b, *_: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((_NUM_BUFS, C, ps, kd), k_pool.dtype),
            pltpu.VMEM((_NUM_BUFS, C, ps, kd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((_NUM_BUFS, 2, C)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, rows, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="block_attend",
    )(page_tables.reshape(-1), context_lens, layer, offsets, wide,
      q.reshape(B, rows, hd), k_pool, v_pool, k.reshape(B, S, kd),
      v.reshape(B, S, kd))
    return out.reshape(T, nh, hd)
