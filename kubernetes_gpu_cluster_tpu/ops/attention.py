"""Attention over the paged KV cache: XLA reference implementations + dispatch.

Two attention shapes exist in the serving hot loop (the part the reference
delegated to vLLM's CUDA PagedAttention; north star requires them as native
TPU kernels — BASELINE.json "PagedAttention and ragged-prefill rewritten as
Pallas/XLA custom-calls"):

- **ragged prefill**: all prompt tokens of the scheduled prefill batch are
  flattened to one ``[T, ...]`` token axis with segment ids; attention is
  causal within each segment. No per-sequence padding waste.
- **paged decode**: one query token per sequence; K/V live in the paged pool
  and are addressed through per-sequence page tables.

This module holds the pure-XLA reference implementations (correct everywhere,
used on CPU meshes and as the numerical oracle in tests), the shard_map
wrappers that run a Pallas kernel per shard under a tp mesh, and
:class:`Kernels`: the ONE place where reference, kernel or per-shard kernel
is chosen, from a value the engine decides at start-up.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# KV page writes
# ---------------------------------------------------------------------------

def write_kv_pages_all_xla(kv_k: jax.Array, kv_v: Optional[jax.Array],
                           k_all: jax.Array, v_all: Optional[jax.Array],
                           slot_mapping: jax.Array
                           ) -> tuple[jax.Array, Optional[jax.Array]]:
    """Write every layer's new K/V vectors into the page pool at once: the
    plain reference (CPU, tests) that the Pallas DMA kernel
    (``ops.pallas.kv_write``) is held to bitwise. :meth:`Kernels.write_pages`
    chooses between them.

    kv_k/kv_v:    [L, P, page_size, n_kv*hd] (the whole pool, heads flattened)
    k_all/v_all:  [L, T, n_kv*hd] (stacked per-layer new entries, heads
                  flattened: the ys of the layer scan)
                  kv_v and v_all are None for a latent-attention model,
                  whose one pool holds rows [c | k_pe | pad]; the result's
                  second element is then None too.
    slot_mapping: [T] int32 flat slot = page_id * page_size + offset.
                  Padding tokens carry slots inside the scrap page 0.

    CRITICAL property: this runs OUTSIDE the layer scan on the donated pool
    and must update it IN PLACE — the served pool takes ~0.9 of the HBM the
    weights leave free, so a single pool-sized temporary is an OOM, not a
    slowdown. Threading the pool through the scan as carry/ys forces a full
    pool copy per step; attention instead reads the pool pre-write and takes
    the current token's K/V separately (see paged_decode_attention_xla).

    The loop is one formulation for every T: a fori_loop of per-token
    dynamic_update_slices, which XLA performs in place in the pool's own
    layout, each waiting for the one before (5.5 us apiece on the v5e at
    L=36, kd=1024; PERF.md section 6, PR 25). The batched row-scatter
    (``.at[:, slots].set``) is NOT in place on TPU: the scatter wants the
    slot axis major-most, so XLA transposes the whole pool to that layout
    and back, and pinning the pool's layout with ``with_layout_constraint``
    still leaves one pool-sized pre-copy at kd=1024 (PR 21, v5e:
    ``copy.44 = bf16[36,54016,1024]{2,0,1}``, 4.12 GB, compile-time OOM in
    the first chunked-prefill step of qwen3-4b).
    """
    # One loop over the pools there are: K and V, or a latent-attention
    # model's one pool of rows [c | k_pe | pad].
    pools = (kv_k,) if kv_v is None else (kv_k, kv_v)
    news = (k_all,) if kv_v is None else (k_all, v_all)
    L, P, ps, kd = kv_k.shape
    T = k_all.shape[1]
    flat = tuple(p.reshape(L, P * ps, kd) for p in pools)
    rows = tuple(a.reshape(L, T, kd).astype(p.dtype)
                 for a, p in zip(news, pools))

    def body(i, flat):
        return tuple(
            jax.lax.dynamic_update_slice(
                f, jax.lax.dynamic_slice_in_dim(r, i, 1, axis=1),
                (0, slot_mapping[i], 0))
            for f, r in zip(flat, rows))

    flat = jax.lax.fori_loop(0, T, body, flat)
    out = tuple(f.reshape(kv_k.shape) for f in flat)
    return out if kv_v is not None else (out[0], None)


# ---------------------------------------------------------------------------
# Ragged prefill attention
# ---------------------------------------------------------------------------

def _block_end(positions: jax.Array, block: int) -> jax.Array:
    """The last position a query at ``positions`` sees: its own (causal),
    or the end of its block of ``block`` positions (block-causal)."""
    return positions if block == 1 else positions | (block - 1)


def ragged_prefill_attention_xla(
    q: jax.Array,            # [T, n_heads, hd] (post-RoPE)
    k: jax.Array,            # [T, n_kv, hd]
    v: jax.Array,            # [T, n_kv, hd]
    seg_ids: jax.Array,      # [T] int32 segment id per token; padding = -1
    positions: jax.Array,    # [T] int32 position within segment
    scale: float,
    block: int = 1,
) -> jax.Array:
    """Dense masked reference implementation: causal within each segment
    (``block`` B > 1: block-causal, key j visible to query i iff
    j // B <= i // B; B is a power of two).
    O(T^2) memory in the score matrix — fine for test shapes and moderate
    prefill buckets; TPU uses the flash-style Pallas kernel instead."""
    T, n_heads, hd = q.shape
    n_kv = k.shape[1]
    q_per_kv = n_heads // n_kv

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # Grouped-query layout: [T, n_kv, q_per_kv, hd]
    qg = qf.reshape(T, n_kv, q_per_kv, hd)
    scores = jnp.einsum("tkgh,skh->kgts", qg, kf)            # [n_kv, g, T, T]

    same_seg = (seg_ids[:, None] == seg_ids[None, :]) & (seg_ids[:, None] >= 0)
    causal = _block_end(positions, block)[:, None] >= positions[None, :]
    mask = same_seg & causal                                  # [T, T]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)           # fully-masked rows
    out = jnp.einsum("kgts,skh->tkgh", probs, vf)             # [T, n_kv, g, hd]
    # v may be narrower than q/k (latent attention: 128 against 192).
    return out.reshape(T, n_heads, v.shape[-1]).astype(q.dtype)


def prefill_history_attention_xla(
    q: jax.Array,            # [T, n_heads, hd] (post-RoPE) — ONE sequence's chunk
    k: jax.Array,            # [T, n_kv, hd] (this chunk's keys)
    v: jax.Array,            # [T, n_kv, hd]
    seg_ids: jax.Array,      # [T] int32: 0 for chunk tokens, -1 padding
    positions: jax.Array,    # [T] int32 GLOBAL positions (offset by history)
    k_pool: jax.Array,       # [P, ps, n_kv*hd] or [L, P, ps, n_kv*hd]
    v_pool: Optional[jax.Array],  # None: values are the key rows (latent)
    page_table: jax.Array,   # [pages_per_seq] int32 (this sequence's pages)
    hist_len: jax.Array,     # [] int32 tokens already committed to the pool
    scale: float,
    layer: Optional[jax.Array] = None,
    block: int = 1,
) -> jax.Array:
    """Chunked-prefill attention: causal within the chunk (block-causal
    with ``block`` > 1) PLUS full attention
    to the sequence's already-committed history in the paged pool.

    This is what lets a prompt longer than the prefill token budget stream
    through in chunks (vLLM's chunked prefill; the reference exposed the knob
    through its chart schema). One sequence per call — the scheduler admits
    chunked prefills solo — so the history gather is [H, kd], not [T, H, kd].
    XLA implementation; the flash-kernel variant is a planned upgrade.
    """
    if v_pool is None:        # shared rows: the value is the key row
        v, v_pool = k, k_pool
    if layer is not None and k_pool.ndim == 4:
        k_pool = jax.lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
        v_pool = jax.lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
    T, n_heads, hd = q.shape
    n_kv = k.shape[1]
    ps = k_pool.shape[1]
    H = page_table.shape[0] * ps
    q_per_kv = n_heads // n_kv

    k_hist = k_pool[page_table].reshape(H, n_kv, hd).astype(jnp.float32)
    v_hist = v_pool[page_table].reshape(H, n_kv, hd).astype(jnp.float32)

    qg = (q.astype(jnp.float32) * scale).reshape(T, n_kv, q_per_kv, hd)
    # history scores: all valid history positions attend (they precede the chunk)
    s_h = jnp.einsum("tkgh,skh->kgts", qg, k_hist)          # [n_kv, g, T, H]
    valid_h = (jnp.arange(H)[None, :] < hist_len) & (seg_ids[:, None] >= 0)
    s_h = jnp.where(valid_h[None, None], s_h, -jnp.inf)
    # in-chunk causal scores (same as ragged prefill)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s_b = jnp.einsum("tkgh,skh->kgts", qg, kf)              # [n_kv, g, T, T]
    same = (seg_ids[:, None] == seg_ids[None, :]) & (seg_ids[:, None] >= 0)
    causal = _block_end(positions, block)[:, None] >= positions[None, :]
    s_b = jnp.where((same & causal)[None, None], s_b, -jnp.inf)

    s = jnp.concatenate([s_h, s_b], axis=-1)                # [n_kv, g, T, H+T]
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)                     # fully-masked rows
    out = (jnp.einsum("kgts,skh->tkgh", p[..., :H], v_hist)
           + jnp.einsum("kgts,skh->tkgh", p[..., H:], vf))
    return out.reshape(T, n_heads, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention
# ---------------------------------------------------------------------------

def paged_decode_attention_xla(
    q: jax.Array,            # [B, n_heads, hd] (post-RoPE)
    k_cache_l: jax.Array,    # [P, page_size, n_kv*hd] (heads flattened)
    v_cache_l: Optional[jax.Array],  # same; None: values are the key rows
    page_tables: jax.Array,  # [B, pages_per_seq] int32 page ids (pad = 0/scrap)
    context_lens: jax.Array, # [B] int32 number of valid tokens (incl. current)
    k_cur: jax.Array,        # [B, n_kv, hd] current token's K (not yet in pool)
    v_cur: jax.Array,        # [B, n_kv, hd] current token's V
    scale: float,
    layer: Optional[jax.Array] = None,  # with a stacked [L, ...] pool
) -> jax.Array:
    """Gather-then-attend reference implementation.

    The pool holds positions 0..context_len-2; the current token's K/V arrive
    separately because pool writes are deferred to one post-scan write
    (Kernels.write_pages: a DMA kernel on the chip, a loop of row updates
    here). The gather materializes [B, pages_per_seq*page_size]
    worth of K/V — HBM-bandwidth-bound, which is what the Pallas kernel
    (pallas_paged_decode) avoids by streaming only valid pages through VMEM
    with online softmax."""
    if v_cache_l is None:     # shared rows: the value is the key row
        v_cache_l, v_cur = k_cache_l, k_cur
    if layer is not None and k_cache_l.ndim == 4:
        k_cache_l = jax.lax.dynamic_index_in_dim(k_cache_l, layer, 0,
                                                 keepdims=False)
        v_cache_l = jax.lax.dynamic_index_in_dim(v_cache_l, layer, 0,
                                                 keepdims=False)
    B, n_heads, hd = q.shape
    P, ps, _ = k_cache_l.shape
    n_kv = k_cur.shape[1]
    pages_per_seq = page_tables.shape[1]
    L = pages_per_seq * ps
    q_per_kv = n_heads // n_kv

    k_seq = k_cache_l[page_tables].reshape(B, L, n_kv, hd).astype(jnp.float32)
    v_seq = v_cache_l[page_tables].reshape(B, L, n_kv, hd).astype(jnp.float32)

    qg = (q.astype(jnp.float32) * scale).reshape(B, n_kv, q_per_kv, hd)
    scores = jnp.einsum("bkgh,blkh->bkgl", qg, k_seq)         # [B, n_kv, g, L]
    # Pool rows valid up to context_len-1 (the current token is separate).
    valid = jnp.arange(L)[None, :] < (context_lens - 1)[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    cur = jnp.einsum("bkgh,bkh->bkg", qg, k_cur.astype(jnp.float32))
    scores = jnp.concatenate([scores, cur[..., None]], axis=-1)  # [B,n_kv,g,L+1]
    probs = jax.nn.softmax(scores, axis=-1)
    out = (jnp.einsum("bkgl,blkh->bkgh", probs[..., :L], v_seq)
           + probs[..., L:] * v_cur.astype(jnp.float32)[:, :, None, :])
    return out.reshape(B, n_heads, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Speculative-verification attention
# ---------------------------------------------------------------------------

def spec_verify_attention_xla(
    q: jax.Array,            # [B*S, n_heads, hd] (post-RoPE), row-major rows
    k: jax.Array,            # [B*S, n_kv, hd] this step's keys (incl. drafts)
    v: jax.Array,            # [B*S, n_kv, hd]
    k_pool: jax.Array,       # [P, ps, n_kv*hd] or [L, P, ps, n_kv*hd]
    v_pool: jax.Array,
    page_tables: jax.Array,  # [B, pages_per_seq] int32 page ids (pad = scrap)
    context_lens: jax.Array, # [B] committed tokens incl. the slice's first
    scale: float,
    layer: Optional[jax.Array] = None,
    causal: bool = True,
    block: Optional[int] = None,
) -> jax.Array:
    """Batched draft verification: B sequences, S = k+1 tokens each
    (``[last committed token, k drafts]``), every token attending to its
    sequence's paged-pool history PLUS the earlier slice tokens causally.
    ``causal=False`` is a block model's pass (``Kernels.block_attention``'s
    XLA twin): the same gather with the slice's tokens under the
    block-causal mask among themselves, key j visible to query i iff
    ``j // block <= i // block`` (``block`` None or S: one block, every
    token visible to every other; S = 2 blocks: the block awaiting its
    commit never sees the open block behind it).

    This is ``paged_decode_attention_xla`` generalized from one query/row to
    S queries/row — the pool gather is identical; the "current token" term
    becomes an S x S causal block. The pool holds positions
    0..context_len-2 (the slice's own K/V arrive in-batch and are committed
    by the caller's post-scan write, the same pre-write contract as every
    other path). Draft slots past the model cap were routed to the scrap
    page by the scheduler; their outputs are garbage the host discards.

    XLA implementation — correct everywhere, GSPMD-partitionable under tp
    meshes (heads shard like the other reference paths). A Pallas kernel
    (streaming only valid pages, S queries per DMA block) is the natural
    upgrade once spec decode is TPU-bench-proven
    (:meth:`Kernels.verify_attention` is its seam).
    """
    if layer is not None and k_pool.ndim == 4:
        k_pool = jax.lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
        v_pool = jax.lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
    B = page_tables.shape[0]
    T, n_heads, hd = q.shape
    S = T // B
    n_kv = k.shape[1]
    ps = k_pool.shape[1]
    L = page_tables.shape[1] * ps
    q_per_kv = n_heads // n_kv

    k_seq = k_pool[page_tables].reshape(B, L, n_kv, hd).astype(jnp.float32)
    v_seq = v_pool[page_tables].reshape(B, L, n_kv, hd).astype(jnp.float32)

    qg = (q.astype(jnp.float32) * scale).reshape(B, S, n_kv, q_per_kv, hd)
    kf = k.astype(jnp.float32).reshape(B, S, n_kv, hd)
    vf = v.astype(jnp.float32).reshape(B, S, n_kv, hd)

    # History scores: every slice token sees the committed pool positions
    # 0..context_len-2 (identical mask for all S queries of a row).
    s_h = jnp.einsum("bskgh,blkh->bkgsl", qg, k_seq)      # [B,n_kv,g,S,L]
    valid_h = jnp.arange(L)[None, :] < (context_lens - 1)[:, None]  # [B, L]
    s_h = jnp.where(valid_h[:, None, None, None, :], s_h, -jnp.inf)
    # In-slice scores: causal within the row's S tokens (the slice is
    # contiguous append-order, so a static lower-triangular mask suffices).
    s_b = jnp.einsum("bskgh,btkh->bkgst", qg, kf)         # [B,n_kv,g,S,S]
    if causal:
        tril = jnp.tril(jnp.ones((S, S), bool))
        s_b = jnp.where(tril[None, None, None], s_b, -jnp.inf)
    elif block is not None and block < S:
        of = jnp.arange(S) // block
        s_b = jnp.where((of[None, :] <= of[:, None])[None, None, None], s_b,
                        -jnp.inf)

    s = jnp.concatenate([s_h, s_b], axis=-1)              # [B,n_kv,g,S,L+S]
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)                   # padding rows
    out = (jnp.einsum("bkgsl,blkh->bskgh", p[..., :L], v_seq)
           + jnp.einsum("bkgst,btkh->bskgh", p[..., L:], vf))
    return out.reshape(T, n_heads, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Tensor-parallel wrappers: Pallas kernels under a GSPMD mesh via shard_map
# ---------------------------------------------------------------------------
#
# pallas_call cannot run under GSPMD auto-partitioning for the paged pool
# layout, but attention is embarrassingly parallel over heads: shard_map over
# the mesh's ``tp`` axis hands each device its local heads (q on the head
# axis, pool/current K/V on the flattened kv-head lane dim) and the kernel
# runs per-shard with no collectives in the body. This is what keeps the fast
# path when serving tp>1 over ICI (round-3 VERDICT weak #3: the engine
# force-disabled Pallas under any mesh and served the multi-chip configs on
# the XLA gather fallback). Requires num_heads and num_kv_heads divisible by
# tp and a 128-aligned per-shard lane dim — the engine checks both at init.

def paged_decode_attention_tp(mesh, q, k_cache_l, v_cache_l, page_tables,
                              context_lens, k_cur, v_cur, scale, *,
                              layer=None, interpret=False):
    """shard_map-wrapped pallas_paged_decode over ``mesh``'s tp axis.
    Shapes/semantics match paged_decode_attention_xla; ``interpret=True`` runs
    the kernel in interpret mode (CPU-mesh parity tests). Each shard's
    kernel sizes its chunks from ITS lane width (a quarter of the pool's
    under tp=4: 256 tokens a chunk where one chip takes 128)."""
    from jax.sharding import PartitionSpec as P

    from .pallas.paged_decode import pallas_paged_decode

    pool_spec = P(*([None] * (k_cache_l.ndim - 1)), "tp")
    head_spec = P(None, "tp", None)
    in_specs = [head_spec, pool_spec, pool_spec, P(), P(), head_spec, head_spec]
    args = [q, k_cache_l, v_cache_l, page_tables, context_lens, k_cur, v_cur]
    if layer is not None:
        in_specs.append(P())
        args.append(jnp.asarray(layer, jnp.int32).reshape(1))

    def body(q, kk, vv, tables, ctx, kc, vc, lyr=None):
        return pallas_paged_decode(q, kk, vv, tables, ctx, kc, vc, scale,
                                   layer=lyr, interpret=interpret)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=head_spec, check_vma=False)(*args)


def write_kv_pages_all_tp(mesh, kv_k, kv_v, k_all, v_all, slot_mapping, *,
                          interpret=False):
    """shard_map-wrapped kv_write over ``mesh``'s tp axis: pool and new rows
    split on the flattened kv-head lane dim, slots replicated. Each device
    updates its own shard of the donated pool in place; no collective."""
    from jax.sharding import PartitionSpec as P

    from .pallas.kv_write import kv_write

    pool_spec = P(None, None, None, "tp")
    rows_spec = P(None, None, "tp")

    def body(kk, vv, ka, va, slots):
        return kv_write(kk, vv, ka, va, slots, interpret=interpret)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pool_spec, pool_spec, rows_spec, rows_spec, P()),
        out_specs=(pool_spec, pool_spec), check_vma=False)(
            kv_k, kv_v, k_all, v_all, slot_mapping)


def prefill_history_attention_tp(mesh, q, k, v, seg_ids, positions, k_pool,
                                 v_pool, page_table, hist_len, scale, *,
                                 layer=None, interpret=False):
    """shard_map-wrapped flash_prefill_history over ``mesh``'s tp axis: q/k/v
    split on heads, the pool on its flattened kv-head lane dim, page table
    and history length replicated — chunked prefill keeps the Pallas fast
    path under GSPMD tp serving."""
    from jax.sharding import PartitionSpec as P

    from .pallas.flash_prefill_hist import flash_prefill_history

    pool_spec = P(*([None] * (k_pool.ndim - 1)), "tp")
    head_spec = P(None, "tp", None)
    in_specs = [head_spec, head_spec, head_spec, P(), P(),
                pool_spec, pool_spec, P(), P()]
    args = [q, k, v, seg_ids, positions, k_pool, v_pool,
            page_table, jnp.asarray(hist_len, jnp.int32)]
    if layer is not None:
        in_specs.append(P())
        args.append(jnp.asarray(layer, jnp.int32).reshape(()))

    def body(q, k, v, seg, pos, kp, vp, pt, hl, lyr=None):
        return flash_prefill_history(q, k, v, seg, pos, kp, vp, pt, hl,
                                     scale, layer=lyr, interpret=interpret)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=head_spec, check_vma=False)(*args)


def ragged_prefill_attention_tp(mesh, q, k, v, seg_ids, positions, scale, *,
                                interpret=False):
    """shard_map-wrapped flash_ragged_prefill over ``mesh``'s tp axis: q split
    on the head axis, k/v on the kv-head axis, seg/pos replicated."""
    from jax.sharding import PartitionSpec as P

    from .pallas.flash_prefill import flash_ragged_prefill

    head_spec = P(None, "tp", None)

    def body(q, k, v, seg, pos):
        return flash_ragged_prefill(q, k, v, seg, pos, scale,
                                    interpret=interpret)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec, P(), P()),
        out_specs=head_spec, check_vma=False)(q, k, v, seg_ids, positions)


# ---------------------------------------------------------------------------
# The choice of kernel: one value, decided once, routing five operations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Kernels:
    """What the engine decides ONCE, at construction, about how the
    operations a forward pass needs (five of attention and the page pool,
    five of the state slots, two of the residual streams) are carried out
    (``LLMEngine._resolve_use_pallas`` builds it, proves every kernel it
    names by compiling it, and hands it to every step program); nothing
    below the engine decides again. The default is the XLA references
    everywhere: what a CPU run, a test oracle and the draft model use.

    ``use_pallas=True`` means the Pallas kernel or its exception, never a
    fallback: a swallowed kernel failure once put XLA gather attention into
    the record as the system. A latent-attention pool (``v_pool is None``:
    one pool whose row is key AND value) takes the kernels of its own; its
    row is one shared head with no kv-head axis to shard, so it never takes
    a per-shard wrapper.
    """
    use_pallas: bool = False
    # The chunk-with-history kernel's own eligibility: under pp the pool's
    # layer axis is sharded outside the tp wrapper's specs, under sp the
    # wrapper would replicate the chunk's attention across the sp group.
    use_pallas_hist: bool = False
    # The GSPMD mesh whose ``tp`` axis the kernels run per shard under
    # (the ``*_tp`` shard_map wrappers above). None on one device, inside
    # the pipeline's own shard_map, and wherever the kernels are off.
    tp_mesh: Any = None
    # ``fn(q, k, v, seg_ids, positions) -> out`` that replaces fresh-prompt
    # attention: ring attention over an sp mesh (parallel/sp.py).
    ring_prefill: Optional[Callable] = None
    # The expert tensors lie whole on one device, so the grouped expert
    # matmuls (a custom call with no partitioning rule) may be handed the
    # stack as it is (``models.llama._moe_mlp``).
    grouped_experts: bool = False
    # The model's ``block_length``: 1 is causal attention; B > 1 makes the
    # prompt's attention block-causal and the rows' pass ``block_attention``.
    block: int = 1

    def xla_only(self) -> "Kernels":
        """The same deployment with every kernel off."""
        return dataclasses.replace(self, use_pallas=False,
                                   use_pallas_hist=False, tp_mesh=None)

    @property
    def int4_pallas(self) -> Optional[bool]:
        """What the int4 consumer (``models.llama._dot`` ->
        ``ops.quant.int4_matmul``) is told: False where the kernels are off
        (the engine's kill-switch forces XLA), else None, which leaves the
        decision to ``int4_matmul``'s own documented opt-in
        (``KGCT_INT4_PALLAS=1`` on a TPU). Never True: that kernel has no
        per-shard wrapper and no chip run behind it."""
        return None if self.use_pallas else False

    def prefill_attention(self, q, k, v, seg_ids, positions, scale):
        """Fresh prompt tokens attending each other, causal within a
        segment: ring attention under sp, else the flash kernel (per shard
        under a tp mesh) or the dense reference."""
        if self.ring_prefill is not None:
            return self.ring_prefill(q, k, v, seg_ids, positions)
        blk = {"block": self.block} if self.block > 1 else {}
        if not self.use_pallas:
            return ragged_prefill_attention_xla(q, k, v, seg_ids, positions,
                                                scale, **blk)
        if self.tp_mesh is not None:
            return ragged_prefill_attention_tp(self.tp_mesh, q, k, v, seg_ids,
                                               positions, scale)
        from .pallas.flash_prefill import flash_ragged_prefill
        return flash_ragged_prefill(q, k, v, seg_ids, positions, scale, **blk)

    def chunk_attention(self, q, k, v, seg_ids, positions, k_pool, v_pool,
                        page_table, hist_len, scale, *, layer=None):
        """One sequence's prompt chunk attending to its history in the pool
        plus itself causally. Gated by ``use_pallas_hist`` alone: where the
        history kernel is ineligible the chunk runs the XLA reference
        (which GSPMD partitions under any mesh) while the other operations
        keep their kernels."""
        blk = {"block": self.block} if self.block > 1 else {}
        if not self.use_pallas_hist:
            return prefill_history_attention_xla(
                q, k, v, seg_ids, positions, k_pool, v_pool, page_table,
                hist_len, scale, layer=layer, **blk)
        if v_pool is None:
            from .pallas.flash_prefill_hist import (
                flash_prefill_history_shared)
            return flash_prefill_history_shared(
                q, k, seg_ids, positions, k_pool, page_table, hist_len,
                scale, layer=layer)
        if self.tp_mesh is not None:
            return prefill_history_attention_tp(
                self.tp_mesh, q, k, v, seg_ids, positions, k_pool, v_pool,
                page_table, hist_len, scale, layer=layer)
        from .pallas.flash_prefill_hist import flash_prefill_history
        return flash_prefill_history(q, k, v, seg_ids, positions, k_pool,
                                     v_pool, page_table, hist_len, scale,
                                     layer=layer, **blk)

    def decode_attention(self, q, k_pool, v_pool, page_tables, context_lens,
                         k_cur, v_cur, scale, *, layer=None):
        """One query token a sequence against its pages. ``layer`` (with a
        stacked [L, P, ps, n_kv*hd] pool) lets the kernel address the pool
        with a dynamic layer index instead of the caller slicing a
        per-layer copy out: the zero-copy path the decode scan uses. The
        K-and-V kernel takes no setting: its chunk's size comes from the
        pool's lane width and dtype and its stream's depth is a constant
        (``ops.pallas.paged_decode``: what was measured, and at which
        geometry)."""
        if not self.use_pallas:
            return paged_decode_attention_xla(
                q, k_pool, v_pool, page_tables, context_lens, k_cur, v_cur,
                scale, layer=layer)
        if v_pool is None:      # each latent page read once, as key and value
            from .pallas.latent_decode import latent_paged_decode
            return latent_paged_decode(q, k_pool, page_tables, context_lens,
                                       k_cur, scale, layer=layer)
        if self.tp_mesh is not None:
            return paged_decode_attention_tp(
                self.tp_mesh, q, k_pool, v_pool, page_tables, context_lens,
                k_cur, v_cur, scale, layer=layer)
        from .pallas.paged_decode import pallas_paged_decode
        return pallas_paged_decode(q, k_pool, v_pool, page_tables,
                                   context_lens, k_cur, v_cur, scale,
                                   layer=layer)

    def verify_attention(self, q, k, v, k_pool, v_pool, page_tables,
                         context_lens, scale, *, layer=None):
        """S = k+1 query tokens a sequence (draft verification). No kernel
        exists: every backend takes the XLA reference (under a tp mesh the
        partitioner shards it over heads)."""
        return spec_verify_attention_xla(q, k, v, k_pool, v_pool,
                                         page_tables, context_lens, scale,
                                         layer=layer)

    def block_attention(self, q, k, v, k_pool, v_pool, page_tables,
                        context_lens, scale, *, layer=None, wide=None):
        """A block model's pass: two blocks of query positions a sequence
        ([the block awaiting its commit | the open block], or [the open
        block | padding] where ``wide`` [B] is False) over its pages, read
        ONCE for all of them, plus the sequence's own keys under the
        block-causal mask. The kernel (``ops.pallas.block_attend``, which
        computes nothing for a padding half) or
        ``spec_verify_attention_xla``."""
        if not self.use_pallas:
            return spec_verify_attention_xla(
                q, k, v, k_pool, v_pool, page_tables, context_lens, scale,
                layer=layer, causal=False, block=self.block)
        from .pallas.block_attend import block_attend
        return block_attend(q, k, v, k_pool, v_pool, page_tables,
                            context_lens, scale, layer=layer,
                            block=self.block, wide=wide)

    def write_pages(self, kv_k, kv_v, k_all, v_all, slot_mapping):
        """The step's new rows into the donated pool, in place: the DMA
        kernel (per shard under a tp mesh, the pool sharded on its lane
        dim) or the XLA loop."""
        if not self.use_pallas:
            return write_kv_pages_all_xla(kv_k, kv_v, k_all, v_all,
                                          slot_mapping)
        if self.tp_mesh is not None and kv_v is not None:
            return write_kv_pages_all_tp(self.tp_mesh, kv_k, kv_v, k_all,
                                         v_all, slot_mapping)
        from .pallas.kv_write import kv_write
        return kv_write(kv_k, kv_v, k_all, v_all, slot_mapping)

    def ssm_update(self, pool, layer, slots, decay, dtx, B, C):
        """A state layer's one-token update of the rows' slots, in place in
        the carried pool (``ops/ssm.py``): the Pallas kernel or the XLA
        reference. A state model runs on one device (refused under a mesh
        at start), so there is no per-shard form."""
        if not self.use_pallas:
            from .ssm import ssm_update_xla
            return ssm_update_xla(pool, layer, slots, decay, dtx, B, C)
        from .pallas.ssm_update import ssm_update
        return ssm_update(pool, layer, slots, decay, dtx, B, C)

    def conv_segments(self, xbc, seg_ids, init_rows, w, b, split):
        """A state layer's conv stage over the segment part, from the
        projected ``xbc`` (its first ``len(seg_ids)`` rows) to the
        recurrence's operands, the pieces ``split`` names (``ops/ssm.py``):
        the Pallas pass or the XLA passes; one device, as ``ssm_update``."""
        if not self.use_pallas:
            from .ssm import conv_operands_xla
            return conv_operands_xla(xbc, seg_ids, init_rows, w, b, split)
        from .pallas.conv_segments import conv_segments
        return conv_segments(xbc, seg_ids, init_rows, w, b, split)

    def ssm_chunk(self, x, dt, dA, B, C, seg_ids, seg_ends, init_state,
                  init_seg, chunk):
        """A Mamba-2 layer's segment part, a chunk at a time
        (``ops/ssm.py``): the Pallas kernel or the XLA form; one device, as
        ``ssm_update``."""
        if not self.use_pallas:
            from .ssm import ssm_chunk_scan_xla
            return ssm_chunk_scan_xla(x, dt, dA, B, C, seg_ids, seg_ends,
                                      init_state, init_seg, chunk)
        from .pallas.ssm_chunk import ssm_chunk
        return ssm_chunk(x, dt, dA, B, C, seg_ids, seg_ends, init_state,
                         init_seg, chunk)

    def kda_update(self, pool, layer, slots, g, beta, q, k, v):
        """A delta-rule layer's one-token update of the rows' slots, in
        place in the carried pool (``ops/kda.py``): the Pallas kernel or the
        XLA reference; one device, as ``ssm_update``."""
        if not self.use_pallas:
            from .kda import kda_update_xla
            return kda_update_xla(pool, layer, slots, g, beta, q, k, v)
        from .pallas.kda_update import kda_update
        return kda_update(pool, layer, slots, g, beta, q, k, v)

    def kda_chunk(self, q, k, v, g, beta, seg_ids, seg_ends, init_state,
                  init_seg, chunk):
        """A delta-rule layer's segment part, a chunk at a time
        (``ops/kda.py``): the Pallas kernel or the XLA form; one device, as
        ``ssm_update``."""
        if not self.use_pallas:
            from .kda import kda_chunk_scan_xla
            return kda_chunk_scan_xla(q, k, v, g, beta, seg_ids, seg_ends,
                                      init_state, init_seg, chunk)
        from .pallas.kda_chunk import kda_chunk
        return kda_chunk(q, k, v, g, beta, seg_ids, seg_ends, init_state,
                         init_seg, chunk)

    def hc_pre(self, x, phi, alpha, bias, hc):
        """What a sublayer reads of the residual streams, and the
        coefficients it leaves through (``ops/hyper_conn.py``): the Pallas
        kernel or the XLA form; one device (a model with streams is a latent
        model, refused under a mesh at start)."""
        if not self.use_pallas:
            from .hyper_conn import hc_pre_xla
            return hc_pre_xla(x, phi, alpha, bias, hc)
        from .pallas.hc_mix import hc_pre
        return hc_pre(x, phi, alpha, bias, hc)

    def hc_post(self, x, f, coef):
        """The streams behind a sublayer: mixed by the doubly stochastic
        map, the sublayer's result added (``ops/hyper_conn.py``); the kernel
        writes them over the old ones."""
        if not self.use_pallas:
            from .hyper_conn import hc_post_xla
            return hc_post_xla(x, f, coef)
        from .pallas.hc_mix import hc_post
        return hc_post(x, f, coef)


NO_KERNELS = Kernels()
