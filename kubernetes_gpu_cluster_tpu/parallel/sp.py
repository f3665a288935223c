"""Sequence/context parallelism: ring attention over the ``sp`` mesh axis.

Long-context prefill is where a single chip runs out of road first: attention
is O(T^2) FLOPs and the KV for one long prompt is O(T) HBM. The reference had
NO answer here — it only CAPPED context (``--max-model-len`` 128-4096,
reference ``values-01-minimal-example6.yaml:19-20``, ``...8.yaml:27``) because
vLLM/NCCL had no sequence-parallel path it could configure. This module is
framework-over-reference capability, TPU-first by construction:

- the sequence axis is sharded over ``sp``: each device holds ``T/sp`` query
  tokens and the matching K/V shard;
- K/V/metadata blocks rotate around the ring with ``lax.ppermute`` (one ICI
  neighbor hop per step — the mesh places ``sp`` adjacent to ``tp`` so hops
  stay on-slice), overlapping each hop with the local block's attention
  compute;
- softmax is accumulated online (flash-style m/l/acc carries in fp32), so no
  device ever materializes a [T, T] score matrix — peak memory per device is
  O((T/sp)^2) scores + O(T/sp) KV;
- causal + segment masking works on GLOBAL positions/segment ids, which
  travel with their K/V block, so ragged multi-sequence prefill batches work
  exactly like ops/attention.ragged_prefill_attention_xla.

This is the blockwise/ring formulation of Liu et al.'s Ring Attention
(arXiv:2310.01889) specialized to causal ragged serving prefill.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

NEG = -1e30  # python float: a jnp scalar would initialise the backend at import


def _block_attend(qg, k_blk, v_blk, q_seg, k_seg, q_pos, k_pos,
                  m, l, acc, scale):
    """One ring step: local queries against one rotating K/V block, online-
    softmax accumulated. qg: [Tl, n_kv, g, hd]; k_blk/v_blk: [Tb, n_kv, hd];
    m/l: [Tl, n_kv, g, 1]; acc: [Tl, n_kv, g, hd]; all fp32."""
    scores = jnp.einsum("tkgh,skh->tkgs", qg * scale, k_blk)  # [Tl,n_kv,g,Tb]
    mask = ((q_seg[:, None] == k_seg[None, :]) & (q_seg[:, None] >= 0)
            & (q_pos[:, None] >= k_pos[None, :]))             # [Tl, Tb]
    scores = jnp.where(mask[:, None, None, :], scores, NEG)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("tkgs,skh->tkgh", p, v_blk)
    return m_new, l, acc


def _ring_body(q, k, v, seg_ids, positions, *, scale, axis, n_kv, q_per_kv):
    """shard_map body: everything here sees the LOCAL shard and the sp axis."""
    Tl, nh, hd = q.shape
    sp = jax.lax.psum(1, axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    qg = q.astype(jnp.float32).reshape(Tl, n_kv, q_per_kv, hd)
    m = jnp.full((Tl, n_kv, q_per_kv, 1), NEG, jnp.float32)
    l = jnp.zeros((Tl, n_kv, q_per_kv, 1), jnp.float32)
    acc = jnp.zeros((Tl, n_kv, q_per_kv, hd), jnp.float32)

    def step(i, carry):
        k_blk, v_blk, k_seg, k_pos, m, l, acc = carry
        m, l, acc = _block_attend(qg, k_blk.astype(jnp.float32),
                                  v_blk.astype(jnp.float32),
                                  seg_ids, k_seg, positions, k_pos,
                                  m, l, acc, scale)
        # Rotate the K/V block (+ its global metadata) one ring hop. The
        # ppermute is issued after compute; XLA overlaps the collective with
        # the next iteration's einsum where the schedule allows.
        k_blk, v_blk, k_seg, k_pos = jax.lax.ppermute(
            (k_blk, v_blk, k_seg, k_pos), axis, perm)
        return k_blk, v_blk, k_seg, k_pos, m, l, acc

    carry = (k, v, seg_ids, positions, m, l, acc)
    *_, m, l, acc = jax.lax.fori_loop(0, sp, step, carry)
    out = acc / jnp.maximum(l, 1e-20)           # fully-masked rows -> 0
    return out.reshape(Tl, nh, hd).astype(q.dtype)


def build_ring_prefill(mesh, num_kv_heads: int, q_per_kv: int, scale: float,
                       axis: str = "sp"):
    """Returns a jitted ragged-prefill attention fn running ring attention
    over ``mesh`` axis ``axis``.

    Signature matches ops.attention.ragged_prefill_attention_xla:
    ``fn(q [T,nh,hd], k [T,n_kv,hd], v, seg_ids [T], positions [T]) ->
    [T,nh,hd]`` with T sharded over the axis (T % axis_size == 0; pad ragged
    tails with seg_id=-1 exactly like the single-chip path).
    """
    body = functools.partial(_ring_body, scale=scale, axis=axis,
                             n_kv=num_kv_heads, q_per_kv=q_per_kv)
    seq = P(axis)
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(seq, seq, seq, seq, seq),
        out_specs=seq,
        check_vma=False)

    @jax.jit
    def ring_prefill(q, k, v, seg_ids, positions):
        return mapped(q, k, v, seg_ids, positions)

    return ring_prefill


def sequence_sharding(mesh, axis: str = "sp"):
    """NamedSharding placing a [T, ...] prefill batch over the sp ring."""
    return NamedSharding(mesh, P(axis))
