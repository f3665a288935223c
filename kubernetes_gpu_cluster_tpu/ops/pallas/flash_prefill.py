"""Ragged (segment-causal) flash prefill attention as a Pallas TPU kernel.

The prefill batch is T flattened prompt tokens with segment ids; attention is
causal within each segment. Because segments are contiguous and positions
increase with the flat index, the mask is exactly

    attend(a, b) <=> seg[a] == seg[b]  and  b <= a

so a flash-attention sweep over the keys at or under a q block's diagonal
with a segment-equality mask computes it in O(T) memory — the XLA fallback
materializes the full [heads, T, T] score tensor.

Grid (blocks of kv heads, q blocks). A step holds ``G`` kv heads and, of
each, its ``g = nh / n_kv`` q heads over ``block_q`` tokens STACKED AS ROWS
(``g * block_q`` rows against that head's K/V: a key tile is one MXU operand
for all of them), about ``_STEP_ROWS`` rows in all. The two geometries are
told apart by the shapes alone and walk the same code: with ``n_kv == nh``
(a latent model's materialised form, 192-wide q/k against 128-wide v) a
head's rows are the q block's tokens and the step holds several heads; with
GQA one or two kv heads fill the step. The heads of a step are independent
chains in one basic block.

What a step walks is what exists, in a loop of its own, not in grid steps:
the K/V of its kv heads lie in VMEM whole (head-major ``[T, hd]`` and
``[T, hv]`` a head, padded to whole key tiles; one fetch a head block, the
block index constant over the q blocks), and the step loops over key tiles
of ``block_k`` keys from the q block's WINDOW START (the tile holding the
first token of the segment its first row belongs to: segments are contiguous
and ascending, so no earlier key is attendable) to its diagonal. Tiles that
lie wholly under the q block's first row and inside the one segment the
whole block belongs to take no mask; the window's first tile where the
segment starts inside it, the diagonal, and every tile of a q block that a
segment boundary crosses take the causal and segment masks (key padding past
T carries a segment id no row has). Nothing over the diagonal; nothing at
all for a q block of padding. The four tile indices a q block needs come in
as one scalar-prefetched array.

Tiles are WIDE (512 keys): what a row pays a tile (its statistics, the two
reductions over its scores, its accumulator's update) does not depend on the
tile's width, and a kv head's rows are MANY (512 where the heads allow:
``block_q`` 128 tokens x g q heads under GQA 4, 256 tokens of one head where
``g`` is 1 or 2), because a key tile is loaded into the MXU once for all of
them. One v5e, a fresh 2048-token segment, ms a call, the kernel before
(a grid step a (head, 128 q, 128 keys) block, float32 operands) -> this one:
32 x 192/128 (xing, kimi-linear) 2.86 -> 0.70, 16 x 192/128 (kimi-vl) 1.45
-> 0.35, 32/8 x 128 (qwen) 2.58 -> 0.65, 32/8 x 64 (granite) 2.55 -> 0.64
(PERF.md section 6, PR 43: 256-key tiles cost a third more, 1024-key tiles
as much as 512 and twice the compile).

The MXU sees operands in the input's dtype with float32 accumulation, and
the two values it is handed are ROUNDED WHERE THE KERNEL BEFORE ROUNDED THEM:
q times ``scale`` as one bf16 value (formed in float32 on the host side of
the call) and p as one bf16 term; the statistics, the sum of p and the
accumulator are float32. That is a choice, not an oversight: with ``scale``
on the float32 scores, or p as two bf16 terms (hi + lo, as
``flash_prefill_hist`` has it), the kernel is closer to a float32 reference
and the probes of the three latent configurations, served alone through
this kernel, leave their goldens' limits: near-tied routers flip on the
difference (PERF.md section 6, PR 43). Float32 inputs (the CPU tests) keep
float32 operands. Rows with ``seg < 0`` come out as zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30  # python scalar: jnp constants captured by kernels are rejected
# Rows of a grid step (kv heads x q heads of each x block_q tokens): enough
# that the step's fixed cost and a key tile's load are spread thin, few
# enough that a tile's scores, probabilities and their two bf16 terms stay a
# few MB of VMEM.
_STEP_ROWS = 1024
# Key padding's segment id: no row carries it (padding rows carry -1).
_NO_SEG = -2
_NN, _NT = (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))


def _prefill_kernel(
    win_ref,      # [4 * nq] int32 scalar prefetch; of q block i the key tiles
                  #  (lo, open_lo, open_hi, hi): it walks [lo, hi), and
                  #  [open_lo, open_hi) of them take no mask
    q_ref,        # [G, g, BQ, hd] VMEM (kv-head-major: a kv head's q heads),
                  #  times ``scale`` already
    k_ref,        # [G, Tp, hd] VMEM: these kv heads' keys, whole
    v_ref,        # [G, Tp, hv] (hv = hd, or narrower: latent attention's
                  #  materialised form has 192-wide q/k and 128-wide v)
    qseg_ref,     # [BQ, 1] int32
    kseg_ref,     # [Tp / BK, 1, BK] int32, by key tile
    out_ref,      # [G, g, BQ, hv]
    m_scr,        # [G, g*BQ, 1] f32
    l_scr,        # [G, g*BQ, 1] f32
    acc_scr,      # [G, g*BQ, hv] f32
    *,
    block_q: int,
    block_k: int,
    block: int = 1,
):
    i = pl.program_id(1)
    G, g, _, hd = q_ref.shape
    bk = block_k
    rows = g * block_q
    cdt = k_ref.dtype
    f32 = jnp.float32
    lo, open_lo, open_hi, hi = (win_ref[4 * i + n] for n in range(4))

    m_scr[...] = jnp.full_like(m_scr, f32(NEG))
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    # Row r of a kv head is (q head r // BQ of its g, token q0 + r % BQ).
    row_tok = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) % block_q
    row_seg = jnp.concatenate([qseg_ref[...]] * g, axis=0)      # [rows, 1]
    if block > 1:
        # Block-causal: a row sees to the END of its block of ``block``
        # tokens (a power of two; segments start on block edges, so the flat
        # index serves). Tile and q-block edges are multiples of it: the
        # windows are the causal ones, only the masked tiles differ.
        row_tok = row_tok | (block - 1)

    def tile(jj, masked):
        """Key tile jj against every row of the step. ``masked`` False: every
        row attends every column. A masked score's probability is
        exp(NEG - m) = 0 exactly once its row has met a real column; what a
        row gathered before that (m still NEG, so p = 1 on masked columns)
        is wiped by alpha = exp(NEG - m) = 0 when it does, and every real row
        meets itself on its diagonal. Padding rows are zeroed at the end."""
        at = pl.ds(pl.multiple_of(jj * bk, bk), bk)
        mask = None
        if masked:
            cols = jj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            mask = (cols <= row_tok) & (kseg_ref[jj] == row_seg)  # [rows, bk]
        for h in range(G):
            s = jax.lax.dot_general(
                q_ref[h].reshape(rows, hd), k_ref[h, at, :], _NT,
                preferred_element_type=f32)
            if masked:
                s = jnp.where(mask, s, NEG)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_scr[h] = m_new
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(cdt), v_ref[h, at, :], _NN,
                                     preferred_element_type=f32)
            acc_scr[h] = acc_scr[h] * alpha + pv

    # The window's first tile where its segment starts inside it, the tiles
    # wholly under the first row and inside the block's one segment, then
    # the diagonal (all of it masked where a boundary crosses the q block).
    jax.lax.fori_loop(lo, open_lo, lambda jj, _: tile(jj, True), None)
    jax.lax.fori_loop(open_lo, open_hi, lambda jj, _: tile(jj, False), None)
    jax.lax.fori_loop(open_hi, hi, lambda jj, _: tile(jj, True), None)

    real = row_seg >= 0
    for h in range(G):
        l = l_scr[h]
        out = jnp.where(real, acc_scr[h] / jnp.where(l > 0, l, 1.0), 0.0)
        out_ref[h] = out.reshape(g, block_q, -1).astype(out_ref.dtype)


def _windows(seg, T: int, block_q: int, block_k: int):
    """Per q block the key tiles it walks, flat [4 * nq] int32: (lo, open_lo,
    open_hi, hi). Segments are contiguous and ascending, so the first row's
    segment start floors every row's attendable keys and the last row is the
    diagonal's end; a block whose first and last rows share a segment lies
    inside it whole."""
    nq = pl.cdiv(T, block_q)
    idx = jnp.arange(T, dtype=jnp.int32)
    change = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    starts = jax.lax.cummax(jnp.where(change, idx, 0))
    q0 = jnp.arange(nq, dtype=jnp.int32) * block_q
    q1 = jnp.minimum(q0 + block_q, T) - 1
    live = jnp.max(jnp.pad(seg, (0, nq * block_q - T), constant_values=-1)
                   .reshape(nq, block_q), axis=1) >= 0
    s0 = starts[q0]
    lo = s0 // block_k
    hi = jnp.where(live, q1 // block_k + 1, lo)
    inside = live & (seg[q0] == seg[q1])
    open_lo = jnp.where(inside, -(-s0 // block_k), lo)
    open_hi = jnp.where(inside, jnp.maximum((q0 + 1) // block_k, open_lo),
                        open_lo)
    return jnp.stack([lo, open_lo, open_hi, hi], axis=1).reshape(-1)


def flash_ragged_prefill(q, k, v, seg_ids, positions, scale, *,
                         block_q: int = None, block_k: int = 512,
                         interpret: bool = False, block: int = 1):
    """q: [T, nh, hd]; k: [T, n_kv, hd]; v: [T, n_kv, hv]; seg_ids: [T]
    (-1 = padding). positions are implied by the flat order (causal within
    segment) and are accepted only for dispatcher signature parity.
    ``block_k``: the keys of a tile; ``block_q``: the tokens of a q block
    (default: 512 rows a kv head, within 128 to 256 tokens). Returns
    [T, nh, hv]."""
    T, nh, hd = q.shape
    hv = v.shape[-1]
    n_kv = k.shape[1]
    g = nh // n_kv
    if block_q is None:
        block_q = max(128, min(256, 512 // g))
    # The MXU's operands: bf16 where q, k and v agree on it, float32 otherwise.
    bf16 = all(a.dtype == jnp.bfloat16 for a in (q, k, v))
    cdt = jnp.bfloat16 if bf16 else jnp.float32
    isz = jnp.dtype(cdt).itemsize
    block_q = min(block_q, T)
    bk = min(block_k, pl.cdiv(T, 16) * 16)
    Tp = pl.cdiv(T, bk) * bk
    nq = pl.cdiv(T, block_q)
    rows = g * block_q
    # kv heads of a step: the most that divide what this call (a tp shard:
    # nh / tp heads, down to one kv head) was given within the row budget.
    G = max(d for d in range(1, n_kv + 1)
            if n_kv % d == 0 and (d == 1 or d * rows <= _STEP_ROWS))

    seg32 = seg_ids.astype(jnp.int32)
    # Head-major so trailing block dims are (tokens, hd) — Mosaic-tileable;
    # keys and values padded with zeros to whole tiles (0 * undefined bytes
    # in P . V would poison every real row).
    q_hm = ((q.astype(jnp.float32) * scale).astype(cdt)
            .transpose(1, 0, 2).reshape(n_kv, g, T, hd))
    k_hm = jnp.pad(k.astype(cdt).transpose(1, 0, 2),
                   ((0, 0), (0, Tp - T), (0, 0)))
    v_hm = jnp.pad(v.astype(cdt).transpose(1, 0, 2),
                   ((0, 0), (0, Tp - T), (0, 0)))
    kseg = jnp.pad(seg32, (0, Tp - T), constant_values=_NO_SEG).reshape(
        Tp // bk, 1, bk)

    kernel = functools.partial(_prefill_kernel, block_q=block_q, block_k=bk)
    if block > 1:
        if T % block or block_q % block or bk % block:
            raise ValueError(
                f"block-causal prefill: {T} tokens in q blocks of {block_q} "
                f"and key tiles of {bk} are not whole blocks of {block}")
        kernel = functools.partial(kernel, block=block)

    def lanes(n):
        return pl.cdiv(n, 128) * 128

    # VMEM: the resident K/V and the q and output blocks (two buffers each),
    # the accumulators, and a tile's scores, probabilities, mask and two
    # bf16 terms (~16 bytes a key) for the step's rows.
    vmem = (2 * G * Tp * (lanes(hd) + lanes(hv)) * isz
            + 2 * G * rows * (lanes(hd) * isz + lanes(hv) * q.dtype.itemsize)
            + G * rows * (2 * 128 + lanes(hv)) * 4
            + G * rows * 16 * bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kv // G, nq),
        in_specs=[
            pl.BlockSpec((G, g, block_q, hd), lambda b, i, win: (b, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((G, Tp, hd), lambda b, i, win: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((G, Tp, hv), lambda b, i, win: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_q, 1), lambda b, i, win: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Tp // bk, 1, bk), lambda b, i, win: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((G, g, block_q, hv),
                               lambda b, i, win: (b, 0, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((G, rows, 1), jnp.float32),
            pltpu.VMEM((G, rows, 1), jnp.float32),
            pltpu.VMEM((G, rows, hv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_kv, g, T, hv), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(max(32 << 20, 2 * vmem), 100 << 20)),
        interpret=interpret,
        name="flash_prefill",
    )(_windows(seg32, T, block_q, bk), q_hm, k_hm, v_hm,
      seg32.reshape(T, 1), kseg)
    return out.reshape(nh, T, hv).transpose(1, 0, 2)
