"""The chunked (SSD) form of a Mamba-2 layer's segment part as one kernel,
the channels on lanes as the state lies.

``ops.ssm.ssm_chunk_scan_xla`` is the same arithmetic as XLA einsums over
``[T, H, P]`` views: with P = 64 a head is half a lane tile, and around the
einsums XLA re-tiles ``[chunks, 256, 64, 64]`` float32 operands, writes the
pairwise decays of every chunk to HBM and carries the state through HBM in a
scan. Here a grid step is (a block of lanes, one chunk of ``SUB`` tokens),
the chunks walked in order with the block's state ``[N, lanes]`` in VMEM
scratch between them; inside it the block's 128-lane tiles (two heads of 64)
follow one another in one body:

1. x arrives as ``[Q, lanes]`` blocks of the conv output's ``[T, d_inner]``
   as it lies (``S[n, h*P + p]``: the state's own lanes) and y leaves the
   same way: no ``[T, H, P]`` view exists in HBM.
2. The per-head operands arrive as ONE ``[Q, 2H]`` float32 block ``[dA |
   dt]``. The running log decay is a log-step sum of sublane rolls; a head's
   values as a column (tokens on sublanes) come from a lane rotation of that
   block that brings the grid step's heads to fixed lanes, as a row (tokens
   on lanes) from its transpose in VMEM scratch. Their pairwise ``exp``, and
   their repeat over a head's P lanes, exist only in vregs.
3. Inside the chunk token i meets token j <= i of its segment through ``M_h
   = (C B^T) exp(cs_i - cs_j) dt_j``, rounded to the model's dtype, one
   ``[Q, Q] x [Q, 128]`` product a head (the tile's other head zeroed); the
   state reaches the tokens of its segment through ONE lane-dense product
   ``C S`` a tile, and takes ``B^T (x w_end)`` the same way.
4. The state's own sums are exact to float32: ``B`` is exact in the model's
   dtype, ``x w_end`` is split in three parts of that dtype (three MXU
   passes for ``Precision.HIGHEST``'s six). ``C S``, which only feeds y,
   takes S in two parts (XLA's default on the chip takes one).
5. A chunk with no real token writes zeros and fetches nothing.

Per segment the kernel also keeps the state handed to the chunk that holds
its last token (``[segments + 1, N, d_inner]``, the other chunks' into one
scrap block): each segment's state at ITS last token is XLA's, from that
chunk alone (``ops.ssm.segment_finals``, shared with the XLA form).

The XLA form's chunk is the published 256; the kernel walks it in halves
(the sum is the same up to rounding): half the pairwise decays and half the
intra-chunk products, none of them above the diagonal block.

What it costs (PERF.md section 6, PR 44): a tile and chunk is a chain of
latencies (rotate, broadcast, exp, split, three products, the state), so as
a LOOP over the tiles the kernel took 0.31 ms a layer at 2048 tokens of
granite-4.0-h-micro; with the tiles in one body the scheduler overlaps them:
0.16 at 1024 lanes a step, 0.15 at 2048 (16 tiles; the build 2 s).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ssm import segment_finals

_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 48 * 1024 * 1024
# Tokens a grid step takes: one tile of lanes for the [Q, Q] matrices.
SUB = 128
# Channels a grid step takes: x, y and the state's [N, LANE_BLOCK]; its 16
# tiles are one body (at 4096: 8 % faster alone, twice the build).
LANE_BLOCK = 2048
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """Both operands in the model's dtype, float32 out: one pass (float32
    operands: full precision, what XLA gives the CPU)."""
    return jax.lax.dot_general(
        a, b, dims, precision=_HI if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _dot_split(a, b, parts):
    """``a`` in the model's dtype (exact there) against float32 ``b`` taken
    in ``parts`` pieces of that dtype, float32 out: three pieces of bfloat16
    hold float32's 24 bits."""
    if a.dtype == jnp.float32:
        return _dot(a, b)
    out = None
    for _ in range(parts):
        piece = b.astype(a.dtype)
        out = _dot(a, piece) if out is None else out + _dot(a, piece)
        b = b - piece.astype(jnp.float32)
    return out


def _cumsum_rows(a):
    """The running sum down the sublanes, in log2(rows) rolls."""
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    step = 1
    while step < a.shape[0]:
        a = a + jnp.where(row >= step, pltpu.roll(a, step, 0), 0.0)
        step *= 2
    return a


def _kernel(n_real_ref, seg_in_ref, seg_last_ref, slot_ref, x_ref, hd_ref,
            b_ref, c_ref, seg_col_ref, seg_row_ref, init_ref, y_ref,
            s_in_ref, state_ref, rows_ref, *, H, P, W):
    del slot_ref                    # consumed by the index maps
    blk, c = pl.program_id(0), pl.program_id(1)
    Q, lanes = x_ref.shape
    f32, dtype = jnp.float32, x_ref.dtype
    heads = W // P                  # of one tile

    @pl.when(c == 0)
    def _():
        state_ref[...] = init_ref[...]

    @pl.when(c >= n_real_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)
        s_in_ref[...] = jnp.zeros_like(s_in_ref)

    @pl.when(c < n_real_ref[0])
    def _():
        seg_in, seg_last = seg_in_ref[c], seg_last_ref[c]
        seg_t, seg_s = seg_col_ref[...], seg_row_ref[...]       # [Q,1] [1,Q]
        meets = ((seg_t == seg_s)
                 & (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)))
        reach = (seg_t == seg_in).astype(f32)                   # [Q, 1]
        ends = (seg_t == seg_last).astype(f32)
        keep = (seg_in == seg_last).astype(f32)
        # [cs | dt], a head a lane; and a head a row.
        hd = hd_ref[...]
        of_decay = jax.lax.broadcasted_iota(jnp.int32, hd.shape, 1) < H
        csdt = jnp.where(of_decay, _cumsum_rows(hd), hd)
        rows_ref[...] = csdt.T
        G = jnp.where(meets, _dot(c_ref[...], b_ref[...], _NT), 0.0)
        Bt = b_ref[...].astype(f32).T.astype(dtype)             # [N, Q]
        head_of = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1) // P

        # The block's heads to lanes 0.. (cs) and H.. (dt).
        mine = pltpu.roll(csdt, (2 * H - blk * (lanes // P)) % (2 * H), 1)
        # The tiles one after the other in ONE body: a loop's iterations do
        # not overlap, and a tile alone is a chain of latencies.
        for t in range(lanes // W):
            at = slice(t * W, (t + 1) * W)
            xt = x_ref[:, at]
            y = cs = dt = jnp.zeros((Q, W), f32)
            for j in range(heads):
                h = t * heads + j
                cs_i, dt_i = mine[:, h:h + 1], mine[:, H + h:H + h + 1]
                cs_j = rows_ref[pl.ds(blk * (lanes // P) + h, 1), :]
                dt_j = rows_ref[pl.ds(H + blk * (lanes // P) + h, 1), :]
                M = G * jnp.exp(jnp.minimum(cs_i - cs_j, 0.0)) * dt_j
                own = head_of == j
                y = y + _dot(M.astype(dtype),
                             jnp.where(own, xt, jnp.zeros_like(xt)))
                cs, dt = jnp.where(own, cs_i, cs), jnp.where(own, dt_i, dt)
            cs_end = cs[Q - 1:Q]
            S = state_ref[:, at]
            y_ref[:, at] = y + (_dot_split(c_ref[...], S, 2)
                                * (jnp.exp(cs) * reach))
            s_in_ref[:, at] = S
            xw = xt.astype(f32) * (jnp.exp(cs_end - cs) * dt * ends)
            state_ref[:, at] = (S * (jnp.exp(cs_end) * keep)
                                + _dot_split(Bt, xw, 3))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk(x: jax.Array, dt: jax.Array, dA: jax.Array, B: jax.Array,
              C: jax.Array, seg_ids: jax.Array, seg_ends: jax.Array,
              init_state: jax.Array, init_seg, chunk: int, *,
              interpret: bool = False):
    """Same contract as ``ops.ssm.ssm_chunk_scan_xla``: x [T, H, P] (a view
    of ``[T, H*P]``: it is read as that), dt, dA [T, H] float32, B, C [T,
    N]; seg_ids [T]; seg_ends [S]; init_state [N, H*P] of segment
    ``init_seg``. Returns (y [T, H, P] float32 without the D skip, each
    segment's final state [S, N, H*P] float32)."""
    T, H, P = x.shape
    N, di, n_seg = B.shape[-1], H * P, seg_ends.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    Q = SUB if chunk % SUB == 0 else chunk
    lanes = min(LANE_BLOCK, di)
    # Heads whose lanes make one tile of (up to) 128.
    heads = next(p for p in range(lanes // P, 0, -1)
                 if (lanes // P) % p == 0 and p * P <= max(128, P))
    W = heads * P
    if di % lanes or lanes % P:
        raise ValueError(f"ssm_chunk: {H} heads of {P} are not whole blocks "
                         f"of {lanes} lanes")
    if not interpret and (Q % 128 or N % 128 or W % 128 or (2 * H) % 128):
        raise ValueError(
            f"ssm_chunk: chunks of {Q}, N = {N}, {H} heads of {P} are not "
            "whole 128-lane tiles")
    pad = -T % Q
    if pad:
        x, dt, dA, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                           for a in (x, dt, dA, B, C))
        seg_ids = jnp.pad(seg_ids, (0, pad), constant_values=-1)
    Tp = T + pad
    nc = Tp // Q
    sc = seg_ids.astype(i32).reshape(nc, Q)
    seg_last = sc[:, -1]
    seg_in = jnp.concatenate([jnp.asarray(init_seg, i32)[None],
                              seg_last[:-1]])
    # Padding stands only behind every segment: the chunks that hold a real
    # token are the first n_real.
    n_real = jnp.sum(jnp.any(sc >= 0, axis=1)).astype(i32)[None]
    # The state handed to a chunk is kept where a segment ends in it.
    e = jnp.maximum(seg_ends, 0)
    c_s = e // Q
    kept = min(n_seg, nc)
    ends_here = jnp.zeros(nc, bool).at[
        jnp.where(seg_ends >= 0, c_s, nc)].set(True, mode="drop")
    slot = jnp.where(ends_here, jnp.cumsum(ends_here) - 1, kept).astype(i32)

    def tokens(width, of_block=False):
        # a chunk without a real token fetches nothing
        return pl.BlockSpec(
            (Q, width), lambda b, c, n, *_: (
                jnp.minimum(c, jnp.maximum(n[0], 1) - 1),
                b if of_block else 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(di // lanes, nc),
        in_specs=[
            tokens(lanes, of_block=True), tokens(2 * H), tokens(N), tokens(N),
            pl.BlockSpec((Q, 1), lambda b, c, *_: (c, 0)),
            pl.BlockSpec((None, 1, Q), lambda b, c, *_: (c, 0, 0)),
            pl.BlockSpec((N, lanes), lambda b, c, *_: (0, b)),
        ],
        out_specs=[
            pl.BlockSpec((Q, lanes), lambda b, c, *_: (c, b)),
            pl.BlockSpec((None, N, lanes),
                         lambda b, c, n, si, sl, slot: (slot[c], 0, b)),
        ],
        scratch_shapes=[pltpu.VMEM((N, lanes), f32),
                        pltpu.VMEM((2 * H, Q), f32)])
    itemsize = jnp.dtype(x.dtype).itemsize
    y, S_in = pl.pallas_call(
        functools.partial(_kernel, H=H, P=P, W=W),
        out_shape=[jax.ShapeDtypeStruct((Tp, di), f32),
                   jax.ShapeDtypeStruct((kept + 1, N, di), f32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * (di * (Q + 5 * N) + (di // lanes) * Q * N),
            transcendentals=Tp * (H * Q + 2 * di),
            bytes_accessed=Tp * di * (itemsize + 4)
            + (kept + 2) * N * di * 4),
        interpret=interpret,
        name="ssm_chunk",
    )(n_real, seg_in, seg_last, slot, x.reshape(Tp, di),
      jnp.concatenate([dA, dt], axis=1).astype(f32), B, C,
      sc.reshape(Tp, 1), sc[:, None, :], init_state.astype(f32))

    # Each segment's last chunk, gathered: as the XLA form's, of Q tokens.
    with jax.named_scope("kgct.ssm.chunk.final"):
        per_chunk = lambda a: a.reshape((nc, Q) + a.shape[1:])[c_s]
        heads_first = lambda a: per_chunk(a).astype(f32).transpose(0, 2, 1)
        final = segment_finals(
            per_chunk(x), per_chunk(B),
            jnp.cumsum(heads_first(dA), axis=-1), heads_first(dt), sc[c_s],
            S_in[slot[c_s]], seg_in[c_s], e % Q)
    return y[:T].reshape(T, H, P), final
