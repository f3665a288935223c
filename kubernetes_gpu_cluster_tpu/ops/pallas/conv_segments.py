"""A state mixer's conv stage over the segment part as one pass: from the
projected ``xbc`` / ``qkv`` ``[T, C]`` to the operands its recurrence reads.

``ops.ssm.conv_operands_xla`` is the same arithmetic as XLA passes: the
four masked taps over ``[T, C]`` in float32 one after the other, the SiLU, a
cast, then slices of the result (and, for a delta-rule layer, two norms and
the re-tiling of ``[T, H x d]`` float32 to ``[T, H, d]``): at 2 k tokens of
kimi-linear 0.6 ms a layer in the step program for 0.18 ms of bytes (here
0.25). A grid step is (a block of channels of EVERY piece the output is
split in, a block of tokens), the token blocks walked in order; inside it a
loop takes 64 tokens by eight 128-lane tiles at a time, its tiles unrolled
in one body:

1. ``xbc`` is read once, in the model's dtype, as ``[tokens, lanes]`` blocks
   of the array AS IT LIES (the step's row tokens lie behind the segment
   part: no slice is cut out for the call), once a piece. The ``K - 1`` rows
   before the 64 are the loop's carry, float32; before a block's first token
   they come from VMEM scratch (the block before left them; the slot's
   ``init_rows`` before token 0).
2. ``out = bias; out += w[k] * x[t - K + 1 + k]`` for k = 0..K-1 in that
   order, float32, a tap dropped where the token it meets is of another
   segment: the same arithmetic in the same order as the XLA form, so the
   sum is bitwise its sum. The shifted rows are sublane rolls of the
   ``[8 + 64, 128]`` rows in vregs; whether a tap meets its segment is one
   bit a (token, tap), an int32 column made outside.
3. SiLU, then what the piece is: rounded to its dtype; or float32 heads,
   each 128-lane tile one head, left as they are (v) or brought to unit
   length by a lane reduction inside the tile and scaled (q, k). Every
   piece is written where it lies, ``[tokens, lanes]`` blocks of ``[T,
   width]``: the heads on lanes, as ``kda_chunk`` reads them (its wrapper
   undoes the ``[T, H, d]`` view this one returns; on the chip ``[T, H x
   d] -> [T, H, d]`` is another TILED layout, 0.3-0.9 ms of copies an
   operand and layer where XLA makes it).

Each segment's new conv rows stay ``ops.ssm.segment_conv_rows``, a gather
of ``S x (K - 1)`` rows of xbc that ``models.llama.state_mixer`` makes for
both forms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ssm import L2_EPS, ConvSplit

_VMEM_LIMIT = 48 * 1024 * 1024
# Tokens a grid step takes, and tokens an iteration of its loop takes (whole
# sublane tiles of either dtype; alone at 2048 tokens 64 rows read 608 us a
# layer of kimi-linear's and 109 of granite's, 32 rows 640 and 121, 16 rows
# 739 and 155; 128 or 512 tokens a grid step read as 256 does: PERF.md
# section 6, PR 47).
TOKEN_BLOCK = 256
ROWS = 64
# Lanes an iteration takes, its 128-lane tiles in ONE body (a loop's
# iterations do not overlap, and a tile alone is a chain of latencies; four
# tiles read 14 % slower).
LANE_GROUP = 1024
HEAD_BLOCK = 8
_HIST = 8               # rows kept before a block: one float32 sublane tile


def _kernel(*refs, K, pieces, activate):
    """refs: bits [TB, 1]; a piece: x [TB, wb], wb [K + 1, wb] (the taps,
    then the bias), init [8, wb]; then a piece's output [TB, wb]; then a
    piece's carry [8, wb]. ``pieces``: (lanes of the block, of a loop
    iteration, of a tile; what a tile's unit rows are scaled by, None: no
    norm) a piece."""
    n = len(pieces)
    bits_ref, ins = refs[0], refs[1:1 + 3 * n]
    outs, carries = refs[1 + 3 * n:1 + 4 * n], refs[1 + 4 * n:]
    f32 = jnp.float32
    TB = bits_ref.shape[0]

    for p, (lanes, group, tile, unit) in enumerate(pieces):
        x_ref, wb_ref, init_ref = ins[3 * p:3 * p + 3]
        o_ref, carry_ref = outs[p], carries[p]

        @pl.when(pl.program_id(1) == 0)
        def _():
            carry_ref[...] = init_ref[...]

        def one_group(g, _):
            at = pl.multiple_of(g * group, group)
            tiles = [pl.ds(at + j * tile, tile) for j in range(group // tile)]

            def rows(i, before):
                here = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
                bits = bits_ref[here, :]
                meets = [jnp.broadcast_to((bits >> k) & 1, (ROWS, tile)) == 1
                         for k in range(K - 1)]
                after = []
                for j, lane in enumerate(tiles):
                    x = x_ref[here, lane].astype(f32)
                    ext = jnp.concatenate([before[j], x], axis=0)
                    out = jnp.broadcast_to(wb_ref[K:K + 1, lane], x.shape)
                    for k in range(K - 1):
                        met = pltpu.roll(ext, K - 1 - k, 0)[_HIST:]
                        out = out + wb_ref[k:k + 1, lane] * jnp.where(
                            meets[k], met, 0.0)
                    out = out + wb_ref[K - 1:K, lane] * x
                    if activate:
                        out = jax.nn.silu(out)
                    if unit is not None:
                        out = out * jax.lax.rsqrt(jnp.sum(
                            out * out, axis=-1, keepdims=True) + L2_EPS)
                        if unit != 1.0:
                            out = out * unit
                    o_ref[here, lane] = out.astype(o_ref.dtype)
                    after.append(x[ROWS - _HIST:])
                return after

            last = jax.lax.fori_loop(
                0, TB // ROWS, rows, [carry_ref[:, lane] for lane in tiles])
            for lane, x in zip(tiles, last):
                carry_ref[:, lane] = x
            return 0

        jax.lax.fori_loop(0, lanes // group, one_group, 0)


def _tap_bits(seg_ids, K):
    """Bit k of entry t: the row tap k meets at token t, ``t - K + 1 + k``,
    is of t's segment (before token 0 lies the first token's own: the
    slot's rows, or zeros). Tap K-1 meets the token itself: no bit."""
    T = seg_ids.shape[0]
    seg_ext = jnp.concatenate(
        [jnp.broadcast_to(seg_ids[:1], (K - 1,)), seg_ids])
    return sum((seg_ext[k:k + T] == seg_ids).astype(jnp.int32) << k
               for k in range(K - 1))


def _blocks(split: ConvSplit, interpret: bool):
    """(a piece's (first channel, channels a grid step takes, channels a
    loop iteration takes, channels of a tile), the grid's channel steps):
    every piece in as many steps."""
    hd = split.head_dim
    if hd is None:
        widths, n_blocks = split.widths, 1
    else:
        H = split.widths[0] // hd
        if any(w != H * hd for w in split.widths):
            raise ValueError(f"conv_segments: pieces of {split.widths} "
                             f"channels are not {H} heads of {hd} each")
        hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
        widths, n_blocks = [hb * hd] * len(split.widths), H // hb
    out, start = [], 0
    for piece, width in zip(split.widths, widths):
        tile = hd or (128 if width % 128 == 0 else width)
        group = next(g for g in range(width, 0, -tile)
                     if width % g == 0 and g <= max(LANE_GROUP, tile))
        if start % width or (not interpret and tile != 128):
            raise ValueError(
                f"conv_segments: a piece of {piece} channels at {start} in "
                f"tiles of {tile} is not whole 128-lane tiles at a whole "
                "block")
        out.append((start, width, group, tile))
        start += piece
    return out, n_blocks


def conv_segments(xbc: jax.Array, seg_ids: jax.Array, init_rows: jax.Array,
                  w: jax.Array, b, split: ConvSplit, *, activate: bool = True,
                  interpret: bool = False):
    """Same contract as ``ops.ssm.conv_operands_xla``: xbc [>= T, C], its
    first T = len(seg_ids) rows the segment part; init_rows [K-1, C]; w [K,
    C]; b [C] or None. Returns the pieces ``split`` names.
    ``activate=False``: the taps' sum itself in the pieces' place (what the
    tests and the chip's gate hold bitwise to the XLA form's)."""
    f32 = jnp.float32
    bias = jnp.zeros((1, w.shape[1]), f32) if b is None else b.astype(f32)[None]
    return _conv_segments(
        xbc, seg_ids, init_rows, jnp.concatenate([w.astype(f32), bias]),
        split=split, activate=activate, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("split", "activate", "interpret"))
def _conv_segments(xbc, seg_ids, init_rows, wb, *, split, activate,
                   interpret):
    """wb [K + 1, C] float32: the taps, then the bias."""
    T, C = seg_ids.shape[0], xbc.shape[1]
    K = wb.shape[0] - 1
    f32 = jnp.float32
    hd = split.head_dim
    if K - 1 > _HIST or sum(split.widths) != C:
        raise ValueError(f"conv_segments: {K} taps over {C} channels in "
                         f"pieces of {split.widths}")
    blocks, n_blocks = _blocks(split, interpret)
    TB = min(TOKEN_BLOCK, -(-T // ROWS) * ROWS)
    dtype = f32 if hd is not None or not activate else (
        split.dtype or xbc.dtype)

    init = jnp.concatenate(
        [jnp.zeros((_HIST - (K - 1), C), f32), init_rows.astype(f32)])
    bits = _tap_bits(seg_ids.astype(jnp.int32), K)[:, None]

    in_specs = [pl.BlockSpec((TB, 1), lambda c, t: (t, 0))]
    operands, out_specs, out_shape, scratch, pieces = [bits], [], [], [], []
    for (start, width, group, tile), unit in zip(
            blocks, split.unit or (None,) * len(blocks)):
        first = start // width
        in_specs += [
            pl.BlockSpec((TB, width), lambda c, t, f=first: (t, f + c)),
            pl.BlockSpec((K + 1, width), lambda c, t, f=first: (0, f + c)),
            pl.BlockSpec((_HIST, width), lambda c, t, f=first: (0, f + c))]
        operands += [xbc, wb, init]
        out_specs.append(pl.BlockSpec((TB, width), lambda c, t: (t, c)))
        out_shape.append(jax.ShapeDtypeStruct((T, n_blocks * width), dtype))
        scratch.append(pltpu.VMEM((_HIST, width), f32))
        pieces.append((width, group, tile, unit if activate else None))

    out = pl.pallas_call(
        functools.partial(_kernel, K=K, pieces=tuple(pieces),
                          activate=activate),
        out_shape=out_shape,
        grid=(n_blocks, pl.cdiv(T, TB)),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=T * C * (2 * K + 8), transcendentals=2 * T * C,
            bytes_accessed=T * C * (jnp.dtype(xbc.dtype).itemsize
                                    + jnp.dtype(dtype).itemsize)),
        interpret=interpret,
        name="conv_segments",
    )(*operands)
    if hd is not None:      # the heads, named: no bytes move
        out = [p.reshape(T, -1, hd) for p in out]
    return tuple(out)
