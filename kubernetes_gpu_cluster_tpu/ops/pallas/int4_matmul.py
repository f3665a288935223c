"""Dequant-fused int4 matmul as a Pallas TPU kernel (W4A16 decode path).

Decode is weight-streaming bound, so the only bytes that may cross the HBM
bus for a quantized matmul are the PACKED nibbles plus the group scales.
This kernel consumes packed int4 tiles (two nibbles per int8 byte along the
input dim — ops/quant.py layout) and dequantizes them in VMEM:

- grid ``(n_tiles, k_tiles)``: the output tile axis is parallel, the input
  (contraction) axis is serialized per output tile and accumulates into the
  revisited f32 output block (same revisit-accumulate structure as the
  paged_decode kernel's chunk loop, expressed through the grid).
- each k step DMAs one ``[Kt/2, Nt]`` packed tile and its ``[Kt/gs, Nt]``
  scale rows HBM->VMEM (half the bytes a bf16 or int8 tile would move),
  sign-extends the nibbles with two arithmetic shifts, interleaves them
  back to ``[Kt, Nt]`` — a SUBLANE-side stack+reshape; the lane dim (out
  channels) is never reshaped, which is the Mosaic constraint that shaped
  paged_decode's block-diagonal trick — applies the per-(group, channel)
  scale on a ``[groups, gs, Nt]`` view, and runs one MXU matmul against the
  activation tile.

The kernel tiles K in multiples of the group size so scale rows never
straddle a tile; N tiles at the 128-lane width. Activations ride along the
whole K extent per output tile ([T, Kt] blocks), which is noise next to the
weight stream at decode batch sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int4_matmul_kernel(x_ref, wp_ref, scale_ref, out_ref, *,
                        group_size: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # Widen before shifting: Mosaic does not legalize shifts on i8 vectors
    # (v5e, libtpu 0.0.34: "failed to legalize operation 'arith.shli'").
    wp = wp_ref[:].astype(jnp.int32)                 # [Kt/2, Nt]
    half, nt = wp.shape
    lo = (wp << 28) >> 28                            # sign-extend low nibble
    hi = wp >> 4                                     # arithmetic: high nibble
    w = jnp.stack([lo, hi], axis=1).reshape(half * 2, nt)   # [Kt, Nt] int32
    wf = w.astype(jnp.float32).reshape(-1, group_size, nt)
    wf = (wf * scale_ref[:][:, None, :]).reshape(half * 2, nt)
    out_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), wf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def pallas_int4_matmul(x, w_packed, scale, *, block_n: int = 256,
                       block_k: int = 1024, interpret: bool = False):
    """x: [T, K] (bf16/f32); w_packed: [K/2, N] int8 (ops/quant.pack_int4
    layout); scale: [K/group_size, N] f32. Returns f32 [T, N].

    ``block_k`` is clamped to a multiple of the group size (scale rows must
    not straddle k tiles); ``block_n`` to the 128-lane width."""
    T, K = x.shape
    half, N = w_packed.shape
    n_groups = scale.shape[0]
    if half * 2 != K:
        raise ValueError(f"packed input dim {half}*2 != activation dim {K}")
    if K % n_groups:
        raise ValueError(f"K={K} not divisible by {n_groups} scale groups")
    gs = K // n_groups

    # Served matmul dims are multiples of 128 by config; unaligned edge
    # cases fall back to the XLA fusion rather than computing a wrong
    # padded edge. Tile selection degrades before falling back: a k tile
    # that doesn't divide K drops to one group, an n tile that doesn't
    # divide N drops to the 128-lane width.
    bk = min(max(gs, block_k - block_k % gs), K)
    if K % bk:
        bk = gs
    bn = min(max(128, block_n - block_n % 128), N)
    if N % bn:
        bn = 128
    if N % 128 or K % bk or (bk // 2) % 32 or ((bk // gs) % 8 and bk != K):
        # lane dim must tile at 128; the packed tile's sublane dim (bk/2)
        # must respect the int8 (32, 128) min tile, and the f32 scale
        # tile's (bk/gs) the (8, 128) one unless it spans the whole array
        # (block_k 1024 / group 128 = 8 rows: the default just meets it).
        from ..quant import int4_matmul_xla
        return int4_matmul_xla(x, w_packed, scale)

    grid = (N // bn, K // bk)
    kernel = functools.partial(_int4_matmul_kernel, group_size=gs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, bk), lambda n, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk // 2, bn), lambda n, k: (k, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk // gs, bn), lambda n, k: (k, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((T, bn), lambda n, k: (0, n),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="int4_matmul",
    )(x, w_packed, scale)
