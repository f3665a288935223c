"""The one-token delta-rule update of a KDA layer, in place in the slot pool.

A decode step of kimi-linear reads and writes every running sequence's
recurrent state in every KDA layer: 2 x 2 MiB a row a layer, 1.9 GB a step
at 64 rows over the 7 KDA layers one chip holds. As ``ssm_update`` does,
each row's slot is fetched by the pipeline's DMA from the block index
``(layer, slots[row])`` (scalar prefetch), updated on the VPU and written
back to the SAME block of the aliased pool; nothing else of the pool moves.
What ``ssm_update`` cannot compute is the read before the write: the state
is contracted with k (``D^T k``) and the result taken off v before the
rank-one update.

The slot is stored ``[heads * d_k, d_v]`` (``ops/kda.py``): a head is one
``[d_k, d_v]`` tile. For each head of a block, one pass over its tile:

    D = S * alpha[:, None]                      decay, per key channel
    r = sum over k of D * k[:, None]            [1, d_v]   what k recalls
    u = beta * (v - r)                          [1, d_v]
    S = D + k[:, None] * u                      rank-one update
    o = sum over k of S * q[:, None]            [1, d_v]

Both sums run over SUBLANES (vector adds and one in-register reduction); a
head's ``v``, ``beta`` (repeated over the lanes) and ``o`` are lane-dense
rows. ``alpha``, ``k`` and ``q`` vary along sublanes: they arrive as columns
``[d_k, heads of the block]`` and each is broadcast over the lanes.
Float32 throughout: the state's precision is the configuration's.

Padding rows name the scrap slot 0: several grid steps then
read-modify-write one block, which nobody reads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Heads a grid step takes of a row's slot: [HEAD_BLOCK * d_k, d_v] float32
# in and out, each double-buffered by the pipeline: the whole slot of 32
# heads of 128 x 128 (4 x 2 MiB). Measured alone on a v5e over the served
# pool, 61 rows, us a call (312 at 819 GB/s): 4 heads 644, 8 heads 533, 16
# heads 487, 32 heads 452 (PERF.md, PR 35): a grid step's fixed cost.
HEAD_BLOCK = 32
_VMEM_LIMIT = 32 * 1024 * 1024


def _kernel(slots_ref, layer_ref, col_ref, row_ref, pool_ref, out_ref,
            o_ref):
    del slots_ref, layer_ref        # consumed by the index maps
    dk, hb = col_ref.shape[-2:]
    dv = pool_ref.shape[1]
    for j in range(hb):             # a head's [d_k, d_v] tile at a time
        tile, sl = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        alpha, k, q = (jnp.broadcast_to(col_ref[i, :, j:j + 1], (dk, dv))
                       for i in range(3))
        d = pool_ref[tile, :] * alpha
        r = jnp.sum(d * k, axis=0, keepdims=True)
        u = row_ref[1:2, sl] * (row_ref[0:1, sl] - r)
        s = d + k * u
        out_ref[tile, :] = s
        o_ref[:, sl] = jnp.sum(s * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def kda_update(pool: jax.Array, layer: jax.Array, slots: jax.Array,
               g: jax.Array, beta: jax.Array, q: jax.Array, k: jax.Array,
               v: jax.Array, *, head_block: int = HEAD_BLOCK,
               interpret: bool = False):
    """pool [Ls, slots, H * d_k, d_v] float32 (donate or carry it: the
    result aliases it); layer: int32 scalar or [1]; slots [R] int32; g [R,
    H, d_k] the log decay; beta [R, H]; q, k [R, H, d_k]; v [R, H, d_v].
    Returns (pool, o [R, H * d_v] float32). Same contract as
    ``ops.kda.kda_update_xla``."""
    Ls, n_slots, _, dv = pool.shape
    R, H, dk = k.shape
    lanes = H * dv
    hb = min(head_block, H)
    if pool.dtype != jnp.float32:
        raise ValueError(f"kda_update: the state pool is {pool.dtype}, "
                         "the update is written for float32")
    if dk % 8 or dv % 128 or H % hb:
        raise ValueError(
            f"kda_update: state [{dk}, {H} x {dv}] is not whole tiles in "
            f"blocks of {hb} heads")
    f32 = jnp.float32
    # [R, 3, H / hb, d_k, hb]: alpha, k and q as columns, a block's heads
    # side by side.
    cols = jnp.stack([jnp.exp(g.astype(f32)), k.astype(f32), q.astype(f32)],
                     axis=1).reshape(R, 3, H // hb, hb, dk).swapaxes(-1, -2)
    rows = jnp.stack([v.astype(f32).reshape(R, lanes),
                      jnp.repeat(beta.astype(f32), dv, axis=-1)], axis=1)
    lane, tiles = hb * dv, hb * dk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, H // hb),
        in_specs=[
            pl.BlockSpec((None, 3, None, dk, hb),
                         lambda r, c, s, l: (r, 0, c, 0, 0)),
            pl.BlockSpec((None, 2, lane), lambda r, c, s, l: (r, 0, c)),
            pl.BlockSpec((None, None, tiles, dv),
                         lambda r, c, s, l: (l[0], s[r], c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, tiles, dv),
                         lambda r, c, s, l: (l[0], s[r], c, 0)),
            pl.BlockSpec((None, 1, lane), lambda r, c, s, l: (r, 0, c)),
        ])
    pool, o = pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, 1, lanes), f32)],
        grid_spec=grid_spec,
        # Operands count the two scalar-prefetch arrays: the pool is the 5th.
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=8 * R * dk * lanes, transcendentals=0,
            bytes_accessed=2 * R * dk * lanes * 4),
        interpret=interpret,
        name="kda_update",
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      cols, rows, pool)
    return pool, o[:, 0]
