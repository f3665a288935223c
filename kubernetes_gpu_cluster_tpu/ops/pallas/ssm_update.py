"""The one-token state update of a state layer, in place in the slot pool.

A decode step of a state model reads and writes every running sequence's
recurrent state in every state layer: 2 x 2 MiB a row a layer at
granite-4.0-h-micro's widths, 9.66 GB a step at 64 rows beside 6.38 GB of
weights. XLA's gather / update / scatter of the pool would copy it or walk
it a row at a time (``ops.attention.write_kv_pages_all_xla`` has the story
of the page pool); here each row's slot is fetched by the pipeline's DMA
from the block index ``(layer, slots[row])`` (scalar prefetch), updated on
the VPU and written back to the SAME block of the aliased pool. Nothing
else of the pool moves.

The slot is stored ``[N, d_inner]`` (``ops/ssm.py``), so for a block of
``lane`` channels

    S = S * decay[None, :] + B[:, None] * dtx[None, :]      [N, lane]
    y = sum over n of S * C[:, None]                        [1, lane]

is lane-dense arithmetic with sublane broadcasts of ``decay``/``dtx`` and a
sublane reduction for ``y``. ``B`` and ``C`` arrive as rows ``[1, N]``;
their column form ``[N, lane]`` (the value of B[n] on every lane) comes from
one aligned 32-bit transpose of the row broadcast to ``[lane, N]``.
Float32 throughout: the state's precision is the configuration's
(PERF.md section 4).

Padding rows name the scrap slot 0, as padding tokens name the scrap page:
several grid steps then read-modify-write one block, which nobody reads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Channels a grid step takes of a row's slot: [N, LANE_BLOCK] float32 in and
# out, each double-buffered by the pipeline (4 x 512 KiB at N = 128).
LANE_BLOCK = 1024
_VMEM_LIMIT = 32 * 1024 * 1024


def _kernel(slots_ref, layer_ref, vec_ref, bc_ref, pool_ref, out_ref,
            y_ref):
    del slots_ref, layer_ref        # consumed by the index maps
    n = pool_ref.shape[0]
    # [1, N] -> [N, N]: row r of the broadcast is B; its transpose holds
    # B[n] along row n. (N is a whole number of 128-lane tiles.)
    b_col = jnp.broadcast_to(bc_ref[0:1, :], (n, n)).T
    c_col = jnp.broadcast_to(bc_ref[1:2, :], (n, n)).T
    for j in range(0, pool_ref.shape[1], n):   # an [N, N] tile at a time
        sl = slice(j, j + n)
        s = (pool_ref[:, sl] * vec_ref[0:1, sl]            # decay
             + b_col * vec_ref[1:2, sl])                   # dt * x
        out_ref[:, sl] = s
        y_ref[:, sl] = jnp.sum(s * c_col, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("lane_block", "interpret"))
def ssm_update(pool: jax.Array, layer: jax.Array, slots: jax.Array,
               decay: jax.Array, dtx: jax.Array, B: jax.Array,
               C: jax.Array, *, lane_block: int = LANE_BLOCK,
               interpret: bool = False):
    """pool [Ls, slots, N, di] float32 (donate or carry it: the result
    aliases it); layer: int32 scalar or [1]; slots [R] int32; decay, dtx
    [R, di] float32; B, C [R, N] float32.
    Returns (pool, y [R, di] float32). Same contract as
    ``ops.ssm.ssm_update_xla``."""
    Ls, n_slots, N, di = pool.shape
    R = slots.shape[0]
    lane = min(lane_block, di)
    if pool.dtype != jnp.float32:
        raise ValueError(f"ssm_update: the state pool is {pool.dtype}, "
                         "the update is written for float32")
    if N % 128 or di % lane or lane % N:
        raise ValueError(
            f"ssm_update: state [{N}, {di}] is not whole 128-lane tiles in "
            f"blocks of {lane} channels")
    vec = jnp.stack([decay, dtx], axis=1).astype(jnp.float32)   # [R, 2, di]
    bc = jnp.stack([B, C], axis=1).astype(jnp.float32)          # [R, 2, N]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, di // lane),
        in_specs=[
            pl.BlockSpec((None, 2, lane), lambda r, c, s, l: (r, 0, c)),
            pl.BlockSpec((None, 2, N), lambda r, c, s, l: (r, 0, 0)),
            pl.BlockSpec((None, None, N, lane),
                         lambda r, c, s, l: (l[0], s[r], 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, N, lane),
                         lambda r, c, s, l: (l[0], s[r], 0, c)),
            pl.BlockSpec((None, 1, lane), lambda r, c, s, l: (r, 0, c)),
        ])
    pool, y = pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, 1, di), jnp.float32)],
        grid_spec=grid_spec,
        # Operands count the two scalar-prefetch arrays: the pool is the 5th.
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=5 * R * N * di, transcendentals=0,
            bytes_accessed=2 * R * N * di * 4),
        interpret=interpret,
        name="ssm_update",
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      vec, bc, pool)
    return pool, y[:, 0]
