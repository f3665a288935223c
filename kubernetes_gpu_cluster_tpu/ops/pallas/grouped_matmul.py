"""Grouped matmul on the chip: ``lhs[rows of group g] @ rhs[g]`` for every
group, the rows sorted by group. A VISIT is one MXU pass of one row tile
against one group's weights, and the visit list is the design: for each
group, ``ceil(its rows / TILE_ROWS)`` visits of its OWN rows, walked in
tiles that start at the group's first row. No tile is computed for two
groups, an empty group costs nothing, and rows that are in no group (a
step's padding pairs, sorted behind every group) are never visited.

What a call costs, measured on a v5e (PERF.md, PR 30): its bytes. A visit
is ~4.1 us of MXU time (128 x 2048 x 1408, 92 % of the peak), and one
group's weights are 5.8 MB = 8.4 us of the bus: with one or two visits a
group the weights' stream, the row tiles in and the float32 result tiles
out are ~0.73 ms where the visits are ~0.45. So every copy is the kernel's
own, and the kernel's work is to keep the bus busy and the MXU under it:

- the rows reach the MXU by DMA at the group's own offset, and a DMA starts
  on a whole HBM tile: every group STARTS on a multiple of ``ROW_ALIGN``
  rows (``group_starts``; the caller lays the rows out so,
  ``models.llama.experts_grouped``), which costs at most 15 rows a
  non-empty group where a tile on a group boundary cost a second pass;
- a visit is one dot over all of K (no accumulator, nothing zeroed, the
  result tile is never read back) that is written out whole; what its last
  rows spill over the group's end is overwritten by the later groups' own
  visits, the writes kept in order, and past the last group it lands in the
  tile of slack that ``padded_rows`` leaves;
- the weights of the next two groups are in flight while this one computes,
  on the second DMA queue, so that the small copies of the row tiles never
  wait behind them.

Why not ``jax.lax.ragged_dot`` on the chip: XLA lowers it to a grouped
kernel of its own with 512-row tiles, 4.11 ms at the 12,672 rows x 64
experts where megablox ``gmm`` (this module until PR 30) took 1.08 ms
(PERF.md, PR 26). ``ragged_dot`` stays the XLA twin, over the same layout:
the CPU path and what this is held to, bitwise on the rows inside groups.

A caller may hand the whole stack of layers as groups with only one layer's
sizes non-zero: the weights are read in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 128
ROW_ALIGN = 16                  # rows of a bf16 tile in HBM
_RHS_TILE_BYTES = 6 * 2**20     # one weight buffer; kimi's 2048 x 1408 fits
_WEIGHT_BUFS = 3                # this group's weights and two groups' ahead
_VMEM_LIMIT = 40 * 2**20        # those, two row tiles, two result tiles


def tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(rows, K, N) of a tile: all of K, and the widest multiple of 128 that
    divides N and keeps the weight tile inside its VMEM share."""
    if k % 128 or n % 128:
        raise ValueError(
            f"grouped_matmul needs K and N in whole 128-lane tiles, not "
            f"{k} x {n}")
    fit = max(1, _RHS_TILE_BYTES // (k * itemsize * 128))
    tn = max(t for t in range(1, n // 128 + 1)
             if (n // 128) % t == 0 and t <= fit) * 128
    return TILE_ROWS, k, tn


# The layout and the visit rule, as functions of the groups' sizes alone
# (NumPy or JAX arrays alike): the caller, the kernel and the host's gauge
# (``Observability.on_expert_load``) all read them here.

def aligned_sizes(group_sizes):
    """Rows each group takes up: its size, rounded up to ``ROW_ALIGN``."""
    return (group_sizes + ROW_ALIGN - 1) // ROW_ALIGN * ROW_ALIGN


def group_starts(group_sizes):
    """First row of each group: the aligned sizes before it."""
    taken = aligned_sizes(group_sizes)
    return taken.cumsum() - taken


def group_visits(group_sizes):
    """Visits of each group: the row tiles that hold its rows."""
    return (group_sizes + TILE_ROWS - 1) // TILE_ROWS


def padded_rows(pairs: int, groups: int) -> int:
    """Rows to lay ``pairs`` rows of at most ``groups`` non-empty groups out
    in: every group's round-up, and one tile for the last visit's spill."""
    gaps = (ROW_ALIGN - 1) * groups
    return -(-(pairs + gaps) // ROW_ALIGN) * ROW_ALIGN + TILE_ROWS


def tile_fill_share(group_sizes) -> float:
    """Rows in groups over the rows the visits compute (0 with no visit)."""
    computed = int(group_visits(group_sizes).sum()) * TILE_ROWS
    return float(group_sizes.sum()) / computed if computed else 0.0


def visit_list(group_sizes: jax.Array, m: int
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(group, first row, first visit of the next group) of every visit, in
    order, and how many there are ([1]): the kernel's grid. The lists are as
    long as ``m`` rows over these groups could ever need; entries past the
    count are not read. A first row never leaves a whole tile outside ``m``
    rows, whatever the sizes say."""
    sizes = group_sizes.astype(jnp.int32)
    visits = group_visits(sizes)
    ends = jnp.cumsum(visits)
    longest = pl.cdiv(m, TILE_ROWS) + sizes.shape[0]
    v = jnp.arange(longest, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(ends, v, side="right", method="compare_all"),
        sizes.shape[0] - 1).astype(jnp.int32)
    tile = v - (ends - visits)[group]
    row = jnp.minimum(group_starts(sizes)[group] + tile * TILE_ROWS,
                      m - TILE_ROWS)
    return group, row, ends[group], jnp.minimum(ends[-1:], longest)


def _kernel(group_ref, row_ref, next_ref, count_ref,     # scalar prefetch
            lhs_hbm,        # [M, K] ANY
            rhs_hbm,        # [G, K, N] ANY
            out_hbm,        # [M, N] ANY
            lhs_buf,        # [2, tm, K] VMEM
            rhs_buf,        # [_WEIGHT_BUFS, K, tn] VMEM
            out_buf,        # [2, tm, tn] VMEM float32
            lhs_sems, rhs_sems, out_sems,
            run_ref,        # [1] SMEM: groups' weights used up so far
            *, tm: int, tn: int):
    j, v = pl.program_id(0), pl.program_id(1)
    count = count_ref[0]
    slot = jax.lax.rem(v, 2)

    def rows(visit):
        return pl.ds(pl.multiple_of(row_ref[visit], ROW_ALIGN), tm)

    def cols(n_tile):
        return pl.ds(pl.multiple_of(n_tile * tn, 128), tn)

    def fetch(visit, s):
        return pltpu.make_async_copy(lhs_hbm.at[rows(visit), :],
                                     lhs_buf.at[s], lhs_sems.at[s])

    def weights(visit, n_tile, run):
        s = jax.lax.rem(run, _WEIGHT_BUFS)
        return pltpu.make_async_copy(
            rhs_hbm.at[group_ref[visit], :, cols(n_tile)], rhs_buf.at[s],
            rhs_sems.at[s])

    def store(visit, s):
        return pltpu.make_async_copy(
            out_buf.at[s], out_hbm.at[rows(visit), cols(j)], out_sems.at[s])

    def send_for(ahead, run):
        """Start the weights' copy of the run of visits (one group's, in
        one N tile) that comes ``ahead`` runs after this visit's, if there
        is one. On the second DMA queue: behind a 6 MB copy on their own
        queue, the rows' small copies waited and the MXU with them (0.87 ->
        0.73 ms at kimi-vl-a3b's mixed step, the bytes' own time)."""
        visit, n_tile = v, j
        for _ in range(ahead):
            behind = next_ref[visit] >= count       # the N tile's last group
            visit = jnp.where(behind, 0, next_ref[visit])
            n_tile = jnp.where(behind, n_tile + 1, n_tile)

        @pl.when(n_tile < pl.num_programs(0))
        def _():
            weights(visit, n_tile, run + ahead).start(priority=1)

    @pl.when((j == 0) & (v == 0))
    def _():
        run_ref[0] = 0
        for ahead in range(_WEIGHT_BUFS - 1):
            send_for(ahead, 0)

    # A group's first visit sends for the weights _WEIGHT_BUFS - 1 groups on
    # (into the buffer the group before it has just left) and waits for its
    # own, which have had the groups in between to arrive in.
    @pl.when((v == 0) | (group_ref[v] != group_ref[jnp.maximum(v - 1, 0)]))
    def _():
        send_for(_WEIGHT_BUFS - 1, run_ref[0])
        weights(v, j, run_ref[0]).wait()

    @pl.when(v == 0)
    def _():
        fetch(0, 0).start()

    @pl.when(v + 1 < count)
    def _():
        fetch(v + 1, 1 - slot).start()

    fetch(v, slot).wait()
    out_buf[slot] = jnp.dot(
        lhs_buf[slot], rhs_buf[jax.lax.rem(run_ref[0], _WEIGHT_BUFS)],
        preferred_element_type=jnp.float32)

    @pl.when(v + 1 == next_ref[v])      # the group's last visit
    def _():
        run_ref[0] = run_ref[0] + 1

    # One store in flight: a visit's spill past its group's end lands before
    # the next group's visit writes the same rows. (It hides under the next
    # visit's dot, several times its length.)
    @pl.when(v > 0)
    def _():
        store(v - 1, 1 - slot).wait()

    store(v, slot).start()

    @pl.when(v == count - 1)
    def _():
        store(v, slot).wait()


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """lhs: [M, K], group g's rows from ``group_starts(group_sizes)[g]`` on;
    rhs: [G, K, N]; group_sizes: [G] int32. M is a multiple of ``ROW_ALIGN``
    and leaves a tile of slack behind the last group (``padded_rows``).
    Returns [M, N] float32; rows outside the groups hold nothing
    meaningful (not even zeros)."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if m % ROW_ALIGN or m < TILE_ROWS:
        raise ValueError(
            f"grouped_matmul needs whole {ROW_ALIGN}-row tiles and at least "
            f"{TILE_ROWS} rows (padded_rows), not {m}")
    tm, _, tn = tiling(k, n, rhs.dtype.itemsize)
    *visits, count = visit_list(group_sizes, m)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, count[0]),
        in_specs=[any_spec, any_spec],
        out_specs=any_spec,
        scratch_shapes=[pltpu.VMEM((2, tm, k), lhs.dtype),
                        pltpu.VMEM((_WEIGHT_BUFS, k, tn), rhs.dtype),
                        pltpu.VMEM((2, tm, tn), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((_WEIGHT_BUFS,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    weights_read = min(rhs.shape[0], pl.cdiv(m, tm))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * lhs.dtype.itemsize * (n // tn)
                            + weights_read * k * n * rhs.dtype.itemsize
                            + m * n * 4)),
        interpret=interpret,
        name="grouped_matmul",
    )(*visits, count, lhs, rhs)
