"""The post-scan KV page write as a Pallas TPU kernel: the donated pool is
updated in place by DMA, every touched tile of a block in flight at once.

What it replaces on the chip: ``ops.attention.write_kv_pages_all_xla``'s XLA
loop, T ``dynamic_update_slice``s of ``[L, 1, kd]`` each waiting for the one
before (5.5 us apiece on the v5e at L=36, kd=1024: 0.70 ms of an 18.6 ms
decode step at T=64 to move 9.4 MB; PERF.md section 6, PR 25). The loop
stays as the reference for ``use_pallas=False``.

**The unit of a copy is one HBM tile: 8 pool rows.** Mosaic refuses a DMA
slice that is not whole tiles, and in HBM a pool is tiled 8 rows x 128 lanes
whatever its element size (``T(8,128)(2,1)`` for 16-bit elements: each row
PAIR is one row of 32-bit words, row 2j in the low halves). So one token's
row is not something a DMA can write, and a row pair is not either. A job
is therefore a read-modify-write of the ``[L, 8, kd]`` tile that holds a
slot: HBM -> VMEM, the new rows put in place, VMEM -> HBM. The kernel sees
pool, new rows and scratch as 32-bit words (``ref.bitcast(uint32)``; no
bytes move), where a single row can be loaded and stored at a dynamic
index: a 16-bit row is merged into its half of the word row by shift and
mask, a 32-bit row replaces its word row.

**One job per tile, not per token.** Tokens that share a tile would lose a
write if each did its own read-modify-write. In every layout the scheduler
builds (prefill, mixed, spec, spec_mixed, decode) the tokens of one
sequence are adjacent and ascending on the token axis and sequences own
distinct pages, so outside the scrap page the tokens of one tile are
neighbours. A job is a run of neighbours in one tile, opened by the first;
rows go in in token order, so of EQUAL slots (padding rows: all slot 0 of
scrap page 0) the last one's row stays, which is what the loop leaves.
A run never spans two grid steps; there the second job's read is ordered
after the first's write, because every write of a step is waited for before
the step ends. Only tiles met twice in one step by tokens that are NOT
neighbours (a mixed step's chunk padding and decode padding, both in scrap
page 0) race; that page may hold anything.

**A grid step takes ``block_t`` tokens** (``_block_tokens``: what the VMEM
budget holds, from L, kd and the element size). Their new rows arrive as an
auto-pipelined ``[L, block_t, kd]`` block of the scan's output (no relayout
before the call); every job's read starts at once; then, in token order, a
job's read is waited for, its rows are placed, and its write starts when
the next job opens, so writes overlap the placing that follows.

In place: both pools are ``input_output_aliases`` of the outputs and never
leave HBM (``memory_space=ANY``); the custom call keeps a donated pool, and
the decode window's scan carry, where they are.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of one HBM tile, whatever the element size: a 16-bit pool is tiled
# (8, 128)(2, 1) there, a 32-bit one (8, 128).
_TILE_ROWS = 8
_VMEM_BUDGET = 48 * 2**20   # blocks + scratch of one grid step
_MAX_BLOCK_T = 128          # two read semaphores a token, of a core's 512
_VMEM_LIMIT = 64 * 2**20    # that and Mosaic's own; a v5e core has 128 MiB


def _kv_write_kernel(
    # scalar prefetch
    slots_ref,      # [T] int32 flat slot = page * page_size + offset
    # then, for n pools (K and V, or a latent model's one):
    #   n inputs   [L, block_t, kd] VMEM, pool dtype: this block's new rows
    #   n inputs   the pools, aliased to the outputs; never touched
    #   n outputs  [L, P, ps, kd] ANY/HBM: the pools
    #   n scratch  [block_t, L, 8/pack, max(kd, 256)] uint32 VMEM: one pool
    #              tile a job, in the first kd lanes
    #   read_sems  DMA [n, block_t]
    #   write_sems DMA [n]
    *refs,
    num_tokens: int,
    block_t: int,
    page_size: int,
    pack: int,
):
    n = (len(refs) - 2) // 4
    new_refs, out_pools, tiles = refs[:n], refs[2 * n:3 * n], refs[3 * n:4 * n]
    read_sems, write_sems = refs[4 * n:]
    L, lanes = tiles[0].shape[1], tiles[0].shape[-1]
    kd = new_refs[0].shape[-1]
    tile_words = _TILE_ROWS // pack          # word rows of a tile
    t0 = pl.program_id(0) * block_t
    n_here = jnp.minimum(block_t, num_tokens - t0)
    # Interpret mode cannot write through a bitcast ref (JAX 0.9.0): there
    # the caller hands the pools over as words already (``_as_words``).
    pools = tuple(p if p.dtype == jnp.uint32 else p.bitcast(jnp.uint32)
                  for p in out_pools)
    news = tuple(r.bitcast(jnp.uint32) for r in new_refs)

    def tile_of(i):
        return slots_ref[t0 + i] // _TILE_ROWS

    def leads(i):
        """Token i opens a job: the first of the block's tokens in its tile."""
        return (i == 0) | (tile_of(i) != tile_of(jnp.maximum(i - 1, 0)))

    def copies(i, write):
        """The DMAs (one a pool) of the job token i leads: its pool tile, as
        [L, 8/pack, kd] words, into scratch slot i or back."""
        tile = tile_of(i)
        tiles_per_page = page_size // _TILE_ROWS
        page = tile // tiles_per_page
        w0 = pl.multiple_of((tile % tiles_per_page) * tile_words, tile_words)
        out = []
        for kv in range(n):
            hbm = pools[kv].at[:, page, pl.ds(w0, tile_words), :]
            vmem = tiles[kv].at[i, :, :, pl.ds(0, kd)]
            out.append(pltpu.make_async_copy(vmem, hbm, write_sems.at[kv])
                       if write else
                       pltpu.make_async_copy(hbm, vmem, read_sems.at[kv, i]))
        return out

    def for_leaders(op):
        def body(i, _):
            @pl.when(leads(i))
            def _():
                op(i)
            return 0
        jax.lax.fori_loop(0, n_here, body, 0)

    def start(i, write):
        for c in copies(i, write):
            c.start()

    def wait(i, write):
        for c in copies(i, write):
            c.wait()

    # Every job's read in flight at once.
    for_leaders(lambda i: start(i, False))

    def place(i, job):
        """Token i's row of every layer into the scratch tile of its job."""
        slot = slots_ref[t0 + i]
        w = (slot % _TILE_ROWS) // pack
        src_shift = ((i % pack) * 16).astype(jnp.uint32)
        dst_shift = ((slot % pack) * 16).astype(jnp.uint32)
        keep = ~(jnp.uint32(0xFFFF) << dst_shift)

        def layer(l, _):
            for kv in range(n):
                x = news[kv][l, pl.ds(i // pack, 1), :]          # [1, kd]
                if lanes != kd:     # scratch rows are wider (see kv_write)
                    x = jnp.concatenate(
                        [x, jnp.zeros((1, lanes - kd), x.dtype)], axis=-1)
                if pack == 2:
                    x = (x >> src_shift) & 0xFFFF
                    old = tiles[kv][job, l, pl.ds(w, 1), :]
                    x = (old & keep) | (x << dst_shift)
                tiles[kv][job, l, pl.ds(w, 1), :] = x
            return 0
        jax.lax.fori_loop(0, L, layer, 0)

    def token(i, job):
        """In token order, so of equal slots the last one's row stays, as in
        the loop. A job's write starts when the next job opens: its rows are
        all in, and it overlaps the merges that follow."""
        lead = leads(i)

        @pl.when(lead & (i > 0))
        def _():
            start(job, True)

        @pl.when(lead)
        def _():
            wait(i, False)
        job = jnp.where(lead, i, job)
        place(i, job)
        return job

    last_job = jax.lax.fori_loop(0, n_here, token, jnp.int32(0))
    start(last_job, True)
    # Before the step ends: the next block may read a tile this one wrote.
    for_leaders(lambda i: wait(i, True))


def _block_tokens(T: int, L: int, kd: int, itemsize: int,
                  pools: int = 2) -> int:
    """Tokens per grid step: as many as the VMEM budget holds of one scratch
    tile and the double-buffered new rows per token and pool, in whole VMEM
    tiles of the new rows (16 rows of 16-bit, 8 of 32-bit)."""
    rows = 32 // itemsize
    per_token = L * kd * itemsize * pools * (_TILE_ROWS + 2)
    fit = max(rows, _VMEM_BUDGET // per_token // rows * rows)
    return min(fit, _MAX_BLOCK_T, pl.cdiv(T, rows) * rows)


def _as_words(pool: jax.Array, pack: int) -> jax.Array:
    """[L, P, ps, kd] -> uint32 [L, P, ps/pack, kd] with the chip's packing
    (row pack*j + h in bits 32/pack * h of word row j): what ``ref.bitcast``
    shows the kernel for free, as a copy for interpret mode."""
    if pack == 1:
        return jax.lax.bitcast_convert_type(pool, jnp.uint32)
    L, P, ps, kd = pool.shape
    rows = pool.reshape(L, P, ps // pack, pack, kd).swapaxes(-1, -2)
    return jax.lax.bitcast_convert_type(rows, jnp.uint32)


def _as_rows(words: jax.Array, dtype, pack: int) -> jax.Array:
    """Inverse of ``_as_words``."""
    rows = jax.lax.bitcast_convert_type(words, dtype)
    if pack == 1:
        return rows
    L, P, wr, kd = words.shape
    return rows.swapaxes(-1, -2).reshape(L, P, wr * pack, kd)


def kv_write(k_pool: jax.Array, v_pool: Optional[jax.Array],
             k_all: jax.Array, v_all: Optional[jax.Array],
             slot_mapping: jax.Array, *, interpret: bool = False
             ) -> tuple[jax.Array, Optional[jax.Array]]:
    """k_pool/v_pool: [L, P, ps, kd] (donate them: the result aliases them);
    k_all/v_all: [L, T, kd] as the layer scan hands them over; slot_mapping:
    [T] int32. Returns the two pools with row ``slot_mapping[t]`` of every
    layer holding ``k_all[:, t].astype(pool.dtype)`` and every other row as
    it was: bitwise what the XLA loop leaves (module docstring for equal
    slots). A latent-attention model has ONE pool (``v_pool`` and ``v_all``
    None; the second result is None): same jobs, one DMA each instead of
    two. ``interpret=True`` (CPU tests) hands the pools over as words:
    interpret mode cannot write through a bitcast ref."""
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    news = (k_all,) if v_pool is None else (k_all, v_all)
    n = len(pools)
    L, P, ps, kd = k_pool.shape
    T = k_all.shape[1]
    dtype = k_pool.dtype
    itemsize = dtype.itemsize
    if kd % 128 != 0 and not interpret:
        # As in paged_decode: Mosaic's own message is an opaque layout error.
        raise ValueError(
            f"paged pool lane dim {kd} (n_kv*head_dim) must be a multiple of "
            f"128 for the Pallas KV write kernel")
    if itemsize not in (2, 4) or pools[-1].dtype != dtype:
        raise ValueError(
            f"kv_write handles 16- and 32-bit pools of one dtype, not "
            f"{dtype}/{pools[-1].dtype}")
    pack = 4 // itemsize
    if ps % _TILE_ROWS:
        raise ValueError(
            f"page_size {ps} must be a multiple of {_TILE_ROWS}, the rows of "
            f"a pool tile in HBM")
    block_t = _block_tokens(T, L, kd, itemsize, n)

    kernel = functools.partial(_kv_write_kernel, num_tokens=T,
                               block_t=block_t, page_size=ps, pack=pack)
    new_spec = pl.BlockSpec((L, block_t, kd), lambda i, *_: (0, i, 0),
                            memory_space=pltpu.VMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    # Mosaic lays a 32-bit VMEM buffer whose rows are exactly 128 lanes out
    # one row a tile, and refuses a DMA between that and the pool's 4-row
    # tiles: a pool of one kv head a shard (kd == 128) gets 256-lane scratch
    # rows and uses the first 128.
    tile_shape = (block_t, L, _TILE_ROWS // pack, max(kd, 256))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(T, block_t),),
        in_specs=[new_spec] * n + [any_spec] * n,
        out_specs=[any_spec] * n,
        scratch_shapes=[pltpu.VMEM(tile_shape, jnp.uint32)] * n
        + [pltpu.SemaphoreType.DMA((n, block_t)),
           pltpu.SemaphoreType.DMA((n,))],
    )
    news = [a.astype(dtype) for a in news]
    if interpret:
        pools = [_as_words(p, pack) for p in pools]
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        grid_spec=grid_spec,
        # Operand numbering counts the scalar-prefetch argument.
        input_output_aliases={1 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kv_write",
    )(slot_mapping.astype(jnp.int32), *news, *pools)
    if interpret:
        out = [_as_rows(p, dtype, pack) for p in out]
    return (out[0], None) if n == 1 else tuple(out)
