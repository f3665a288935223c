"""Grouped matmul on the chip: ``lhs[rows of group g] @ rhs[g]`` for every
group, the rows sorted by group. The kernel is JAX's own megablox ``gmm``
(``jax.experimental.pallas.ops.tpu.megablox``), called with the tiling that
measured best for expert layers: 128 rows, and K and N as whole as VMEM
holds, so that a visit of one (row tile, group) pair loads the group's
weights once and its rows are at most 127 wasted.

Why not ``jax.lax.ragged_dot`` on the chip: XLA lowers it to a grouped
kernel of its own with 512-row tiles; at 12,672 routed rows over 64 experts
(~198 rows a group) it took 4.11 ms a matmul on the v5e where this takes
1.08 ms, bitwise the same result (PERF.md, PR 26). ``ragged_dot`` stays the
XLA twin: the CPU tests' path and what this is held to.

Empty groups cost nothing, so a caller may hand the whole stack of layers
as groups and only one layer's sizes non-zero (``models.llama
.experts_grouped``): the weights are read in place.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_gmm = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

TILE_ROWS = 128
_RHS_TILE_BYTES = 6 * 2**20     # one of two buffers; kimi's 2048 x 1408 fits


class _NamedPallas:
    """``megablox.gmm`` builds its ``pallas_call`` without a name, and the
    device trace would show it as ``%kernel.N``. The module's own handle on
    ``jax.experimental.pallas`` (and nobody else's) is replaced by this
    pass-through that names the call, so that the per-layer metric finds its
    events (``perfbench/readers/kernel_flops_share.py``)."""

    def __getattr__(self, attr):
        return getattr(pl, attr)

    @staticmethod
    def pallas_call(*args, **kwargs):
        kwargs.setdefault("name", "grouped_matmul")
        return pl.pallas_call(*args, **kwargs)


_gmm.pl = _NamedPallas()


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


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """lhs: [M, K], rows sorted by group; rhs: [G, K, N]; group_sizes: [G]
    int32 with sum <= M. Returns [M, N] float32; rows past the groups' end
    hold nothing meaningful. M is padded to whole row tiles here."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    pad = -m % TILE_ROWS
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, k), lhs.dtype)], axis=0)
    out = _gmm.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                   preferred_element_type=jnp.float32,
                   tiling=tiling(k, n, rhs.dtype.itemsize),
                   interpret=interpret)
    return out[:m] if pad else out
