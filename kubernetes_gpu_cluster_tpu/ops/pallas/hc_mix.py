"""The stream mixers of manifold-constrained hyper-connections as two kernels
(``ops/hyper_conn.py`` has the mathematics and the XLA forms).

A sublayer of xing4.0 reads a learned mix of four residual streams and
writes back through a doubly stochastic map of them. As XLA that is a
product, forty tiny dependent normalisations and three passes over the
streams a sublayer; here ``hc_pre`` reads a block of tokens' streams ONCE
(the product with Phi on the MXU from the stored values, the mean square,
the Sinkhorn rounds on values held in VMEM, the sublayer's input) and
``hc_post`` reads them once more and writes the new streams over the old
(aliased).

The coefficients of a token lie along the 128 lanes of one float32 row
(``ops.hyper_conn.COLS``): row i of the n x n map at lanes 64 + 8 i + j. A
sum over a row's j is then a butterfly over lane bits 0-2 and a sum over a
column's i one over bits 3-5, each stage two lane rotations, a select and
an add, whatever the block's token count; padding lanes hold zeros and the
lanes under 64 only ever meet each other. Float32 after the product.

A grid step takes ``BLOCK`` tokens (the whole axis where it is shorter; the
last block of a longer axis may be partial: its rows past the end are read
as they lie and never written).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..hyper_conn import COLS, POST_AT, RES_AT, ROW, HCSettings

BLOCK = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def _row_parts(rows: int):
    """A block's tokens in parts of at most 64: a part's n streams, the
    sublayer's result and an accumulator then fit the register file."""
    return [(r, min(r + 64, rows)) for r in range(0, rows, 64)]


def _lanes(col):
    """A coefficient column [rows, 1] over a whole 128-lane tile, once a
    part: the products in the loop over a stream's tiles then meet tiles of
    one shape and no lane broadcast stands inside it."""
    return jnp.broadcast_to(col, (col.shape[0], 128))


def _stages(n: int) -> int:
    return max(n - 1, 0).bit_length()


def _butterfly(x, lane, bits):
    """Every lane's sum over the lanes that differ from it in ``bits``."""
    for bit in bits:
        x = x + jnp.where((lane & bit) == 0, pltpu.roll(x, COLS - bit, 1),
                          pltpu.roll(x, bit, 1))
    return x


def _pre_kernel(x_ref, phi_ref, ab_ref, y_ref, coef_ref, *, hc: HCSettings,
                d: int):
    n, f32 = hc.n, jnp.float32
    rows = x_ref.shape[0]
    u = jnp.dot(x_ref[...], phi_ref[...], preferred_element_type=f32)
    sq = jnp.zeros((rows, 128), f32)
    for at in range(0, n * d, 128):
        xc = x_ref[:, at:at + 128].astype(f32)
        sq = sq + xc * xc
    ms = jnp.sum(sq, axis=-1, keepdims=True) * (1.0 / (n * d))
    v = u * jax.lax.rsqrt(ms + hc.rms_eps) * ab_ref[0:1, :] + ab_ref[1:2, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    sig = jax.nn.sigmoid(v)
    in_res = ((lane >= RES_AT) & ((lane & (ROW - 1)) < n)
              & (lane < RES_AT + ROW * n))
    m = jnp.where(in_res, jnp.exp(jnp.clip(v, *hc.clamp)), 0.0)
    row_bits = [1 << b for b in range(_stages(n))]
    col_bits = [ROW << b for b in range(_stages(n))]

    def sinkhorn(_, m):
        m = m / (_butterfly(m, lane, col_bits) + hc.eps)
        return m / (_butterfly(m, lane, row_bits) + hc.eps)

    m = jax.lax.fori_loop(0, hc.iters, sinkhorn, m)
    coef = (jnp.where(lane < n, sig, 0.0)
            + jnp.where((lane >= POST_AT) & (lane < POST_AT + n), 2.0 * sig,
                        0.0)
            + jnp.where(in_res, m, 0.0))
    coef_ref[...] = coef
    for r0, r1 in _row_parts(rows):
        pre = [_lanes(coef[r0:r1, j:j + 1]) for j in range(n)]
        for at in range(0, d, 128):
            y = pre[0] * x_ref[r0:r1, at:at + 128].astype(f32)
            for j in range(1, n):
                y = y + pre[j] * x_ref[r0:r1, j * d + at:j * d + at + 128
                                       ].astype(f32)
            y_ref[r0:r1, at:at + 128] = y.astype(y_ref.dtype)


def _post_kernel(x_ref, f_ref, coef_ref, out_ref, *, n: int, d: int):
    f32 = jnp.float32
    for r0, r1 in _row_parts(x_ref.shape[0]):
        coef = coef_ref[r0:r1, :]
        post = [_lanes(coef[:, POST_AT + i:POST_AT + i + 1])
                for i in range(n)]
        res = [[_lanes(coef[:, RES_AT + ROW * i + j:RES_AT + ROW * i + j + 1])
                for j in range(n)] for i in range(n)]
        for at in range(0, d, 128):
            f = f_ref[r0:r1, at:at + 128].astype(f32)
            xs = [x_ref[r0:r1, j * d + at:j * d + at + 128].astype(f32)
                  for j in range(n)]
            for i in range(n):
                acc = post[i] * f
                for j in range(n):
                    acc = acc + res[i][j] * xs[j]
                out_ref[r0:r1, i * d + at:i * d + at + 128] = acc.astype(
                    out_ref.dtype)


def _grid(T: int):
    rows = T if T <= BLOCK else BLOCK
    return rows, (pl.cdiv(T, rows),)


def _check(n: int, d: int, name: str) -> None:
    if d % 128 or not 1 <= n <= ROW:
        raise ValueError(f"{name}: {n} streams of {d} are not 1 to {ROW} "
                         "streams of whole 128-lane tiles")


@functools.partial(jax.jit, static_argnames=("hc", "interpret"))
def hc_pre(x: jax.Array, phi: jax.Array, alpha: jax.Array, bias: jax.Array,
           hc: HCSettings, *, interpret: bool = False):
    """Same contract as ``ops.hyper_conn.hc_pre_xla``: x [T, n d], phi
    [n d, COLS], alpha [3], bias [COLS] -> (y [T, d], coef [T, COLS]
    float32)."""
    T, n = x.shape[0], hc.n
    d = x.shape[1] // n
    _check(n, d, "hc_pre")
    f32 = jnp.float32
    lane = jnp.arange(COLS)
    alpha = alpha.astype(f32)
    ab = jnp.stack([jnp.where(lane < POST_AT, alpha[0],
                              jnp.where(lane < RES_AT, alpha[1], alpha[2])),
                    bias.astype(f32)])
    rows, grid = _grid(T)
    y, coef = pl.pallas_call(
        functools.partial(_pre_kernel, hc=hc, d=d),
        out_shape=[jax.ShapeDtypeStruct((T, d), x.dtype),
                   jax.ShapeDtypeStruct((T, COLS), f32)],
        grid=grid,
        in_specs=[pl.BlockSpec((rows, n * d), lambda t: (t, 0)),
                  pl.BlockSpec((n * d, COLS), lambda t: (0, 0)),
                  pl.BlockSpec((2, COLS), lambda t: (0, 0))],
        out_specs=[pl.BlockSpec((rows, d), lambda t: (t, 0)),
                   pl.BlockSpec((rows, COLS), lambda t: (t, 0))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * T * n * d * (COLS + 2), transcendentals=2 * T * COLS,
            bytes_accessed=(T * (n + 1) * d + n * d * COLS) * x.dtype.itemsize
            + T * COLS * 4),
        interpret=interpret,
        name="hc_pre",
    )(x, phi, ab)
    return y, coef


@functools.partial(jax.jit, static_argnames=("interpret",))
def hc_post(x: jax.Array, f: jax.Array, coef: jax.Array, *,
            interpret: bool = False) -> jax.Array:
    """Same contract as ``ops.hyper_conn.hc_post_xla``: x [T, n d], f
    [T, d], coef [T, COLS] -> the new streams [T, n d], written over x's
    buffer where the caller lets it go."""
    T, d = f.shape
    n = x.shape[1] // d
    _check(n, d, "hc_post")
    rows, grid = _grid(T)
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, d=d),
        out_shape=jax.ShapeDtypeStruct((T, n * d), x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, n * d), lambda t: (t, 0)),
                  pl.BlockSpec((rows, d), lambda t: (t, 0)),
                  pl.BlockSpec((rows, COLS), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((rows, n * d), lambda t: (t, 0)),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * T * n * (n + 1) * d, transcendentals=0,
            bytes_accessed=T * (2 * n + 1) * d * x.dtype.itemsize
            + T * COLS * 4),
        interpret=interpret,
        name="hc_post",
    )(x, f.astype(x.dtype), coef)
