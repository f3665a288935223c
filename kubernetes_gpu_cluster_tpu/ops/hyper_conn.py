"""Manifold-constrained hyper-connections: the residual as ``n`` parallel
streams (DeepSeek-AI, "mHC", arXiv:2512.24880, over Zhu et al.,
"Hyper-Connections", arXiv:2409.19606; xing4_0's ``hc_*`` keys).

Around a sublayer F (an attention or an MLP, with the layer's own norm in
front of it), per token, ``X`` [n, d] the streams:

    u      = (x~ / rms(x~)) Phi                 x~ = vec(X), Phi [n d, 2n + n^2]
    H_pre  = sigmoid(a_pre  u[0:n]   + b_pre)                          [n]
    H_post = 2 sigmoid(a_post u[n:2n] + b_post)                        [n]
    M      = exp(clip(a_res mat(u[2n:]) + b_res, lo, hi))              [n, n]
    ``iters`` times:  M /= colsum(M) + eps;  M /= rowsum(M) + eps
    y  = sum_j H_pre[j] X[j]                    what F reads          (``hc_pre``)
    X'[i] = sum_j M[i, j] X[j] + H_post[i] F(y)                        (``hc_post``)

The stored ``Phi`` has the norm's gain folded into its rows (the loader's
fold, in float32, rounded once; the random draw's gain is 1) and lies in the
128 lanes of ``COLS``: ``H_pre``'s columns at 0.., ``H_post``'s at 8..,
row i of ``M`` at 64 + 8 i.., zeros between. The coefficients of a token
travel from ``hc_pre`` to ``hc_post`` as one float32 row in the same lanes
(``unpack``). ``x~ Phi`` takes the stored values as operands and sums in
float32; everything after it is float32; the streams keep the model's dtype.

The streams travel FLAT, ``[T, n d]`` (stream j in columns j d to (j + 1) d):
on the chip a ``[T, n, d]`` array is tiled over its last two dimensions, so
each reshape between it and the rows the mixers read is a copy of all the
streams (0.2 ms at 2112 tokens of 4 x 3584, once a sublayer, measured:
PERF.md, PR 42).

These are the XLA forms: what the CPU runs and ``NO_KERNELS`` names. The
chip's are ``ops/pallas/hc_mix.py``, chosen by ``ops.attention.Kernels``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

COLS = 128          # lanes of a stored Phi and of a coefficient row
POST_AT = 8         # H_post's first lane
RES_AT = 64         # M's first lane; row i at RES_AT + ROW * i
ROW = 8             # lanes between the rows of M: at most 8 streams


class HCSettings(NamedTuple):
    """The static settings of a model's stream mixers."""
    n: int
    iters: int
    eps: float
    clamp: tuple
    rms_eps: float


def settings(cfg) -> HCSettings:
    return HCSettings(cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                      tuple(cfg.hc_res_clamp), cfg.rms_norm_eps)


def res_lanes(n: int) -> jax.Array:
    """[n, n] int32: the lane of M[i, j]."""
    return (RES_AT + ROW * jnp.arange(n)[:, None] + jnp.arange(n)[None, :])


def pack(pre: jax.Array, post: jax.Array, res: jax.Array) -> jax.Array:
    """[..., n], [..., n], [..., n, n] -> [..., COLS] in the stored lanes."""
    n = pre.shape[-1]
    lead = pre.shape[:-1]
    z = lambda w: jnp.zeros(lead + (w,), pre.dtype)
    rows = [jnp.concatenate([res[..., i, :], z(ROW - n)], -1)
            for i in range(n)]
    return jnp.concatenate(
        [pre, z(POST_AT - n), post, z(RES_AT - POST_AT - n), *rows,
         z(COLS - RES_AT - ROW * n)], -1)


def unpack(coef: jax.Array, n: int):
    """[..., COLS] -> (H_pre [..., n], H_post [..., n], H_res [..., n, n])."""
    return (coef[..., :n], coef[..., POST_AT:POST_AT + n],
            coef[..., res_lanes(n)])


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """[..., n, n] positive -> doubly stochastic: ``iters`` rounds of
    columns, then rows."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, one, m)


def coefficients(u: jax.Array, alpha: jax.Array, bias: jax.Array,
                 hc: HCSettings) -> jax.Array:
    """The normalised product ``u`` [T, COLS] float32 -> the coefficient
    rows [T, COLS] float32."""
    u_pre, u_post, u_res = unpack(u, hc.n)
    b_pre, b_post, b_res = unpack(bias, hc.n)
    pre = jax.nn.sigmoid(alpha[0] * u_pre + b_pre)
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u_post + b_post)
    m = jnp.exp(jnp.clip(alpha[2] * u_res + b_res, *hc.clamp))
    return pack(pre, post, sinkhorn(m, hc.iters, hc.eps))


def hc_pre_xla(x: jax.Array, phi: jax.Array, alpha: jax.Array,
               bias: jax.Array, hc: HCSettings):
    """x [T, n d] the streams; phi [n d, COLS]; alpha [3] float32 (pre,
    post, res); bias [COLS] float32. Returns (y [T, d] in x's dtype, coef
    [T, COLS] float32)."""
    T, n = x.shape[0], hc.n
    f32 = jnp.float32
    u = jnp.dot(x, phi, preferred_element_type=f32)
    xf = x.astype(f32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    coef = coefficients(u * jax.lax.rsqrt(ms + hc.rms_eps), alpha, bias, hc)
    y = jnp.sum(coef[:, :n, None] * xf.reshape(T, n, -1), axis=1)
    return y.astype(x.dtype), coef


def hc_post_xla(x: jax.Array, f: jax.Array, coef: jax.Array) -> jax.Array:
    """x [T, n d]; f [T, d] the sublayer's result; coef [T, COLS] from
    ``hc_pre``. Returns the new streams [T, n d] in x's dtype."""
    f32 = jnp.float32
    T, d = f.shape
    n = x.shape[1] // d
    _, post, res = unpack(coef, n)
    # Products and sums on the VPU: a float32 einsum on the chip would round
    # the coefficients to bf16 on its way through the MXU.
    xs = x.astype(f32).reshape(T, 1, n, d)
    out = (jnp.sum(res[:, :, :, None] * xs, axis=2)
           + post[:, :, None] * f.astype(f32)[:, None, :])
    return out.astype(x.dtype).reshape(T, n * d)
