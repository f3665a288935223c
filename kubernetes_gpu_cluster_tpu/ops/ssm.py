"""The state mixer's operations (Mamba-2, as granitemoehybrid uses it): XLA
references of the causal depthwise conv, the chunked segment scan and the
one-token state update.

What a state layer keeps of a sequence is a fixed SLOT, not pages
(engine/kv_cache.py): the recurrent state, float32, stored ``[N, d_inner]``
(the state dim on sublanes, the mixer's channels ``head * P + p`` on lanes:
``S[n, h*P + p]`` is the published ``ssm_state[h, p, n]``), and the last
``d_conv - 1`` rows of the conv's input. In this layout the update of one
token is plain lane-dense arithmetic,

    S = S * decay[None, :] + B[:, None] * (dt * x)[None, :]
    y = sum_n S[n, :] * C[n]

with ``decay`` and ``dt`` repeated over each head's P lanes.

The references here are what the CPU runs, what ``NO_KERNELS`` names and
what the Pallas kernels are held to: ``ssm_update``
(``ops/pallas/ssm_update.py``) and, for the chunked scan, ``ssm_chunk``
(``ops/pallas/ssm_chunk.py``: the channels stay on lanes and a lane block's
state in VMEM from chunk to chunk; as einsums over ``[T, H, P]`` views the
scan's time on the chip is re-tilings and copies, not its products).
``ops.attention.Kernels.ssm_update`` and ``.ssm_chunk`` choose.
"""

from __future__ import annotations

import itertools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


def write_slots(pool: jax.Array, rows: jax.Array, slots: jax.Array,
                layer=None) -> jax.Array:
    """``pool[layer, slots[i]] = rows[i]`` (``layer`` None: ``pool[:,
    slots[i]] = rows[:, i]``), one dynamic_update_slice after the other:
    the form XLA performs in place on a donated or carried pool (a batched
    scatter transposes the pool on the TPU: see
    ``ops.attention.write_kv_pages_all_xla``). Later duplicates win; padding
    entries all name the scrap slot."""
    def body(i, pool):
        if layer is None:
            row = jax.lax.dynamic_slice_in_dim(rows, i, 1, axis=1)
            start = (0, slots[i]) + (0,) * (pool.ndim - 2)
        else:
            row = jax.lax.dynamic_slice_in_dim(rows, i, 1, axis=0)[None]
            start = (layer, slots[i]) + (0,) * (pool.ndim - 2)
        return jax.lax.dynamic_update_slice(pool, row.astype(pool.dtype),
                                            start)
    return jax.lax.fori_loop(0, slots.shape[0], body, pool)


# ---------------------------------------------------------------------------
# Causal depthwise conv over [x | B | C]
# ---------------------------------------------------------------------------

class ConvSplit(NamedTuple):
    """What leaves the conv stage of a segment part, for the recurrence:
    the activated ``[T, C]`` cut into pieces of ``widths`` channels, in
    order. ``head_dim`` None: each piece ``[T, width]`` rounded to ``dtype``
    (None: the input's). Else each piece float32 heads ``[T, width //
    head_dim, head_dim]``, piece i brought to unit length over every head
    and scaled by ``unit[i]`` (None: left as it is)."""
    widths: tuple
    dtype: Any = None
    head_dim: Optional[int] = None
    unit: tuple = ()


L2_EPS = 1e-6


def l2_normalise(x: jax.Array, eps: float = L2_EPS) -> jax.Array:
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def segment_conv_rows(xbc: jax.Array, seg_ids: jax.Array, seg_ends: jax.Array,
                      init_rows: jax.Array) -> jax.Array:
    """Each segment's new conv rows [S, K-1, C] in xbc's dtype: its last
    K-1 inputs, zeros or ``init_rows`` (the segment that starts at token 0)
    where it is shorter; seg_ends [S] the last token of each segment (-1:
    absent). A gather of S x (K-1) rows of xbc as it lies (its rows behind
    the segment part are never named)."""
    K1 = init_rows.shape[0]
    e = jnp.maximum(seg_ends, 0)
    at = e[:, None] - (K1 - 1) + jnp.arange(K1)[None, :]      # row of xbc
    seg_at = jnp.where(at >= 0, seg_ids[jnp.maximum(at, 0)], seg_ids[0])
    rows = jnp.where((at >= 0)[..., None], xbc[jnp.maximum(at, 0)],
                     init_rows.astype(xbc.dtype)[jnp.clip(at + K1, 0, K1 - 1)])
    return jnp.where((seg_at == seg_ids[e][:, None])[..., None], rows,
                     0).astype(xbc.dtype)


def conv_segments(xbc: jax.Array, seg_ids: jax.Array, init_rows: jax.Array,
                  w: jax.Array, b: jax.Array) -> jax.Array:
    """The conv over the segment part: token t reads its own row and the
    ``K - 1`` before it OF ITS SEGMENT; before a segment's first token lie
    zeros, or ``init_rows`` for the segment that starts at token 0 (a chunk
    with history: the slot's rows; else zeros).

    xbc [T, C]; seg_ids [T]; init_rows [K-1, C]; w [K, C] (tap K-1 meets
    the token itself, as torch's conv1d weight [C, 1, K] does); b [C] or
    None (a conv without bias). Returns conv + bias [T, C] float32. (What a
    slot keeps of each segment is ``segment_conv_rows``.)"""
    T = xbc.shape[0]
    K = w.shape[0]
    ext = jnp.concatenate([init_rows.astype(xbc.dtype), xbc], axis=0)
    seg_ext = jnp.concatenate(
        [jnp.broadcast_to(seg_ids[:1], (K - 1,)), seg_ids])
    out = jnp.broadcast_to(
        jnp.float32(0) if b is None else b.astype(jnp.float32), xbc.shape)
    wf = w.astype(jnp.float32)
    for k in range(K):
        same = seg_ext[k:k + T] == seg_ids
        out = out + wf[k] * jnp.where(
            same[:, None], ext[k:k + T].astype(jnp.float32), 0.0)
    return out


def split_activated(xs: jax.Array, split: ConvSplit):
    """The activated conv output [n, C], float32 or already rounded, as the
    pieces ``split`` names."""
    pieces = jnp.split(xs, list(itertools.accumulate(split.widths))[:-1],
                       axis=1)
    if split.head_dim is None:
        return tuple(pieces)
    heads = [p.reshape(p.shape[0], -1, split.head_dim) for p in pieces]
    return tuple(p.astype(jnp.float32) if unit is None
                 else l2_normalise(p) * unit
                 for p, unit in zip(heads, split.unit))


def conv_operands_xla(xbc: jax.Array, seg_ids: jax.Array,
                      init_rows: jax.Array, w: jax.Array, b,
                      split: ConvSplit):
    """The conv stage of a segment part as XLA passes: ``conv_segments``
    over the first T = len(seg_ids) rows of xbc [>= T, C], SiLU, the cast,
    the pieces (a tuple). What the CPU runs, what ``NO_KERNELS`` names and
    what the kernel (``ops/pallas/conv_segments.py``) is held to."""
    out = conv_segments(xbc[:seg_ids.shape[0]], seg_ids, init_rows, w, b)
    return split_activated(
        jax.nn.silu(out).astype(split.dtype or xbc.dtype), split)


def conv_rows(xbc: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array):
    """The conv of one new token a row against the row's slot.
    xbc [R, C]; state [R, K-1, C] (oldest first); b [C] or None. Returns
    (conv + bias [R, C] float32, the new rows [R, K-1, C])."""
    full = jnp.concatenate([state.astype(xbc.dtype), xbc[:, None]], axis=1)
    out = jnp.einsum("rkc,kc->rc", full.astype(jnp.float32),
                     w.astype(jnp.float32))
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out, full[:, 1:]


# ---------------------------------------------------------------------------
# The recurrence  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  y_t = S_t C_t
# ---------------------------------------------------------------------------

def ssm_update_xla(pool: jax.Array, layer, slots: jax.Array,
                   decay: jax.Array, dtx: jax.Array, B: jax.Array,
                   C: jax.Array):
    """One token a row: read the row's slot, update, emit y, write back.
    pool [Ls, slots, N, di] float32; layer: int32 scalar; slots [R];
    decay = exp(dt A) and dtx = dt * x, both [R, di] float32 (per head,
    repeated over its P lanes); B, C [R, N] float32.
    Returns (pool, y [R, di] float32)."""
    S = pool[layer, slots]                                     # [R, N, di]
    S = S * decay[:, None, :] + B[:, :, None] * dtx[:, None, :]
    y = jnp.einsum("rnc,rn->rc", S, C,
                   precision=jax.lax.Precision.HIGHEST)
    return write_slots(pool, S, slots, layer), y


def segment_finals(x_s: jax.Array, B_s: jax.Array, cs_s: jax.Array,
                   dt_s: jax.Array, sc_s: jax.Array, S_in_s: jax.Array,
                   seg_in_s: jax.Array, o_s: jax.Array) -> jax.Array:
    """Each segment's state at ITS last token: the carry into that token's
    chunk, decayed, plus what the segment's tokens up to there add. Every
    operand is of the segment's last chunk: x_s [S, Q, H, P], B_s [S, Q, N],
    cs_s [S, H, Q] the running sum of the chunk's log decay, dt_s [S, H, Q],
    sc_s [S, Q] its seg_ids, S_in_s [S, N, H*P] the state it was handed, of
    segment seg_in_s [S], o_s [S] the token's place in the chunk. Shared by
    the XLA form and the kernel (``ops/pallas/ssm_chunk.py``), whose chunks
    are shorter. Returns [S, N, H*P] float32."""
    S, Q, H, P = x_s.shape
    f32 = jnp.float32
    cs_o = jnp.take_along_axis(cs_s, o_s[:, None, None], axis=-1)
    seg_of = jnp.take_along_axis(sc_s, o_s[:, None], axis=-1)      # [S, 1]
    upto = (jnp.arange(Q)[None, :] <= o_s[:, None]) & (sc_s == seg_of)
    w = jnp.exp(jnp.where(upto[:, None, :], cs_o - cs_s, -jnp.inf)
                ) * dt_s                                           # [S, H, Q]
    xw = (x_s.astype(f32) * w.transpose(0, 2, 1)[..., None]
          ).reshape(S, Q, H * P)
    kept = jnp.repeat(jnp.exp(cs_o[..., 0])
                      * (seg_in_s[:, None] == seg_of), P, axis=-1)
    return (S_in_s * kept[:, None, :]
            + jnp.einsum("sjn,sjd->snd", B_s.astype(f32), xw,
                         precision=jax.lax.Precision.HIGHEST))


def ssm_chunk_scan_xla(x: jax.Array, dt: jax.Array, dA: jax.Array,
                       B: jax.Array, C: jax.Array, seg_ids: jax.Array,
                       seg_ends: jax.Array, init_state: jax.Array,
                       init_seg, chunk: int):
    """The recurrence over the segment part, a chunk of ``chunk`` tokens at
    a time (the SSD form): inside a chunk token i meets token j <= i of ITS
    segment through the decay-masked product ``(C_i . B_j) exp(cs_i - cs_j)
    dt_j``, one [Q, Q] matmul a head; between chunks only the state is
    carried, float32, and it reaches only the tokens of the segment it
    belongs to. A segment boundary may fall anywhere in a chunk.

    x [T, H, P]; dt [T, H] float32 (after softplus); dA [T, H] float32 =
    dt * A (the log decay, <= 0); B, C [T, N]; seg_ids [T] (-1: padding,
    only behind every segment); seg_ends [S] each segment's last token (-1:
    absent); init_state [N, H*P] float32, the state before token 0, of
    segment ``init_seg`` (a chunk with history: the slot's, segment 0; else
    give a segment no token has).
    Returns (y [T, H, P] float32 WITHOUT the D skip, each segment's final
    state [S, N, H*P] float32; an absent segment's is meaningless)."""
    T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, dA, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                           for a in (x, dt, dA, B, C))
        seg_ids = jnp.pad(seg_ids, (0, pad), constant_values=-1)
    nc = (T + pad) // Q
    f32 = jnp.float32
    xc = x.reshape(nc, Q, H, P)
    Bc, Cc = B.reshape(nc, Q, N), C.reshape(nc, Q, N)
    sc = seg_ids.reshape(nc, Q)
    dtc = dt.reshape(nc, Q, H).transpose(0, 2, 1)              # [nc, H, Q]
    cs = jnp.cumsum(dA.reshape(nc, Q, H).transpose(0, 2, 1), axis=-1)

    def per_lane(a):        # [..., H] -> [..., H*P], each head's over its P
        return jnp.repeat(a, P, axis=-1)

    # Inside the chunks, all at once.
    with jax.named_scope("kgct.ssm.scan.intra"):
        mask = ((sc[:, :, None] == sc[:, None, :])
                & (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]))
        L = jnp.exp(jnp.where(mask[:, None],
                              cs[:, :, :, None] - cs[:, :, None, :],
                              -jnp.inf))                       # [nc,H,i,j]
        G = jnp.einsum("cin,cjn->cij", Cc, Bc, preferred_element_type=f32)
        M = G[:, None] * L * dtc[:, :, None, :]
        y = jnp.einsum("chij,cjhp->cihp", M.astype(x.dtype), xc,
                       preferred_element_type=f32)

    # What each chunk adds to the state at its end, and what it keeps of
    # the state it was handed (nothing, where the segment changed).
    with jax.named_scope("kgct.ssm.scan.carry"):
        seg_last = sc[:, -1]
        seg_in = jnp.concatenate(
            [jnp.asarray(init_seg, sc.dtype)[None], seg_last[:-1]])
        w_end = (jnp.exp(cs[:, :, -1:] - cs) * dtc
                 * (sc == seg_last[:, None])[:, None, :])      # [nc, H, Q]
        xw = (xc.astype(f32) * w_end.transpose(0, 2, 1)[..., None]
              ).reshape(nc, Q, H * P)
        inc = jnp.einsum("cjn,cjd->cnd", Bc.astype(f32), xw,
                         precision=jax.lax.Precision.HIGHEST)
        keep = per_lane(jnp.exp(cs[:, :, -1])
                        * (seg_in == seg_last)[:, None])       # [nc, di]

        def step(S, xs):
            keep_c, inc_c = xs
            return S * keep_c[None, :] + inc_c, S

        _, S_in = jax.lax.scan(step, init_state.astype(f32), (keep, inc))
        reach = (jnp.exp(cs).transpose(0, 2, 1)
                 * (sc == seg_in[:, None])[..., None])         # [nc, Q, H]
        # (The state's own sums run at full float32, six MXU passes on
        # the chip; what only feeds y, rounded to the model's dtype anyway,
        # takes the default.)
        y = y + (jnp.einsum("cin,cnd->cid", Cc.astype(f32), S_in)
                 .reshape(nc, Q, H, P) * reach[..., None])

    with jax.named_scope("kgct.ssm.scan.final"):
        e = jnp.maximum(seg_ends, 0)
        c_s = e // Q
        final = segment_finals(xc[c_s], Bc[c_s], cs[c_s], dtc[c_s], sc[c_s],
                               S_in[c_s], seg_in[c_s], e % Q)
    return y.reshape(nc * Q, H, P)[:T], final


def ssm_recurrence(x: jax.Array, dt: jax.Array, dA: jax.Array, B: jax.Array,
                   C: jax.Array, init_state: jax.Array):
    """ONE sequence, token by token: what the chunked scan is tested
    against (tests/test_ssm_hybrid.py). Shapes as ``ssm_chunk_scan_xla``;
    returns (y [T, H, P] float32, the final state [N, H*P])."""
    T, H, P = x.shape
    f32 = jnp.float32

    def step(S, xs):
        x_t, dt_t, dA_t, B_t, C_t = xs
        decay = jnp.repeat(jnp.exp(dA_t), P)
        dtx = jnp.repeat(dt_t, P) * x_t.reshape(-1).astype(f32)
        S = S * decay[None, :] + B_t.astype(f32)[:, None] * dtx[None, :]
        return S, jnp.sum(S * C_t.astype(f32)[:, None], axis=0)

    S, y = jax.lax.scan(step, init_state.astype(f32), (x, dt, dA, B, C))
    return y.reshape(T, H, P), S
