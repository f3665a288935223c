"""The delta-rule state mixer's operations (KDA, kimi_linear's linear
attention; arXiv:2510.26692): XLA references of the chunked segment form
and the one-token state update.

Per head, with a per-CHANNEL log decay ``g_t`` (<= 0), a write strength
``beta_t`` in (0, 1), unit keys and scaled unit queries:

    D   = Diag(exp(g_t)) S_{t-1}
    S_t = D + beta_t k_t (v_t - D^T k_t)^T            S in R^{d_k x d_v}
    o_t = S_t^T q_t

Unlike a decay plus an outer product (``ops/ssm.py``) the update READS the
state before it writes it: what the key already recalls (``D^T k``) is taken
off the value first.

What a state layer keeps of a sequence is a slot (engine/kv_cache.py): the
state, float32, stored ``[heads * d_k, d_v]`` (a head's key dims ``h * d_k +
k`` on sublanes, its value dim on lanes), and the last ``taps - 1`` rows of
the conv's input ``[q | k | v]``. In that layout a head is one ``[d_k, d_v]``
tile, both sums of the update run over sublanes, a head's ``v``, ``beta`` and
``o`` are lane-dense rows, and the per-head form ``[heads, d_k, d_v]`` is a
reshape. (With the heads on lanes it was a transpose, and XLA moved that
transpose onto the slice's operand: the whole pool, copied every step that
resumes a chunk from its slot.)

The references here are what the CPU runs, what ``NO_KERNELS`` names and
what the two Pallas kernels are held to: ``kda_update``
(``ops/pallas/kda_update.py``) and, for the chunked form, ``kda_chunk``
(``ops/pallas/kda_chunk.py``: a chunk's operands stay in VMEM from the
running decay to o). ``ops.attention.Kernels.kda_update`` and ``.kda_chunk``
choose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ssm import l2_normalise, write_slots  # noqa: F401 (l2_normalise: its callers name it here)

_HI = jax.lax.Precision.HIGHEST
# Tokens of a chunk whose decay differences are formed pair by pair; between
# such sub-chunks they pass through the sub-chunk's first position.
SUB_CHUNK = 16


def heads_first(state: jax.Array, H: int) -> jax.Array:
    """A slot's state [..., H * d_k, d_v] as [..., H, d_k, d_v]."""
    return state.reshape(state.shape[:-2] + (H, -1, state.shape[-1]))


def slot_layout(state: jax.Array) -> jax.Array:
    """[..., H, d_k, d_v] back to the slot's [..., H * d_k, d_v]."""
    return state.reshape(state.shape[:-3] + (-1, state.shape[-1]))


# ---------------------------------------------------------------------------
# One token a row
# ---------------------------------------------------------------------------

def kda_update_xla(pool: jax.Array, layer, slots: jax.Array, g: jax.Array,
                   beta: jax.Array, q: jax.Array, k: jax.Array,
                   v: jax.Array):
    """One token a row: read the row's slot, decay, take off what the key
    recalls, write the rank-one update, emit o, write back.
    pool [Ls, slots, H * d_k, d_v] float32; layer: int32 scalar; slots [R];
    g [R, H, d_k] the log decay; beta [R, H]; q, k [R, H, d_k]; v [R, H,
    d_v]; all float32. Returns (pool, o [R, H * d_v] float32)."""
    R, H, dk = k.shape
    S = heads_first(pool[layer, slots], H)                     # [R,H,k,v]
    D = S * jnp.exp(g)[..., None]
    r = jnp.sum(D * k[..., None], axis=2)                      # [R, H, v]
    u = beta[..., None] * (v - r)
    S = D + k[..., None] * u[:, :, None]
    o = jnp.sum(S * q[..., None], axis=2)
    return (write_slots(pool, slot_layout(S), slots, layer),
            o.reshape(R, -1))


# ---------------------------------------------------------------------------
# The segment part, a chunk at a time
# ---------------------------------------------------------------------------

def _decay_dots(left: jax.Array, k: jax.Array, g: jax.Array, G: jax.Array,
                precision=_HI) -> jax.Array:
    """``M[t, s] = sum_c left[t, c] k[s, c] exp(G[t, c] - G[s, c])`` for
    s <= t, 0 elsewhere: a decay-weighted Gram matrix of one chunk.
    left, k, g, G: [..., Q, d] float32, G the inclusive cumulative sum of
    the log decays g (<= 0). ``exp(-G[s])`` alone overflows under a strong
    gate, so no exponent here is ever positive: within a sub-chunk of
    ``SUB_CHUNK`` tokens the differences are formed pair by pair; a later
    sub-chunk meets an earlier one through the position before its own
    first token, ``exp(G[t] - G_ref) * exp(G_ref - G[s])``, both <= 1."""
    Q, d = k.shape[-2:]
    sub = min(SUB_CHUNK, Q)
    nb = Q // sub
    lead = k.shape[:-2]
    blocks = lambda a: a.reshape(lead + (nb, sub, d))
    Lb, Kb, Gb = blocks(left), blocks(k), blocks(G)
    tri = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    E = jnp.exp(jnp.where(tri[..., None],
                          Gb[..., :, None, :] - Gb[..., None, :, :],
                          -jnp.inf))                    # [.., nb, t, s, d]
    diag = jnp.sum(Lb[..., :, None, :] * Kb[..., None, :, :] * E, axis=-1)
    if nb == 1:
        return diag.reshape(lead + (Q, Q))
    # G before each sub-chunk's first token (0 before the chunk's).
    ref = (Gb - blocks(g))[..., 0, :]                   # [.., nb, d]
    Lt = Lb * jnp.exp(Gb - ref[..., None, :])
    before = (jnp.arange(Q)[None, :]
              < (jnp.arange(nb) * sub)[:, None])        # [nb, Q]
    Kt = k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], ref[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))                                      # [.., nb, Q, d]
    off = jnp.einsum("...btd,...bsd->...bts", Lt, Kt, precision=precision)
    off = off.reshape(lead + (nb, sub, nb, sub))
    own = jnp.eye(nb, dtype=bool)[:, None, :, None]
    return jnp.where(own, diag[..., :, :, None, :], off
                     ).reshape(lead + (Q, Q))


def _solve_unit_lower(A: jax.Array, rhs: jax.Array) -> jax.Array:
    """``X`` of ``(I + A) X = rhs`` for strictly lower-triangular A
    [..., Q, Q]; rhs [..., Q, W]. The diagonal sub-chunk blocks are inverted
    by ``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)...`` (N is nilpotent; at 16
    rows the powers stay small whatever the keys, where at 64 a prompt of
    one repeated token loses every digit), the blocks below them by forward
    substitution, a sub-chunk at a time. Full float32: U feeds the state."""
    Q = A.shape[-1]
    sub = min(SUB_CHUNK, Q)
    nb = Q // sub
    lead = A.shape[:-2]
    Ab = A.reshape(lead + (nb, sub, nb, sub))
    N = jnp.stack([Ab[..., i, :, i, :] for i in range(nb)], axis=-3)
    mm = lambda a, b: jnp.einsum("...ij,...jk->...ik", a, b, precision=_HI)
    inv, power, n = jnp.eye(sub, dtype=A.dtype) - N, N, 1
    while 2 * n < sub:
        power, n = mm(power, power), 2 * n
        inv = inv + mm(inv, power)
    R = rhs.reshape(lead + (nb, sub, -1))
    xs = []
    for i in range(nb):
        r = R[..., i, :, :]
        for j in range(i):
            r = r - mm(Ab[..., i, :, j, :], xs[j])
        xs.append(mm(inv[..., i, :, :], r))
    return jnp.stack(xs, axis=-3).reshape(rhs.shape)


def segment_finals(k_s: jax.Array, G_s: jax.Array, sc_s: jax.Array,
                    S_in_s: jax.Array, U_s: jax.Array, seg_in_s: jax.Array,
                    o_s: jax.Array) -> jax.Array:
    """Each segment's state at ITS last token: the carry into that token's
    chunk, decayed, plus what the segment's tokens up to there add. Every
    operand is of the segment's last chunk: k_s, G_s [S, H, Q, d_k] (G the
    running sum of the chunk's g), sc_s [S, Q] its seg_ids, S_in_s [S, H,
    d_k, d_v] the state it was handed, of segment seg_in_s [S], U_s [S, H,
    Q, d_v], o_s [S] the token's place in the chunk. Returns [S, H, d_k,
    d_v] float32."""
    t_idx = jnp.arange(k_s.shape[2])
    G_o = jnp.take_along_axis(G_s, o_s[:, None, None, None], axis=2)
    seg_of = jnp.take_along_axis(sc_s, o_s[:, None], axis=1)       # [S, 1]
    upto = (t_idx[None, :] <= o_s[:, None]) & (sc_s == seg_of)
    k_fin = k_s * jnp.exp(jnp.where(
        upto[:, None, :, None], G_o - G_s, -jnp.inf))
    return (S_in_s * (jnp.exp(G_o[:, :, 0, :])
                      * (seg_in_s[:, None] == seg_of)[..., None])[..., None]
            + jnp.einsum("shqd,shqv->shdv", k_fin, U_s, precision=_HI))


def kda_chunk_scan_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array, seg_ids: jax.Array,
                       seg_ends: jax.Array, init_state: jax.Array, init_seg,
                       chunk: int):
    """The recurrence over the segment part, ``chunk`` tokens at a time.
    With ``u_t = beta_t (v_t - D_t^T k_t)`` the state is ``S_t = Diag(e^g_t)
    S_{t-1} + k_t u_t^T``; inside a chunk, ``G`` the running sum of g,

        (I + A) U = Beta (V - (K * e^G) S_0),   A[t, s] = beta_t <k_t * e^(G_t - G_s), k_s>  (s < t)
        o_t = (q_t * e^G_t)^T S_0 + sum_{s <= t} <q_t * e^(G_t - G_s), k_s> u_s

    with tokens meeting only tokens of THEIR segment and ``S_0`` reaching
    only the segment it belongs to; between chunks only the state is
    carried, float32. A segment boundary may fall anywhere in a chunk.
    ``(I + A)^-1`` is applied to [Beta V | Beta K e^G] for every chunk at
    once, so the walk over the chunks is two small products a chunk. Every
    product runs at full float32 (six MXU passes): all of them together are
    ~10 GFLOP a layer at 2 k tokens, 0.3 ms of MXU time where a layer takes
    6 ms in this form (the rest is traffic XLA makes for itself: the
    pairwise decays in HBM, the transposes, the scan's carry), and at the
    default precision o alone read 4e-3 of its largest value from the
    recurrence's (v5e, PR 35).

    q, k [T, H, d_k] (unit keys, scaled unit queries), v [T, H, d_v], g
    [T, H, d_k] (log decay, <= 0), beta [T, H]: float32; seg_ids [T] (-1:
    padding, only behind every segment); seg_ends [S] each segment's last
    token (-1: absent); init_state [H * d_k, d_v] float32, the state before
    token 0, of segment ``init_seg`` (a chunk with history: the slot's,
    segment 0; else give a segment no token has).
    Returns (o [T, H, d_v] float32, each segment's final state [S,
    H * d_k, d_v] float32; an absent segment's is meaningless)."""
    T, H, dk = k.shape
    Q = chunk
    pad = -T % Q
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta))
        seg_ids = jnp.pad(seg_ids, (0, pad), constant_values=-1)
    nc = (T + pad) // Q
    f32 = jnp.float32
    # [nc, H, Q, d]
    ch = lambda a: a.astype(f32).reshape((nc, Q) + a.shape[1:]).swapaxes(1, 2)
    qc, kc, vc, gc = ch(q), ch(k), ch(v), ch(g)
    bc = beta.astype(f32).reshape(nc, Q, H).swapaxes(1, 2)     # [nc, H, Q]
    sc = seg_ids.reshape(nc, Q)
    G = jnp.cumsum(gc, axis=2)
    eG = jnp.exp(G)
    seg_last = sc[:, -1]
    seg_in = jnp.concatenate(
        [jnp.asarray(init_seg, sc.dtype)[None], seg_last[:-1]])
    same = sc[:, :, None] == sc[:, None, :]                    # [nc, t, s]
    t_idx = jnp.arange(Q)
    reach = (sc == seg_in[:, None]).astype(f32)[:, None, :, None]

    with jax.named_scope("kgct.kda.chunk.intra"):
        A = _decay_dots(kc, kc, gc, G) * bc[..., None] * (
            same & (t_idx[:, None] > t_idx[None, :]))[:, None]
        P = _decay_dots(qc, kc, gc, G) * (
            same & (t_idx[:, None] >= t_idx[None, :]))[:, None]
        X = _solve_unit_lower(A, bc[..., None] * jnp.concatenate(
            [vc, kc * eG], axis=-1))
        U0, W = X[..., :vc.shape[-1]], X[..., vc.shape[-1]:] * reach

    # The walk: what reaches each chunk, and what it leaves behind (nothing
    # of the state it was handed, where the segment changed).
    with jax.named_scope("kgct.kda.chunk.carry"):
        k_end = (kc * jnp.exp(G[:, :, -1:, :] - G)
                 * (sc == seg_last[:, None])[:, None, :, None])
        keep = eG[:, :, -1, :] * (seg_in == seg_last)[:, None, None]

        def step(S, xs):
            W_c, U0_c, k_end_c, keep_c = xs
            u = U0_c - jnp.einsum("hqd,hdv->hqv", W_c, S, precision=_HI)
            S_out = S * keep_c[..., None] + jnp.einsum(
                "hqd,hqv->hdv", k_end_c, u, precision=_HI)
            return S_out, (S, u)

        _, (S_in, U) = jax.lax.scan(
            step, heads_first(init_state.astype(f32), H),
            (W, U0, k_end, keep))
        o = (jnp.einsum("chqd,chdv->chqv", qc * eG * reach, S_in,
                        precision=_HI)
             + jnp.einsum("chts,chsv->chtv", P, U, precision=_HI))

    with jax.named_scope("kgct.kda.chunk.final"):
        e = jnp.maximum(seg_ends, 0)
        c_s = e // Q
        final = segment_finals(kc[c_s], G[c_s], sc[c_s], S_in[c_s], U[c_s],
                               seg_in[c_s], e % Q)
    o = o.swapaxes(1, 2).reshape(nc * Q, H, -1)[:T]
    return o, slot_layout(final)


def kda_recurrence(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, init_state: jax.Array):
    """ONE sequence, token by token: what the chunked form is tested
    against (tests/test_kda_hybrid.py). Shapes as ``kda_chunk_scan_xla``;
    returns (o [T, H, d_v] float32, the final state [H * d_k, d_v])."""
    H = k.shape[1]
    f32 = jnp.float32

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = (a.astype(f32) for a in xs)
        D = S * jnp.exp(g_t)[..., None]                        # [H, k, v]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", D, k_t,
                                             precision=_HI))
        S = D + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    S, o = jax.lax.scan(step, heads_first(init_state.astype(f32), H),
                        (q, k, v, g, beta))
    return o, slot_layout(S)
