"""The chunked delta-rule form of a KDA layer's segment part, a chunk's
operands resident in VMEM from the cumulative decay to o.

``ops.kda.kda_chunk_scan_xla`` is the same arithmetic as XLA einsums: it
writes the pairwise decays of every sub-chunk to HBM twice (553 MB a layer
at 2 k tokens), transposes four operands to ``[chunks, heads, Q, d]`` and o
back, and walks the chunks in a scan whose carry passes through HBM. Here a
grid step is (a block of eight heads, one chunk), the chunks walked in order
with the heads' states in VMEM scratch between them; inside it a loop takes
the heads two at a time (a group), its body unrolled:

1. q, k, v, g arrive as ``(Q, heads of the block x d)`` blocks of ``[T, H
   x d]``, the heads on lanes: how the conv stage (``conv_segments``) and
   the gate's projection leave them, so a head's ``[Q, d]`` is a slice of
   whole lane tiles (the contract's ``[T, H, d]`` is undone by a reshape
   that meets the producer's own and moves no byte; where XLA has to MAKE
   ``[T, H, d]`` it is another TILED layout, 0.3-0.9 ms of copies an
   operand and layer: PERF.md section 6, PR 47). o leaves as ``(Q, heads,
   d)`` blocks of ``[T, H, d]``, a token's eight heads one tile (a head's
   rows written with a stride over sublanes): what the gate's norm over
   each head reads.
2. ``G`` is the running sum of g (one product with the lower-triangular
   ones, a group of heads at a time). The decays between two tokens of one
   16-row sub-chunk are formed pair by pair, ``exp(G_t - G_s)``, ONCE, and
   give both A (k against k) and P (q against k); between sub-chunks they
   pass through the position before the later one's first token, as
   ``ops.kda._decay_dots`` has it: no exponent is ever positive.
3. ``(I + A) [U0 | W] = Beta [V | K e^G]``: the sub-chunks' own blocks are
   inverted by the nilpotent product, the blocks below them by forward
   substitution a sub-chunk at a time, as ``ops.kda._solve_unit_lower``
   does, here applied to the identity and followed by ONE product with the
   right-hand side. It is written on whole matrices: ``X <- inv (I - L X)``
   from ``X = inv`` settles one more sub-chunk's rows each time, each from
   the rows settled before it; the rows not yet settled are overwritten.
   The matrices are ``[R, R]`` with R = 128: two heads' ``[64, 64]`` blocks
   on one diagonal, so a six-pass product fills the MXU's tile once for two
   heads (what bounds the kernel is the count of such products, 23 a pair
   of heads and chunk: PERF.md section 6, PR 36).
4. The carry: ``u = U0 - W S``, ``o = (q e^G) S + P u``, ``S <- S e^G_end +
   k_end^T u``, under the masks ``seg_ids`` gives (tokens meet only tokens
   of their segment, the state reaches only the segment it belongs to and is
   dropped where the segment changed). The state is held TRANSPOSED, ``[d_v,
   d_k]``: its decay runs along lanes.
5. A chunk with no real token writes zeros and fetches nothing new.

Per chunk the kernel also writes the state it was handed and ``u``: each
segment's state at ITS last token is a gather over at most S chunks behind
it (``ops.kda.segment_finals``, shared with the XLA form).

Every product is float32 at full precision (``Precision.HIGHEST``: the
configuration's, ``ops/kda.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kda import SUB_CHUNK, segment_finals, slot_layout

_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 32 * 1024 * 1024
# Heads a grid step takes: a token's tile of sublanes.
HEAD_BLOCK = 8
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _decay_dots(heads, sub):
    """The decay-weighted Gram matrices of a group of heads before their
    masks, ``sum_c left[t, c] k[s, c] exp(G[t, c] - G[s, c])`` for left = k
    (A) and left = q (P): each [R, R], R = heads x Q, a row or column
    standing for (head, token). Right on and under the diagonal inside a
    head's own block, anything finite elsewhere. ``heads``: (q, k, G) each
    [Q, d] a head."""
    Q = heads[0][1].shape[0]
    R, f32 = len(heads) * Q, jnp.float32
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, R), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    a_rows = [[] for _ in heads]
    p_rows = [[] for _ in heads]
    for i in range(Q // sub):
        lo = i * sub
        for h, (q, k, G) in enumerate(heads):
            Gb, kb, qb = G[lo:lo + sub], k[lo:lo + sub], q[lo:lo + sub]
            # Inside the sub-chunk, eight rows (one tile of sublanes)
            # against one token s at a time; the tiles wholly above s are
            # not visited. Unrolled: the reductions of one token overlap
            # the next one's (as a loop the kernel took 6.6 ms for 2.0).
            a_acc = [jnp.zeros((8, R), f32) for _ in range(sub // 8)]
            p_acc = list(a_acc)
            for s in range(sub):
                ks, Gs = kb[s:s + 1], Gb[s:s + 1]
                here = lane == h * Q + lo + s
                for j in range(s // 8, sub // 8):
                    r = slice(8 * j, 8 * j + 8)
                    e = ks * jnp.exp(jnp.minimum(Gb[r] - Gs, 0.0))
                    a_acc[j] = jnp.where(
                        here, jnp.sum(kb[r] * e, axis=1, keepdims=True),
                        a_acc[j])
                    p_acc[j] = jnp.where(
                        here, jnp.sum(qb[r] * e, axis=1, keepdims=True),
                        p_acc[j])
            a_rows[h].append(jnp.concatenate(a_acc, axis=0))
            p_rows[h].append(jnp.concatenate(p_acc, axis=0))
        if i:
            # The earlier sub-chunks, through G before this one's first
            # token: both factors' exponents are <= 0. One product for the
            # group: a head's rows against another head's columns are
            # masked with everything else outside its block.
            decay = [jnp.exp(jnp.minimum(G[lo:lo + sub] - G[lo - 1:lo], 0.0))
                     for _, _, G in heads]
            left = jnp.concatenate(
                [k[lo:lo + sub] * d for (_, k, _), d in zip(heads, decay)]
                + [q[lo:lo + sub] * d for (q, _, _), d in zip(heads, decay)],
                axis=0)
            right = jnp.concatenate(
                [jnp.where(row < lo, k * jnp.exp(jnp.minimum(
                    G[lo - 1:lo] - G, 0.0)), 0.0) for _, k, G in heads],
                axis=0)
            off = _dot(left, right, _NT)                # [2 heads sub, R]
            for h in range(len(heads)):
                a_rows[h][i] = a_rows[h][i] + off[h * sub:(h + 1) * sub]
                p_rows[h][i] = p_rows[h][i] + off[
                    (len(heads) + h) * sub:(len(heads) + h + 1) * sub]
    stack = lambda rows: jnp.concatenate([b for h in rows for b in h], axis=0)
    return stack(a_rows), stack(p_rows)


def _unit_lower_inverse(A, own, sub, Q):
    """``(I + A)^-1`` for strictly lower-triangular A [R, R], zero between
    heads; ``own`` [R, R]: 1.0 where two tokens share a sub-chunk."""
    R = A.shape[0]
    N = A * own
    L = A - N
    eye = (jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
           ).astype(jnp.float32)
    inv, power, n = eye - N, N, 1
    while 2 * n < sub:
        power, n = _dot(power, power), 2 * n
        inv = inv + _dot(inv, power)
    X = inv
    for _ in range(Q // sub - 1):
        X = _dot(inv, eye - _dot(L, X))
    return X


def _kernel(n_real_ref, seg_in_ref, seg_last_ref, q_ref, k_ref, v_ref, g_ref,
            beta_ref, seg_col_ref, seg_row_ref, init_ref, o_ref, u_ref,
            s_in_ref, state_ref, *, hb, group, dk, dv, sub):
    c = pl.program_id(1)
    Q = q_ref.shape[0]
    R, f32 = group * Q, jnp.float32

    @pl.when(c == 0)
    def _():
        state_ref[...] = init_ref[...]

    @pl.when(c >= n_real_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        u_ref[...] = jnp.zeros_like(u_ref)
        s_in_ref[...] = jnp.zeros_like(s_in_ref)

    @pl.when(c < n_real_ref[0])
    def _():
        seg_in, seg_last = seg_in_ref[c], seg_last_ref[c]
        # Rows and columns of the group's [R, R] matrices: (head, token).
        seg_t, seg_s = seg_col_ref[...], seg_row_ref[...]       # [R,1] [1,R]
        t_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
        s_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
        same = (seg_t == seg_s) & (t_i // Q == s_i // Q)
        m_a = (same & (t_i > s_i)).astype(f32)
        m_p = (same & (t_i >= s_i)).astype(f32)
        own = (t_i // sub == s_i // sub).astype(f32)
        ones = ((t_i >= s_i) & (t_i // Q == s_i // Q)).astype(f32)
        reach = (seg_t[:Q] == seg_in).astype(f32)               # [Q, 1]
        ends = (seg_t[:Q] == seg_last).astype(f32)
        keep = (seg_in == seg_last).astype(f32)
        stack = lambda parts: jnp.concatenate(parts, axis=0)

        def one_group(n, carry):
            # The group's heads, by their place in the block: a loop over
            # the groups, so the body is traced and lowered once a call (a
            # program pays that before it can look itself up in the compile
            # cache, at every start).
            js = [n * group + h for h in range(group)]
            rows = [pl.ds(pl.multiple_of(j * dv, dv), dv) for j in js]
            of_k = [pl.ds(pl.multiple_of(j * dk, dk), dk) for j in js]
            G2 = _dot(ones, stack([g_ref[:, c] for c in of_k]))
            heads = [(q_ref[:, c], k_ref[:, c], G2[h * Q:(h + 1) * Q])
                     for h, c in enumerate(of_k)]
            eG = [jnp.exp(G) for _, _, G in heads]
            A, P = _decay_dots(heads, sub)
            beta = stack([beta_ref[j] for j in js])             # [R, 1]
            X = _dot(_unit_lower_inverse(A * beta * m_a, own, sub, Q),
                     beta * jnp.concatenate(
                         [stack([v_ref[:, r] for r in rows]),
                          stack([k * e for (_, k, _), e in zip(heads, eG)])],
                         axis=1))
            St = [state_ref[r, :] for r in rows]                # [d_v, d_k]
            us, reached = [], []
            for h, (q, _, _) in enumerate(heads):
                Xh = X[h * Q:(h + 1) * Q]
                # What the state gives W's rows and q's, in one product.
                got = _dot(stack([Xh[:, dv:] * reach, q * eG[h] * reach]),
                           St[h], _NT)
                us.append(Xh[:, :dv] - got[:Q])
                reached.append(got[Q:])
            own_u = _dot(P * m_p, stack(us))
            for h, (j, (_, k, G)) in enumerate(zip(js, heads)):
                o_ref[:, j, :] = reached[h] + own_u[h * Q:(h + 1) * Q]
                u_ref[:, j, :] = us[h]
                s_in_ref[rows[h], :] = St[h]
                k_end = k * jnp.exp(jnp.minimum(G[Q - 1:Q] - G, 0.0)) * ends
                state_ref[rows[h], :] = (St[h] * (eG[h][Q - 1:Q] * keep)
                                         + _dot(us[h], k_end, _TN))
            return carry

        jax.lax.fori_loop(0, hb // group, one_group, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, seg_ids: jax.Array, seg_ends: jax.Array,
              init_state: jax.Array, init_seg, chunk: int, *,
              interpret: bool = False):
    """Same contract as ``ops.kda.kda_chunk_scan_xla``: q, k, g [T, H, d_k],
    v [T, H, d_v], beta [T, H]; seg_ids [T]; seg_ends [S]; init_state
    [H * d_k, d_v] of segment ``init_seg``. Returns (o [T, H, d_v] float32,
    each segment's final state [S, H * d_k, d_v] float32)."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    Q, f32 = chunk, jnp.float32
    sub = min(SUB_CHUNK, Q)
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
    # Heads whose [Q, Q] matrices share one [R, R] product, R <= 128.
    group = next(p for p in range(hb, 0, -1)
                 if hb % p == 0 and p * Q <= max(128, Q))
    if Q % sub or sub % 8:
        raise ValueError(f"kda_chunk: a chunk of {Q} is not whole sub-chunks "
                         f"of {SUB_CHUNK} tokens")
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(f"kda_chunk: heads of [{dk}, {dv}] are not whole "
                         "lane tiles")
    pad = -T % Q
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta))
        seg_ids = jnp.pad(seg_ids, (0, pad), constant_values=-1)
    Tp = T + pad
    nc = Tp // Q
    seg_ids = seg_ids.astype(jnp.int32)
    sc = seg_ids.reshape(nc, Q)
    seg_last = sc[:, -1]
    seg_in = jnp.concatenate(
        [jnp.asarray(init_seg, jnp.int32)[None], seg_last[:-1]])
    # Padding stands only behind every segment: the chunks that hold a real
    # token are the first n_real.
    n_real = jnp.sum(jnp.any(sc >= 0, axis=1)).astype(jnp.int32)[None]

    # The heads on lanes, as the producers leave them (the module's
    # docstring, 1).
    flat = [a.astype(f32).reshape(Tp, -1) for a in (q, k, v, g)]
    # A head's beta as a column, [H, T, 1]; 4 T H bytes.
    beta_b = beta.astype(f32).T[..., None]
    init_t = init_state.astype(f32).reshape(H, dk, dv).swapaxes(1, 2)
    seg_rows = jnp.tile(sc, (1, group))       # a chunk's, once a head

    def tokens(width):       # a chunk without a real token fetches nothing
        return pl.BlockSpec(
            (Q, hb * width), lambda h, c, n, *_: (
                jnp.minimum(c, jnp.maximum(n[0], 1) - 1), h))

    # What only ``segment_finals`` reads, of chunks that hold a segment's
    # last token: the chunks behind the real ones share ONE block, written
    # once.
    behind = lambda c, n: jnp.minimum(c, n[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(H // hb, nc),
        in_specs=[
            tokens(dk), tokens(dk), tokens(dv), tokens(dk),
            pl.BlockSpec((hb, Q, 1), lambda h, c, *_: (h, c, 0)),
            pl.BlockSpec((None, group * Q, 1), lambda h, c, *_: (c, 0, 0)),
            pl.BlockSpec((None, 1, group * Q), lambda h, c, *_: (c, 0, 0)),
            pl.BlockSpec((hb * dv, dk), lambda h, c, *_: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Q, hb, dv), lambda h, c, *_: (c, h, 0)),
            pl.BlockSpec((Q, hb, dv),
                         lambda h, c, n, *_: (behind(c, n), h, 0)),
            pl.BlockSpec((None, hb * dv, dk),
                         lambda h, c, n, *_: (behind(c, n), h, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hb * dv, dk), f32)])
    o, U, S_in = pl.pallas_call(
        functools.partial(_kernel, hb=hb, group=group, dk=dk, dv=dv,
                          sub=sub),
        out_shape=[jax.ShapeDtypeStruct((Tp, H, dv), f32),
                   jax.ShapeDtypeStruct((Tp, H, dv), f32),
                   jax.ShapeDtypeStruct((nc, H * dv, dk), f32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tp * H * (Q * (6 * Q + 3 * dk + 5 * dv + sub * dk)
                                + 3 * dk * dv),
            transcendentals=Tp * H * dk * (sub + 6),
            bytes_accessed=4 * Tp * H * (3 * dk + 3 * dv)
            + 4 * nc * H * dk * dv),
        interpret=interpret,
        name="kda_chunk",
    )(n_real, seg_in, seg_last, *flat, beta_b,
      seg_rows[:, :, None], seg_rows[:, None, :],
      init_t.reshape(H * dv, dk))

    # Each segment's last chunk, gathered: [S, H, Q, d] as the XLA form's.
    with jax.named_scope("kgct.kda.chunk.final"):
        e = jnp.maximum(seg_ends, 0)
        c_s = e // Q
        # (of k and g the chunks are picked BEFORE the heads are named:
        # [T, H x d] is another tiled layout than [T, H, d])
        per_chunk = lambda a: (a.reshape((nc, Q) + a.shape[1:])[c_s]
                               .reshape(-1, Q, H, a.size // (Tp * H))
                               .swapaxes(1, 2))
        final = segment_finals(
            per_chunk(flat[1]), jnp.cumsum(per_chunk(flat[3]), axis=2),
            sc[c_s], S_in.reshape(nc, H, dv, dk)[c_s].swapaxes(2, 3),
            per_chunk(U), seg_in[c_s], e % Q)
    return o[:T], slot_layout(final)
