"""Plain reference of XingChen-AGI/Xing4.0-29B-A4B (``model_type`` xing4_0): a
deepseek_v3 decoder (latent attention with a low-rank query, YaRN on the
rope dims, sigmoid-routed experts with a choice bias beside a shared one,
two leading dense layers) whose residual is ``n = hc_mult`` parallel streams
mixed by manifold-constrained hyper-connections (DeepSeek-AI, "mHC",
arXiv:2512.24880, over Zhu et al., "Hyper-Connections", arXiv:2409.19606)
around every attention and every MLP.

The forward pass of one whole sequence in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: materialised attention over the
full sequence, Python loops over layers, experts and Sinkhorn rounds, no
cache, no kernels, no batching. It takes the ENGINE's parameter tree
(``models.llama.init_params``), so the same seeded weights go through both.

Per token, streams X [n, d], eps = ``rms_norm_eps``:

    X_0[j] = Emb(token) for every j;   logits = RMSNorm(sum_j X_L[j]) W_head

    a sublayer F (attention behind input_norm, or the MLP behind
    post_attn_norm), with its own Phi [n d, 2n + n^2], a_pre, a_post, a_res,
    b_pre, b_post [n], b_res [n, n]:
        x~ = vec(X);  u = (x~ / sqrt(mean(x~^2) + eps)) Phi
        H_pre  = sigmoid(a_pre u[0:n] + b_pre)
        H_post = 2 sigmoid(a_post u[n:2n] + b_post)
        M = exp(clip(a_res mat(u[2n:]) + b_res, clamp_min, clamp_max))   row-major
        hc_sinkhorn_iters times:  M /= colsum(M) + hc_eps;  M /= rowsum(M) + hc_eps
        y = sum_j H_pre[j] X[j];   f = F(RMSNorm_layer(y))
        X'[i] = sum_j M[i, j] X[j] + H_post[i] f

    attention:  c_q = RMSNorm_q(x W_qa);  q = c_q W_qb -> [nh, nope + rope]
        a = x W_kva;  c = RMSNorm_kv(a[:r]);  k_pe = a[r:]
        YaRN-RoPE on q's last rope dims and on k_pe
        k = [c W_uk[h] | k_pe];  v = c W_uv[h]
        P = softmax_causal(q k^T (nope + rope)^-1/2 m^2);  out = (P v) W_o
        m = 0.1 mscale_all_dim ln(factor) + 1
    YaRN (deepseek_v3's):  cd(t) = dim ln(orig / (2 pi t)) / (2 ln theta)
        low = max(floor(cd(beta_fast)), 0);  high = min(ceil(cd(beta_slow)), dim - 1)
        ramp_i = clip((i - low) / (high - low), 0, 1), i < dim / 2
        inv_freq_i = theta^(-2 i / dim) ((1 - ramp_i) + ramp_i / factor)
        cos, sin scaled by m(mscale) / m(mscale_all_dim)
    dense layer:   W_down(silu(W_gate x) * W_up x)
    expert layer:  s = sigmoid(x W_r);  idx = top_k(s + b);  w = s[idx]
                   w = w / (sum w + 1e-20) * routed_scaling_factor
                   sum_k w_k E_idx_k(x) + S(x)

Departures from the published model and what its config leaves open, all of
them (``assumed`` in perfbench/configs/xing4.0-29b-a4b-bf16.json):

- **The hyper-connection keys give sizes, not equations.** ``hc_eps`` sits in
  the Sinkhorn denominators; a round normalises columns, then rows
  (arXiv:2512.24880's ``T_r(T_c(.))``); the streams start as copies of the
  embedding and end as their sum (arXiv:2409.19606).
- **The norm's gain is folded into Phi.** The tree's ``hc_<site>_phi`` is
  ``gamma * Phi`` row by row, as a loader would fold a checkpoint's (in
  float32, rounded once); the random draw's gamma is 1, where the fold is
  exact. Phi, the biases and the coefficient rows lie in 128 lanes: H_pre's
  columns at 0, H_post's at 8, row i of M at 64 + 8 i (``_lanes``).
- **The multi-token-prediction module** (``num_nextn_predict_layers`` 1) is
  left out: the server does not run it.
- **RoPE layout**: half-split over de-interleaved columns, as
  ``kimi_vl_a3b_lm.py`` beside this file explains.
- ``W_uk``/``W_uv`` are ``kv_b_proj`` split per head; ``n_group`` =
  ``topk_group`` = 1: the group-limited choice is the identity.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _yarn(dim, theta, sc):
    """(inv_freq [dim / 2], the factor on cos and sin, m^2 on the scores)."""
    half = dim // 2
    inv_freq = theta ** -(np.arange(half, dtype=np.float64) / half)
    if not sc:
        return inv_freq.astype(np.float32), 1.0, 1.0
    factor, orig = float(sc["factor"]), float(
        sc["original_max_position_embeddings"])

    def cd(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(cd(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(cd(float(sc["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    m = lambda scale: 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0
    inv_freq = inv_freq * ((1.0 - ramp) + ramp / factor)
    return (inv_freq.astype(np.float32),
            m(float(sc.get("mscale", 1))) / m(float(sc.get("mscale_all_dim", 0))),
            m(float(sc.get("mscale_all_dim", 0))) ** 2)


def _rope(x, positions, inv_freq, cos_factor):
    """x: [T, n, rope]; half-split rotation of (x[i], x[i + rope/2])."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq      # [T, half]
    cos = (jnp.cos(ang) * cos_factor)[:, None, :]
    sin = (jnp.sin(ang) * cos_factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _attention(lp, cfg, x, positions):
    """The attention sublayer's F over its normed input x [T, d]."""
    T = x.shape[0]
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    inv_freq, cos_factor, m2 = _yarn(rope, cfg.rope_theta,
                                     cfg.rope_scaling_dict)
    c_q = _rms(x @ lp["w_qa"], lp["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ lp["w_qb"]).reshape(T, nh, nope + rope)
    a = x @ lp["w_kva"]
    c = _rms(a[:, :r], lp["kv_norm"], cfg.rms_norm_eps)
    q_pe = _rope(q[..., nope:], positions, inv_freq, cos_factor)
    k_pe = _rope(a[:, None, r:], positions, inv_freq, cos_factor)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k_nope = jnp.einsum("tc,hcn->thn", c, lp["w_uk"])
    v = jnp.einsum("tc,hcv->thv", c, lp["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, nh, rope))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) * ((nope + rope) ** -0.5 * m2)
    causal = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shv->thv", p, v).reshape(T, -1) @ lp["wo"]


def _experts(lp, cfg, x):
    s = jax.nn.sigmoid(x @ lp["router"])                         # [T, E]
    _, idx = jax.lax.top_k(s + lp["router_bias"], cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # [T]
        y = y + w_e[:, None] * _swiglu(x, lp["w_gate"][e], lp["w_up"][e],
                                       lp["w_down"][e])
    if "ws_gate" in lp:
        y = y + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y


def _lanes(n):
    """Where the tree keeps a mixer's 2n + n^2 columns in its 128 lanes."""
    pre, post = np.arange(n), 8 + np.arange(n)
    res = 64 + 8 * np.arange(n)[:, None] + np.arange(n)[None, :]
    return pre, post, res


def stream_coefficients(lp, cfg, site, X):
    """X [T, n, d] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the
    sublayer at ``site`` ("attn" or "mlp")."""
    T, n, d = X.shape
    pre, post, res = _lanes(n)
    phi, alpha, bias = (lp[f"hc_{site}_phi"], lp[f"hc_{site}_alpha"],
                        lp[f"hc_{site}_bias"])
    flat = X.reshape(T, n * d)
    rho = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                        + cfg.rms_norm_eps)
    u = (flat * rho) @ phi                                       # [T, 128]
    h_pre = jax.nn.sigmoid(alpha[0] * u[:, pre] + bias[pre])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * u[:, post] + bias[post])
    lo, hi = cfg.hc_res_clamp
    m = jnp.exp(jnp.clip(alpha[2] * u[:, res] + bias[res], lo, hi))
    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps)  # columns
        m = m / (jnp.sum(m, axis=2, keepdims=True) + cfg.hc_eps)  # rows
    return h_pre, h_post, m


def _sublayer(lp, cfg, site, X, fn):
    h_pre, h_post, h_res = stream_coefficients(lp, cfg, site, X)
    y = jnp.sum(h_pre[:, :, None] * X, axis=1)
    f = fn(y)
    return (jnp.sum(h_res[:, :, :, None] * X[:, None, :, :], axis=2)
            + h_post[:, :, None] * f[:, None, :])


def hidden_states(params, cfg, token_ids, precision="highest"):
    """token_ids: [T] ints of ONE sequence -> the final streams [T, n, d]
    (before their sum and the last norm), float32. ``precision``: the
    matmul precision; anything but "highest" is a DEGRADED reading."""
    if any(a.dtype == jnp.int8 for a in jax.tree.leaves(params)):
        raise ValueError("the reference takes dense-precision weights, not "
                         "a quantized tree")
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)
    tokens = jnp.asarray(token_ids, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision(precision):
        emb = jnp.asarray(params["embed"][tokens], jnp.float32)
        X = jnp.stack([emb] * cfg.hc_mult, axis=1)
        for group in ("dense_layers", "layers"):
            stack = params.get(group)
            if stack is None:
                continue
            for l in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = f32(jax.tree.map(lambda a: a[l], stack))
                X = _sublayer(lp, cfg, "attn", X, lambda y: _attention(
                    lp, cfg, _rms(y, lp["input_norm"], cfg.rms_norm_eps),
                    positions))

                def mlp(y):
                    x = _rms(y, lp["post_attn_norm"], cfg.rms_norm_eps)
                    if "router" in lp:
                        return _experts(lp, cfg, x)
                    return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
                X = _sublayer(lp, cfg, "mlp", X, mlp)
    return X


def forward(params, cfg, token_ids, precision="highest"):
    """token_ids: [T] -> logits [T, vocab] float32: row t is the
    distribution of token t+1 given tokens 0..t."""
    X = hidden_states(params, cfg, token_ids, precision)
    with jax.default_matmul_precision(precision):
        x = _rms(jnp.sum(X, axis=1),
                 jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.rms_norm_eps)
        return x @ jnp.asarray(params["lm_head"], jnp.float32)
