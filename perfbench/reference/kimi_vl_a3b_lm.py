"""Plain reference of the language model of moonshotai/Kimi-VL-A3B-Instruct
(a deepseek_v3 decoder: latent attention, sigmoid-routed fine-grained experts
with a choice bias, shared experts, leading dense layers).

The forward pass of one whole sequence in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: materialised attention over the
full sequence, a Python loop over layers and over experts, no cache, no
kernels, no batching, no grouped or sorted dispatch. It takes the ENGINE's
parameter tree (``models.llama.init_params`` / ``engine.weights``), so the
same seeded weights go through both.

For layer input h [T, d], eps = ``rms_norm_eps``:

    x = RMSNorm(h);  q = x W_q -> [T, nh, nope + rope]
    a = x W_kva -> [T, r + rope];  c = RMSNorm(a[:, :r]);  k_pe = a[:, r:]
    RoPE(theta, no scaling) on q's last rope dims and on k_pe
    k_nope = c W_uk[head], v = c W_uv[head];  k = [k_nope | k_pe]
    P = softmax_causal(q k^T / sqrt(nope + rope));  h += (P v) W_o
    dense layer:   h += W_down(silu(W_gate x2) * W_up x2), x2 = RMSNorm(h)
    expert layer:  s = sigmoid(x2 W_r);  idx = top_k(s + b);  w = s[idx]
                   w = w / (sum w + 1e-20) * routed_scaling_factor
                   h += sum_k w_k E_idx_k(x2) + S(x2)
    logits = RMSNorm(h) W_head

Departures from the published model, all of them:

- **Text only.** The vision tower (MoonViT) and its projector are not here:
  the catalog gives them no sizes. The server refuses image content.
- **RoPE layout.** The published modeling code rotates interleaved pairs
  (x[2i], x[2i+1]) of the rope dims, by de-interleaving them at run time.
  The engine's tree holds those columns of ``W_q`` and ``W_kva``
  de-interleaved already (``engine/weights.py`` permutes a checkpoint's;
  random weights have no order), so rotating half-split here, (x[i],
  x[i + rope/2]), turns the same pairs by the same angles.
- ``W_uk``/``W_uv`` are ``kv_b_proj`` split per head into its key and value
  halves, as the tree stores them; the product is the same.
- ``n_group`` = ``topk_group`` = 1: the group-limited choice is the identity
  and is not written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: [T, n, rope]; half-split rotation of (x[i], x[i + rope/2])."""
    half = x.shape[-1] // 2
    inv_freq = theta ** -(np.arange(half, dtype=np.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq      # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _attention(lp, cfg, h, positions):
    T = h.shape[0]
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    w_q, w_o = lp["wq"], lp["wo"]       # float32: see hidden_states
    x = _rms(h, lp["input_norm"], cfg.rms_norm_eps)
    q = (x @ w_q).reshape(T, nh, nope + rope)
    a = x @ lp["w_kva"]
    c = _rms(a[:, :r], lp["kv_norm"], cfg.rms_norm_eps)
    q_pe = _rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = _rope(a[:, None, r:], positions, cfg.rope_theta)      # [T, 1, rope]
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k_nope = jnp.einsum("tc,hcn->thn", c, lp["w_uk"])
    v = jnp.einsum("tc,hcv->thv", c, lp["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, nh, rope))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(nope + rope)
    causal = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shv->thv", p, v).reshape(T, -1)
    return h + o @ w_o


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


def hidden_states(params, cfg, token_ids, precision="highest"):
    """token_ids: [T] ints of ONE sequence -> final hidden states [T, d]
    (before the last norm), float32. ``precision``: the matmul precision;
    anything but "highest" is a DEGRADED reading (the benchmark's golden
    writer measures the noise of bf16 passes with "default")."""
    if any(a.dtype == jnp.int8 for a in jax.tree.leaves(params)):
        raise ValueError("the reference takes dense-precision weights, not "
                         "a quantized tree")
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)
    tokens = jnp.asarray(token_ids, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision(precision):
        h = jnp.asarray(params["embed"][tokens], jnp.float32)
        for group in ("dense_layers", "layers"):
            stack = params.get(group)
            if stack is None:
                continue
            for l in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = f32(jax.tree.map(lambda a: a[l], stack))
                h = _attention(lp, cfg, h, positions)
                x = _rms(h, lp["post_attn_norm"], cfg.rms_norm_eps)
                if "router" in lp:
                    h = h + _experts(lp, cfg, x)
                else:
                    h = h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h


def forward(params, cfg, token_ids, precision="highest"):
    """token_ids: [T] -> logits [T, vocab] float32: row t is the
    distribution of token t+1 given tokens 0..t."""
    h = hidden_states(params, cfg, token_ids, precision)
    with jax.default_matmul_precision(precision):
        x = _rms(h, jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.rms_norm_eps)
        return x @ jnp.asarray(params["lm_head"], jnp.float32)
