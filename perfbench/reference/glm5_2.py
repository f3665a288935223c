"""Plain reference of zai-org/GLM-5.2 (``model_type`` glm_moe_dsa): a
deepseek_v3 decoder (latent attention behind a low-rank query, plain rope on
the rope dims, sigmoid-routed experts with a choice bias beside a shared one,
leading dense layers) with DeepSeek Sparse Attention: a lightning indexer in
the layers whose ``indexer_types`` entry is "full" scores every earlier token
and keeps the ``index_topk`` best; that layer and the "shared" layers behind
it attend over the kept tokens only.

The forward pass of one whole sequence in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: Python loops over layers,
attention heads, index heads and the experts held, materialised attention
with the choice as a mask from ``lax.top_k`` on the float32 scores, no cache,
no kernels, no batching. It takes the ENGINE's parameter tree
(``models.llama.init_params``), so the same seeded weights go through both,
and the same share of the experts and of the vocabulary.

Per token t, normed hidden x_t, position p_t, eps = ``rms_norm_eps``:

    attention:  c_q = RMSNorm_q(x W_qa);  q = c_q W_qb -> [nh, nope + rope]
        a = x W_kva;  c = RMSNorm_kv(a[:r]);  k_pe = a[r:]
        rope on q's last rope dims and on k_pe
        k = [c W_uk[h] | k_pe];  v = c W_uv[h]
        P = softmax over s in S_t of q k^T (nope + rope)^-1/2;  out = (P v) W_o
    indexer ("full" layers; H heads of D, ONE key a token):
        q^I = c_q W^I_qb -> [H, D];   k^I = LayerNorm(x W^I_k) (with bias) -> [D]
        rope on the FIRST rope dims of both
        w = x W^I_w * H^-1/2 * D^-1/2 -> [H]
        I[t, s] = sum_h w[t, h] relu(q^I[t, h] . k^I[s]),  s <= t
        S_t = the min(index_topk, t + 1) positions of largest I[t, .]
              (``lax.top_k``: ties to the lower position)
    "shared" layers: S_t of the nearest "full" layer before them
    dense layer:   W_down(silu(W_gate x) * W_up x)
    expert layer:  s = sigmoid(x W_r);  idx = top_k(s + b);  w = s[idx]
                   w = w / (sum w + 1e-20) * routed_scaling_factor
                   sum over the chosen experts HELD of w_k E_idx_k(x), + S(x)

Departures from the published model and what its config leaves open, all of
them (``assumed`` in perfbench/configs/glm-5.2-bf16.json):

- **No fp8 and no Hadamard rotation in the indexer.** The published kernels
  rotate q^I and k^I by a Hadamard matrix and quantise them to fp8; the
  config states no quantisation, the rotation leaves q . k unchanged in
  exact arithmetic, and index keys are the model's dtype here.
- **The multi-token-prediction module** (``num_nextn_predict_layers`` 1,
  ``index_share_for_mtp_iteration``) is left out: the server does not run it.
- **Which dims of an index vector rotate**: the first ``qk_rope_head_dim``
  (DeepSeek-V3.2's indexer splits [pe | nope]), half-split over
  de-interleaved columns as ``kimi_vl_a3b_lm.py`` beside this file explains.
- **The key's LayerNorm** has a bias and eps 1e-6 (DeepSeek-V3.2's
  ``LayerNorm``); the scale on w is H^-1/2 (``weights_proj``'s) times D^-1/2
  (the score's softmax scale).
- **Experts held**: experts ``experts_first`` to ``experts_first +
  experts_held - 1``; what the others would add is left out, here as in the
  program. ``W_uk``/``W_uv`` are ``kv_b_proj`` split per head; ``n_group`` =
  ``topk_group`` = 1: the group-limited choice is the identity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

K_NORM_EPS = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope(x, positions, theta):
    """x: [T, n, rope]; half-split rotation of (x[i], x[i + rope/2])."""
    half = x.shape[-1] // 2
    inv_freq = (theta ** -(np.arange(half, dtype=np.float64) / half)
                ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq      # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def index_key(ip, cfg, x, positions):
    """k^I [T, D]: one key a token for every index head."""
    rope = cfg.qk_rope_head_dim
    k = _layer_norm(x @ ip["wk"], ip["k_norm"], ip["k_norm_b"], K_NORM_EPS)
    return jnp.concatenate(
        [_rope(k[:, None, :rope], positions, cfg.rope_theta)[:, 0],
         k[:, rope:]], axis=-1)


def index_scores(ip, cfg, c_q, x, positions):
    """I [T, T] float32: row t scores every position (the caller masks)."""
    T = x.shape[0]
    H, D, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = (c_q @ ip["wq_b"]).reshape(T, H, D)
    q = jnp.concatenate([_rope(q[..., :rope], positions, cfg.rope_theta),
                         q[..., rope:]], axis=-1)
    k = index_key(ip, cfg, x, positions)
    w = (x @ ip["w_w"]) * (H * D) ** -0.5
    scores = jnp.zeros((T, T), jnp.float32)
    for h in range(H):
        scores = scores + w[:, h, None] * jax.nn.relu(q[:, h] @ k.T)
    return scores


def choice_mask(scores, positions, topk):
    """[T, T] bool: row t keeps the min(topk, t + 1) best of s <= t."""
    causal = positions[:, None] >= positions[None, :]
    T = scores.shape[0]
    if T <= topk:
        return causal
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    kept = jnp.zeros((T, T), bool).at[jnp.arange(T)[:, None], idx].set(True)
    return kept & causal


def _attention(lp, cfg, x, c_q, positions, mask):
    """The attention sublayer over its normed input x [T, d], each token
    over the positions ``mask`` [T, T] keeps for it."""
    T = x.shape[0]
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (c_q @ lp["w_qb"]).reshape(T, nh, nope + rope)
    a = x @ lp["w_kva"]
    c = _rms(a[:, :r], lp["kv_norm"], cfg.rms_norm_eps)
    q_pe = _rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = _rope(a[:, None, r:], positions, cfg.rope_theta)[:, 0]
    out = []
    for h in range(nh):
        k = jnp.concatenate([c @ lp["w_uk"][h], k_pe], axis=-1)
        qh = jnp.concatenate([q[:, h, :nope], q_pe[:, h]], axis=-1)
        s = (qh @ k.T) * (nope + rope) ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        out.append(p @ (c @ lp["w_uv"][h]))
    return jnp.concatenate(out, axis=-1) @ lp["wo"]


def _experts(lp, cfg, x):
    s = jax.nn.sigmoid(x @ lp["router"])                         # [T, E]
    _, idx = jax.lax.top_k(s + lp["router_bias"], cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in range(lp["w_gate"].shape[0]):           # the experts held here
        w_e = jnp.sum(jnp.where(idx == cfg.experts_first + e, w, 0.0), -1)
        rows = np.nonzero(np.asarray(w_e))[0]         # its tokens alone
        if rows.size:
            y = y.at[rows].add(w_e[rows, None] * _swiglu(
                x[rows], lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e]))
    if "ws_gate" in lp:
        y = y + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y


def hidden_states(params, cfg, token_ids, precision="highest", dense=False):
    """token_ids: [T] ints of ONE sequence -> the final residual [T, d]
    (before the last norm), float32. ``precision``: the matmul precision;
    anything but "highest" is a DEGRADED reading, as is ``dense`` (the
    choice dropped: every layer attends to every earlier token)."""
    if any(a.dtype == jnp.int8 for a in jax.tree.leaves(params)):
        raise ValueError("the reference takes dense-precision weights, not "
                         "a quantized tree")
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)
    tokens = jnp.asarray(token_ids, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    layer, mask = 0, None
    with jax.default_matmul_precision(precision):
        h = jnp.asarray(params["embed"][tokens], jnp.float32)
        for group in ("dense_layers", "layers"):
            stack = params.get(group)
            if stack is None:
                continue
            for l in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = f32(jax.tree.map(lambda a: a[l], stack))
                x = _rms(h, lp["input_norm"], cfg.rms_norm_eps)
                c_q = _rms(x @ lp["w_qa"], lp["q_a_norm"], cfg.rms_norm_eps)
                if cfg.indexer_types[layer] == "full":
                    j = cfg.index_layers.index(layer)
                    ip = f32(jax.tree.map(lambda a: a[j], params["indexer"]))
                    mask = choice_mask(
                        index_scores(ip, cfg, c_q, x, positions), positions,
                        positions.shape[0] if dense else cfg.index_topk)
                h = h + _attention(lp, cfg, x, c_q, positions, mask)
                x = _rms(h, lp["post_attn_norm"], cfg.rms_norm_eps)
                h = h + (_experts(lp, cfg, x) if "router" in lp else
                         _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]))
                layer += 1
    return h


def forward(params, cfg, token_ids, precision="highest", dense=False):
    """token_ids: [T] -> logits [T, vocab] float32: row t is the
    distribution of token t+1 given tokens 0..t."""
    h = hidden_states(params, cfg, token_ids, precision, dense)
    with jax.default_matmul_precision(precision):
        x = _rms(h, jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.rms_norm_eps)
        return x @ jnp.asarray(params["lm_head"], jnp.float32)
