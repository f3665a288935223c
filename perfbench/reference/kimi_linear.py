"""Plain reference of moonshotai/Kimi-Linear-48B-A3B-Instruct (``kimi_linear``,
arXiv:2510.26692): KDA (gated delta-rule) state layers and latent-attention
layers WITHOUT positional encoding in a repeating pattern; a dense SwiGLU in
the leading layer, sigmoid-routed experts beside a shared expert in the
others; untied head.

The forward pass of one whole sequence in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: the delta rule written token by
token (``lax.scan`` over T, NO chunks, no slots), a Python loop over the
layers in ``cfg.layer_types``' order, latent attention materialised over the
full sequence, the experts by a plain loop, no cache, no kernels, no
batching. It takes the ENGINE's parameter tree (``models.llama.init_params``),
so the same seeded weights go through both, and the same SHARE: the experts
``cfg.experts_first`` .. ``+ cfg.experts_held`` and the first
``cfg.vocab_size`` ids. What the absent experts would add is left out,
exactly as the program leaves it out: the share's result is the whole
layer's minus the other shares' routed parts (``tests/test_kda_hybrid.py``
adds four shares up to the whole).

With h [T, d], eps = ``rms_norm_eps``:

    h0 = embed[tokens]
    every layer:  h += Mixer(RMSNorm(h));  h += MLP(RMSNorm(h))
    KDA, per head (H heads of d_k = d_v):
        [q' | k' | v'] = x W_qkv
        q^, k^, v^ = silu(causal_depthwise_conv(.)) of each (4 taps, no bias)
        q = q^ / sqrt(|q^|^2 + 1e-6) * d_k^-1/2;  k = k^ / sqrt(|k^|^2 + 1e-6);  v = v^
        g = -exp(A_log) * softplus((x W_f_down) W_f_up + dt_bias)       [H, d_k], per channel
        beta = sigmoid(x W_beta)                                        [H]
        D = Diag(exp(g_t)) S_{t-1};  S_t = D + beta_t k_t (v_t - D^T k_t)^T;  o_t = S_t^T q_t
        out = (RMSNorm_{d_v}(o_t) * w_norm * sigmoid((x W_g_down) W_g_up)) W_out
    MLA:  q = x W_q (nope | "rope" dims, NO rotation);  [c | k_pe] = x W_kva;  c = RMSNorm(c)
          k = [c W_uk[head] | k_pe];  v = c W_uv[head];  softmax_causal(q k^T (nope + rope)^-1/2) v;  W_o
    MLP, layer 1:   W_down(silu(x W_gate) * x W_up)
    MLP, others:    s = sigmoid(x W_router);  top-k of s + bias;  w = s[chosen] / sum * factor
                    sum over the chosen experts THAT ARE HELD of w_e SwiGLU_e(x)  +  SwiGLU_shared(x)
    logits = RMSNorm(h) W_head

Departures from the published model, all of them:

- The tree stores ``q_proj``, ``k_proj`` and ``v_proj`` side by side as
  ``w_qkv`` and their three depthwise convs as one ``conv_w`` [K, 3 H d_k]
  (the checkpoint's are [channels, 1, K] each); ``kv_b_proj`` split per head
  into ``w_uk``/``w_uv``; each SwiGLU's gate and up halves apart. The
  products are the same.
- Not given by the catalog row and taken from the family's code
  (flash-linear-attention's ``kda``): the convs and ``W_g_up`` carry no bias,
  the L2 norm's eps is 1e-6, the output norm's eps is ``rms_norm_eps``.
- One routing group: the group-limited step is the identity and is not
  written.

``state_dtype`` is NOT part of the reference: it rounds S to that dtype after
every token, the DEGRADED reading that shows what a bfloat16 state would do
(the configuration holds the state in float32; PERF.md section 4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _mla(lp, cfg, x):
    T, nh = x.shape[0], cfg.num_heads
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    q = (x @ lp["wq"]).reshape(T, nh, -1)
    a = x @ lp["w_kva"]
    c = _rms(a[:, :r], lp["kv_norm"], cfg.rms_norm_eps)
    k = jnp.concatenate(
        [jnp.einsum("tc,hcn->thn", c, lp["w_uk"]),
         jnp.broadcast_to(a[:, None, r:], (T, nh, rope))], axis=-1)
    v = jnp.einsum("tc,hcv->thv", c, lp["w_uv"])
    s = jnp.einsum("thd,shd->hts", q, k) * q.shape[-1] ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shv->thv", p, v).reshape(T, -1) @ lp["wo"]


def _kda(lp, cfg, x, state_dtype=None):
    T = x.shape[0]
    H, hd, K = cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_d_conv
    qkv = x @ lp["w_qkv"]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv])
    qkv = jax.nn.silu(sum(lp["conv_w"][j] * padded[j:j + T]
                          for j in range(K)))
    q, k, v = (qkv[:, i * H * hd:(i + 1) * H * hd].reshape(T, H, hd)
               for i in range(3))
    q, k = _unit(q) * hd ** -0.5, _unit(k)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        (x @ lp["w_f_down"]) @ lp["w_f_up"] + lp["dt_bias"]).reshape(T, H, hd)
    beta = jax.nn.sigmoid(x @ lp["w_beta"])                       # [T, H]

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        D = jnp.exp(g_t)[:, :, None] * S                          # [H, k, v]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", D, k_t))
        S = D + k_t[:, :, None] * u[:, None, :]
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, hd, hd), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, lp["kda_norm"], cfg.rms_norm_eps).reshape(T, -1)
    gate = jax.nn.sigmoid((x @ lp["w_g_down"]) @ lp["w_g_up"])
    return (o * gate) @ lp["w_out"]


def _experts(lp, cfg, x):
    """The routed sum over the chosen experts that are held, plus the shared
    expert. ``lp``'s expert tensors hold experts ``cfg.experts_first`` on."""
    s = jax.nn.sigmoid(x @ lp["router"])
    _, idx = jax.lax.top_k(s + lp["router_bias"], cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    out = jnp.zeros_like(x)
    for e in range(lp["w_gate"].shape[0]):
        mine = jnp.sum(jnp.where(idx == cfg.experts_first + e, w, 0.0),
                       axis=-1, keepdims=True)                    # [T, 1]
        out = out + mine * _swiglu(x, lp["w_gate"][e], lp["w_up"][e],
                                   lp["w_down"][e])
    if "ws_gate" in lp:
        out = out + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out


def hidden_states(params, cfg, token_ids, precision="highest",
                  state_dtype=None):
    """token_ids: [T] ints of ONE sequence -> final hidden states [T, d]
    (before the last norm), float32. ``precision``: the matmul precision;
    anything but "highest" is a DEGRADED reading. A layer's weights are
    taken to float32 one layer at a time (the whole tree at once is 17 GB at
    the served cut)."""
    if any(a.dtype == jnp.int8 for a in jax.tree.leaves(params)):
        raise ValueError("the reference takes dense-precision weights, not "
                         "a quantized tree")
    tokens = jnp.asarray(token_ids, jnp.int32)
    eps, at = cfg.rms_norm_eps, {}
    with jax.default_matmul_precision(precision):
        h = jnp.asarray(params["embed"][tokens], jnp.float32)
        for i, kind in enumerate(cfg.layer_types):
            name = (("dense_" if i < cfg.num_dense_layers else "")
                    + ("layers" if kind == "attention" else "ssm_layers"))
            at[name] = at.get(name, -1) + 1
            lp = jax.tree.map(
                lambda a: jnp.asarray(a[at[name]], jnp.float32), params[name])
            x = _rms(h, lp["input_norm"], eps)
            h = h + (_mla(lp, cfg, x) if kind == "attention"
                     else _kda(lp, cfg, x, state_dtype))
            x = _rms(h, lp["post_attn_norm"], eps)
            h = h + (_experts(lp, cfg, x) if "router" in lp else
                     _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return h


def forward(params, cfg, token_ids, precision="highest", state_dtype=None):
    """token_ids: [T] -> logits [T, vocab] float32: row t is the
    distribution of token t+1 given tokens 0..t."""
    h = hidden_states(params, cfg, token_ids, precision, state_dtype)
    with jax.default_matmul_precision(precision):
        x = _rms(h, jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.rms_norm_eps)
        return x @ jnp.asarray(params["lm_head"], jnp.float32)
