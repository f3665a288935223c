"""Plain reference of ibm-granite/granite-4.0-h-micro (``granitemoehybrid``:
Mamba-2 state layers and GQA attention layers in a repeating pattern, one
SwiGLU after every mixer, four scalar multipliers, tied head).

The forward pass of one whole sequence in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: the recurrence written token by
token (``lax.scan`` over T, NO chunking), a Python loop over the layers in
``cfg.layer_types``' order, materialised attention over the full sequence,
no cache, no slots, no kernels, no batching. It takes the ENGINE's parameter
tree (``models.llama.init_params`` / ``engine.weights``), so the same seeded
weights go through both.

With h [T, d], eps = ``rms_norm_eps``, r = ``residual_multiplier``:

    h0 = embed[tokens] * embedding_multiplier
    every layer:  h += r * mixer(RMSNorm(h));  h += r * W_down(silu(x W_gate) * x W_up), x = RMSNorm(h)
    attention:    q, k, v = x W_q, x W_k, x W_v (no bias, NO positional encoding)
                  o = softmax_causal(q k^T * attention_multiplier) v;  out = o W_o
    state mixer:  z = x W_z;  xBC = x W_xbc;  dt = x W_dt
                  xBC = silu(causal_depthwise_conv1d(xBC, w, bias));  [x | B | C] = xBC
                  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t   (S: [heads, P, N], zero before token 0)
                  y_t = S_t C_t + D x_t
                  out = (RMSNorm(y * silu(z)) * w_norm) W_out
    logits = RMSNorm(h) embed^T / logits_scaling

Departures from the published model, all of them:

- The tree stores ``W_gate`` and ``W_up`` apart (the checkpoint's
  ``shared_mlp.input_linear`` is their concatenation; the loader splits it)
  and the conv's weight as [K, channels] (the checkpoint's is [channels, 1,
  K]), and the mixer's in-projection as ``w_z``, ``w_xbc`` and ``w_dt``
  (the checkpoint's ``in_proj`` is their concatenation); the products are
  the same.
- ``time_step_limit`` is (0, inf) by the family's default: the clamp of dt
  is the identity and is not written.
- ``mamba_n_groups`` is 1: B and C are shared by all heads and the gated
  norm runs over all of d_inner; another value is refused by the config.
- ``num_local_experts`` is 0: there is no router, the "shared" MLP is the
  layer's only MLP.

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


def _attention(lp, cfg, x):
    T = x.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ lp["wq"]).reshape(T, nh, hd)
    k = (x @ lp["wk"]).reshape(T, nkv, hd)
    v = (x @ lp["wv"]).reshape(T, nkv, hd)
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    s = jnp.einsum("thd,shd->hts", q, k) * cfg.attention_multiplier
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v).reshape(T, -1) @ lp["wo"]


def _state_mixer(lp, cfg, x, state_dtype=None):
    T = x.shape[0]
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    di, K = H * P, cfg.mamba_d_conv
    z, xbc, dt = x @ lp["w_z"], x @ lp["w_xbc"], x @ lp["w_dt"]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][k] * padded[k:k + T] for k in range(K)))
    xs = xbc[:, :di].reshape(T, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                       # [T, H]
    A = -jnp.exp(lp["A_log"])                                      # [H]

    def token(S, xs_t):
        x_t, B_t, C_t, dt_t = xs_t
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hpn,n->hp", S, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (xs, B, C, dt))
    y = (y + lp["D"][None, :, None] * xs).reshape(T, di)
    return _rms(y * jax.nn.silu(z), lp["ssm_norm"], cfg.rms_norm_eps) @ lp["w_out"]


def hidden_states(params, cfg, token_ids, precision="highest",
                  state_dtype=None):
    """token_ids: [T] ints of ONE sequence -> final hidden states [T, d]
    (before the last norm), float32. ``precision``: the matmul precision;
    anything but "highest" is a DEGRADED reading."""
    if any(a.dtype == jnp.int8 for a in jax.tree.leaves(params)):
        raise ValueError("the reference takes dense-precision weights, not "
                         "a quantized tree")
    tokens = jnp.asarray(token_ids, jnp.int32)
    r, eps = cfg.residual_multiplier, cfg.rms_norm_eps
    at = {"attention": 0, "mamba": 0}
    stacks = {"attention": params["layers"], "mamba": params["ssm_layers"]}
    with jax.default_matmul_precision(precision):
        h = (jnp.asarray(params["embed"][tokens], jnp.float32)
             * cfg.embedding_multiplier)
        for kind in cfg.layer_types:
            lp = jax.tree.map(
                lambda a: jnp.asarray(a[at[kind]], jnp.float32), stacks[kind])
            at[kind] += 1
            x = _rms(h, lp["input_norm"], eps)
            h = h + r * (_attention(lp, cfg, x) if kind == "attention"
                         else _state_mixer(lp, cfg, x, state_dtype))
            x = _rms(h, lp["post_attn_norm"], eps)
            h = h + r * _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h


def forward(params, cfg, token_ids, precision="highest", state_dtype=None):
    """token_ids: [T] -> logits [T, vocab] float32: row t is the
    distribution of token t+1 given tokens 0..t."""
    h = hidden_states(params, cfg, token_ids, precision, state_dtype)
    with jax.default_matmul_precision(precision):
        x = _rms(h, jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.rms_norm_eps)
        return x @ jnp.asarray(params["embed"], jnp.float32).T / cfg.logits_scaling
