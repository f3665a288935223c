"""Bytes and operations a latent-attention (MLA), fine-grained-expert
decoder needs, from the configuration's shapes alone. ``roofline.py`` counts
a dense decoder with K and V per head; this counts the deepseek_v3 block
(kimi-vl-a3b's language model): a cache of one row [c | k_pe] a token a
layer, routed experts of which a step streams only those its rows touch,
shared experts, leading dense layers, an untied head.

``cfg`` is a configuration file of this directory: the published HF keys.
"""

from __future__ import annotations

from .roofline import _dtype_bytes


def attention_params(cfg: dict) -> int:
    """W_q (no q_lora), W_kva, W_kvb, W_o of one layer."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * nh * (nope + rope) + h * (r + rope)
            + r * nh * (nope + v) + nh * v * h)


def expert_params(cfg: dict) -> int:
    """One routed expert: a SwiGLU of width moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_fixed_params(cfg: dict) -> int:
    """What an expert layer streams whatever the routing: attention, the
    shared experts (one SwiGLU of their summed width), the router."""
    return (attention_params(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"] * cfg["n_routed_experts"])


def dense_layer_params(cfg: dict) -> int:
    return attention_params(cfg) + 3 * cfg["hidden_size"] \
        * cfg["intermediate_size"]


def experts_touched_share(cfg: dict, rows: float) -> float:
    """Expected share of a layer's routed experts that ``rows`` tokens, each
    choosing top-k of E uniformly, touch: 1 - (1 - k/E)^rows."""
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    return 1.0 - (1.0 - k / e) ** max(rows, 0.0)


def streamed_weight_bytes(cfg: dict, rows: float) -> float:
    """HBM bytes of weights one decode step of ``rows`` rows reads once:
    the dense layers, every expert layer's fixed part and the experts its
    rows touch, and the output head (the embedding is a gather of ``rows``
    rows, not a stream)."""
    n_dense = cfg["first_k_dense_replace"]
    n_expert = cfg["num_hidden_layers"] - n_dense
    touched = experts_touched_share(cfg, rows) * cfg["n_routed_experts"]
    params = (n_dense * dense_layer_params(cfg)
              + n_expert * (expert_layer_fixed_params(cfg)
                            + touched * expert_params(cfg))
              + cfg["hidden_size"] * cfg["vocab_size"])
    return params * _dtype_bytes(cfg)


def resident_weight_bytes(cfg: dict) -> int:
    """Every expert of every layer, the head and the embedding."""
    n_dense = cfg["first_k_dense_replace"]
    n_expert = cfg["num_hidden_layers"] - n_dense
    params = (n_dense * dense_layer_params(cfg)
              + n_expert * (expert_layer_fixed_params(cfg)
                            + cfg["n_routed_experts"] * expert_params(cfg))
              + 2 * cfg["hidden_size"] * cfg["vocab_size"])
    return params * _dtype_bytes(cfg)


def kv_row_bytes(cfg: dict) -> int:
    """One token's latent row in one layer: [c | k_pe], bf16, no V. What
    MUST be read; the pool pads the row to whole 128-lane tiles, which a
    kernel that reads whole rows moves too and this does not count."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2


def kv_bytes_per_token(cfg: dict) -> int:
    return kv_row_bytes(cfg) * cfg["num_hidden_layers"]


def decode_step_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Least HBM traffic of one decode step: the weights its rows touch
    once, and every cached row of the contexts in flight once a layer (as
    key and value both)."""
    return (streamed_weight_bytes(cfg, rows)
            + kv_bytes_per_token(cfg) * context_tokens)


def latent_decode_kernel_bytes(cfg: dict, rows: float,
                               context_tokens: float) -> float:
    """Least HBM traffic of ONE call of the decode attention kernel (one
    layer): the cached rows of the contexts in flight, and each row's query
    and output at the row width per head."""
    nh = cfg["num_attention_heads"]
    return kv_row_bytes(cfg) * (context_tokens + 2 * rows * nh)


def expert_flops_per_token(cfg: dict) -> int:
    """Routed and shared expert matmuls of one token in one expert layer."""
    return 2 * expert_params(cfg) * (cfg["num_experts_per_tok"]
                                     + cfg["n_shared_experts"])


def grouped_matmul_flops(cfg: dict, rows: float) -> float:
    """One grouped expert matmul over ``rows`` routed (token, expert) rows:
    hidden x expert width either way (gate, up: [rows, h] x [h, w]; down:
    [rows, w] x [w, h])."""
    return 2.0 * rows * cfg["hidden_size"] * cfg["moe_intermediate_size"]
