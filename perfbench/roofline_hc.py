"""Bytes a deepseek_v3 decoder with residual streams needs, from the
configuration's shapes alone: xing4_0's block (latent attention behind a
LOW-RANK query, four residual streams mixed by manifold-constrained
hyper-connections around every attention and every MLP). What is unchanged
from kimi-vl's block is ``roofline_latent_moe.py``'s, imported; its
``attention_params`` counts a full-rank query (this model's is 0.23 GB
smaller over 8 layers) and knows no mixers, so the layer counts are written
again here.

``cfg`` is a configuration file of this directory: the published HF keys.
"""

from __future__ import annotations

from .roofline import _dtype_bytes
from .roofline_latent_moe import (expert_params, experts_touched_share,
                                  kv_bytes_per_token)

# Lanes a stored mixer (Phi's columns, a bias, a token's coefficient row)
# lies in: ``kubernetes_gpu_cluster_tpu/ops/hyper_conn.py``'s COLS.
HC_COLS = 128


def attention_params(cfg: dict) -> int:
    """W_qa, W_qb (the query through its latent), W_kva, W_kvb, W_o of one
    layer."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v, qr = cfg["qk_nope_head_dim"], cfg["v_head_dim"], cfg["q_lora_rank"]
    return (h * qr + qr * nh * (nope + rope) + h * (r + rope)
            + r * nh * (nope + v) + nh * v * h)


def mixer_params(cfg: dict) -> int:
    """The published 2n + n^2 columns of Phi over n d rows, of ONE
    sublayer's mixer (its three gains and 2n + n^2 biases beside them)."""
    n = cfg["hc_mult"]
    return (n * cfg["hidden_size"] + 1) * (2 * n + n * n) + 3


def mixer_stored_bytes(cfg: dict) -> int:
    """... and what the server keeps and a step streams of it: Phi in
    ``HC_COLS`` lanes in the model's dtype, the gains and the biases (in
    the same lanes) in float32."""
    n = cfg["hc_mult"]
    return (n * cfg["hidden_size"] * HC_COLS * _dtype_bytes(cfg)
            + (3 + HC_COLS) * 4)


def _layer_small_bytes(cfg: dict) -> int:
    """A layer's norms: input, post-attention, the query latent's, the kv
    latent's."""
    return (2 * cfg["hidden_size"] + cfg["q_lora_rank"]
            + cfg["kv_lora_rank"]) * _dtype_bytes(cfg)


def layer_fixed_bytes(cfg: dict, dense: bool) -> int:
    """What a layer streams whatever the routing: attention, both mixers,
    the norms, and a dense MLP or the shared experts with the float32
    router and its bias."""
    it = _dtype_bytes(cfg)
    h = cfg["hidden_size"]
    fixed = (attention_params(cfg) * it + 2 * mixer_stored_bytes(cfg)
             + _layer_small_bytes(cfg))
    if dense:
        return fixed + 3 * h * cfg["intermediate_size"] * it
    return (fixed + cfg["n_shared_experts"] * expert_params(cfg) * it
            + (h + 1) * cfg["n_routed_experts"] * 4)


def _layers(cfg: dict):
    n_dense = cfg["first_k_dense_replace"]
    return n_dense, cfg["num_hidden_layers"] - n_dense


def resident_weight_bytes(cfg: dict) -> int:
    """Every tensor the server holds: ``/health``'s ``weight_bytes``."""
    n_dense, n_expert = _layers(cfg)
    it = _dtype_bytes(cfg)
    return (n_dense * layer_fixed_bytes(cfg, True)
            + n_expert * (layer_fixed_bytes(cfg, False)
                          + cfg["n_routed_experts"] * expert_params(cfg) * it)
            + (2 * cfg["vocab_size"] + 1) * cfg["hidden_size"] * it)


def streamed_weight_bytes(cfg: dict, rows: float) -> float:
    """HBM bytes of weights one decode step of ``rows`` rows reads once:
    every layer's fixed part, the experts its rows touch in expectation,
    and the output head (the embedding is a gather of ``rows`` rows)."""
    n_dense, n_expert = _layers(cfg)
    it = _dtype_bytes(cfg)
    touched = experts_touched_share(cfg, rows) * cfg["n_routed_experts"]
    return (n_dense * layer_fixed_bytes(cfg, True)
            + n_expert * (layer_fixed_bytes(cfg, False)
                          + touched * expert_params(cfg) * it)
            + cfg["hidden_size"] * cfg["vocab_size"] * it)


def hc_pre_bytes(cfg: dict, tokens: float) -> float:
    """Least HBM traffic of ONE ``hc_pre`` call: every token's n streams
    read and its mix written, in the model's dtype; Phi, the gains and the
    biases read; a float32 coefficient row a token written."""
    n, d, it = cfg["hc_mult"], cfg["hidden_size"], _dtype_bytes(cfg)
    return (tokens * (n * d + d) * it + mixer_stored_bytes(cfg)
            + tokens * HC_COLS * 4)


def hc_post_bytes(cfg: dict, tokens: float) -> float:
    """... and of ONE ``hc_post`` call: the streams read and written, the
    sublayer's result read, the coefficient rows read."""
    n, d, it = cfg["hc_mult"], cfg["hidden_size"], _dtype_bytes(cfg)
    return tokens * (2 * n * d + d) * it + tokens * HC_COLS * 4


def stream_step_bytes(cfg: dict, tokens: float) -> float:
    """What the mixers of one step move beside their weights (those are in
    ``streamed_weight_bytes``): two sublayers a layer."""
    per = (hc_pre_bytes(cfg, tokens) - mixer_stored_bytes(cfg)
           + hc_post_bytes(cfg, tokens))
    return 2 * cfg["num_hidden_layers"] * per


def decode_step_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Least HBM traffic of one decode step: the weights its rows touch
    once, every cached row of the contexts in flight once a layer, and the
    rows' streams through the mixers."""
    return (streamed_weight_bytes(cfg, rows)
            + kv_bytes_per_token(cfg) * context_tokens
            + stream_step_bytes(cfg, rows))
