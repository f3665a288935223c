"""Bytes a decoder that generates by diffusion over blocks needs, from the
configuration's shapes alone (sdar_moe: SDAR-30B-A3B-Chat): qk-norm GQA
layers, each with a softmax router over ``num_experts`` experts of width
``moe_intermediate_size`` (no shared expert, no dense layer), an untied
head. A PASS runs ``block_length`` positions a row over the row's pages: it
is this model's decode step.

``cfg`` is a configuration file of this directory: the published HF keys,
cut as its ``reduced`` says. The counts are the engine's tree's, tensor by
tensor (``models.llama.init_params``): ``resident_weight_bytes`` is what
``/health`` ``weight_bytes`` reads.
"""

from __future__ import annotations

from .roofline import _dtype_bytes
from .roofline_kda import experts_hit_share


def block_length(cfg: dict) -> int:
    return int(cfg["block_length"])


def layer_fixed_params(cfg: dict) -> int:
    """What a layer streams whatever the routing: q, k, v, o, the two
    norms, the per-head q and k norms, the router."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * h * nh * hd + 2 * h * nkv * hd + 2 * h + 2 * hd
            + h * cfg["num_experts"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def resident_weight_bytes(cfg: dict) -> int:
    """Every expert of every layer, the embedding, the head, the last
    norm: ``/health`` ``weight_bytes``."""
    per_layer = layer_fixed_params(cfg) + cfg["num_experts"] * expert_params(cfg)
    return _dtype_bytes(cfg) * (
        cfg["num_hidden_layers"] * per_layer
        + cfg["hidden_size"] * (2 * cfg["vocab_size"] + 1))


def streamed_weight_bytes(cfg: dict, rows: float) -> float:
    """HBM bytes of weights one pass over ``rows`` rows reads once: every
    layer's fixed part, the experts its rows x block_length positions reach
    in expectation (each choosing top-k of E uniformly), the head (the
    embedding is a gather, not a stream)."""
    hit = experts_hit_share(
        {"num_experts_per_token": cfg["num_experts_per_tok"],
         "num_experts": cfg["num_experts"]},
        rows * block_length(cfg)) * cfg["num_experts"]
    per_layer = layer_fixed_params(cfg) + hit * expert_params(cfg)
    return _dtype_bytes(cfg) * (
        cfg["num_hidden_layers"] * per_layer
        + cfg["hidden_size"] * (cfg["vocab_size"] + 1))


def kv_bytes_per_token(cfg: dict) -> int:
    """One token's K and V over every layer, in the model's dtype."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * _dtype_bytes(cfg))


def pass_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Least HBM traffic of one pass, whatever implements it: the weights
    its positions reach once and the K and V of the contexts in flight once
    a layer. (A commit's page write, block_length rows a sequence, is a
    thousandth of that and is left out: the floor stays a floor.)"""
    return (streamed_weight_bytes(cfg, rows)
            + kv_bytes_per_token(cfg) * context_tokens)


def block_attend_kernel_bytes(cfg: dict, rows: float,
                              context_tokens: float) -> float:
    """Least HBM traffic of ONE call of the block-attend kernel (one layer
    of one pass): every row's valid pages, WHOLE, K and V (a row of mean
    context c holds c / 128 + 1/2 pages in expectation)."""
    if rows <= 0:
        return 0.0
    page = cfg["warmup"]["page_size"]
    return ((context_tokens / page + rows / 2) * 2 * page
            * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _dtype_bytes(cfg))
