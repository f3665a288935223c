"""Bytes and operations a deepseek_v3 decoder with DeepSeek Sparse Attention
and a SHARE of its routed experts needs, from the configuration's shapes
alone (glm_moe_dsa: GLM-5.2). Every layer holds latent pages (one row [c |
k_pe] a token); the layers whose ``indexer_types`` entry is "full" hold an
indexer and one index key a token beside them; attention reads the
``index_topk`` rows a query chose, whatever its context holds; the leading
layers have a dense SwiGLU, the others a router over ALL published experts,
the experts HELD here (``n_routed_experts`` of the file; the published count
is ``published.n_routed_experts``) and a shared expert; the head is untied,
over the vocabulary slice.

``cfg`` is a configuration file of this directory: the published HF keys,
cut as its ``reduced`` says. The counts are the engine's tree's, tensor by
tensor (``models.llama._init_mla_mixer``, ``_init_indexers`` and their
neighbours): ``resident_weight_bytes`` is what ``/health`` ``weight_bytes``
reads.
"""

from __future__ import annotations

from . import roofline_kda
from .roofline import _dtype_bytes


def has_indexer(cfg: dict) -> bool:
    return bool(cfg.get("index_topk")) and "indexer_types" in cfg


def index_layers(cfg: dict) -> int:
    """Layers that hold an indexer and index keys."""
    return sum(t == "full" for t in cfg["indexer_types"])


def layers_in_conditionals(cfg: dict) -> tuple:
    """(layers with an indexer, layers) whose choice a device trace shows
    as a ``conditional``: the program scans its leading dense layers and
    its expert layers as a section each, one body whose indexer runs
    behind a predicate; a section of ONE layer is no loop on the device and
    its predicate a constant, so its indexer is not inside one."""
    nd, types = cfg["first_k_dense_replace"], cfg["indexer_types"]
    seen = [t for part in (types[:nd], types[nd:]) if len(part) > 1
            for t in part]
    return sum(t == "full" for t in seen), len(seen)


def router_width(cfg: dict) -> int:
    """Experts the router scores: the published count, whatever is held."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def latent_row_elements(cfg: dict, padded: bool = True) -> int:
    """[c | k_pe] of one token in one layer; the pool stores it in whole
    128-lane tiles, and a gathered row is read as stored."""
    n = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-n // 128) * 128 if padded else n


def mla_mixer_params(cfg: dict) -> int:
    """W_qa with its norm, W_qb, W_kva with its norm, W_kvb, W_o."""
    h, nh, qr = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["q_lora_rank"])
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * qr + qr + qr * nh * (nope + rope) + h * (r + rope) + r
            + r * nh * (nope + v) + nh * v * h)


def indexer_params(cfg: dict) -> int:
    """wq_b, wk with its LayerNorm (weight and bias), weights_proj."""
    h, qr = cfg["hidden_size"], cfg["q_lora_rank"]
    H, D = cfg["index_n_heads"], cfg["index_head_dim"]
    return qr * H * D + h * D + 2 * D + h * H


def expert_params(cfg: dict) -> int:
    """One routed expert: a SwiGLU of width moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_fixed_bytes(cfg: dict, layer: int) -> int:
    """What layer ``layer`` streams whatever the routing: its attention, its
    indexer where it holds one, its two norms, and the dense SwiGLU or the
    shared expert with the router (the router and its choice bias are
    float32)."""
    it, h = _dtype_bytes(cfg), cfg["hidden_size"]
    model, f32 = mla_mixer_params(cfg) + 2 * h, 0
    if cfg["indexer_types"][layer] == "full":
        model += indexer_params(cfg)
    if layer < cfg["first_k_dense_replace"]:
        model += 3 * h * cfg["intermediate_size"]
    else:
        model += cfg["n_shared_experts"] * expert_params(cfg)
        f32 += (h + 1) * router_width(cfg)
    return model * it + f32 * 4


def _expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def resident_weight_bytes(cfg: dict) -> int:
    """Every held expert of every layer, the head and the embedding: what
    ``/health`` ``weight_bytes`` counts."""
    it = _dtype_bytes(cfg)
    total = cfg["hidden_size"] * (2 * cfg["vocab_size"] + 1) * it
    total += sum(layer_fixed_bytes(cfg, l)
                 for l in range(cfg["num_hidden_layers"]))
    return total + (_expert_layers(cfg) * cfg["n_routed_experts"]
                    * expert_params(cfg) * it)


def experts_hit_share(cfg: dict, rows: float) -> float:
    """``roofline_kda.experts_hit_share`` under this file's key names."""
    return roofline_kda.experts_hit_share(
        {"num_experts_per_token": cfg["num_experts_per_tok"],
         "num_experts": cfg["n_routed_experts"],
         "published": {"num_experts": router_width(cfg)}}, rows)


def streamed_weight_bytes(cfg: dict, rows: float) -> float:
    """HBM bytes of weights one decode step of ``rows`` rows reads once:
    every layer's fixed part, the held experts its rows reach, and the
    vocabulary slice's head (the embedding is a gather of ``rows`` rows)."""
    it = _dtype_bytes(cfg)
    hit = experts_hit_share(cfg, rows) * cfg["n_routed_experts"]
    total = cfg["hidden_size"] * (cfg["vocab_size"] + 1) * it
    total += sum(layer_fixed_bytes(cfg, l)
                 for l in range(cfg["num_hidden_layers"]))
    return total + _expert_layers(cfg) * hit * expert_params(cfg) * it


def chosen_tokens(cfg: dict, rows: float, context_tokens: float) -> float:
    """Tokens the rows attend to in one layer: each its ``index_topk`` at
    most (``context_tokens``: the sum of their contexts)."""
    if rows <= 0:
        return 0.0
    return rows * min(context_tokens / rows, cfg["index_topk"])


def chosen_rows_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """The latent rows ONE layer's decode attention must read: the rows
    chosen, as the pool stores them (1280 B each)."""
    return (chosen_tokens(cfg, rows, context_tokens)
            * latent_row_elements(cfg) * 2)


def index_key_bytes(cfg: dict, keys: float) -> float:
    """``keys`` index keys of ONE indexer, bf16."""
    return keys * cfg["index_head_dim"] * 2


def index_score_flops(cfg: dict, queries: float, visible_keys: float) -> float:
    """I[t, s] for ``queries`` tokens against ``visible_keys`` keys each:
    every index head's dot product."""
    return (queries * visible_keys * cfg["index_n_heads"]
            * cfg["index_head_dim"] * 2)


def index_least_seconds(cfg: dict, peaks: dict, rows: float,
                        context_tokens: float) -> float:
    """The least time ONE indexer's scores of a decode step can take: the
    larger of their operations at the bf16 peak and the visible index keys
    at the HBM's bandwidth."""
    per_row = context_tokens / rows if rows > 0 else 0.0
    return max(index_score_flops(cfg, rows, per_row)
               / peaks["bf16_flops_per_s"],
               index_key_bytes(cfg, context_tokens)
               / peaks["hbm_bytes_per_s"])


def decode_step_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Least HBM traffic of one decode step, whatever implements it: the
    weights its rows reach once, the chosen latent rows in every layer, and
    every visible index key in every layer that holds an indexer."""
    return (streamed_weight_bytes(cfg, rows)
            + cfg["num_hidden_layers"]
            * chosen_rows_bytes(cfg, rows, context_tokens)
            + index_layers(cfg) * index_key_bytes(cfg, context_tokens))
