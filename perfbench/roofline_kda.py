"""Bytes and operations a decoder of delta-rule (KDA) state layers, latent
attention layers and a SHARE of fine-grained experts needs, from the
configuration's shapes alone (kimi_linear: Kimi-Linear-48B-A3B). A KDA layer
keeps a fixed slot a sequence (the state [heads, d_k, d_v] in float32: the
configuration's ``assumed``; the three convs' last inputs in the model's
dtype); only the latent layers hold pages, one row [c | k_pe] a token; the
leading layer has a dense SwiGLU, the others a router over ALL published
experts, the experts HELD here (``num_experts`` of the file; the published
count is ``published.num_experts``) and a shared expert; the head is untied,
over the vocabulary slice.

``cfg`` is a configuration file of this directory: the published HF keys,
cut as its ``reduced`` says. The counts are the engine's tree's, tensor by
tensor (``models.llama._init_kda_mixer`` and its neighbours):
``resident_weight_bytes`` is what ``/health`` ``weight_bytes`` reads.
"""

from __future__ import annotations

from .roofline import _dtype_bytes

STATE_BYTES = 4     # the recurrent state is held and updated in float32


def layer_counts(cfg: dict) -> tuple:
    """(KDA layers, latent layers) among layers 1..num_hidden_layers of the
    two 1-based lists (the lists are the published model's, whole)."""
    lin, depth = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    return (sum(1 for i in lin["kda_layers"] if i <= depth),
            sum(1 for i in lin["full_attn_layers"] if i <= depth))


def router_width(cfg: dict) -> int:
    """Experts the router scores: the published count, whatever is held."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def kda_width(cfg: dict) -> int:
    """heads x head width: q, k and v alike."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def kda_mixer_params(cfg: dict) -> tuple:
    """(parameters in the model's dtype, parameters kept in float32) of one
    KDA mixer: q|k|v and out projections, the three convs, the two low-rank
    pairs, beta, the per-head norm; ``dt_bias`` a channel and ``A_log`` a
    head in float32."""
    h, lin, w = cfg["hidden_size"], cfg["linear_attn_config"], kda_width(cfg)
    hd, nh = lin["head_dim"], lin["num_heads"]
    model = (h * 3 * w + lin["short_conv_kernel_size"] * 3 * w
             + 2 * (h * hd + hd * w) + h * nh + hd + w * h)
    return model, w + nh


def mla_mixer_params(cfg: dict) -> int:
    """W_q (no q_lora), W_kva with its norm, W_kvb, W_o of one layer."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * nh * (nope + rope) + h * (r + rope) + r
            + r * nh * (nope + v) + nh * v * h)


def expert_params(cfg: dict) -> int:
    """One routed expert: a SwiGLU of width moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_fixed_bytes(cfg: dict, kda: bool, dense: bool) -> int:
    """What a layer streams whatever the routing: its mixer, its two norms,
    and the dense SwiGLU or the shared expert with the router (the router
    and its choice bias are float32)."""
    it, h = _dtype_bytes(cfg), cfg["hidden_size"]
    model, f32 = kda_mixer_params(cfg) if kda else (mla_mixer_params(cfg), 0)
    model += 2 * h
    if dense:
        model += 3 * h * cfg["intermediate_size"]
    else:
        model += cfg["num_shared_experts"] * expert_params(cfg)
        f32 += (h + 1) * router_width(cfg)
    return model * it + f32 * 4


def _layers(cfg: dict):
    """(is KDA, is dense) of each held layer, in order."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return [(i in kda, i <= cfg["first_k_dense_replace"])
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def experts_hit_share(cfg: dict, rows: float) -> float:
    """Expected share of the experts HELD that ``rows`` tokens, each choosing
    top-k of all E published experts uniformly, reach: 1 - (1 - k/E)^rows,
    the same for a held expert as for any (ISSUE 35 writes it 1 - (1 -
    1/E)^(rows k): within half a percent). The decode step's floor counts
    only these, so a dispatch that skips the unhit experts' weights cannot
    read over 100 % for it, and one that streams all of them reads lower."""
    k, e = cfg["num_experts_per_token"], router_width(cfg)
    return 1.0 - (1.0 - k / e) ** max(rows, 0.0)


def streamed_weight_bytes(cfg: dict, rows: float) -> float:
    """HBM bytes of weights one decode step of ``rows`` rows reads once:
    every layer's fixed part, the held experts its rows reach, and the
    vocabulary slice's head (the embedding is a gather of ``rows`` rows,
    not a stream)."""
    it = _dtype_bytes(cfg)
    hit = experts_hit_share(cfg, rows) * cfg["num_experts"]
    total = cfg["hidden_size"] * (cfg["vocab_size"] + 1) * it
    for kda, dense in _layers(cfg):
        total += layer_fixed_bytes(cfg, kda, dense)
        if not dense:
            total += hit * expert_params(cfg) * it
    return total


def resident_weight_bytes(cfg: dict) -> int:
    """Every held expert of every layer, the head and the embedding: what
    ``/health`` ``weight_bytes`` counts."""
    it = _dtype_bytes(cfg)
    total = cfg["hidden_size"] * (2 * cfg["vocab_size"] + 1) * it
    for kda, dense in _layers(cfg):
        total += layer_fixed_bytes(cfg, kda, dense)
        if not dense:
            total += cfg["num_experts"] * expert_params(cfg) * it
    return total


def state_bytes_per_row_layer(cfg: dict) -> int:
    """One sequence's slot in one KDA layer: the state [heads, d_k, d_v] in
    float32 and the last ``taps - 1`` inputs of the three convs in the
    model's dtype."""
    lin = cfg["linear_attn_config"]
    return (kda_width(cfg) * lin["head_dim"] * STATE_BYTES
            + (lin["short_conv_kernel_size"] - 1) * 3 * kda_width(cfg)
            * _dtype_bytes(cfg))


def state_bytes_per_seq(cfg: dict) -> int:
    return layer_counts(cfg)[0] * state_bytes_per_row_layer(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """One token's latent rows [c | k_pe] over the LATENT layers only, bf16,
    no V. What MUST be read; the pool pads the row to whole 128-lane tiles,
    which this does not count."""
    return (layer_counts(cfg)[1]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2)


def decode_step_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Least HBM traffic of one decode step, whatever implements it: the
    weights its rows reach once, every row's slot (the float32 state and the
    conv rows) read and written in every KDA layer, and the latent rows of
    the contexts in flight once a latent layer."""
    return (streamed_weight_bytes(cfg, rows)
            + 2 * rows * state_bytes_per_seq(cfg)
            + kv_bytes_per_token(cfg) * context_tokens)


def kda_update_kernel_bytes(cfg: dict, rows: float) -> float:
    """Least HBM traffic of ONE call of the state-update kernel (one KDA
    layer): each row's state read and written (2 x 2 MiB at 32 heads of 128
    x 128), and its vectors in float32: alpha, k and q a key channel, v,
    beta (repeated over its head's lanes) and o a value channel."""
    w, hd = kda_width(cfg), cfg["linear_attn_config"]["head_dim"]
    return rows * (2 * w * hd * STATE_BYTES + 6 * w * 4)


def kda_update_kernel_flops(cfg: dict, rows: float) -> float:
    """Vector operations of one call: 8 a state element (decay, two
    multiply-adds of the sums over k, the rank-one update's multiply-add):
    0.03 of the FLOPs the bytes' time would allow; the kernel is bound by
    its bytes."""
    w, hd = kda_width(cfg), cfg["linear_attn_config"]["head_dim"]
    return rows * 8.0 * w * hd
