"""Bytes and operations a hybrid decoder of state-space (Mamba-2) layers and
GQA attention layers needs, from the configuration's shapes alone.
``roofline.py`` counts a dense decoder whose every layer holds K and V; this
counts the granitemoehybrid block (granite-4.0-h-micro): ``layer_types``
says which layers are state layers, a state layer keeps a fixed slot of
recurrent state a sequence (float32: the configuration's ``assumed``) and
the conv's last inputs (the model's dtype), only the attention layers hold
pages, every layer has one SwiGLU, the head is the tied embedding.

``cfg`` is a configuration file of this directory: the published HF keys.
"""

from __future__ import annotations

from .roofline import _dtype_bytes

STATE_BYTES = 4     # the recurrent state is held and updated in float32


def layer_counts(cfg: dict) -> tuple:
    """(state layers, attention layers) of ``layer_types``."""
    types = cfg["layer_types"]
    return types.count("mamba"), types.count("attention")


def d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_channels(cfg: dict) -> int:
    """[x | B | C]: what the depthwise conv runs over."""
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mlp_params(cfg: dict) -> int:
    """The one SwiGLU every layer has (``shared_intermediate_size``)."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def state_mixer_params(cfg: dict) -> int:
    """In-projection [z | xBC | dt], out-projection, and the small tensors:
    the conv's weight and bias, dt_bias, A_log, D, the gated norm."""
    h, di, c = cfg["hidden_size"], d_inner(cfg), conv_channels(cfg)
    nh = cfg["mamba_n_heads"]
    return (h * (di + c + nh) + di * h
            + c * (cfg["mamba_d_conv"] + 1) + 3 * nh + di)


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o of one attention layer (no bias)."""
    h, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = h // nh
    return 2 * h * nh * hd + 2 * h * nkv * hd


def layer_params(cfg: dict, kind: str) -> int:
    """One layer of ``kind``: its mixer, its SwiGLU, its two norms."""
    mixer = state_mixer_params(cfg) if kind == "mamba" \
        else attention_params(cfg)
    return mixer + mlp_params(cfg) + 2 * cfg["hidden_size"]


def resident_weight_bytes(cfg: dict) -> int:
    """Every layer by its type, the final norm and ONE embedding table (the
    head is tied to it), in the model's dtype; a state layer's dt_bias,
    A_log and D are kept in float32."""
    n_state, n_attn = layer_counts(cfg)
    params = (n_state * layer_params(cfg, "mamba")
              + n_attn * layer_params(cfg, "attention")
              + cfg["hidden_size"] * (cfg["vocab_size"] + 1))
    return (params * _dtype_bytes(cfg)
            + n_state * 3 * cfg["mamba_n_heads"] * (4 - _dtype_bytes(cfg)))


def streamed_weight_bytes(cfg: dict) -> int:
    """HBM bytes of weights one decode step reads once: all of them. The
    embedding is read whole as the tied head; its gather of a row a
    sequence is not counted beside that."""
    return resident_weight_bytes(cfg)


def state_bytes_per_row_layer(cfg: dict) -> int:
    """One sequence's slot in one state layer: the recurrent state
    [heads, head width, state] in float32 and the conv's last
    ``d_conv - 1`` inputs in the model's dtype."""
    return (d_inner(cfg) * cfg["mamba_d_state"] * STATE_BYTES
            + (cfg["mamba_d_conv"] - 1) * conv_channels(cfg)
            * _dtype_bytes(cfg))


def state_bytes_per_seq(cfg: dict) -> int:
    return layer_counts(cfg)[0] * state_bytes_per_row_layer(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over the ATTENTION layers only; bf16 pool."""
    nkv = cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return layer_counts(cfg)[1] * 2 * nkv * hd * 2


def decode_step_bytes(cfg: dict, rows: float, context_tokens: float) -> float:
    """Least HBM traffic of one decode step: the weights once, every row's
    slot read and written in every state layer, and the attention layers'
    K and V of the contexts in flight."""
    return (streamed_weight_bytes(cfg)
            + 2 * rows * state_bytes_per_seq(cfg)
            + kv_bytes_per_token(cfg) * context_tokens)


def ssm_update_kernel_bytes(cfg: dict, rows: float) -> float:
    """Least HBM traffic of ONE call of the state-update kernel (one state
    layer): each row's recurrent state read and written, its decay and
    dt * x vectors and its y at d_inner float32 each, its B and C."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    return rows * (2 * di * n * STATE_BYTES + (3 * di + 2 * n) * 4)


def scan_flops_per_token(cfg: dict) -> int:
    """The chunked scan of one token in ONE state layer at the published
    chunk Q: its row of C B^T (2 Q N), its row of the decay-masked product
    against x (2 Q d_inner), its share of the chunk's state increment B^T
    (w x) and its read of the carried state C S (2 N d_inner each)."""
    q, n, di = cfg["mamba_chunk_size"], cfg["mamba_d_state"], d_inner(cfg)
    return 2 * q * n + 2 * q * di + 4 * n * di


def matmul_flops_per_token(cfg: dict) -> int:
    """2 FLOPs a multiply-add over every weight matrix a token meets
    (projections, SwiGLUs, the head); the scan and attention are extra."""
    n_state, n_attn = layer_counts(cfg)
    h, di, c = cfg["hidden_size"], d_inner(cfg), conv_channels(cfg)
    state = h * (di + c + cfg["mamba_n_heads"]) + di * h
    return 2 * (n_state * (state + mlp_params(cfg))
                + n_attn * (attention_params(cfg) + mlp_params(cfg))
                + h * cfg["vocab_size"])
