"""Decoder-only transformer for serving: llama-class dense + mixtral-class MoE.

One config-driven implementation covers every family the framework serves
(Llama 1/2/3, TinyLlama, Qwen2/2.5 [attention bias], Qwen3 [qk-norm],
Mixtral [sparse MoE]) — the model set the reference deployed through vLLM
images (reference ``values-01-minimal-example*.yaml`` modelURL fields) plus the
BASELINE.json north-star models.

TPU-first design decisions:
- Pure functions over a params pytree; layer weights are **stacked** with a
  leading ``[L, ...]`` axis and the layer loop is a ``lax.scan`` — one traced
  layer body regardless of depth (compile time O(1) in L), and the paged KV
  pool's ``[L, ...]`` leading axis threads through the scan as xs/ys.
- ONE entry point, ``forward``, over one flattened token axis
  ``[segment tokens | row tokens]`` (``StepMeta``): prompt tokens that are
  causal within their segment, and running sequences' tokens against the
  paged cache. Every step program of the serving loop is a case of it. New
  K/V are scattered into the page pool via precomputed slot mappings
  (padding slots land in the scrap page).
- Matmuls stay in model dtype (bf16) with fp32 accumulation on the MXU
  (``preferred_element_type``); norms/softmax in fp32.
- Only the hidden states that feed sampling are projected to logits
  (``logits_indices``), so the ``[*, vocab]`` matmul runs on B rows, not T.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..engine.kv_cache import KVCache
from ..ops import quant as quant_ops
from ..ops import dsa
from ..ops import hyper_conn
from ..ops import ssm as ssm_ops
from ..ops.rope import apply_rope, rope_cos_sin
from ..ops.attention import NO_KERNELS, Kernels
from ..ops.pallas import grouped_matmul as gm

Params = dict[str, Any]


class StepMeta(NamedTuple):
    """Metadata of one step over ONE padded token axis
    ``T = [segment tokens | row tokens]``; either part may be absent.

    - The **segment part** (``seg_ids`` given) is prompt tokens, causal
      within their segment: whole prompts packed side by side, or, with
      ``chunk_page_table``/``hist_len``, ONE sequence's chunk that also
      attends to what it already has in the pool.
    - The **row part** (``page_tables`` given) is the running sequences'
      tokens against their pages: ``forward``'s static ``row_width`` tokens
      a sequence (1: decode; ``S = k + 1``: a slice of draft verification;
      ``2 * block_length``: a block model's pass).

    The split is static per compiled shape: the row part is the last
    ``page_tables.shape[0] * row_width`` tokens. The two parts' sequences
    are disjoint and each addresses only its own pages, so no attention
    crosses the split."""
    # In the order of the step programs' packed int buffer. ``positions`` and
    # ``slot_mapping`` are always given; the rest say which parts exist.
    # [T] int32 segment id per token, -1 = padding (a chunk's tokens carry
    # 0); only the segment part's entries are read.
    seg_ids: Optional[jax.Array] = None
    # [T] int32 global positions (RoPE input).
    positions: Optional[jax.Array] = None
    # [T] int32 flat KV slot of each token (padding -> the scrap page).
    slot_mapping: Optional[jax.Array] = None
    # [B] int32 the tokens whose hidden state is returned; None: every one.
    logits_indices: Optional[jax.Array] = None
    # A chunk with history: [hist_width] int32 its sequence's pages, and
    # [] int32 how many of its tokens the pool already holds.
    chunk_page_table: Optional[jax.Array] = None
    hist_len: Optional[jax.Array] = None
    # The rows: [R, pages] int32 page ids (pad = scrap page), and [R] int32
    # committed tokens including the row's first.
    page_tables: Optional[jax.Array] = None
    context_lens: Optional[jax.Array] = None
    # A state model's slots (engine/kv_cache.py): [S] int32 the slot of
    # segment s (S is static: the most segments the step can hold; absent
    # ones name the scrap slot 0), and [R] int32 the slot of each row
    # (padding rows: the scrap slot).
    seg_slots: Optional[jax.Array] = None
    row_slots: Optional[jax.Array] = None
    # A block model's rows are two blocks wide, [the block awaiting its
    # commit | the open block] (engine/block.py): [R] bool, False where a
    # row has none awaiting and its second block is padding.
    row_wide: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype: Optional[jnp.dtype] = None) -> Params:
    """Random-init params (bench/tests; real weights come from engine.weights).
    Layout: stacked [L, ...] per-layer tensors + embed/final_norm/lm_head."""
    dtype = dtype or cfg.jnp_dtype

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    if cfg.quantization is not None:
        if cfg.quantization not in quant_ops.QUANT_METHODS:
            raise ValueError(
                f"unsupported quantization {cfg.quantization!r} "
                f"(one of {quant_ops.QUANT_METHODS})")
        return _init_params_quant(cfg, key, dtype, w)

    if cfg.is_mla or cfg.num_dense_layers or cfg.num_shared_experts:
        return _init_params_deepseek(cfg, key, dtype, w)

    d, L = cfg.hidden_size, cfg.num_kv_layers
    nh, nkv, hd, ff = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size
    E = cfg.num_experts
    keys = iter(jax.random.split(key, 16))

    layers: Params = {
        "input_norm": jnp.ones((L, d), dtype),
        "post_attn_norm": jnp.ones((L, d), dtype),
        "wq": w(next(keys), (L, d, nh * hd), d),
        "wk": w(next(keys), (L, d, nkv * hd), d),
        "wv": w(next(keys), (L, d, nkv * hd), d),
        "wo": w(next(keys), (L, nh * hd, d), nh * hd),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, nh * hd), dtype)
        layers["bk"] = jnp.zeros((L, nkv * hd), dtype)
        layers["bv"] = jnp.zeros((L, nkv * hd), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, hd), dtype)
        layers["k_norm"] = jnp.ones((L, hd), dtype)
    if cfg.is_moe and cfg.moe_intermediate_size:
        # qwen3_moe's class (sdar_moe): experts of a width of their own,
        # 128 of them a layer, drawn a layer at a time (``_stacked_normal``).
        ffe = cfg.moe_intermediate_size
        layers["router"] = w(next(keys), (L, d, E), d)
        layers["w_gate"] = _stacked_normal(next(keys), (L, E, d, ffe), d,
                                           dtype)
        layers["w_up"] = _stacked_normal(next(keys), (L, E, d, ffe), d, dtype)
        layers["w_down"] = _stacked_normal(next(keys), (L, E, ffe, d), ffe,
                                           dtype)
    elif cfg.is_moe:
        layers["router"] = w(next(keys), (L, d, E), d)
        layers["w_gate"] = w(next(keys), (L, E, d, ff), d)
        layers["w_up"] = w(next(keys), (L, E, d, ff), d)
        layers["w_down"] = w(next(keys), (L, E, ff, d), ff)
    else:
        if cfg.mlp_type != "mlp":
            layers["w_gate"] = w(next(keys), (L, d, ff), d)
        layers["w_up"] = w(next(keys), (L, d, ff), d)
        layers["w_down"] = w(next(keys), (L, ff, d), ff)
    _add_opt_extras(cfg, layers, dtype)

    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), dtype),
        "layers": layers,
    }
    if cfg.norm_type == "layernorm":
        params["final_norm_b"] = jnp.zeros((d,), dtype)
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = w(next(keys), (cfg.max_model_len + 2, d), d)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (d, cfg.vocab_size), d)
    if cfg.has_state:
        params["ssm_layers"] = _init_state_layers(cfg, next(keys), dtype, w)
    return params


def _init_swiglu(L: int, d: int, ff: int, keys, w) -> Params:
    return {"w_gate": w(next(keys), (L, d, ff), d),
            "w_up": w(next(keys), (L, d, ff), d),
            "w_down": w(next(keys), (L, ff, d), ff)}


def _init_mamba_mixer(cfg: ModelConfig, S: int, keys, w, dtype) -> Params:
    """``S`` stacked Mamba-2 mixers with their layer's two norms.
    The mixer's in-projection is stored in three pieces, ``w_z`` [d,
    d_inner], ``w_xbc`` [d, d_inner + 2 N] (columns [x | B | C]) and
    ``w_dt`` [d, heads]: the checkpoint's ``in_proj`` is their
    concatenation. (Whole, its 8512 columns are not whole 128-lane tiles:
    the chip keeps it transposed and every step program copies 1.26 GB
    back. And z is consumed at the gate, xBC at once: as one product of a
    2 k-token step XLA computed it twice rather than keep it.)
    ``conv_w`` is [K, channels], tap K-1 on the token itself. The recurrence's own parameters are drawn as
    ``mamba_ssm`` initialises them, so that the state neither dies nor
    explodes over thousands of tokens: ``A_log = log U(1, 16)``, ``dt_bias =
    softplus^-1(log-uniform(1e-3, 1e-1))``, ``D = 1``, the conv (weight and
    bias) uniform in +-1/2 (torch's conv1d default at a fan-in of 4). Those
    three vectors a head stay float32, whatever the model's dtype."""
    d = cfg.hidden_size
    H, di, C = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    K = cfg.mamba_d_conv
    u = jax.random.uniform
    dt = jnp.exp(u(next(keys), (S, H), jnp.float32,
                   jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "input_norm": jnp.ones((S, d), dtype),
        "post_attn_norm": jnp.ones((S, d), dtype),
        "w_z": w(next(keys), (S, d, di), d),
        "w_xbc": w(next(keys), (S, d, C), d),
        "w_dt": w(next(keys), (S, d, H), d),
        "conv_w": u(next(keys), (S, K, C), jnp.float32, -0.5, 0.5
                    ).astype(dtype),
        "conv_b": u(next(keys), (S, C), jnp.float32, -0.5, 0.5).astype(dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(u(next(keys), (S, H), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((S, H), jnp.float32),
        "ssm_norm": jnp.ones((S, di), dtype),
        "w_out": w(next(keys), (S, di, d), di),
    }


def _init_kda_mixer(cfg: ModelConfig, S: int, keys, w, dtype) -> Params:
    """``S`` stacked KDA (gated delta-rule) mixers with their layer's two
    norms. ``w_qkv`` [d, 3 H d_k] is the checkpoint's q, k and v projections
    side by side and ``conv_w`` [K, 3 H d_k] its three depthwise convs
    likewise (tap K-1 on the token itself; no bias): one product and one
    conv over whole 128-lane tiles. The decay gate and the output gate each
    pass through a low-rank pair of ``kda_head_dim``. The recurrence's own
    parameters are drawn as flash-linear-attention initialises them, so that
    the state neither dies nor explodes over thousands of tokens: ``A_log =
    log U(1, 16)`` a head, ``dt_bias = softplus^-1(log-uniform(1e-3, 1e-1))``
    a channel, both float32 whatever the model's dtype; the convs uniform in
    +-1/2 (torch's conv1d default at a fan-in of 4)."""
    d, H, hd, K = (cfg.hidden_size, cfg.kda_n_heads, cfg.kda_head_dim,
                   cfg.kda_d_conv)
    u = jax.random.uniform
    dt = jnp.exp(u(next(keys), (S, H * hd), jnp.float32,
                   jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "input_norm": jnp.ones((S, d), dtype),
        "post_attn_norm": jnp.ones((S, d), dtype),
        "w_qkv": w(next(keys), (S, d, 3 * H * hd), d),
        "conv_w": u(next(keys), (S, K, 3 * H * hd), jnp.float32, -0.5, 0.5
                    ).astype(dtype),
        "w_f_down": w(next(keys), (S, d, hd), d),
        "w_f_up": w(next(keys), (S, hd, H * hd), hd),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(u(next(keys), (S, H), jnp.float32, 1.0, 16.0)),
        "w_beta": w(next(keys), (S, d, H), d),
        "w_g_down": w(next(keys), (S, d, hd), d),
        "w_g_up": w(next(keys), (S, hd, H * hd), hd),
        "kda_norm": jnp.ones((S, hd), dtype),
        "w_out": w(next(keys), (S, H * hd, d), H * hd),
    }


def _init_mla_mixer(cfg: ModelConfig, L: int, keys, w, dtype) -> Params:
    """``L`` stacked latent-attention mixers with their layer's two norms.
    ``w_uk``/``w_uv`` are ``kv_b_proj`` split per head into its key and
    value halves ([nh, r, nope] and [nh, r, v]): the absorbed form contracts
    them with q and with the output per head. With ``q_lora_rank`` the query
    passes through a latent with a norm of its own (``w_qa``, ``q_a_norm``,
    ``w_qb``: deepseek_v3's ``q_a_proj``, ``q_a_layernorm``, ``q_b_proj``)
    and there is no ``wq``."""
    d, nh = cfg.hidden_size, cfg.num_heads
    r, nope, rope, vd = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    qr = cfg.q_lora_rank
    query = ({"wq": w(next(keys), (L, d, nh * (nope + rope)), d)} if not qr
             else {"w_qa": w(next(keys), (L, d, qr), d),
                   "q_a_norm": jnp.ones((L, qr), dtype),
                   "w_qb": w(next(keys), (L, qr, nh * (nope + rope)), qr)})
    return {
        "input_norm": jnp.ones((L, d), dtype),
        "post_attn_norm": jnp.ones((L, d), dtype),
        **query,
        "w_kva": w(next(keys), (L, d, r + rope), d),
        "kv_norm": jnp.ones((L, r), dtype),
        "w_uk": w(next(keys), (L, nh, r, nope), r),
        "w_uv": w(next(keys), (L, nh, r, vd), r),
        "wo": w(next(keys), (L, nh * vd, d), nh * vd),
    }


def _init_indexers(cfg: ModelConfig, keys, w, dtype) -> Params:
    """The ``indexer`` stack: one entry a "full" layer
    (``cfg.index_layers``), in the layers' order, whichever weight stack the
    layer itself lies in. ``wq_b`` [q_lora_rank, H * D] reads the query
    latent, ``wk`` [d, D] and ``w_w`` [d, H] the layer's normed input; the
    key's LayerNorm has a bias (``ops/dsa.py``)."""
    n, d, qr = len(cfg.index_layers), cfg.hidden_size, cfg.q_lora_rank
    H, D = cfg.index_n_heads, cfg.index_head_dim
    return {"wq_b": w(next(keys), (n, qr, H * D), qr),
            "wk": w(next(keys), (n, d, D), d),
            "k_norm": jnp.ones((n, D), dtype),
            "k_norm_b": jnp.zeros((n, D), dtype),
            "w_w": w(next(keys), (n, d, H), d)}


def _init_experts(cfg: ModelConfig, L: int, keys, w, dtype) -> Params:
    """``L`` stacked DeepSeek-V3-class expert MLPs: the router over ALL
    ``num_experts`` (float32) with its choice bias, drawn small and NOT zero
    (N(0, 0.01)), so that choosing by score + bias and weighing by the raw
    score differ; the experts this process HOLDS (``num_local_experts``:
    an absent expert's weights are never allocated); the shared experts as
    one SwiGLU of their summed width."""
    d, E, ffe = cfg.hidden_size, cfg.num_experts, cfg.expert_width
    held, ffs = cfg.num_local_experts, cfg.num_shared_experts * ffe
    mlp = {
        "router": w(next(keys), (L, d, E), d).astype(jnp.float32),
        "router_bias": 0.01 * jax.random.normal(next(keys), (L, E),
                                                jnp.float32),
        "w_gate": _stacked_normal(next(keys), (L, held, d, ffe), d, dtype),
        "w_up": _stacked_normal(next(keys), (L, held, d, ffe), d, dtype),
        "w_down": _stacked_normal(next(keys), (L, held, ffe, d), ffe, dtype),
    }
    if ffs:
        mlp["ws_gate"] = w(next(keys), (L, d, ffs), d)
        mlp["ws_up"] = w(next(keys), (L, d, ffs), d)
        mlp["ws_down"] = w(next(keys), (L, ffs, d), ffs)
    return mlp


_MIXER_INITS = {"mamba": _init_mamba_mixer, "kda": _init_kda_mixer}

HC_SITES = ("attn", "mlp")      # a layer's mixer, its MLP


def _init_stream_mixers(cfg: ModelConfig, L: int, keys, dtype) -> Params:
    """``L`` layers' stream mixers, one a sublayer (``ops/hyper_conn.py``):
    ``hc_<site>_phi`` [L, n d, COLS] in the model's dtype, the norm's gain
    (1 in this draw) folded in; ``hc_<site>_alpha`` [L, 3] and
    ``hc_<site>_bias`` [L, COLS] float32. The draw is chosen so that all
    three maps move the result and twenty Sinkhorn rounds do converge: Phi ~
    N(0, 1 / (n d)), alpha = (1, 1, 1/4), b_pre and b_post ~ N(0, 1), b_res
    = I + N(0, 1/4). (A stronger diagonal converges more slowly: at 3 I +
    N(0, 1) under alpha 1 the columns still miss 1 by 3e-2 after 20.)"""
    n, nd = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
    normal = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)
    out: Params = {}
    for site in HC_SITES:
        phi = normal((L, nd, 2 * n + n * n)) * (nd ** -0.5)
        out[f"hc_{site}_phi"] = hyper_conn.pack(
            phi[..., :n], phi[..., n:2 * n],
            phi[..., 2 * n:].reshape(L, nd, n, n)).astype(dtype)
        out[f"hc_{site}_alpha"] = jnp.tile(
            jnp.array([1.0, 1.0, 0.25], jnp.float32), (L, 1))
        out[f"hc_{site}_bias"] = hyper_conn.pack(
            normal((L, n)), normal((L, n)),
            jnp.eye(n) + 0.5 * normal((L, n, n)))
    return out


def _init_state_layers(cfg: ModelConfig, key: jax.Array, dtype, w) -> Params:
    """Random init of a dense model's state layers ``[num_state_layers,
    ...]``, each mixer with its dense MLP (the attention layers of a typed
    model are ``layers``, as everywhere)."""
    if cfg.is_moe or cfg.is_mla or cfg.quantization is not None:
        raise ValueError(
            f"{cfg.name}: state layers beside dense-precision GQA attention "
            "layers come with dense MLPs only")
    keys = iter(jax.random.split(key, 12))
    S = cfg.num_state_layers
    return {**_MIXER_INITS[cfg.state_kind](cfg, S, keys, w, dtype),
            **_init_swiglu(S, cfg.hidden_size, cfg.intermediate_size, keys, w)}


def _stacked_normal(key, shape, fan_in: int, dtype) -> jax.Array:
    """``w(key, shape, fan_in)`` for a stacked ``[L, ...]`` tensor too large
    to draw at once: the float32 draw of all 64 experts of 8 layers is 5.9 GB
    beside 11 GB of weights on a 16 GB chip. One layer at a time into a
    donated buffer."""
    buf = jnp.zeros(shape, dtype)

    @functools.partial(jax.jit, donate_argnums=0)
    def put(buf, k, l):
        x = jax.random.normal(k, shape[1:], jnp.float32) * (fan_in ** -0.5)
        return jax.lax.dynamic_update_index_in_dim(buf, x.astype(dtype), l, 0)

    for l, k in enumerate(jax.random.split(key, shape[0])):
        buf = put(buf, k, l)
    return buf


def layer_stacks(cfg: ModelConfig) -> dict:
    """The weight stacks of a model and how many layers each holds, in the
    order the layers run: a layer's weights (its mixer's AND its MLP's) lie
    in ``layers`` (attention) or ``ssm_layers`` (a state mixer), the
    leading dense layers of an expert model in ``dense_layers`` /
    ``dense_ssm_layers``. A section's tail continues its period's stacks."""
    stacks = {}
    for types, repeats, dense in cfg.layer_sections:
        pre = "dense_" if dense and cfg.is_moe else ""
        for kind in types:
            name = pre + ("layers" if kind == "attention" else "ssm_layers")
            stacks[name] = stacks.get(name, 0) + repeats
    return stacks


def _init_params_deepseek(cfg: ModelConfig, key: jax.Array, dtype, w) -> Params:
    """Random init of the trees whose layers pair a mixer with an MLP of
    another make: the DeepSeek-V3-class tree (kimi-vl-a3b's language
    model), ``dense_layers`` (the ``first_k_dense_replace`` leading layers,
    stacked) beside ``layers`` (the expert layers, stacked), each with the
    latent-attention tensors; and kimi-linear's, where a layer of either
    group may be a KDA state layer instead (``dense_ssm_layers`` /
    ``ssm_layers``: ``layer_stacks``)."""
    if not (cfg.is_mla and cfg.is_moe):
        raise ValueError(
            f"{cfg.name}: latent attention, leading dense layers and shared "
            "experts are served together (the deepseek_v3 block) or not at all")
    if cfg.quantization is not None:
        raise ValueError(
            f"--quantization {cfg.quantization} with a latent-attention "
            "model: the absorbed projections and the grouped expert matmuls "
            "have no int8/int4 path")
    d = cfg.hidden_size
    keys = iter(jax.random.split(
        key, 96 if cfg.hc_mult > 1 else 64 if cfg.has_state else 32))
    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), dtype),
    }
    for name, L in layer_stacks(cfg).items():
        mixer = (_MIXER_INITS[cfg.state_kind] if "ssm" in name
                 else _init_mla_mixer)
        params[name] = {
            **mixer(cfg, L, keys, w, dtype),
            **(_init_swiglu(L, d, cfg.intermediate_size, keys, w)
               if name.startswith("dense_")
               else _init_experts(cfg, L, keys, w, dtype)),
            **(_init_stream_mixers(cfg, L, keys, dtype)
               if cfg.hc_mult > 1 else {})}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (d, cfg.vocab_size), d)
    if cfg.index_topk:
        params["indexer"] = _init_indexers(cfg, keys, w, dtype)
    return params


def _add_opt_extras(cfg: ModelConfig, layers: Params, dtype) -> None:
    """Per-layer OPT-class extras: LayerNorm biases and linear biases."""
    d, L, ff = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size
    if cfg.norm_type == "layernorm":
        layers["input_norm_b"] = jnp.zeros((L, d), dtype)
        layers["post_attn_norm_b"] = jnp.zeros((L, d), dtype)
    if cfg.linear_bias:
        layers["bo"] = jnp.zeros((L, d), dtype)
        layers["b_up"] = jnp.zeros((L, ff), dtype)
        layers["b_down"] = jnp.zeros((L, d), dtype)


def _init_params_quant(cfg: ModelConfig, key: jax.Array, dtype, w) -> Params:
    """Random-init directly in the quantized layout (same pytree structure
    as quantize_params output). Materializing the full bf16 model first and
    quantizing after — the naive path — peaks at 2x the bf16 footprint, which
    OOMs an 8B model on a 16 GB chip; random-init weights are synthetic
    anyway (bench/tests), so the big matmul weights are drawn in their
    quantized storage directly with a constant fan-in scale and nothing
    large ever exists in bf16. int4 draws the PACKED bytes (each holding
    two uniform nibbles), so the init's peak footprint is the packed
    half-size buffer. Real checkpoints quantize tensor-by-tensor at load
    (engine/weights.py)."""
    d, L = cfg.hidden_size, cfg.num_layers
    nh, nkv, hd, ff = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size
    E = cfg.num_experts
    gs = cfg.quant_group_size
    keys = iter(jax.random.split(key, 24))

    def wq8(key, shape, fan_in):
        if cfg.quantization == "int4":
            # Uniform random bytes = two uniform [-8, 7] nibbles each;
            # dequant std ~= 4.6 * scale ~= 0.66 * fan_in^-0.5 — same
            # magnitude class as the bf16 init, quality irrelevant for
            # random weights.
            din = shape[-2]
            if din % gs:
                raise ValueError(f"int4 input dim {din} not divisible by "
                                 f"quant_group_size {gs}")
            packed = jax.random.randint(
                key, shape[:-2] + (din // 2,) + shape[-1:], -128, 128,
                jnp.int8)
            scale = jnp.full(shape[:-2] + (din // gs,) + shape[-1:],
                             fan_in ** -0.5 / 7.0, jnp.float32)
            return packed, scale
        # dequant std ~= 73 * scale ~= 0.57 * fan_in^-0.5: same magnitude
        # class as the bf16 init; quality is irrelevant for random weights.
        q = jax.random.randint(key, shape, -127, 128, jnp.int8)
        scale = jnp.full(shape[:-2] + shape[-1:], fan_in ** -0.5 / 127.0,
                         jnp.float32)
        return q, scale

    layers: Params = {
        "input_norm": jnp.ones((L, d), dtype),
        "post_attn_norm": jnp.ones((L, d), dtype),
    }
    for name, shape, fan in (("wq", (L, d, nh * hd), d),
                             ("wk", (L, d, nkv * hd), d),
                             ("wv", (L, d, nkv * hd), d),
                             ("wo", (L, nh * hd, d), nh * hd)):
        layers[name], layers[name + "_scale"] = wq8(next(keys), shape, fan)
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, nh * hd), dtype)
        layers["bk"] = jnp.zeros((L, nkv * hd), dtype)
        layers["bv"] = jnp.zeros((L, nkv * hd), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, hd), dtype)
        layers["k_norm"] = jnp.ones((L, hd), dtype)
    mlp_shapes = [("w_gate", (L, E, d, ff) if cfg.is_moe else (L, d, ff), d),
                  ("w_up", (L, E, d, ff) if cfg.is_moe else (L, d, ff), d),
                  ("w_down", (L, E, ff, d) if cfg.is_moe else (L, ff, d), ff)]
    if not cfg.is_moe and cfg.mlp_type == "mlp":
        mlp_shapes = mlp_shapes[1:]
    if cfg.is_moe:
        layers["router"] = w(next(keys), (L, d, E), d)
    for name, shape, fan in mlp_shapes:
        layers[name], layers[name + "_scale"] = wq8(next(keys), shape, fan)
    _add_opt_extras(cfg, layers, dtype)

    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), dtype),
        "layers": layers,
    }
    if cfg.norm_type == "layernorm":
        params["final_norm_b"] = jnp.zeros((d,), dtype)
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = w(next(keys), (cfg.max_model_len + 2, d), d)
    if not cfg.tie_word_embeddings:
        params["lm_head"], params["lm_head_scale"] = wq8(
            next(keys), (d, cfg.vocab_size), d)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) * (xf - mu), axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * weight + bias


def _norm(cfg: ModelConfig, x: jax.Array, store: Params,
          name: str) -> jax.Array:
    """Config-dispatched normalization: llama-class RMSNorm or OPT-class
    LayerNorm (with bias, stored as ``<name>_b``). norm_type is static
    config, so the branch resolves at trace time."""
    if cfg.norm_type == "layernorm":
        return layer_norm(x, store[name], store[name + "_b"],
                          cfg.rms_norm_eps)
    return rms_norm(x, store[name], cfg.rms_norm_eps)


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array,
           positions: jax.Array) -> jax.Array:
    """Token embedding lookup, plus OPT-class learned positional embeddings
    (HF OPTLearnedPositionalEmbedding keeps a +2 offset into the table)."""
    h = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    if cfg.pos_embedding == "learned":
        h = h + params["pos_embed"][positions + 2]
    return h


def _dot(x: jax.Array, lp: Params, name: str,
         use_pallas: Optional[bool] = None) -> jax.Array:
    """x @ lp[name] in f32, transparently handling the quant ladder
    (ops/quant.py) — this is the ONE sanctioned consumer of quantized
    weights (pinned by the KGCT009 quant-surface lint rule):

    - int8 (per-output-channel scale): the int8->bf16 convert fuses into
      the dot (weights stream from HBM at half the bytes) and the scale
      applies as one [out]-vector multiply on the f32 result.
    - int4 (packed nibbles + group scales, ``scale.ndim == w.ndim``): the
      dequant-fused matmul contracts per input group and folds the scales
      into the f32 partials — no dequantized weight copy in HBM
      (ops.quant.int4_matmul; ``use_pallas`` is ``Kernels.int4_pallas``:
      False forces the XLA fusion, None leaves it to that function's own
      opt-in).
    - dense-precision weights take the plain path.
    """
    w = lp[name]
    if w.dtype == jnp.int8:
        scale = lp[name + "_scale"]
        if quant_ops.is_packed_int4(w, scale):
            return quant_ops.int4_matmul(x, w, scale, use_pallas=use_pallas)
        out = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
        return out * scale
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


# HF ACT2FN["gelu"] is the exact erf GELU; jax.nn.gelu defaults to the tanh
# approximation, which accumulates ~1e-3 activation error per layer and
# breaks HF-parity tolerances.
_MLP_ACTS = {"relu": jax.nn.relu,
             "gelu": functools.partial(jax.nn.gelu, approximate=False),
             "gelu_new": jax.nn.gelu,   # HF's tanh-approximated variant
             "silu": jax.nn.silu}


def _dense_mlp(lp: Params, x: jax.Array, cfg: ModelConfig,
               tp_axis: Optional[str] = None,
               use_pallas: Optional[bool] = None) -> jax.Array:
    """Megatron MLP: gate/up column-sharded, down row-sharded. Under GSPMD
    (tp_axis=None) the psum is inserted by the partitioner; inside shard_map
    (parallel/pp.py) ``tp_axis`` names the manual mesh axis to reduce over.
    ``mlp_type="mlp"`` is the OPT-class fc1/act/fc2 block (w_up/w_down with
    biases, no gate); biases add AFTER the down-projection reduce so they
    are applied exactly once under tp."""
    if cfg.mlp_type == "mlp":
        h = _dot(x, lp, "w_up", use_pallas)
        if "b_up" in lp:
            h = h + lp["b_up"]
        h = _MLP_ACTS[cfg.mlp_act](h).astype(x.dtype)
        out = _dot(h, lp, "w_down", use_pallas)
        if tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)
        if "b_down" in lp:
            out = out + lp["b_down"]
        return out.astype(x.dtype)
    gate = _dot(x, lp, "w_gate", use_pallas)
    up = _dot(x, lp, "w_up", use_pallas)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = _dot(h, lp, "w_down", use_pallas)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out.astype(x.dtype)


def moe_route(lp: Params, x: jax.Array, cfg: ModelConfig
              ) -> tuple[jax.Array, jax.Array]:
    """THE router, for both expert classes: scores over all experts in
    float32 (softmax: Mixtral; sigmoid: DeepSeek-V3/kimi), the top-k CHOSEN
    by score + ``router_bias`` where the model has one (the noaux_tc
    correction bias, which never enters the weights), weights = the raw
    scores of the chosen, normalised over the k and scaled. Softmax over all
    then normalising the chosen equals Mixtral's softmax over the top-k
    logits. ``n_group`` = ``topk_group`` = 1 in every served config, so the
    group-limited step is the identity and is not written.
    Returns (idx [T, k] int32, weights [T, k] float32)."""
    logits = jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32))
    scores = (jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choice = scores + lp["router_bias"] if "router_bias" in lp else scores
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


# When a step whose experts lie whole on the device runs them by DENSE
# dispatch (every expert over every token) and not by the grouped path: when
# dense wastes nothing. (a) The step routes enough pairs that every expert
# is hit anyway, so grouped dispatch could not skip an expert's weights:
# T * k >= 4 * E (kimi-vl-a3b, 6 of 64: from 43 tokens; 95.7 % of the experts
# are hit at 32, 99.8 % at 64). (b) Its E/k times the routed FLOPs cost
# nothing while the step waits for those weights: T x 2 FLOP a weight against
# one 2-byte read of it, so up to the chip's FLOP-to-byte balance (197
# TFLOP/s / 819 GB/s = 240 tokens on a v5e). Then the batched matmul that
# streams the stacked weights in place beats a sort, a gather and three
# kernels a layer. Measured on the v5e IN the decode program (9 layers of
# kimi-vl-a3b, 1-2.7 k cached tokens a row, ms a step, dense / grouped;
# PERF.md, PR 26): 8 rows 13.18 / 8.32, 16 rows 13.47 / 11.19, 32 rows
# 14.04 / 14.15, 64 rows 15.49 / 16.33, 128 rows 18.01 / 19.29; a layer's
# experts alone at 256 tokens 1.97 / 1.83, at 1088 tokens 7.68 / 2.84.
DENSE_DISPATCH_MAX_TOKENS = 128
DENSE_DISPATCH_MIN_PAIRS_PER_EXPERT = 4


def held_pairs(T: int, cfg: ModelConfig) -> float:
    """The (token, expert) pairs a step of T tokens sends to the experts
    HELD here, under uniform routing: all T * k where every expert is held,
    the held share of them (a quarter at 64 of 256) otherwise."""
    return (T * cfg.num_experts_per_tok * cfg.num_local_experts
            / cfg.num_experts)


def dense_dispatch_pays(T: int, cfg: ModelConfig) -> bool:
    """Whether a step of T tokens should run the dense-precision experts
    held here by dense dispatch (the comment above: every HELD expert is
    hit anyway by the pairs that reach the held ones, and the extra FLOPs
    hide under the weight stream)."""
    return (T <= DENSE_DISPATCH_MAX_TOKENS
            and held_pairs(T, cfg) >= (DENSE_DISPATCH_MIN_PAIRS_PER_EXPERT
                                       * cfg.num_local_experts))


def grouped_dispatch(T: int, cfg: ModelConfig, kernels: Kernels) -> bool:
    """Whether a step program of T tokens (padding included) runs its experts
    by grouped dispatch: the one test, ``_moe_mlp``'s and that of the host's
    gauge of what the grouped kernel computed."""
    return kernels.grouped_experts and not dense_dispatch_pays(T, cfg)


_EXPERT_KEYS = ("w_gate", "w_up", "w_down",
                "w_gate_scale", "w_up_scale", "w_down_scale")


def experts_grouped(lp: Params, x: jax.Array, idx: jax.Array,
                    w: jax.Array, sizes: jax.Array,
                    layer: Optional[jax.Array] = None,
                    use_pallas: bool = False,
                    valid: Optional[jax.Array] = None) -> jax.Array:
    """Token-sorted grouped expert matmuls: the routed (token, expert)
    pairs are sorted by expert and each expert's SwiGLU runs over its own
    contiguous rows, so the work is the pairs' and not experts x tokens
    (``use_pallas``: ``ops.pallas.grouped_matmul``, the kernel or its
    exception; else its XLA twin ``jax.lax.ragged_dot``). Every pair of a
    real token is computed whatever the imbalance: there is no capacity and
    nothing is dropped. A padding token's pairs (``valid`` False) are in no
    group: they sort behind every expert's, get no row, and the token's
    result is zero; so are the pairs of an expert this process does not
    hold (``valid`` a pair: their part of the sum is another process's).
    x: [T, d]; idx/w: [T, k], idx among the E experts of ``lp``; sizes: [E]
    int32, the real pairs of each; valid: [T] or [T, k] bool, or None (every
    pair is real and its expert here).
    Returns [T, d] float32.

    The rows are laid out as the kernel walks them (``grouped_matmul``'s
    ``group_starts``: every expert's rows start on a whole HBM tile, so a
    row tile belongs to one expert), for the twin as well: it is handed the
    sizes rounded up likewise, and computes the few rows in between for
    nobody. Rows outside the groups hold nothing meaningful and are never
    gathered back.

    ``lp``'s expert tensors are one layer's [E, ...] or, with ``layer``, the
    whole stack's [n, E, ...]: the kernel is then handed the stack as n*E
    groups of which only this layer's are not empty. Indexing the layer out
    first would COPY its experts (1.1 GB a layer at kimi-vl-a3b's widths,
    a third of the step's device time when it was done so): a custom call
    cannot read through a dynamic slice."""
    T, k = idx.shape
    E = sizes.shape[0]
    expert = idx if valid is None else jnp.where(
        valid if valid.ndim == 2 else valid[:, None], idx, E)
    # One stable sort lays the rows out: behind the pairs come ROW_ALIGN - 1
    # fillers an expert, of which each expert owns what rounds its rows up
    # (the rest, like a padding token's pairs, sort behind every expert: E).
    fill = jnp.arange(gm.ROW_ALIGN - 1)[None, :] < (
        gm.aligned_sizes(sizes) - sizes)[:, None]
    keys = jnp.concatenate([
        expert.reshape(-1),
        jnp.where(fill, jnp.arange(E)[:, None], E).reshape(-1)])
    order = jnp.argsort(keys, stable=True)       # row -> pair (or filler)
    # ... and a tile more, for the last visit's spill (gm.padded_rows).
    src = jnp.minimum(order, T * k - 1) // k
    xs = x[jnp.pad(src, (0, gm.padded_rows(T * k, E) - src.shape[0]))]
    # Dense-precision experts only: _moe_mlp keeps int8/int4 ones on _dot.
    w_gate, w_up, w_down = (lp[n] for n in ("w_gate", "w_up", "w_down"))
    if layer is not None:
        n_layers = w_gate.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * E,), sizes.dtype), sizes, (layer * E,))
        w_gate, w_up, w_down = (a.reshape((n_layers * E,) + a.shape[2:])
                                for a in (w_gate, w_up, w_down))
    if use_pallas:
        matmul = gm.grouped_matmul
    else:
        sizes = gm.aligned_sizes(sizes)
        matmul = functools.partial(jax.lax.ragged_dot,
                                   preferred_element_type=jnp.float32)
    gate = matmul(xs, w_gate, sizes)
    up = matmul(xs, w_up, sizes)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    y = matmul(h, w_down, sizes)                                # [rows, d]
    # Back to token order by a gather (a scatter-add serialises on the chip).
    y = y[jnp.argsort(order)[:T * k]] * w.reshape(-1, 1)    # pair -> row
    if valid is not None:
        y = jnp.where((expert < E).reshape(-1, 1), y, 0.0)
    return y.reshape(T, k, -1).sum(axis=1)


def experts_dense(lp: Params, x: jax.Array, idx: jax.Array, w: jax.Array,
                  cfg: ModelConfig, ep_axis: Optional[str] = None,
                  use_pallas: Optional[bool] = None) -> jax.Array:
    """Dense dispatch: every (local) expert runs over all tokens and combine
    weights zero the pairs that were not routed. Exact, E/k times the
    grouped path's FLOPs: the path of steps that hit every expert under the
    FLOP-to-byte balance (``dense_dispatch_pays``), and kept where experts are sharded (under expert parallelism each device
    evaluates its local experts and the combine reduces over ``ep``) and
    for int8/int4 experts (``_dot``); the oracle the grouped path is tested
    against. ``lp``'s expert tensors are ONE layer's. Returns [T, d]
    float32."""
    E = cfg.num_experts
    combine = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                      * w[..., None], axis=1)                # [T, E]
    E_local = lp["w_gate"].shape[0]  # E under GSPMD; E/ep inside shard_map
    if ep_axis is not None and E_local != E:
        start = jax.lax.axis_index(ep_axis) * E_local
        combine = jax.lax.dynamic_slice_in_dim(combine, start, E_local, axis=1)
    elif cfg.experts_held:      # this process's share, by configuration
        combine = combine[:, cfg.experts_first:cfg.experts_first + E_local]

    def expert_fn(ep_params):
        gate = _dot(x, ep_params, "w_gate", use_pallas)
        up = _dot(x, ep_params, "w_up", use_pallas)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        return _dot(h, ep_params, "w_down", use_pallas)              # [T, d]

    expert_params = {k: lp[k] for k in _EXPERT_KEYS if k in lp}
    expert_outs = jax.vmap(expert_fn)(expert_params)  # [E_local, T, d]
    return jnp.einsum("te,etd->td", combine, expert_outs)


def _moe_mlp(lp: Params, x: jax.Array, cfg: ModelConfig,
             tp_axis: Optional[str] = None,
             ep_axis: Optional[str] = None,
             kernels: Kernels = NO_KERNELS,
             load_out: Optional[list] = None,
             stacked: Optional[tuple] = None,
             valid: Optional[jax.Array] = None) -> jax.Array:
    """Sparse expert layer: route (``moe_route``), run the routed experts,
    add the shared experts where the model has them. Expert compute is
    dense dispatch unless ``kernels.grouped_experts``: the engine's word
    that the expert tensors lie WHOLE on the device that runs this (no GSPMD
    mesh shards them, no manual ``tp``/``ep`` axis), so the grouped kernel, a
    custom call with no partitioning rule, may be handed the stack as it is.
    Then it is chosen from the step's size (``dense_dispatch_pays``): dense
    where every expert is hit anyway and the step is under the chip's
    FLOP-to-byte balance, else the token-sorted grouped path
    (``grouped_dispatch``). Sharded and quantized experts are always dense
    dispatch (PERF.md: debt): the engine gives no such word for them, and it
    is refused here.
    ``valid``: [T] bool, False on the step's padding tokens (None: there
    are none). Padding is not load: its pairs are in no expert's count, and
    the grouped path computes nothing for them.
    A model that holds a SHARE of its experts (``cfg.experts_held`` from
    ``cfg.experts_first``) routes over all of them all the same and sums
    over the chosen experts that are held, on either path; what the absent
    ones would add is another process's to compute and nothing here stands
    in for it, nor for the exchange. The shared experts are computed here.
    ``load_out``: a list that is given the real routed pairs each expert was
    sent, [E] int32 over ALL experts (``_layer_scan``'s ``moe_load``; the
    host reads the held ones' balance and the share of pairs that reached
    them). ``stacked``: (the whole stack's expert tensors
    [n, E, ...], this layer's index in it) where the caller kept them out
    of ``lp`` (``_layer_scan``: so that no layer's experts are copied)."""
    with jax.named_scope("kgct.moe.route"):
        idx, w = moe_route(lp, x, cfg)
        # Pairs of each expert, as a sum of one-hot rows: a bincount is a
        # scatter-add, which the chip performs one update after the other.
        # (A padding token's row is all zeros: its expert is out of range.)
        sent = idx if valid is None else jnp.where(valid[:, None], idx, -1)
        load = jnp.sum(jax.nn.one_hot(sent.reshape(-1), cfg.num_experts,
                                      dtype=jnp.int32), axis=0)
    experts, layer = stacked if stacked is not None else (lp, None)
    grouped, int4 = kernels.grouped_experts, kernels.int4_pallas
    with jax.named_scope("kgct.moe.experts"):
        if grouped and (tp_axis is not None or ep_axis is not None):
            raise ValueError("grouped expert dispatch inside a manual "
                             "tp/ep shard_map: its experts are sharded")
        if grouped and experts["w_gate"].dtype == jnp.int8:
            raise ValueError("grouped expert dispatch over quantized "
                             "experts: the kernel takes whole precision")
        if grouped_dispatch(x.shape[0], cfg, kernels):
            sizes = load
            if cfg.experts_held:    # the pairs of an absent expert: no group
                first, held = cfg.experts_first, cfg.experts_held
                here = (idx >= first) & (idx < first + held)
                valid = here if valid is None else here & valid[:, None]
                idx, sizes = idx - first, load[first:first + held]
            out = experts_grouped(experts, x, idx, w, sizes, layer,
                                  kernels.use_pallas, valid)
        else:
            if layer is not None:   # one layer's, read in place by the dots
                experts = {k: jax.lax.dynamic_index_in_dim(
                    a, layer, 0, keepdims=False) for k, a in experts.items()}
            out = experts_dense(experts, x, idx, w, cfg, ep_axis, int4)
    reduce_axes = tuple(a for a in (ep_axis, tp_axis) if a is not None)
    if reduce_axes:
        out = jax.lax.psum(out, reduce_axes)
    out = out.astype(x.dtype)
    if "ws_gate" in lp:
        with jax.named_scope("kgct.moe.shared"):
            out = out + _dense_mlp(
                {"w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
                 "w_down": lp["ws_down"]}, x, cfg, use_pallas=int4)
    if load_out is not None:
        load_out.append(load)
    return out


def _mla_qkv(lp: Params, cfg: ModelConfig, x: jax.Array,
             positions: jax.Array, use_pallas: Optional[bool] = None,
             with_latent: bool = False):
    """Latent attention's projections. x: [T, d] -> q [T, nh, nope + rope]
    (RoPE on its last ``rope`` dims; through a latent of its own where the
    model has ``q_lora_rank``) and the cache row [T, kv_row_padded] =
    [c (its own RMSNorm) | k_pe (RoPE, one head shared by all) | zeros].
    RoPE is half-split over the rope dims: the loader de-interleaves those
    columns of a checkpoint (engine/weights.py), which rotates the same
    pairs the published code does. ``with_latent``: the query latent c_q
    [T, q_lora_rank] comes back third (an indexer reads it)."""
    T = x.shape[0]
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    c_q = None
    if "w_qa" in lp:    # the query through its latent and the latent's norm
        with jax.named_scope("kgct.mla.q_lora"):
            c_q = rms_norm(_dot(x, lp, "w_qa", use_pallas).astype(x.dtype),
                           lp["q_a_norm"], cfg.rms_norm_eps)
            q = _dot(c_q, lp, "w_qb", use_pallas).astype(x.dtype)
    else:
        q = _dot(x, lp, "wq", use_pallas).astype(x.dtype)
    q = q.reshape(T, q.shape[-1] // cfg.head_dim, cfg.head_dim)
    a = _dot(x, lp, "w_kva", use_pallas).astype(x.dtype)      # [T, r + rope]
    c = rms_norm(a[:, :r], lp["kv_norm"], cfg.rms_norm_eps)
    k_pe = a[:, r:]
    if cfg.pos_embedding == "rope":   # "none" (kimi_linear's mla_use_nope):
        # the "rope" dims of q and of the shared key are used unrotated
        cos, sin = rope_cos_sin(positions, rope, cfg.rope_theta,
                                scaling=cfg.rope_scaling_dict)
        q = jnp.concatenate(
            [q[..., :-rope], apply_rope(q[..., -rope:], cos, sin)], axis=-1)
        k_pe = apply_rope(k_pe[:, None], cos, sin)[:, 0]
    pad = jnp.zeros((T, cfg.kv_row_padded - cfg.kv_row_dim), x.dtype)
    row = jnp.concatenate([c, k_pe, pad], axis=-1)
    return (q, row, c_q) if with_latent else (q, row)


def mla_materialise(lp: Params, cfg: ModelConfig, row: jax.Array):
    """Cache rows [T, R] -> per-head k [T, nh, nope + rope] and v
    [T, nh, v]: the form fresh tokens attend each other in."""
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    c = row[:, :r]
    k_nope = jnp.einsum("tc,hcn->thn", c, lp["w_uk"],
                        preferred_element_type=jnp.float32).astype(row.dtype)
    v = jnp.einsum("tc,hcv->thv", c, lp["w_uv"],
                   preferred_element_type=jnp.float32).astype(row.dtype)
    k_pe = jnp.broadcast_to(row[:, None, r:r + rope],
                            k_nope.shape[:2] + (rope,))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def mla_absorbed(lp: Params, cfg: ModelConfig, q: jax.Array, row: jax.Array,
                 attend) -> jax.Array:
    """The form cached tokens are met in: q's nope part is carried into the
    latent space (``q_lat = q_nope W_uk[head]``), so a score is one dot
    product of [q_lat | q_pe] with a cache row and every head reads the SAME
    row (multi-query attention whose value is the key row's first r lanes);
    the latent output leaves through ``W_uv[head]``. ``attend(q_abs
    [T, nh, R], rows [T, 1, R]) -> [T, nh, R]`` is any shared-row attention
    of ops.attention (``v_pool=None``). Same function as the materialised
    form (tests/test_mla_moe.py)."""
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    q_lat = jnp.einsum("thn,hcn->thc", q[..., :-rope], lp["w_uk"],
                       preferred_element_type=jnp.float32).astype(q.dtype)
    pad = jnp.zeros(q.shape[:2] + (row.shape[-1] - r - rope,), q.dtype)
    q_abs = jnp.concatenate([q_lat, q[..., -rope:], pad], axis=-1)
    o_lat = attend(q_abs, row[:, None, :])[..., :r]
    return jnp.einsum("thc,hcv->thv", o_lat, lp["w_uv"],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _qkv(lp: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
         use_pallas: Optional[bool] = None):
    """Project + per-head norm (qwen3) + RoPE. x: [T, d] -> q [T,nh,hd], k/v [T,nkv,hd].
    Head counts are derived from the projection widths (not cfg) so the same
    code runs on tp-local shards inside shard_map (parallel/pp.py)."""
    T = x.shape[0]
    q = _dot(x, lp, "wq", use_pallas)
    k = _dot(x, lp, "wk", use_pallas)
    v = _dot(x, lp, "wv", use_pallas)
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.astype(x.dtype).reshape(T, q.shape[-1] // cfg.head_dim, cfg.head_dim)
    k = k.astype(x.dtype).reshape(T, k.shape[-1] // cfg.head_dim, cfg.head_dim)
    v = v.astype(x.dtype).reshape(T, v.shape[-1] // cfg.head_dim, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    if cfg.pos_embedding == "rope":
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                scaling=cfg.rope_scaling_dict)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def mla_chunk_attention(lp: Params, cfg: ModelConfig, q: jax.Array,
                        row: jax.Array, seg_ids: jax.Array,
                        positions: jax.Array, pool: jax.Array,
                        page_table: jax.Array, hist_len: jax.Array,
                        layer_idx: jax.Array, kernels: Kernels) -> jax.Array:
    """One sequence's prompt chunk under latent attention. With nothing of
    the sequence in the pool yet (``hist_len == 0``: a whole prompt in one
    step, the first chunk of a long one) the chunk's tokens attend each
    other in the MATERIALISED form; with history (a later chunk, a
    prefix-cache hit) the pool is met in the absorbed form, and the chunk's
    own rows with it in the same shared-row sweep (3.4x the attention FLOPs
    for that part; merging the two forms' softmaxes would spare it). The
    choice is the device's, from ``hist_len``: both are in the program."""
    scale = cfg.attn_scale

    def fresh(_):
        k, v = mla_materialise(lp, cfg, row)
        return kernels.prefill_attention(q, k, v, seg_ids, positions, scale)

    def with_history(_):
        return mla_absorbed(
            lp, cfg, q, row, lambda qa, rows: kernels.chunk_attention(
                qa, rows, None, seg_ids, positions, pool, None, page_table,
                hist_len, scale, layer=layer_idx))

    return jax.lax.cond(hist_len == 0, fresh, with_history, None)


class _SparseAttention:
    """A sparse-attention model's choice over one step's token axis
    (``ops/dsa.py``), wired to the step's pools: what ``forward`` hands
    ``_layer_scan`` (``choose``, ``start``) and its two attention parts
    (``segment``, ``rows``). The choice is (the segment part's mask [n_seg,
    m] over [the chunk's history | the part's own tokens], or None where it
    has no more candidates than ``index_topk`` and keeps what it may attend
    to; the rows' chosen tokens [R, k], each the row of a latent-pool LAYER
    it lies in or -1 for the row's own token, and which of them exist): a
    row's candidates are its pages' positions and, last, its own token.
    (The pool rows are looked up where the choice is made and not in each
    layer that attends over it: the lookup in the page table is a gather of
    its own, 0.26 ms for 16 rows on the v5e.)"""

    def __init__(self, cfg: ModelConfig, meta: StepMeta, kv: KVCache,
                 n_seg: int, n_rows: int):
        self.cfg, self.meta, self.kv = cfg, meta, kv
        self.n_seg, self.n_rows = n_seg, n_rows

    @staticmethod
    def _pool_rows(pool, layer, pages):
        """Rows of one layer of a stacked pool, ``pages`` [...] whole: a
        gather of the pool as it lies, no slice of the layer."""
        flat = pool.reshape((-1,) + pool.shape[2:])
        got = flat[layer * pool.shape[1] + pages]
        return got.reshape(pages.shape[:-1] + (-1, pool.shape[-1]))

    def _allowed(self):
        """[n_seg, m] bool: which of its candidates a token of the segment
        part may attend to."""
        meta, n = self.meta, self.n_seg
        seg, pos = meta.seg_ids[:n], meta.positions[:n]
        own = ((seg[:, None] == seg[None, :]) & (seg[:, None] >= 0)
               & (pos[:, None] >= pos[None, :]))
        if meta.chunk_page_table is None:
            return own
        H = meta.chunk_page_table.shape[0] * self.kv.page_size
        hist = (jnp.arange(H)[None, :] < meta.hist_len) & (seg[:, None] >= 0)
        return jnp.concatenate([hist, own], axis=1)

    def choose(self, q_i, w_i, k_i, j):
        """The choice of "full" layer number j, from its indexer's
        projections over the step's tokens."""
        meta, kv, n = self.meta, self.kv, self.n_seg
        topk = self.cfg.index_topk
        seg_mask = rows = None
        if n:
            allowed = self._allowed()
            if allowed.shape[1] > topk:
                with jax.named_scope("kgct.dsa.index"):
                    keys = k_i[:n]
                    if meta.chunk_page_table is not None:
                        keys = jnp.concatenate([self._pool_rows(
                            kv.idx, j, meta.chunk_page_table), keys])
                    scores = dsa.index_scores(q_i[:n], w_i[:n], keys)
                with jax.named_scope("kgct.dsa.select"):
                    seg_mask = dsa.topk_mask(scores, allowed, topk)
        if self.n_rows:
            with jax.named_scope("kgct.dsa.index"):
                keys = self._pool_rows(kv.idx, j, meta.page_tables)
                scores = jnp.concatenate(
                    [dsa.row_scores(q_i[n:], w_i[n:], keys),
                     dsa.row_scores(q_i[n:], w_i[n:], k_i[n:, None])],
                    axis=1)
            with jax.named_scope("kgct.dsa.select"):
                allowed = jnp.concatenate(
                    [jnp.arange(keys.shape[1])[None, :]
                     < meta.context_lens[:, None] - 1,
                     jnp.ones((self.n_rows, 1), bool)], axis=1)
                idx, exists = dsa.topk_indices(scores, allowed, topk)
                S, ps = keys.shape[1], kv.page_size
                at = jnp.minimum(idx, S - 1)
                page = jnp.take_along_axis(meta.page_tables, at // ps, axis=1)
                rows = (jnp.where(idx == S, -1, page * ps + at % ps), exists)
        return seg_mask, rows

    def start(self, dtype):
        """A choice of the step's structure to start the layers' carry from
        (the first layer is "full": it is never read)."""
        cfg, T = self.cfg, self.n_seg + self.n_rows
        shapes = jax.eval_shape(
            self.choose,
            jax.ShapeDtypeStruct((T, cfg.index_n_heads, cfg.index_head_dim),
                                 dtype),
            jax.ShapeDtypeStruct((T, cfg.index_n_heads), jnp.float32),
            jax.ShapeDtypeStruct((T, cfg.index_head_dim), dtype),
            jax.ShapeDtypeStruct((), jnp.int32))
        return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)

    def segment(self, lp, q, row, choice, layer_idx):
        """The segment part over what its mask keeps of [the chunk's
        history | the part's own rows], materialised."""
        meta, rows = self.meta, row
        if meta.chunk_page_table is not None:
            rows = jnp.concatenate([self._pool_rows(
                self.kv.k, layer_idx, meta.chunk_page_table), rows])
        mask = choice[0] if choice[0] is not None else self._allowed()
        with jax.named_scope("kgct.dsa.attend"):
            return dsa.attend_masked(
                q, *mla_materialise(lp, self.cfg, rows), mask,
                self.cfg.attn_scale)

    def rows(self, lp, q, row, choice, layer_idx):
        """A running row over its chosen positions: their latent rows
        gathered from its pages, its own row where it chose itself."""
        cfg, pool = self.cfg, self.kv.k
        at, exists = choice[1]

        def attend(qa, _):
            with jax.named_scope("kgct.dsa.attend"):
                flat = pool.reshape((-1, pool.shape[-1]))
                got = flat[layer_idx * pool.shape[1] * pool.shape[2]
                           + jnp.maximum(at, 0)]
                got = jnp.where((at < 0)[..., None], row[:, None, :], got)
                return dsa.attend_gathered(qa, got, exists, cfg.attn_scale,
                                           cfg.kv_lora_rank)
        return mla_absorbed(lp, cfg, q, row, attend)


class _Stack(NamedTuple):
    """What ``_layer_scan`` knows of the weight stack a section's layers of
    one kind lie in: the stack without its expert tensors, those tensors
    (read in place by layer index), the pool layer index of the stack's
    first layer, and that of the section's first layer of the kind."""
    layers: Optional[Params] = None
    experts: Optional[Params] = None
    first: int = 0
    at: int = 0


class _StateKind(NamedTuple):
    """What one kind of state mixer is made of, for ``state_mixer``: the
    prefix of its named scopes, the scope of its segment form, and

    - ``project(lp, cfg, x) -> (conv_in [T, C], per_token, consts)``: the
      conv's input, the recurrence's other per-token inputs ([T, ...] each)
      and its constants;
    - ``split(cfg) -> ops.ssm.ConvSplit``: the pieces the conv's activated
      output [n, C] leaves the conv stage in, the recurrence's operands,
      and what it is rounded to before the recurrence (the conv ROWS a slot
      keeps are the model's dtype either way);
    - ``segments(cfg, kernels, pieces, per_token, consts, seg, seg_ends,
      state0, init_seg) -> (y [n, width] float32, final [S,
      *cfg.state_shape])``;
    - ``rows(cfg, kernels, pool, layer, slots, xr [R, C] float32,
      per_token, consts) -> (pool, y [R, width] float32)``;
    - ``gate(lp, cfg, x, y [T, width]) -> out [T, d] float32``."""
    scope: str
    segment_scope: str
    project: Any
    split: Any
    segments: Any
    rows: Any
    gate: Any


def _mamba_project(lp, cfg, x):
    P = cfg.mamba_d_head
    xbc = _dot(x, lp, "w_xbc").astype(x.dtype)                   # [T, C]
    dt = jax.nn.softplus(_dot(x, lp, "w_dt") + lp["dt_bias"])    # [T, H]
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    D = jnp.repeat(lp["D"].astype(jnp.float32), P)
    return xbc, (dt,), (A, D)


def _mamba_split(cfg):
    """[x | B | C], each where ``ssm_chunk`` reads it."""
    return ssm_ops.ConvSplit(
        (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_state))


def _mamba_segments(cfg, kernels, pieces, per_token, consts, seg, seg_ends,
                    state0, init_seg):
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    (dt,), (A, D), (x, B, C) = per_token, consts, pieces
    y, final = kernels.ssm_chunk(
        x.reshape(-1, H, P), dt, dt * A, B, C, seg, seg_ends, state0,
        init_seg, cfg.mamba_chunk_size)
    return y.reshape(x.shape) + D * x.astype(jnp.float32), final


def _mamba_rows(cfg, kernels, pool, layer, slots, xr, per_token, consts):
    P, N, di = cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_inner
    (dt,), (A, D) = per_token, consts
    pool, y = kernels.ssm_update(
        pool, layer, slots, jnp.repeat(jnp.exp(dt * A), P, axis=-1),
        jnp.repeat(dt, P, axis=-1) * xr[:, :di],
        xr[:, di:di + N], xr[:, di + N:])
    return pool, y + D * xr[:, :di]


def _mamba_gate(lp, cfg, x, y):
    g = y * jax.nn.silu(_dot(x, lp, "w_z"))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    return _dot(g.astype(x.dtype) * lp["ssm_norm"], lp, "w_out")


def _low_rank(x, lp, name):
    """x through the pair ``<name>_down``, ``<name>_up``: float32. What
    passes between the two (``kda_head_dim`` wide) is not rounded to the
    model's dtype: the second product is 128 deep, 2 GFLOP at 2 k tokens."""
    mid = _dot(x, lp, name + "_down")
    return jnp.dot(mid, lp[name + "_up"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _kda_project(lp, cfg, x):
    """g stays [T, H hd] here, the heads on lanes as the projection leaves
    it, and is named [T, H, hd] where a recurrence takes it: made [T, H,
    hd] HERE it is another tiled layout on the chip, which XLA reached by a
    copy into one padded fourfold, a slice of the segment part and a copy
    back (0.9 ms a layer at 2 k tokens: PERF.md section 6, PR 47)."""
    qkv = _dot(x, lp, "w_qkv").astype(x.dtype)                # [T, 3 H hd]
    g = jnp.repeat(-jnp.exp(lp["A_log"].astype(jnp.float32)),
                   cfg.kda_head_dim) * jax.nn.softplus(
        _low_rank(x, lp, "w_f") + lp["dt_bias"])
    return qkv, (g, jax.nn.sigmoid(_dot(x, lp, "w_beta"))), ()


def _kda_split(cfg):
    """The conv's output [n, 3 H hd] as unit q (scaled), unit k, v: float32
    [n, H, hd] each."""
    H, hd = cfg.kda_n_heads, cfg.kda_head_dim
    return ssm_ops.ConvSplit((H * hd,) * 3, jnp.float32, hd,
                             (hd ** -0.5, 1.0, None))


def _kda_segments(cfg, kernels, pieces, per_token, consts, seg, seg_ends,
                  state0, init_seg):
    (g, beta), (q, k, v) = per_token, pieces
    o, final = kernels.kda_chunk(
        q, k, v, g.reshape(k.shape), beta, seg, seg_ends, state0, init_seg,
        cfg.kda_chunk_size)
    return o.reshape(q.shape[0], -1), final


def _kda_rows(cfg, kernels, pool, layer, slots, xr, per_token, consts):
    (g, beta), (q, k, v) = per_token, ssm_ops.split_activated(
        xr, _kda_split(cfg))
    return kernels.kda_update(pool, layer, slots, g.reshape(k.shape), beta,
                              q, k, v)


def _kda_gate(lp, cfg, x, y):
    hd = cfg.kda_head_dim
    o = y.reshape(y.shape[0], -1, hd)                          # per head
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps) * lp["kda_norm"]
    o = o.reshape(y.shape) * jax.nn.sigmoid(_low_rank(x, lp, "w_g"))
    return _dot(o.astype(x.dtype), lp, "w_out")


_STATE_MIXERS = {
    "mamba": _StateKind("kgct.ssm", "scan", _mamba_project, _mamba_split,
                        _mamba_segments, _mamba_rows, _mamba_gate),
    # q, k and v stay float32 from the conv to the recurrence: k and q are
    # normalised next, and the state they write is float32.
    "kda": _StateKind("kgct.kda", "chunk", _kda_project, _kda_split,
                      _kda_segments, _kda_rows, _kda_gate),
}


def state_conv_split(cfg: ModelConfig) -> ssm_ops.ConvSplit:
    """The pieces a state layer's conv stage leaves its output in."""
    return _STATE_MIXERS[cfg.state_kind].split(cfg)


def state_mixer(lp: Params, cfg: ModelConfig, x: jax.Array, meta: StepMeta,
                n_seg: int, ssm: jax.Array, conv: jax.Array,
                layer: jax.Array, kernels: Kernels = NO_KERNELS):
    """The mixer of one state layer over the step's token axis ``[segment
    tokens | row tokens]``, whichever kind the model has (``_STATE_MIXERS``).
    x: [T, d], the layer's normed input.

    Mamba-2 (granitemoehybrid):

        z = x W_z;   xBC = x W_xbc;   dt = x W_dt      (in_proj, in three pieces)
        xBC = silu(causal_depthwise_conv(xBC) + b);   [x | B | C] = xBC
        dt = softplus(dt + dt_bias);   A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
        out = (RMSNorm(y * silu(z)) * w_norm) W_out

    ``time_step_limit`` is (0, inf) in every served config: the clamp of dt
    is the identity and is not written. One group: B and C are shared by
    all heads, and the gated norm runs over all of d_inner.

    KDA (kimi_linear), per head:

        [q | k | v] = silu(causal_depthwise_conv(x W_qkv));   q, k unit, q * d_k^-1/2
        g = -exp(A_log) softplus((x W_f_down) W_f_up + dt_bias)   per channel
        beta = sigmoid(x W_beta)
        D = Diag(exp(g_t)) S_{t-1};   S_t = D + beta_t k_t (v_t - D^T k_t)^T;   o_t = S_t^T q_t
        out = (RMSNorm_head(o) * w_norm * sigmoid((x W_g_down) W_g_up)) W_out

    What the kinds share is what a slot means. The segment part runs the
    conv and the kind's chunked form within its segments; a chunk with
    history (``meta.hist_len`` > 0) starts from its slot, anything else from
    zero WHATEVER the slot held; each segment's final state goes to its
    slot. The row part is the one-token update of each row's slot (a kernel
    of ``kernels``: in place). ``ssm`` [Ls, slots, *cfg.state_shape]
    float32 is threaded (the scans carry it); ``conv`` [Ls, slots, K-1,
    channels] is read as the step found it, and this layer's new rows come
    back for the one write behind the scan, as new K/V do.
    Returns (out [T, d] float32, ssm, new conv rows [S + R, K-1, channels]
    for ``meta.seg_slots`` then ``meta.row_slots``)."""
    kind = _STATE_MIXERS[cfg.state_kind]
    scope = lambda part: jax.named_scope(f"{kind.scope}.{part}")
    f32 = jnp.float32
    with scope("proj"):
        xbc, per_token, consts = kind.project(lp, cfg, x)
    ys, conv_new, xbc_rows = [], [], xbc
    if n_seg:
        seg = meta.seg_ids[:n_seg]
        n_segs = meta.seg_slots.shape[0]
        seg_ends = jnp.max(jnp.where(
            seg[None, :] == jnp.arange(n_segs)[:, None],
            jnp.arange(n_seg)[None, :], -1), axis=1)
        # A chunk with history continues from its slot; the step programs
        # without one (packed whole prompts) have no such read at all.
        resumes = None if meta.hist_len is None else meta.hist_len > 0
        slot0 = meta.seg_slots[0]

        def start(pool):
            first = jnp.zeros(pool.shape[2:], pool.dtype)
            if resumes is None:
                return first
            return jnp.where(resumes, pool[layer, slot0], first)

        with scope("conv"):
            # The projection's small readers (each segment's new rows, the
            # row part's tokens) BEFORE its large one: scheduled behind the
            # recurrence, XLA computed the whole projection again for each
            # (``%convolution_convert_fusion.N.remat``, 4 ms of granite's
            # 1600-token mixed step).
            init = start(conv)
            xbc, rows, xbc_rows = jax.lax.optimization_barrier(
                (xbc, ssm_ops.segment_conv_rows(xbc, seg, seg_ends, init),
                 xbc[n_seg:]))
            pieces = kernels.conv_segments(
                xbc, seg, init, lp["conv_w"], lp.get("conv_b"),
                state_conv_split(cfg))
            conv_new.append(rows)
        with scope(kind.segment_scope):
            y, final = kind.segments(
                cfg, kernels, pieces, [a[:n_seg] for a in per_token], consts,
                seg, seg_ends, start(ssm), 0 if resumes is not None else -2)
            ssm = ssm_ops.write_slots(ssm, final, meta.seg_slots, layer)
            ys.append(y)
    if x.shape[0] > n_seg:
        slots = meta.row_slots
        with scope("conv"):
            c_out, rows = ssm_ops.conv_rows(
                xbc_rows, conv[layer, slots], lp["conv_w"],
                lp.get("conv_b"))
            xr = jax.nn.silu(c_out).astype(
                state_conv_split(cfg).dtype or x.dtype).astype(f32)
            conv_new.append(rows)
        with scope("update"):
            ssm, y = kind.rows(cfg, kernels, ssm, layer, slots, xr,
                               [a[n_seg:] for a in per_token], consts)
            ys.append(y)
    with scope("gate"):
        out = kind.gate(lp, cfg, x, jnp.concatenate(ys, axis=0))
    return out, ssm, jnp.concatenate(conv_new, axis=0)


# ---------------------------------------------------------------------------
# Forward passes (scan over stacked layers; attn addresses the pool by index)
# ---------------------------------------------------------------------------

def _layer_scan(params: Params, cfg: ModelConfig, h: jax.Array,
                positions: jax.Array, attn_fn,
                kernels: Kernels = NO_KERNELS,
                tp_axis: Optional[str] = None,
                ep_axis: Optional[str] = None,
                moe_load: Optional[list] = None,
                valid: Optional[jax.Array] = None,
                state_fn=None, ssm: Optional[jax.Array] = None,
                choose_fn=None, choice=None):
    """Run the stack section by section (``cfg.layer_sections``), each a
    scan of ONE PERIOD of typed layers over the stacked weights: the
    leading dense layers, then the shortest run of layer types whose whole
    repetitions cover the layers behind them, then a tail shorter than a
    period. A homogeneous model is one section of one attention layer, and
    its scan is the scan over its layers; granite-4.0-h-micro's period is [5
    state, 1 attention, 4 state], scanned 4 times, with an inner scan over
    each run of state layers; kimi-linear's sections are its dense KDA
    layer, 6 x [KDA, KDA, MLA, KDA] and the tail [KDA, MLA]: the program
    holds one body a layer kind of each section, whatever the depth. A
    layer is (a mixer: attention or the model's state mixer) x (an MLP:
    dense or experts), and its weights lie in its mixer's stack
    (``layer_stacks``). ``h``, the carry, is the residual: [T, d], or the
    [T, n d] streams of a model with hyper-connections; every sublayer
    enters and leaves it through one pair of functions (``enter``/``leave``
    below). Pool layer indices count per kind through all the
    sections: an attention layer addresses layer ``i`` of the page pools, a
    state layer layer ``j`` of the slot pools.

    The KV pool does NOT travel through the scan: it is closed over whole and
    ``attn_fn`` receives the LAYER INDEX (scanned as xs) to address it.
    Slicing the pool per layer as scan xs — the previous design — made XLA
    materialize a [1, P, ps, kd] copy of each layer's pool every layer every
    substep (~1.4 ms/substep, ~20% of decode, measured in the round-3 device
    trace); the Pallas kernel addresses the stacked pool with a dynamic layer
    index instead, moving zero pool bytes. Each layer's freshly projected
    K/V come out as scan ys, and the caller commits them to the pool in ONE
    in-place write of the donated pool after the scan
    (ops.attention.Kernels.write_pages: on the chip a Pallas kernel that
    read-modify-writes the touched pool tiles by DMA, all in flight at
    once; elsewhere a loop of row updates). Threading the pool through the
    scan as carry/ys would force a full pool copy per step.

    attn_fn(lp, q, k, v, layer_idx) -> attn_out, where the pool holds tokens
    written in PREVIOUS steps only (attention folds the current step's k/v in
    directly).

    ``kernels`` reaches the matmuls only as the int4 consumer's rule
    (``Kernels.int4_pallas``) and the expert layers' dispatch (``_moe_mlp``);
    attention's choice is ``attn_fn``'s. ``tp_axis``/``ep_axis`` name manual
    mesh axes when running inside shard_map (parallel/pp.py); under GSPMD
    they stay None and the SPMD partitioner inserts the equivalent
    collectives.

    Returns (h, k_all, v_all, ssm, conv_rows) with k_all/v_all:
    [attention layers, T, n_kv_local * hd] —
    heads flattened per layer, the pool's own row layout, so the post-scan
    write consumes the scan's output buffers as they are (flattening the
    stacked [L, T, n_kv, hd] afterwards is a relayout: a second full copy
    of both, 2 x 144 MB at qwen3-4b T=2048, while the pool leaves no room).
    A latent-attention model (``cfg.is_mla``) has one pool: k_all is its
    rows [L, T, kv_row_padded], v_all is None, and ``attn_fn`` is handed
    (lp, q [T, nh, nope + rope], row [T, kv_row_padded], None, layer_idx).

    ``state_fn(lp, x, ssm, layer_idx) -> (out, ssm, conv_rows)`` is the
    state layers' mixer (``state_mixer`` under the step's meta). ``ssm``,
    the recurrent-state pool, IS carried through the scans: its update is a
    read-modify-write of the rows' own slots, in place (a Pallas call that
    aliases the pool, or dynamic_update_slices), and a layer's output needs
    the updated state, so it cannot wait for the end of the scan as the
    page write does. The conv rows do wait: they come back stacked
    [state layers, S + R, ...].

    A sparse-attention model (``cfg.index_topk``): ``choose_fn(q_i, w_i,
    k_i, j) -> choice`` turns the indexer's projections of "full" layer
    number ``j`` (``ops.dsa.project``) into what ``attn_fn`` is handed in
    v's place: the rows each query attends to. ``choice`` (any value of
    that structure to start from) is carried from layer to layer beside the
    residual: a "full" layer replaces it, a "shared" layer passes it on,
    both in one body (the layer's type is scanned, the branch is the
    device's). v_all is then the index keys [layers, T, index_head_dim],
    zeros for a "shared" layer.

    ``moe_load``: a list the caller owns; where the stack has expert layers
    the step's real routed pairs of each expert of each layer, [n_layers, E]
    int32, are appended to it (a traced value of the caller's own trace:
    the step program returns it beside its tokens, so the host reads the
    routing balance from a fetch it makes anyway). ``valid``: [T] bool, the
    step's real tokens, for the expert layers (``_moe_mlp``).
    """
    int4 = kernels.int4_pallas
    r = cfg.residual_multiplier
    hc = hyper_conn.settings(cfg) if cfg.hc_mult > 1 else None

    def scaled(branch):     # granite: h += r * branch (r == 1: nothing)
        return branch if r == 1.0 else branch * r

    # THE residual path, at all three sites (an attention layer's mixer, a
    # state layer's, a layer's MLP). The carry ``h`` is the residual: [T, d],
    # or a model's ``hc_mult`` streams side by side, [T, n d]
    # (``ops/hyper_conn.py``).
    def enter(lp, h, site):
        """What the sublayer at ``site`` reads of the residual (its own norm
        comes next), and what ``leave`` needs: the streams' learned mix and
        the token's coefficients; of one stream, itself and nothing."""
        if hc is None:
            return h, None
        with jax.named_scope("kgct.hc.pre"):
            return kernels.hc_pre(h, lp[f"hc_{site}_phi"],
                                  lp[f"hc_{site}_alpha"],
                                  lp[f"hc_{site}_bias"], hc)

    def leave(h, branch, coef):
        """The residual behind the sublayer: ``h + r * branch``, or the
        streams through their doubly stochastic map with the branch added
        to each by its weight."""
        if coef is None:
            return h + scaled(branch).astype(h.dtype)
        with jax.named_scope("kgct.hc.post"):
            return kernels.hc_post(h, branch.astype(h.dtype), coef)

    def at(stack, i):
        """Layer ``i`` of a stack, read where it lies. (A slice of a scan's
        xs handed to an inner scan is a COPY of those layers' weights every
        step: 1.4 GB a period at granite-4.0-h-micro's widths.)"""
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), stack)

    def mlp_of(lp, h, experts, i):
        """The layer's MLP over its normed input, whatever its mixer was: an
        expert layer if it holds a router (the leading dense layers of a
        deepseek_v3 stack do not), else the dense MLP. ``experts``/``i``:
        the expert tensors of the layer's stack and its index there.
        Returns (h + the branch, the layer's expert load or ())."""
        y, coef = enter(lp, h, "mlp")
        x = _norm(cfg, y, lp, "post_attn_norm")
        load = [] if moe_load is not None else None
        if "router" in lp:
            mlp = _moe_mlp(lp, x, cfg, tp_axis=tp_axis, ep_axis=ep_axis,
                           kernels=kernels, load_out=load,
                           stacked=(experts, i), valid=valid)
        else:
            mlp = _dense_mlp(lp, x, cfg, tp_axis=tp_axis, use_pallas=int4)
        return leave(h, mlp, coef), tuple(load or ())

    def state_layer(stack, carry, layer_idx):
        h, ssm = carry
        lp = at(stack.layers, layer_idx - stack.first)
        y, coef = enter(lp, h, "attn")
        x = _norm(cfg, y, lp, "input_norm")
        with jax.named_scope(_STATE_MIXERS[cfg.state_kind].scope):
            out, ssm, conv_rows = state_fn(lp, x, ssm, layer_idx)
        h = leave(h, out, coef)
        h, load = mlp_of(lp, h, stack.experts, layer_idx - stack.first)
        return (h, ssm), (conv_rows, load)

    if cfg.index_topk:
        # layer -> which entry of the ``indexer`` stack (and layer of the
        # index-key pool) it chooses with; -1: it shares.
        index_of = jnp.asarray(
            [cfg.index_layers.index(i) if t == "full" else -1
             for i, t in enumerate(cfg.indexer_types)], jnp.int32)

    def chosen_rows(lp, x, c_q, choice, layer_idx):
        """The layer's choice and its new index keys: a "full" layer's own,
        a "shared" layer's the one it was handed (and no keys)."""
        j = index_of[layer_idx]

        def full(_):
            with jax.named_scope("kgct.dsa.index"):
                q_i, w_i, k_i = dsa.project(
                    at(params["indexer"], j), cfg, c_q, x, positions)
            return choose_fn(q_i, w_i, k_i, j), k_i

        def shared(_):
            return choice, jnp.zeros((x.shape[0], cfg.index_head_dim),
                                     x.dtype)
        return jax.lax.cond(j >= 0, full, shared, None)

    def attn_layer(h, lp, stack, layer_idx, choice):
        y, coef = enter(lp, h, "attn")
        x = _norm(cfg, y, lp, "input_norm")
        if cfg.index_topk:
            with jax.named_scope("kgct.mla"):
                q, row, c_q = _mla_qkv(lp, cfg, x, positions, int4,
                                       with_latent=True)
            choice, k_i = chosen_rows(lp, x, c_q, choice, layer_idx)
            with jax.named_scope("kgct.mla"):
                attn_out = attn_fn(lp, q, row, choice, layer_idx)
        elif cfg.is_mla:
            with jax.named_scope("kgct.mla"):
                q, row = _mla_qkv(lp, cfg, x, positions, int4)
                attn_out = attn_fn(lp, q, row, None, layer_idx)
        else:
            q, k, v = _qkv(lp, cfg, x, positions, int4)
            attn_out = attn_fn(lp, q, k, v, layer_idx)
        attn_out = attn_out.reshape(x.shape[0], -1)
        o = _dot(attn_out, lp, "wo", int4)
        if tp_axis is not None:  # row-sharded wo: partial sums over local heads
            o = jax.lax.psum(o, tp_axis)
        if "bo" in lp:           # after the reduce: applied exactly once
            o = o + lp["bo"]
        h = leave(h, o, coef)
        h, load = mlp_of(lp, h, stack.experts, layer_idx - stack.first)
        if cfg.index_topk:
            return h, choice, ((row, k_i), load)
        if cfg.is_mla:
            return h, choice, ((row,), load)
        return h, choice, ((k.reshape(k.shape[0], -1),
                            v.reshape(v.shape[0], -1)), load)

    def split(stack):
        """A stack's expert tensors stay OUT of what the bodies index or
        scan: they read them in place, by layer index (see
        experts_grouped)."""
        experts = {k: stack[k] for k in _EXPERT_KEYS
                   if k in stack and "router" in stack}
        return {k: a for k, a in stack.items() if k not in experts}, experts

    def whole(a):    # the scan's [periods, n, ...] ys back to [layers, ...]
        return a.reshape((-1,) + a.shape[2:])

    def run_section(carry, types, repeats, attn, state):
        """``repeats`` periods of ``types`` as one scan: the period's
        attention layers one after the other, each run of state layers an
        inner scan over pool layer indices. ``attn``/``state``: the
        ``_Stack`` the period's layers of that kind lie in. The period's
        attention layer is scanned as xs where the period holds one and the
        section is its whole stack (every model without a tail)."""
        n_attn, n_state = types.count("attention"), len(types) - types.count(
            "attention")
        runs, seen = [], {"attention": 0, "state": 0}
        for kind in types:
            kind = kind if kind == "attention" else "state"
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen[kind], 1])
            seen[kind] += 1
        as_xs = n_attn == 1 and repeats == jax.tree.leaves(
            attn.layers)[0].shape[0]

        def period_body(carry, xs):
            (h, ssm, choice), (attn_p, attn_idx, ssm_idx) = carry, xs
            rows, loads, conv_rows = [], [], []
            for kind, start, count in runs:
                if kind == "state":
                    (h, ssm), (conv, load) = jax.lax.scan(
                        functools.partial(state_layer, state), (h, ssm),
                        ssm_idx + jnp.arange(start, start + count,
                                             dtype=jnp.int32))
                    conv_rows.append(conv)
                    loads.extend(load)          # [count, E], or nothing
                    continue
                for j in range(start, start + count):
                    idx = attn_idx + j if j else attn_idx
                    h, choice, (row, load) = attn_layer(
                        h, attn_p if as_xs else at(attn.layers,
                                                   idx - attn.first),
                        attn, idx, choice)
                    rows.append(row)
                    loads.extend(a[None] for a in load)
            rows = tuple(jnp.stack(a) for a in zip(*rows))  # [n_attn, T, ..]
            loads = jnp.concatenate(loads) if loads else ()
            conv_rows = jnp.concatenate(conv_rows) if conv_rows else ()
            return (h, ssm, choice), (rows, loads, conv_rows)

        idx = lambda stack, n: (jnp.arange(
            stack.at, stack.at + repeats * n, n, dtype=jnp.int32)
            if n else None)
        carry, (rows, loads, conv) = jax.lax.scan(
            period_body, carry,
            (attn.layers if as_xs else None, idx(attn, n_attn),
             idx(state, n_state)), length=repeats)
        return (carry, tuple(whole(a) for a in rows),
                loads if isinstance(loads, tuple) else whole(loads),
                whole(conv) if n_state else None)

    # The stack, section by section (``cfg.layer_sections``): the leading
    # dense layers, the periods, the tail; pool layer indices count on per
    # kind through all of them, a stack's own indices from its first layer.
    carry, rows, loads, conv_rows = (h, ssm, choice), [], [], []
    pool_at = {"attention": 0, "state": 0}
    stack_at: dict = {}
    held = layer_stacks(cfg)
    for types, repeats, dense in cfg.layer_sections:
        pre = "dense_" if dense and cfg.is_moe else ""
        # A pipeline stage (parallel/pp.py) holds its share of the layers:
        # the periods of a section are those its stacks hold here.
        first_of = pre + ("layers" if types[0] == "attention"
                          else "ssm_layers")
        repeats = repeats * jax.tree.leaves(
            params[first_of])[0].shape[0] // held[first_of]
        args = {}
        for kind, name in (("attention", pre + "layers"),
                           ("state", pre + "ssm_layers")):
            n = sum((t == "attention") == (kind == "attention")
                    for t in types)
            args[kind] = _Stack()
            if n:
                first = stack_at.setdefault(name, pool_at[kind])
                args[kind] = _Stack(*split(params[name]), first,
                                    pool_at[kind])
                pool_at[kind] += n * repeats
        carry, row, load, conv = run_section(
            carry, types, repeats, args["attention"], args["state"])
        if row:
            rows.append(row)
        if not isinstance(load, tuple):
            loads.append(load)
        if conv is not None:
            conv_rows.append(conv)
    h, ssm, _ = carry
    if loads:    # [expert layers, E], in the sections' order
        moe_load.append(loads[0] if len(loads) == 1
                        else jnp.concatenate(loads))
    rows = (rows[0] if len(rows) == 1 else
            tuple(jnp.concatenate(r, axis=0) for r in zip(*rows)))
    conv_rows = (None if not conv_rows else conv_rows[0]
                 if len(conv_rows) == 1 else jnp.concatenate(conv_rows))
    return (h, rows[0], rows[1] if len(rows) == 2 else None, ssm, conv_rows)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            meta: StepMeta, kv: KVCache, kernels: Kernels = NO_KERNELS, *,
            row_width: int = 1,
            hidden_in: Optional[jax.Array] = None,
            tp_axis: Optional[str] = None,
            ep_axis: Optional[str] = None,
            moe_load: Optional[list] = None):
    """THE forward pass: one program over the token axis
    ``[segment tokens | row tokens]`` that ``meta`` describes. Embedding,
    QKV/MLP matmuls and norms run once over all T tokens (the weight
    streaming a decode step pays is amortised over whatever rides along);
    only attention splits, at the static boundary, and each part routes
    through the operation the pure steps use:

    ============================  =======================================
    segment part, fresh           ``kernels.prefill_attention``
    segment part, with history    ``kernels.chunk_attention``
    row part, ``row_width == 1``  ``kernels.decode_attention``
    row part, ``row_width > 1``   ``kernels.verify_attention``
    row part, a block model's     ``kernels.block_attention``
    ============================  =======================================

    A part that is absent adds no operation: a pure decode or a pure
    prefill traces to exactly its own program. A latent-attention model
    (``cfg.is_mla``) meets fresh tokens in the materialised form and cached
    ones in the absorbed form (``mla_materialise``/``mla_absorbed``).

    Every part reads the pool PRE-write (this step's K/V fold in directly)
    and all new K/V, rejected drafts' included, commit in the one post-scan
    write; a rejected slot sits past its sequence's committed length and is
    overwritten before any later step reads it.

    ``hidden_in`` replaces the embedding lookup and ``tp_axis``/``ep_axis``
    name the manual mesh axes for a pipeline stage (parallel/pp.py's
    shard_map body); ``moe_load``: see ``_layer_scan``.

    A state model's state layers (``cfg.has_state``) run ``state_mixer``
    over the same token axis: the chunked scan on the segment part, the
    one-token update on the row part, against the slots ``meta.seg_slots``
    and ``meta.row_slots`` name in ``kv.ssm``/``kv.conv``.

    A model with residual streams (``cfg.hc_mult`` > 1) carries [T, n d]
    between the embedding, fanned out to every stream, and the final norm,
    which reads the streams' sum (``_layer_scan``'s ``enter``/``leave``).

    Returns (normed hidden of ``meta.logits_indices``' tokens, or of every
    token where that is None [*, d]; new_kv, the new state slots in it;
    raw_hidden [T, d] ([T, n d] with streams), which is what rotates stage
    to stage)."""
    scale = cfg.attn_scale
    h = (_embed(params, cfg, tokens, meta.positions)
         if hidden_in is None else hidden_in)
    if cfg.hc_mult > 1 and hidden_in is None:
        # Every residual stream starts as the embedding (arXiv:2409.19606);
        # the streams lie side by side in a row (ops/hyper_conn.py).
        h = jnp.tile(h, (1, cfg.hc_mult))
    n_rows = (0 if meta.page_tables is None
              else meta.page_tables.shape[0] * row_width)
    n_seg = 0 if meta.seg_ids is None else tokens.shape[0] - n_rows
    if n_seg < 0 or n_seg + n_rows != tokens.shape[0]:
        raise ValueError(
            f"{tokens.shape[0]} tokens are not {n_seg} segment tokens + "
            f"{n_rows} row tokens")

    sparse = (_SparseAttention(cfg, meta, kv, n_seg, n_rows)
              if cfg.index_topk else None)

    def segment_attn(lp, q, k, v, seg_ids, positions, layer_idx):
        if sparse and (meta.chunk_page_table is not None
                       or q.shape[0] > cfg.index_topk):
            # A chunk (a first one too: over no history the mask keeps what
            # is causal), or packed prompts long enough to choose.
            return sparse.segment(lp, q, k, v, layer_idx)
        if meta.chunk_page_table is None:
            # Each sequence's whole prompt is in this batch: tokens attend
            # within the in-batch k/v only (a latent model's in the
            # materialised form).
            if cfg.is_mla:
                k, v = mla_materialise(lp, cfg, k)
            return kernels.prefill_attention(q, k, v, seg_ids, positions,
                                             scale)
        if cfg.is_mla:
            return mla_chunk_attention(
                lp, cfg, q, k, seg_ids, positions, kv.k,
                meta.chunk_page_table, meta.hist_len, layer_idx, kernels)
        return kernels.chunk_attention(
            q, k, v, seg_ids, positions, kv.k, kv.v, meta.chunk_page_table,
            meta.hist_len, scale, layer=layer_idx)

    def row_attn(lp, q, k, v, layer_idx):
        # The pool holds positions 0..ctx-2. The STACKED pool + dynamic
        # layer index go straight to the kernel: no per-layer pool slice is
        # ever materialised (see _layer_scan).
        if cfg.block_length > 1:
            # A block model's pass: the row's two blocks over its pages.
            with jax.named_scope("kgct.block.attend"):
                return kernels.block_attention(
                    q, k, v, kv.k, kv.v, meta.page_tables,
                    meta.context_lens, scale, layer=layer_idx,
                    wide=meta.row_wide)
        if row_width > 1:
            return kernels.verify_attention(
                q, k, v, kv.k, kv.v, meta.page_tables, meta.context_lens,
                scale, layer=layer_idx)
        if sparse:
            return sparse.rows(lp, q, k, v, layer_idx)
        if cfg.is_mla:
            # Absorbed form: each latent page is read ONCE, as key and value.
            return mla_absorbed(
                lp, cfg, q, k, lambda qa, rows: kernels.decode_attention(
                    qa, kv.k, None, meta.page_tables, meta.context_lens,
                    rows, None, scale, layer=layer_idx))
        return kernels.decode_attention(
            q, kv.k, kv.v, meta.page_tables, meta.context_lens, k, v, scale,
            layer=layer_idx)

    def attn_fn(lp, q, k, v, layer_idx):
        if not n_rows:
            return segment_attn(lp, q, k, v, meta.seg_ids, meta.positions,
                                layer_idx)
        if not n_seg:
            return row_attn(lp, q, k, v, layer_idx)
        # v is None for a latent model (k is its cache row), or its
        # indexer's choice, of which each part reads its own half.
        qs, ks, qr, kr = q[:n_seg], k[:n_seg], q[n_seg:], k[n_seg:]
        vs, vr = (v, v) if v is None or sparse else (v[:n_seg], v[n_seg:])
        return jnp.concatenate(
            [segment_attn(lp, qs, ks, vs, meta.seg_ids[:n_seg],
                          meta.positions[:n_seg], layer_idx),
             row_attn(lp, qr, kr, vr, layer_idx)], axis=0)

    # The step's real tokens, for the expert layers: a prompt token of a
    # segment, a row that holds a sequence. (The same tokens whose
    # ``slot_mapping`` is not the scrap page's; a decode window's padding
    # rows count a context of 1 and pass for real, as before.)
    real = None
    if cfg.is_moe:
        parts = [meta.seg_ids[:n_seg] >= 0] if n_seg else []
        if n_rows:
            parts.append(jnp.repeat(meta.context_lens > 0, row_width))
        if meta.row_wide is not None:
            # ... and of a block model's row its second block only where
            # it has two.
            parts[-1] &= (meta.row_wide[:, None] | (
                jnp.arange(row_width) < cfg.block_length)).reshape(-1)
        real = jnp.concatenate(parts)
    state_fn = None
    if cfg.has_state:
        def state_fn(lp, x, ssm, layer_idx):
            return state_mixer(lp, cfg, x, meta, n_seg, ssm, kv.conv,
                               layer_idx, kernels)
    h, k_all, v_all, ssm, conv_rows = _layer_scan(
        params, cfg, h, meta.positions, attn_fn, kernels, tp_axis=tp_axis,
        ep_axis=ep_axis, moe_load=moe_load, valid=real, state_fn=state_fn,
        ssm=kv.ssm, **(dict(choose_fn=sparse.choose,
                            choice=sparse.start(h.dtype)) if sparse else {}))
    conv = kv.conv
    if cfg.has_state:
        # The state layers' new conv rows, every layer's at once, as the
        # pages are written: segments' slots, then rows'.
        conv = ssm_ops.write_slots(conv, conv_rows, jnp.concatenate(
            ([meta.seg_slots] if n_seg else [])
            + ([meta.row_slots] if n_rows else [])))
    idx_pool = kv.idx
    if sparse:
        # The "full" layers' index keys go where their latent rows go: the
        # same slots of the index-key pool's own layers.
        idx_pool, _ = kernels.write_pages(
            kv.idx, None, v_all[jnp.asarray(cfg.index_layers)], None,
            meta.slot_mapping)
        v_all = None
    new_kv = KVCache(*kernels.write_pages(kv.k, kv.v, k_all, v_all,
                                          meta.slot_mapping), ssm, conv,
                     idx_pool)
    selected = h if meta.logits_indices is None else h[meta.logits_indices]
    if cfg.hc_mult > 1:     # ... and their sum is what the final norm reads
        selected = jnp.sum(selected.astype(jnp.float32).reshape(
            selected.shape[0], cfg.hc_mult, -1), axis=1).astype(h.dtype)
    return _norm(cfg, selected, params, "final_norm"), new_kv, h


def compute_logits(params: Params, cfg: ModelConfig, hidden: jax.Array,
                   kernels: Kernels = NO_KERNELS) -> jax.Array:
    """hidden [B, d] -> logits [B, V] in fp32. ``kernels`` reaches the int4
    head matmul by the one rule of ``Kernels.int4_pallas``."""
    if cfg.tie_word_embeddings:
        logits = jnp.dot(hidden, params["embed"].T,
                         preferred_element_type=jnp.float32)
    else:
        logits = _dot(hidden, params, "lm_head", kernels.int4_pallas)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits
