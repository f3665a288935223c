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
- Two entry points matching the serving hot loop: ``forward_prefill`` (ragged
  flattened prompt tokens, causal-within-segment) and ``forward_decode`` (one
  token per sequence against the paged cache). Both scatter K/V into the page
  pool via precomputed slot mappings (padding slots land in the scrap page).
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
from ..ops.rope import apply_rope, rope_cos_sin
from ..ops.attention import (
    write_kv_pages_all,
    ragged_prefill_attention,
    ragged_prefill_attention_tp,
    prefill_history_attention,
    prefill_history_attention_tp,
    paged_decode_attention,
    paged_decode_attention_tp,
    mixed_attention,
    spec_mixed_attention,
    spec_verify_attention,
)

Params = dict[str, Any]


class PrefillMeta(NamedTuple):
    """Metadata for a ragged prefill step over T flattened prompt tokens."""
    seg_ids: jax.Array        # [T] int32 sequence id per token; padding = -1
    positions: jax.Array      # [T] int32 position within its sequence
    slot_mapping: jax.Array   # [T] int32 flat KV slot (scrap page for padding)
    logits_indices: jax.Array # [B] int32 index into T of each seq's last token


class DecodeMeta(NamedTuple):
    """Metadata for a decode step: one new token per sequence."""
    positions: jax.Array      # [B] int32 position of the new token
    slot_mapping: jax.Array   # [B] int32 flat KV slot for the new token
    page_tables: jax.Array    # [B, pages_per_seq] int32 page ids (pad = scrap)
    context_lens: jax.Array   # [B] int32 valid tokens incl. the new one


class SpecMeta(NamedTuple):
    """Metadata for a speculative-verification step over one padded token
    axis ``T = R_pad * S``: every running sequence contributes S = k+1
    contiguous slots (its last committed token + k drafts), attending to
    its own paged-pool history plus the earlier slice tokens causally.
    The per-row slot count S is static per compiled shape
    (``S = T // page_tables.shape[0]``)."""
    seg_ids: jax.Array          # [T] int32: row id on real slots, -1 padding
    positions: jax.Array        # [T] int32 global positions (RoPE input)
    slot_mapping: jax.Array     # [T] int32 KV write slot (overflow -> scrap)
    page_tables: jax.Array      # [R_pad, pages_bucket] per-row history pages
    context_lens: jax.Array     # [R_pad] committed tokens incl. slot 0's


class MixedMeta(NamedTuple):
    """Metadata for a mixed step over one padded token axis
    ``T = Tp_bucket + R_pad``: a prefill chunk (tokens [0:Tp_bucket), one
    sequence, attending to its pool history) followed by decode rows
    (tokens [Tp_bucket:T), one per running sequence, against the paged
    pool). The split point is static per compiled shape:
    ``Tp_bucket = T - page_tables.shape[0]``."""
    seg_ids: jax.Array          # [T] int32: 0 on chunk tokens, -1 elsewhere
    positions: jax.Array        # [T] int32 global positions (RoPE)
    slot_mapping: jax.Array     # [T] int32 KV write slot (pad -> scrap page)
    logits_indices: jax.Array   # [R_pad] rows to sample: decode rows then
                                # the chunk's last token
    chunk_page_table: jax.Array # [1, hist_width] the chunk seq's pages
    hist_len: jax.Array         # [] int32 chunk history already in the pool
    page_tables: jax.Array      # [R_pad, pages_bucket] decode page tables
    context_lens: jax.Array     # [R_pad] decode valid tokens incl. current


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

    d, L = cfg.hidden_size, cfg.num_layers
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
    if cfg.is_moe:
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
      (ops.quant.int4_matmul; Pallas kernel on TPU).
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


def _moe_mlp(lp: Params, x: jax.Array, cfg: ModelConfig,
             tp_axis: Optional[str] = None,
             ep_axis: Optional[str] = None,
             use_pallas: Optional[bool] = None) -> jax.Array:
    """Mixtral-style sparse MoE, dense-dispatch formulation: every expert runs
    over all tokens; combine weights zero out non-routed pairs. Exact (no
    capacity drops) and shard-friendly: under expert parallelism each device
    evaluates its local experts and the combine reduces over the expert axis —
    a psum over ``ep`` (automatic under GSPMD since the combine einsum
    contracts E; explicit when ``ep_axis`` names a manual shard_map axis).
    T is small in the serving hot loop, so the extra FLOPs stay MXU-bound
    rather than latency-critical."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    # Router always sees the full expert set (router weights replicated).
    router_logits = jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32))
    topk_vals, topk_idx = jax.lax.top_k(router_logits, k)           # [T, k]
    topk_w = jax.nn.softmax(topk_vals, axis=-1)                      # [T, k]
    # [T, k, E] one-hot routing -> [T, E] combine weights.
    combine = jnp.sum(jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)
                      * topk_w[..., None], axis=1)
    E_local = lp["w_gate"].shape[0]  # E under GSPMD; E/ep inside shard_map
    if ep_axis is not None and E_local != E:
        start = jax.lax.axis_index(ep_axis) * E_local
        combine = jax.lax.dynamic_slice_in_dim(combine, start, E_local, axis=1)

    def expert_fn(ep_params):
        gate = _dot(x, ep_params, "w_gate", use_pallas)
        up = _dot(x, ep_params, "w_up", use_pallas)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        return _dot(h, ep_params, "w_down", use_pallas)              # [T, d]

    expert_params = {k: lp[k] for k in
                     ("w_gate", "w_up", "w_down",
                      "w_gate_scale", "w_up_scale", "w_down_scale")
                     if k in lp}
    expert_outs = jax.vmap(expert_fn)(expert_params)  # [E_local, T, d]
    out = jnp.einsum("te,etd->td", combine, expert_outs)
    reduce_axes = tuple(a for a in (ep_axis, tp_axis) if a is not None)
    if reduce_axes:
        out = jax.lax.psum(out, reduce_axes)
    return out.astype(x.dtype)


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


def _mlp_block(lp: Params, cfg: ModelConfig, x: jax.Array,
               tp_axis: Optional[str] = None,
               ep_axis: Optional[str] = None,
               use_pallas: Optional[bool] = None) -> jax.Array:
    if cfg.is_moe:
        return _moe_mlp(lp, x, cfg, tp_axis=tp_axis, ep_axis=ep_axis,
                        use_pallas=use_pallas)
    return _dense_mlp(lp, x, cfg, tp_axis=tp_axis, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# Forward passes (scan over stacked layers; attn addresses the pool by index)
# ---------------------------------------------------------------------------

def _layer_scan(params: Params, cfg: ModelConfig, h: jax.Array,
                positions: jax.Array, attn_fn,
                layer_slice=None,
                tp_axis: Optional[str] = None,
                ep_axis: Optional[str] = None,
                use_pallas: Optional[bool] = None,
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scan the layer body over stacked weights.

    The KV pool does NOT travel through the scan: it is closed over whole and
    ``attn_fn`` receives the LAYER INDEX (scanned as xs) to address it.
    Slicing the pool per layer as scan xs — the previous design — made XLA
    materialize a [1, P, ps, kd] copy of each layer's pool every layer every
    substep (~1.4 ms/substep, ~20% of decode, measured in the round-3 device
    trace); the Pallas kernel addresses the stacked pool with a dynamic layer
    index instead, moving zero pool bytes. Each layer's freshly projected
    K/V come out as scan ys, and the caller commits them to the pool in ONE
    in-place write of the donated pool after the scan
    (ops.attention.write_kv_pages_all: on the chip a Pallas kernel that
    read-modify-writes the touched pool tiles by DMA, all in flight at
    once; elsewhere a loop of row updates). Threading the pool through the
    scan as carry/ys would force a full pool copy per step.

    attn_fn(lp, q, k, v, layer_idx) -> attn_out, where the pool holds tokens
    written in PREVIOUS steps only (attention folds the current step's k/v in
    directly).

    ``layer_slice`` restricts to a contiguous [start, stop) layer range.
    ``tp_axis``/``ep_axis`` name manual mesh axes when running inside
    shard_map (parallel/pp.py); under GSPMD they stay None and the SPMD
    partitioner inserts the equivalent collectives.

    Returns (h, k_all, v_all) with k_all/v_all: [L, T, n_kv_local * hd] —
    heads flattened per layer, the pool's own row layout, so the post-scan
    write consumes the scan's output buffers as they are (flattening the
    stacked [L, T, n_kv, hd] afterwards is a relayout: a second full copy
    of both, 2 x 144 MB at qwen3-4b T=2048, while the pool leaves no room).
    """
    layers = params["layers"]
    if layer_slice is not None:
        start, stop = layer_slice
        layers = jax.tree.map(lambda a: a[start:stop], layers)

    def body(h, xs):
        lp, layer_idx = xs
        resid = h
        x = _norm(cfg, h, lp, "input_norm")
        q, k, v = _qkv(lp, cfg, x, positions, use_pallas)
        attn_out = attn_fn(lp, q, k, v, layer_idx)
        attn_out = attn_out.reshape(x.shape[0], -1)
        o = _dot(attn_out, lp, "wo", use_pallas)
        if tp_axis is not None:  # row-sharded wo: partial sums over local heads
            o = jax.lax.psum(o, tp_axis)
        if "bo" in lp:           # after the reduce: applied exactly once
            o = o + lp["bo"]
        h = resid + o.astype(h.dtype)
        resid = h
        x = _norm(cfg, h, lp, "post_attn_norm")
        h = resid + _mlp_block(lp, cfg, x, tp_axis=tp_axis, ep_axis=ep_axis,
                               use_pallas=use_pallas)
        return h, (k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1))

    n_layers = jax.tree.leaves(layers)[0].shape[0]
    h, (k_all, v_all) = jax.lax.scan(
        body, h, (layers, jnp.arange(n_layers, dtype=jnp.int32)))
    return h, k_all, v_all


def forward_prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
                    meta: PrefillMeta, kv: KVCache,
                    layer_slice=None, use_pallas=None,
                    hidden_in: Optional[jax.Array] = None,
                    tp_axis: Optional[str] = None,
                    ep_axis: Optional[str] = None,
                    attn_mesh=None, attn_impl=None):
    """Ragged prefill over T flattened tokens. Returns (selected_hidden [B, d],
    new_kv, raw_hidden [T, d]). ``hidden_in`` replaces the embedding lookup for
    non-first pipeline stages; ``raw_hidden`` is what rotates stage-to-stage.
    ``attn_mesh``: under a GSPMD mesh, run the Pallas attention per-shard via
    shard_map over the tp axis (ops.attention.ragged_prefill_attention_tp).
    ``attn_impl``: full override ``fn(q, k, v, seg_ids, positions) -> out``
    (the engine passes ring attention here for sp>1 meshes)."""
    scale = cfg.head_dim ** -0.5
    h = (_embed(params, cfg, tokens, meta.positions)
         if hidden_in is None else hidden_in)

    def attn_fn(lp, q, k, v, layer_idx):
        # Prefill attends within the in-batch k/v only (each sequence's whole
        # prompt is in this batch); the pool is written post-scan for decode.
        if attn_impl is not None:
            return attn_impl(q, k, v, meta.seg_ids, meta.positions)
        if attn_mesh is not None:
            return ragged_prefill_attention_tp(attn_mesh, q, k, v,
                                               meta.seg_ids, meta.positions,
                                               scale)
        return ragged_prefill_attention(q, k, v, meta.seg_ids, meta.positions,
                                        scale, use_pallas=use_pallas)

    h, k_all, v_all = _layer_scan(params, cfg, h, meta.positions, attn_fn,
                                  layer_slice, tp_axis=tp_axis,
                                  ep_axis=ep_axis, use_pallas=use_pallas)
    if layer_slice is not None:
        kv = KVCache(k=kv.k[layer_slice[0]:layer_slice[1]],
                     v=kv.v[layer_slice[0]:layer_slice[1]])
    new_kv = KVCache(*write_kv_pages_all(kv.k, kv.v, k_all, v_all,
                                         meta.slot_mapping,
                                         use_pallas=use_pallas,
                                         mesh=attn_mesh))
    selected = h[meta.logits_indices]
    return _norm(cfg, selected, params, "final_norm"), new_kv, h


def forward_prefill_hist(params: Params, cfg: ModelConfig, tokens: jax.Array,
                         meta: PrefillMeta, kv: KVCache,
                         page_table: jax.Array, hist_len: jax.Array,
                         use_pallas=None, attn_mesh=None,
                         hidden_in: Optional[jax.Array] = None,
                         tp_axis: Optional[str] = None,
                         ep_axis: Optional[str] = None):
    """Chunked prefill: one sequence's chunk attending to its pool history +
    itself causally (ops.attention.prefill_history_attention). Returns
    (normed_selected [1, d], new_kv, raw_hidden [T, d]). ``attn_mesh``: under
    a GSPMD mesh, run the Pallas history kernel per-shard via shard_map over
    the tp axis. ``hidden_in``/``tp_axis``/``ep_axis``: manual-mesh entry for
    non-first pipeline stages (parallel/pp.py's pipelined chunked prefill)."""
    scale = cfg.head_dim ** -0.5
    h = (_embed(params, cfg, tokens, meta.positions)
         if hidden_in is None else hidden_in)

    def attn_fn(lp, q, k, v, layer_idx):
        if attn_mesh is not None:
            return prefill_history_attention_tp(
                attn_mesh, q, k, v, meta.seg_ids, meta.positions, kv.k, kv.v,
                page_table, hist_len, scale, layer=layer_idx)
        return prefill_history_attention(
            q, k, v, meta.seg_ids, meta.positions, kv.k, kv.v,
            page_table, hist_len, scale, layer=layer_idx,
            use_pallas=use_pallas)

    h, k_all, v_all = _layer_scan(params, cfg, h, meta.positions, attn_fn,
                                  tp_axis=tp_axis, ep_axis=ep_axis,
                                  use_pallas=use_pallas)
    new_kv = KVCache(*write_kv_pages_all(kv.k, kv.v, k_all, v_all,
                                         meta.slot_mapping,
                                         use_pallas=use_pallas,
                                         mesh=attn_mesh))
    selected = h[meta.logits_indices]
    return _norm(cfg, selected, params, "final_norm"), new_kv, h


def forward_mixed(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  meta: MixedMeta, kv: KVCache,
                  use_pallas=None, use_pallas_hist=None, attn_mesh=None):
    """Mixed prefill/decode step (stall-free batching): ONE forward over the
    combined token axis — embedding, QKV/MLP matmuls and norms run once for
    chunk and decode tokens together, so the weight streaming a decode step
    pays is amortized over the prefill chunk riding along — with attention
    split at the static chunk/decode boundary: chunk tokens run history
    attention against their own pool pages, decode rows run paged decode
    (ops.attention.mixed_attention). Returns (normed_selected [R_pad, d],
    new_kv, raw_hidden [T, d]).

    Single-mesh and GSPMD-tp regimes only — under pp the layer stack is
    sharded outside this path and under sp ring attention replaces the
    ragged kernels; the engine falls back to the legacy scheduler policy
    there."""
    scale = cfg.head_dim ** -0.5
    h = _embed(params, cfg, tokens, meta.positions)
    n_prefill = tokens.shape[0] - meta.page_tables.shape[0]

    def attn_fn(lp, q, k, v, layer_idx):
        return mixed_attention(
            q, k, v, meta.seg_ids, meta.positions, kv.k, kv.v,
            meta.chunk_page_table, meta.hist_len, meta.page_tables,
            meta.context_lens, scale, n_prefill=n_prefill, layer=layer_idx,
            use_pallas=use_pallas, use_pallas_hist=use_pallas_hist,
            attn_mesh=attn_mesh)

    h, k_all, v_all = _layer_scan(params, cfg, h, meta.positions, attn_fn,
                                  use_pallas=use_pallas)
    new_kv = KVCache(*write_kv_pages_all(kv.k, kv.v, k_all, v_all,
                                         meta.slot_mapping,
                                         use_pallas=use_pallas,
                                         mesh=attn_mesh))
    selected = h[meta.logits_indices]
    return _norm(cfg, selected, params, "final_norm"), new_kv, h


def forward_spec_mixed(params: Params, cfg: ModelConfig, tokens: jax.Array,
                       meta: MixedMeta, kv: KVCache, S: int,
                       use_pallas=None, use_pallas_hist=None,
                       attn_mesh=None):
    """Spec×mixed step: ONE forward over the combined
    ``[prefill chunk | verify slices]`` token axis — embedding, QKV/MLP
    matmuls and norms run once for chunk and verify tokens together (the
    weight streaming a verify step pays is amortized over the chunk riding
    along, the same economics that motivated mixed batching) — with
    attention split at the static chunk/verify boundary
    (ops.attention.spec_mixed_attention). ``S = k+1`` is config-static per
    compiled shape (the engine passes it as a jit static arg):
    ``n_prefill = T - R_pad * S``. Returns (normed_selected
    [R_pad*S + 1, d] — every verify slot plus the chunk's last token —
    new_kv, raw_hidden [T, d])."""
    scale = cfg.head_dim ** -0.5
    h = _embed(params, cfg, tokens, meta.positions)
    n_prefill = tokens.shape[0] - meta.page_tables.shape[0] * S

    def attn_fn(lp, q, k, v, layer_idx):
        return spec_mixed_attention(
            q, k, v, meta.seg_ids, meta.positions, kv.k, kv.v,
            meta.chunk_page_table, meta.hist_len, meta.page_tables,
            meta.context_lens, scale, n_prefill=n_prefill, layer=layer_idx,
            use_pallas=use_pallas, use_pallas_hist=use_pallas_hist,
            attn_mesh=attn_mesh)

    h, k_all, v_all = _layer_scan(params, cfg, h, meta.positions, attn_fn,
                                  use_pallas=use_pallas)
    new_kv = KVCache(*write_kv_pages_all(kv.k, kv.v, k_all, v_all,
                                         meta.slot_mapping,
                                         use_pallas=use_pallas,
                                         mesh=attn_mesh))
    selected = h[meta.logits_indices]
    return _norm(cfg, selected, params, "final_norm"), new_kv, h


def forward_spec_verify(params: Params, cfg: ModelConfig, tokens: jax.Array,
                        meta: SpecMeta, kv: KVCache, use_pallas=None,
                        attn_mesh=None):
    """Speculative-verification forward: ONE program scores every running
    sequence's k drafted tokens. Embedding, QKV/MLP matmuls and norms run
    over the flat ``[R_pad * S]`` token axis (the weight streaming a decode
    step pays is amortized over all draft positions — the same economics
    as mixed batching); attention runs the batched draft-verification
    shape (ops.attention.spec_verify_attention: paged-pool history + an
    S x S causal block per row). Returns (normed_hidden [T, d] over EVERY
    slot — the verifier needs logits at all draft positions, not one
    sampled row — new_kv, raw_hidden [T, d]). All new K/V (including
    drafts that will be rejected) commit in the one post-scan write;
    rejected slots sit past the sequence's committed length and are
    overwritten before any later step reads them. ``attn_mesh``: under a
    GSPMD mesh the KV write kernel runs per shard (attention here is XLA
    on every backend)."""
    scale = cfg.head_dim ** -0.5
    h = _embed(params, cfg, tokens, meta.positions)

    def attn_fn(lp, q, k, v, layer_idx):
        return spec_verify_attention(
            q, k, v, kv.k, kv.v, meta.page_tables, meta.context_lens, scale,
            layer=layer_idx, use_pallas=use_pallas)

    h, k_all, v_all = _layer_scan(params, cfg, h, meta.positions, attn_fn,
                                  use_pallas=use_pallas)
    new_kv = KVCache(*write_kv_pages_all(kv.k, kv.v, k_all, v_all,
                                         meta.slot_mapping,
                                         use_pallas=use_pallas,
                                         mesh=attn_mesh))
    return _norm(cfg, h, params, "final_norm"), new_kv, h


def forward_decode(params: Params, cfg: ModelConfig, tokens: jax.Array,
                   meta: DecodeMeta, kv: KVCache,
                   layer_slice=None, use_pallas=None,
                   hidden_in: Optional[jax.Array] = None,
                   tp_axis: Optional[str] = None,
                   ep_axis: Optional[str] = None,
                   attn_mesh=None):
    """Decode step: B sequences, one new token each, against the paged pool.
    Returns (normed_hidden [B, d], new_kv, raw_hidden [B, d]).
    ``attn_mesh``: under a GSPMD mesh, run the Pallas attention per-shard via
    shard_map over the tp axis (ops.attention.paged_decode_attention_tp)."""
    scale = cfg.head_dim ** -0.5
    h = (_embed(params, cfg, tokens, meta.positions)
         if hidden_in is None else hidden_in)

    if layer_slice is not None:
        kv = KVCache(k=kv.k[layer_slice[0]:layer_slice[1]],
                     v=kv.v[layer_slice[0]:layer_slice[1]])

    def attn_fn(lp, q, k, v, layer_idx):
        # Pool holds positions 0..ctx-2; this step's k/v fold in directly and
        # are committed to the pool in one post-scan write. The STACKED pool
        # + dynamic layer index go straight to the kernel — no per-layer pool
        # slice is ever materialized (see _layer_scan docstring).
        if attn_mesh is not None:
            return paged_decode_attention_tp(attn_mesh, q, kv.k, kv.v,
                                             meta.page_tables,
                                             meta.context_lens, k, v, scale,
                                             layer=layer_idx)
        return paged_decode_attention(q, kv.k, kv.v, meta.page_tables,
                                      meta.context_lens, k, v, scale,
                                      layer=layer_idx, use_pallas=use_pallas)

    h, k_all, v_all = _layer_scan(params, cfg, h, meta.positions, attn_fn,
                                  layer_slice, tp_axis=tp_axis, ep_axis=ep_axis)
    new_kv = KVCache(*write_kv_pages_all(kv.k, kv.v, k_all, v_all,
                                         meta.slot_mapping,
                                         use_pallas=use_pallas,
                                         mesh=attn_mesh))
    return _norm(cfg, h, params, "final_norm"), new_kv, h


def compute_logits(params: Params, cfg: ModelConfig, hidden: jax.Array,
                   use_pallas: Optional[bool] = None) -> jax.Array:
    """hidden [B, d] -> logits [B, V] in fp32. ``use_pallas`` reaches the
    dequant-fused int4 head matmul (same tri-state as the attention
    kernels: None = auto by backend, False = the XLA kill-switch)."""
    if cfg.tie_word_embeddings:
        return jnp.dot(hidden, params["embed"].T,
                       preferred_element_type=jnp.float32)
    return _dot(hidden, params, "lm_head", use_pallas)
