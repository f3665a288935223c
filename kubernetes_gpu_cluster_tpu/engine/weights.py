"""Weight loading: local HF safetensors checkpoints -> stacked params pytree.

The reference pre-staged model weights on every node and mounted them via
hostPath (``old_README.md:1482-1561``, ``values-01-minimal-example3.yaml:22-30``)
— the same zero-egress deployment story applies here: weights are read from a
LOCAL directory (git-lfs clone / rsync, as the reference did), never
downloaded at serving time.

Mapping: HF per-layer tensors (torch ``[out, in]`` convention) are transposed
to our right-multiply ``[in, out]`` layout and STACKED along a leading [L]
axis to match models/llama.py's scanned-layer params. Families covered match
config/model_config.py: llama-class (Llama 1/2/3, TinyLlama), Qwen2/2.5
(attention bias), Qwen3 (qk-norm, tied embeddings), Mixtral (MoE experts).

Memory discipline: tensors are read lazily from the safetensors mmap and
written straight into preallocated per-parameter numpy buffers, so peak host
memory is ~one copy of the model (required for 8B on a 16G host; 70B loads
are expected to run sharded, one host per PP stage / TP shard via
``shardings``, where jax.device_put uploads only the addressable shards).
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..config.model_config import MODEL_PRESETS
from ..utils import get_logger

logger = get_logger("engine.weights")

Params = dict[str, Any]


def config_from_hf(path: str, name: Optional[str] = None) -> ModelConfig:
    """Build a ModelConfig from a local HF checkpoint's config.json — any
    llama/qwen2/qwen3/mixtral-architecture model works without a preset."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    if "text_config" in hf:
        # A kimi_vl checkpoint: the language model's settings are nested,
        # beside a vision tower this server does not run (text only).
        logger.info("%s config: serving text_config (%s); vision_config "
                    "is not served", hf.get("model_type", "multimodal"),
                    hf["text_config"].get("model_type"))
        hf = hf["text_config"]
    arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
    if arch == "OPTForCausalLM":
        return _opt_config_from_hf(hf, name or
                                   os.path.basename(os.path.normpath(path)))
    if hf.get("model_type") == "kimi_linear":
        return _kimi_linear_config_from_hf(
            hf, name or os.path.basename(os.path.normpath(path)))
    if hf.get("kv_lora_rank"):
        return _deepseek_config_from_hf(
            hf, name or os.path.basename(os.path.normpath(path)))
    if hf.get("model_type") == "granitemoehybrid":
        return _granite_hybrid_config_from_hf(
            hf, name or os.path.basename(os.path.normpath(path)))
    num_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // num_heads
    rope_scaling = None
    if hf.get("rope_scaling"):
        from ..ops.rope import scaled_inv_freq
        raw = {k: v for k, v in hf["rope_scaling"].items()
               if isinstance(v, (str, int, float, bool))}
        # Validate NOW — an unsupported type (yarn, dynamic, ...) must fail
        # the load, not silently serve with unscaled RoPE. YaRN is written
        # as deepseek_v3 scales it (the softmax scale takes m^2): the
        # latent-attention decoder's, not this one's.
        if (raw.get("rope_type") or raw.get("type")) == "yarn":
            raise ValueError(
                "unsupported rope_scaling type 'yarn' for a K|V decoder "
                "(supported: llama3, linear; yarn with latent attention)")
        scaled_inv_freq(head_dim, float(hf.get("rope_theta", 10000.0)), raw)
        rope_scaling = tuple(sorted(raw.items()))
    return ModelConfig(
        name=name or os.path.basename(os.path.normpath(path)),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=head_dim,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        attention_bias=bool(hf.get("attention_bias",
                                   arch == "Qwen2ForCausalLM")),
        qk_norm=arch == "Qwen3ForCausalLM",
        num_experts=hf.get("num_local_experts", 0),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        max_model_len=min(int(hf.get("max_position_embeddings", 4096)), 8192),
    )


# glm_moe_dsa's sparse-attention keys the decoder implements (ops/dsa.py).
# ``index_share_for_mtp_iteration`` speaks of the multi-token-prediction
# module, which is not served; ``indexer_rope_interleave`` of the
# checkpoint's column order, which a loader undoes (half-split here).
_INDEX_KEYS = {"index_topk", "index_n_heads", "index_head_dim",
               "indexer_types", "index_topk_freq", "index_skip_topk_offset",
               "index_topk_pattern", "index_share_for_mtp_iteration",
               "indexer_rope_interleave"}


def _index_fields(hf: dict, name: str) -> dict:
    """The ``index_*`` keys of a deepseek_v3-class config as ModelConfig
    fields; {} for a model without an indexer. A key the decoder does not
    implement refuses the load by its name: served without it the model
    would attend densely under a sparse model's name."""
    keys = sorted(k for k in hf if k.startswith(("index_", "indexer_")))
    if not keys:
        return {}
    unknown = [k for k in keys if k not in _INDEX_KEYS]
    if unknown or hf.get("index_topk_pattern") is not None:
        raise ValueError(
            f"{name}: config.json "
            f"{', '.join(unknown) or 'index_topk_pattern'} is not "
            "implemented by the latent-attention decoder's indexer")
    topk, types = hf.get("index_topk"), hf.get("indexer_types")
    if not topk:
        raise ValueError(f"{name}: config.json {', '.join(keys)} without "
                         "index_topk: nothing says how many tokens a query "
                         "keeps")
    if not types or not hf.get("q_lora_rank"):
        raise ValueError(
            f"{name}: config.json index_topk {topk} without "
            + ("indexer_types" if not types else "q_lora_rank")
            + ": the decoder does not guess which layers choose and which "
            "share, and the indexer's query reads the query latent")
    freq, off = hf.get("index_topk_freq"), hf.get("index_skip_topk_offset")
    if freq is not None and off is not None and list(types) != [
            "full" if i < off or (i - off) % freq == freq - 1 else "shared"
            for i in range(len(types))]:
        raise ValueError(
            f"{name}: config.json indexer_types is not what "
            f"index_topk_freq {freq} and index_skip_topk_offset {off} give")
    return dict(index_topk=int(topk), index_n_heads=int(hf["index_n_heads"]),
                index_head_dim=int(hf["index_head_dim"]),
                indexer_types=tuple(types))


_HC_KEYS = {"hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max"}


def _deepseek_config_from_hf(hf: dict, name: str) -> ModelConfig:
    """deepseek_v3-class language model (kimi_vl's ``text_config``; xing4_0):
    latent attention, with a low-rank query where ``q_lora_rank`` says so,
    sigmoid/noaux_tc routing, shared experts, leading dense layers, YaRN on
    the rope dims; xing4_0's residual streams (the ``hc_*`` / ``mhc_*``
    keys: manifold-constrained hyper-connections). A multi-token-prediction
    module (``num_nextn_predict_layers``) is accepted and NOT served: the
    decoder is the model without it, and the log says so. What the decoder
    does not implement refuses the load by name."""
    from ..config.model_config import HF_SHAPE_KEYS
    from ..ops.rope import scaled_inv_freq
    rope_scaling = None
    if hf.get("rope_parameters"):   # glm_moe_dsa: theta and type in a group
        rp = hf["rope_parameters"]
        if rp.get("rope_type", "default") != "default":
            raise ValueError(
                f"{name}: config.json rope_parameters rope_type="
                f"{rp['rope_type']!r} is not implemented by the "
                "latent-attention decoder")
        hf = {**hf, "rope_theta": rp.get("rope_theta",
                                         hf.get("rope_theta", 10000.0))}
    nd = hf.get("first_k_dense_replace", 0)
    mlp_types = hf.get("mlp_layer_types")
    if mlp_types is not None and list(mlp_types) != (
            ["dense"] * nd + ["sparse"] * (len(mlp_types) - nd)):
        raise ValueError(
            f"{name}: config.json mlp_layer_types is not "
            f"first_k_dense_replace={nd} dense layers and expert layers "
            "behind them")
    if hf.get("rope_scaling"):
        raw = {k: v for k, v in hf["rope_scaling"].items()
               if isinstance(v, (str, int, float, bool))}
        try:    # a type the tree lacks refuses the load, by its name
            scaled_inv_freq(hf["qk_rope_head_dim"],
                            float(hf.get("rope_theta", 10000.0)), raw)
        except ValueError as e:
            raise ValueError(f"{name}: config.json rope_scaling: {e}") from e
        rope_scaling = tuple(sorted(raw.items()))
    unknown = sorted(k for k in hf if k.startswith(("hc_", "mhc_"))
                     and k not in _HC_KEYS)
    for key, ok in (("hc_*", not unknown),
                    ("n_group", hf.get("n_group", 1) == 1),
                    ("topk_group", hf.get("topk_group", 1) == 1),
                    ("moe_layer_freq", hf.get("moe_layer_freq", 1) == 1),
                    ("hidden_act", hf.get("hidden_act", "silu") == "silu"),
                    ("attention_bias", not hf.get("attention_bias"))):
        if not ok:
            what = (f"{key}={hf.get(key)!r}" if key in hf
                    else ", ".join(unknown))
            raise ValueError(
                f"{name}: config.json {what} is not "
                "implemented by the latent-attention decoder")
    if hf.get("num_nextn_predict_layers"):
        logger.info(
            "%s: num_nextn_predict_layers=%s: the multi-token-prediction "
            "module is not served (a draft from it needs a verify step over "
            "latent pages); the decoder runs without it", name,
            hf["num_nextn_predict_layers"])
    fields = {ours: hf[theirs] for theirs, ours in HF_SHAPE_KEYS.items()
              if hf.get(theirs) is not None}
    fields["max_model_len"] = min(int(fields.get("max_model_len", 4096)), 8192)
    fields.setdefault("num_kv_heads", fields["num_heads"])
    fields.update(_index_fields(hf, name))
    if hf.get("hc_mult", 1) > 1:
        fields.update(
            hc_mult=hf["hc_mult"],
            hc_sinkhorn_iters=int(hf.get("hc_sinkhorn_iters", 20)),
            hc_eps=float(hf.get("hc_eps", 1e-6)),
            hc_res_clamp=(float(hf.get("mhc_h_res_clamp_min", -30.0)),
                          float(hf.get("mhc_h_res_clamp_max", 30.0))))
    return ModelConfig(
        name=name,
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        scoring_func=hf.get("scoring_func", "softmax"),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        **fields)


def _kimi_linear_config_from_hf(hf: dict, name: str) -> ModelConfig:
    """kimi_linear (Kimi-Linear-48B-A3B): KDA state layers and latent
    attention layers by the two 1-based lists of ``linear_attn_config``,
    kimi_vl's MLA widths (``mla_use_nope``: no rotation), a leading dense
    layer, sigmoid-routed experts beside a shared one. The family names its
    expert keys otherwise than deepseek_v3 does. Entries of the lists past
    ``num_hidden_layers`` are another stage's; a layer neither list names,
    or both, refuses the load by name, as does what the decoder does not
    implement."""
    lin = hf["linear_attn_config"]
    for key, ok in (
            ("q_lora_rank", hf.get("q_lora_rank") is None),
            ("rope_scaling", not hf.get("rope_scaling")),
            ("num_expert_group", hf.get("num_expert_group", 1) == 1),
            ("topk_group", hf.get("topk_group", 1) == 1),
            ("moe_layer_freq", hf.get("moe_layer_freq", 1) == 1),
            ("num_nextn_predict_layers",
             not hf.get("num_nextn_predict_layers")),
            ("moe_router_activation_func",
             hf.get("moe_router_activation_func", "sigmoid")
             in ("sigmoid", "softmax")),
            ("hidden_act", hf.get("hidden_act", "silu") == "silu")):
        if not ok:
            raise ValueError(
                f"{name}: config.json {key}={hf.get(key)!r} is not "
                "implemented by the delta-rule decoder")
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    types = []
    for layer in range(1, hf["num_hidden_layers"] + 1):     # 1-based
        if (layer in kda) == (layer in full):
            raise ValueError(
                f"{name}: config.json linear_attn_config names layer "
                f"{layer} in " + ("both" if layer in kda else "neither")
                + " of kda_layers and full_attn_layers")
        types.append("kda" if layer in kda else "attention")
    return ModelConfig(
        name=name, vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads",
                            hf["num_attention_heads"]),
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        pos_embedding="none" if hf.get("mla_use_nope") else "rope",
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_token"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_shared_experts=hf.get("num_shared_experts", 0),
        first_k_dense_replace=hf.get("first_k_dense_replace", 0),
        scoring_func=hf.get("moe_router_activation_func", "sigmoid"),
        norm_topk_prob=bool(hf.get("moe_renormalize", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        layer_types=tuple(types), kda_n_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_d_conv=lin.get("short_conv_kernel_size", 4),
        max_model_len=min(int(hf.get("max_position_embeddings", 4096)), 8192),
    )


def _granite_hybrid_config_from_hf(hf: dict, name: str) -> ModelConfig:
    """granitemoehybrid (granite-4.0-h): Mamba-2 state layers and GQA
    attention layers by ``layer_types``, one SwiGLU a layer, four scalar
    multipliers. What the decoder does not implement refuses the load by
    name (the family's expert variants among it)."""
    for key, ok in (
            ("num_local_experts", not hf.get("num_local_experts")),
            ("position_embedding_type",
             hf.get("position_embedding_type", "nope") in ("nope", None)),
            ("mamba_n_groups", hf.get("mamba_n_groups", 1) == 1),
            ("mamba_proj_bias", not hf.get("mamba_proj_bias")),
            ("mamba_conv_bias", hf.get("mamba_conv_bias", True)),
            ("mamba_expand", hf.get("mamba_expand", 2) * hf["hidden_size"]
             == hf["mamba_n_heads"] * hf["mamba_d_head"]),
            ("time_step_limit", "time_step_limit" not in hf),
            ("hidden_act", hf.get("hidden_act", "silu") == "silu"),
            ("attention_bias", not hf.get("attention_bias"))):
        if not ok:
            raise ValueError(
                f"{name}: config.json {key}={hf.get(key)!r} is not "
                "implemented by the state-layer decoder")
    num_heads = hf["num_attention_heads"]
    return ModelConfig(
        name=name, vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["shared_intermediate_size"],
        num_layers=hf["num_hidden_layers"], num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=hf["hidden_size"] // num_heads, pos_embedding="none",
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        layer_types=tuple(hf["layer_types"]),
        mamba_n_heads=hf["mamba_n_heads"], mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"], mamba_n_groups=1,
        mamba_d_conv=hf["mamba_d_conv"],
        mamba_chunk_size=hf.get("mamba_chunk_size", 256),
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf.get("logits_scaling", 1.0)),
        max_model_len=min(int(hf.get("max_position_embeddings", 4096)), 8192),
    )


def _validate_act(act: str) -> str:
    """Fail the LOAD on an unmapped activation, not the first trace."""
    from ..models.llama import _MLP_ACTS
    if act not in _MLP_ACTS:
        raise ValueError(f"unsupported activation_function {act!r}; "
                         f"supported: {sorted(_MLP_ACTS)}")
    return act


def _opt_config_from_hf(hf: dict, name: str) -> ModelConfig:
    """OPT (the reference's minimal-example model, facebook/opt-125m at
    reference values-01-minimal-example.yaml:8): learned positions (+2
    offset), pre-LN LayerNorm with biases, biased ReLU fc1/fc2 MLP, tied
    head. Served through the shared decoder graph (models/llama.py) via
    ModelConfig flags."""
    h = hf["hidden_size"]
    num_heads = hf["num_attention_heads"]
    if hf.get("word_embed_proj_dim", h) != h:
        raise ValueError("OPT word_embed_proj_dim != hidden_size (projected "
                         "embeddings) is not supported")
    if not hf.get("do_layer_norm_before", True):
        raise ValueError("OPT post-LN variants (do_layer_norm_before=false, "
                         "e.g. opt-350m) are not supported")
    bias = bool(hf.get("enable_bias", True))
    return ModelConfig(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=h,
        intermediate_size=hf["ffn_dim"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=num_heads,
        head_dim=h // num_heads,
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        attention_bias=bias,
        norm_type="layernorm",
        pos_embedding="learned",
        mlp_type="mlp",
        mlp_act=_validate_act(hf.get("activation_function", "relu")),
        linear_bias=bias,
        max_model_len=min(int(hf.get("max_position_embeddings", 2048)), 8192),
    )


class _Checkpoint:
    """All *.safetensors files of a checkpoint dir behind one name->tensor
    lookup (lazy: tensors are materialized per get())."""

    def __init__(self, path: str):
        from safetensors import safe_open

        self._handles = []
        self._index: dict[str, int] = {}
        files = sorted(f for f in os.listdir(path)
                       if f.endswith(".safetensors"))
        if not files:
            raise FileNotFoundError(f"no *.safetensors under {path}")
        for f in files:
            h = safe_open(os.path.join(path, f), framework="np")
            i = len(self._handles)
            self._handles.append(h)
            for key in h.keys():
                self._index[key] = i
        logger.info("checkpoint %s: %d files, %d tensors", path, len(files),
                    len(self._index))

    def __contains__(self, key: str) -> bool:
        return key in self._index

    def get(self, key: str) -> np.ndarray:
        arr = self._handles[self._index[key]].get_tensor(key)
        if arr.dtype == np.dtype("V2"):   # raw bf16 comes back as void16
            arr = arr.view(jnp.bfloat16)
        return arr

    def get_t(self, key: str) -> np.ndarray:
        """Fetch a torch [out, in] matrix as [in, out]."""
        return np.ascontiguousarray(self.get(key).T)

    def slice(self, key: str, idx: tuple) -> np.ndarray:
        """Ranged read: only the requested byte ranges leave the file
        (safetensors PySafeSlice). ``idx``: tuple of slices in the tensor's
        ON-DISK (torch) layout."""
        s = self._handles[self._index[key]].get_slice(key)
        arr = s[idx] if len(idx) > 1 else s[idx[0]]
        if arr.dtype == np.dtype("V2"):
            arr = arr.view(jnp.bfloat16)
        return arr


def _load_streamed(ckpt: _Checkpoint, cfg: ModelConfig, shardings: Any,
                   dtype) -> Params:
    """Shard-aware streaming load: each process materializes ONLY the slices
    its addressable devices need (``jax.make_array_from_callback``), read
    from the safetensors via ranged reads — never the full stacked model.
    Host RSS is ~(this host's shard bytes) + one transient layer slice, so a
    llama-3-70b load over a pp*tp mesh stays tens-of-GB-per-host instead of
    the ~140 GB a full host-side stack would take (BASELINE config 5; the
    reference's analogue is the pre-staged /models hostPath story,
    old_README.md:1482-1561).

    Quantization notes (ops/quant.py):

    - int8: scales are per OUTPUT channel over the FULL input dim.
      Column-sharded (out-split) weights quantize their slice exactly —
      every shard sees the full input dim. Row-sharded (in-split) weights
      (wo, w_down) read the full [out, in] layer row-block to compute the
      scale, then quantize only their input columns, so every shard agrees
      with the global scale bit-for-bit.
    - int4: scales are per (input-dim group, output channel), and the
      packed/scale params carry the input dim at 1/2 resp. 1/group_size
      resolution. Column-sharded weights see the full input dim, so
      slice-quantize == global quantize as for int8. Row-sharded weights
      shard the GROUP axis: shard boundaries must land on group boundaries
      (validated here), after which each shard's groups are fully contained
      in its slice — quantizing the slice alone reproduces the global
      packed bytes and scales bit-for-bit, with no full-row read at all."""
    from ..ops.quant import (int4_group_scale, quantize_tensor,
                             quantize_tensor_int4)

    L, d = cfg.num_layers, cfg.hidden_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ff, E, V = cfg.intermediate_size, cfg.num_experts, cfg.vocab_size
    pre = "model.layers.{}."
    quant = cfg.quantization is not None
    int4 = cfg.quantization == "int4"
    gs = cfg.quant_group_size

    def packed_shape(shape):
        """Logical weight shape -> stored (possibly nibble-packed) shape."""
        if not int4:
            return shape
        return shape[:-2] + (shape[-2] // 2,) + shape[-1:]

    def scale_shape(shape):
        """Logical weight shape -> its scale param's shape."""
        if int4:
            return shape[:-2] + (shape[-2] // gs,) + shape[-1:]
        return shape[:-2] + shape[-1:]

    def norm_idx(idx, shape):
        out = []
        for dim, sl in zip(shape, idx):
            start, stop, step = sl.indices(dim)
            if step != 1:
                raise ValueError(f"non-contiguous shard slice {sl}")
            out.append(slice(start, stop))
        return tuple(out)

    def make(shape, sharding, fetch, out_dtype):
        memo: dict = {}   # dedupe replicated shards within this one param

        def cb(idx):
            nidx = norm_idx(idx, shape)
            key = tuple((s.start, s.stop) for s in nidx)
            if key not in memo:
                memo[key] = np.ascontiguousarray(
                    np.asarray(fetch(nidx), dtype=out_dtype))
            return memo[key]

        return jax.make_array_from_callback(tuple(shape), sharding, cb)

    def stacked(per_layer):
        """[L, ...] param from a per-layer reader(l, rest_slices)."""

        def fetch(nidx):
            lsl, rest = nidx[0], nidx[1:]
            first = per_layer(lsl.start, rest)
            out = np.empty((lsl.stop - lsl.start,) + first.shape, first.dtype)
            out[0] = first
            for i, l in enumerate(range(lsl.start + 1, lsl.stop), 1):
                out[i] = per_layer(l, rest)
            return out

        return fetch

    # --- per-layer readers (rest slices are in OUR [in, out] layout) -------
    def t_layer(suffix):
        def per_layer(l, rest):
            si, so = rest
            return ckpt.slice(pre.format(l) + suffix, (so, si)).T
        return per_layer

    def d_layer(suffix):
        def per_layer(l, rest):
            return ckpt.slice(pre.format(l) + suffix, rest)
        return per_layer

    # Scales computed while quantizing a weight shard are remembered (they
    # are tiny: one f32 per output channel) so the companion *_scale param —
    # built right after its weight, with matching output ranges by
    # construction of the sharding specs — is served without re-reading and
    # re-reducing the same checkpoint rows. Halves int8 load I/O.
    scale_cache: dict = {}

    def _scale_from(key, wf_rows):
        amax = np.max(np.abs(wf_rows.astype(np.float32)), axis=1)
        scale = np.maximum(amax / 127.0, 1e-8).astype(np.float32)
        scale_cache[key] = scale
        return scale

    def q_w_col(suffix):
        """int8 weight, column-sharded (full in per shard): slice-quantize
        == global quantize."""
        def per_layer(l, rest):
            si, so = rest
            w = ckpt.slice(pre.format(l) + suffix, (so, slice(None))).T
            wq, scale = quantize_tensor(np.ascontiguousarray(w))
            scale_cache[(suffix, l, so.start, so.stop)] = scale
            return wq[si, :]
        return per_layer

    def q_scale_col(suffix):
        def per_layer(l, rest):
            (so,) = rest
            key = (suffix, l, so.start, so.stop)
            if key in scale_cache:
                return scale_cache.pop(key)
            return _scale_from(
                key, ckpt.slice(pre.format(l) + suffix, (so, slice(None))))
        return per_layer

    def q_w_row(suffix):
        """int8 weight, row-sharded (in-split): the scale needs the full
        input dim, so read the full [out, in] rows, then quantize only this
        shard's input columns."""
        def per_layer(l, rest):
            si, so = rest
            raw = ckpt.slice(pre.format(l) + suffix, (so, slice(None)))
            wf = raw.astype(np.float32)
            scale = np.maximum(np.max(np.abs(wf), axis=1) / 127.0, 1e-8)
            scale_cache[(suffix, l, so.start, so.stop)] = scale.astype(
                np.float32)
            wq = np.clip(np.round(wf[:, si] / scale[:, None]), -127, 127)
            return wq.astype(np.int8).T
        return per_layer

    def q_scale_row(suffix):
        def per_layer(l, rest):
            (so,) = rest
            key = (suffix, l, so.start, so.stop)
            if key in scale_cache:
                return scale_cache.pop(key)
            return _scale_from(
                key, ckpt.slice(pre.format(l) + suffix, (so, slice(None))))
        return per_layer

    # --- int4 readers: group-wise scales, nibble-packed input dim ----------
    def _check_group_align(r0: int, r1: int, suffix: str) -> None:
        if r0 % gs or r1 % gs:
            raise ValueError(
                f"int4 row-shard slice [{r0}:{r1}) of {suffix!r} does not "
                f"align with quant_group_size={gs}: group scales could not "
                f"survive the sharding (lower tp or change the group size)")

    def q4_w_col(suffix):
        """int4 packed weight, column-sharded (full input dim per shard):
        slice-quantize == global quantize, as for int8."""
        def per_layer(l, rest):
            si, so = rest           # si over the PACKED input dim
            w = ckpt.slice(pre.format(l) + suffix, (so, slice(None))).T
            wq, scale = quantize_tensor_int4(np.ascontiguousarray(w), gs)
            scale_cache[(suffix, l, so.start, so.stop)] = scale
            return wq[si, :]
        return per_layer

    def q4_scale_col(suffix):
        def per_layer(l, rest):
            sg, so = rest
            key = (suffix, l, so.start, so.stop)
            if key in scale_cache:
                return scale_cache.pop(key)[sg]
            raw = ckpt.slice(pre.format(l) + suffix, (so, slice(None)))
            return int4_group_scale(np.ascontiguousarray(raw.T), gs)[sg]
        return per_layer

    def q4_w_row(suffix):
        """int4 packed weight, row-sharded (input-split): group boundaries
        align with the shard boundary (validated), so this shard's groups
        are computed from its own rows alone — identical to the global
        quantize, and only the shard's byte ranges are read."""
        def per_layer(l, rest):
            si, so = rest           # si over the PACKED input dim; so full
            r0, r1 = si.start * 2, si.stop * 2
            _check_group_align(r0, r1, suffix)
            raw = ckpt.slice(pre.format(l) + suffix, (so, slice(r0, r1)))
            wq, scale = quantize_tensor_int4(
                np.ascontiguousarray(raw.T), gs)
            scale_cache[(suffix, l, r0 // gs, r1 // gs)] = scale
            return wq
        return per_layer

    def q4_scale_row(suffix):
        def per_layer(l, rest):
            sg, so = rest           # sg over the group axis; so full out
            key = (suffix, l, sg.start, sg.stop)
            if key in scale_cache:
                return scale_cache.pop(key)
            r0, r1 = sg.start * gs, sg.stop * gs
            raw = ckpt.slice(pre.format(l) + suffix, (so, slice(r0, r1)))
            return int4_group_scale(np.ascontiguousarray(raw.T), gs)
        return per_layer

    qw_col, qs_col = (q4_w_col, q4_scale_col) if int4 else (q_w_col,
                                                            q_scale_col)
    qw_row, qs_row = (q4_w_row, q4_scale_row) if int4 else (q_w_row,
                                                            q_scale_row)

    def expert(w_name, reader):
        """[L, E, ...] from per-expert tensors; reuses a per-layer reader by
        rewriting the key suffix per expert."""
        def per_layer(l, rest):
            esl, wrest = rest[0], rest[1:]
            parts = []
            for e in range(esl.start, esl.stop):
                r = reader(f"block_sparse_moe.experts.{e}.{w_name}.weight")
                parts.append(r(l, wrest))
            return np.stack(parts)
        return per_layer

    sh_l = shardings["layers"]
    out_layers: Params = {
        "input_norm": make((L, d), sh_l["input_norm"],
                           stacked(d_layer("input_layernorm.weight")), dtype),
        "post_attn_norm": make(
            (L, d), sh_l["post_attn_norm"],
            stacked(d_layer("post_attention_layernorm.weight")), dtype),
    }
    attn = {"wq": ("self_attn.q_proj.weight", (L, d, nh * hd)),
            "wk": ("self_attn.k_proj.weight", (L, d, nkv * hd)),
            "wv": ("self_attn.v_proj.weight", (L, d, nkv * hd))}
    for name, (suffix, shape) in attn.items():
        if quant:
            out_layers[name] = make(packed_shape(shape), sh_l[name],
                                    stacked(qw_col(suffix)), np.int8)
            out_layers[name + "_scale"] = make(
                scale_shape(shape), sh_l[name + "_scale"],
                stacked(qs_col(suffix)), np.float32)
        else:
            out_layers[name] = make(shape, sh_l[name],
                                    stacked(t_layer(suffix)), dtype)
    if quant:
        out_layers["wo"] = make(packed_shape((L, nh * hd, d)), sh_l["wo"],
                                stacked(qw_row("self_attn.o_proj.weight")),
                                np.int8)
        out_layers["wo_scale"] = make(
            scale_shape((L, nh * hd, d)), sh_l["wo_scale"],
            stacked(qs_row("self_attn.o_proj.weight")), np.float32)
    else:
        out_layers["wo"] = make((L, nh * hd, d), sh_l["wo"],
                                stacked(t_layer("self_attn.o_proj.weight")),
                                dtype)
    if cfg.attention_bias:
        for ours, theirs, width in (("bq", "q_proj", nh * hd),
                                    ("bk", "k_proj", nkv * hd),
                                    ("bv", "v_proj", nkv * hd)):
            out_layers[ours] = make(
                (L, width), sh_l[ours],
                stacked(d_layer(f"self_attn.{theirs}.bias")), dtype)
    if cfg.qk_norm:
        for ours, theirs in (("q_norm", "q_norm"), ("k_norm", "k_norm")):
            out_layers[ours] = make(
                (L, hd), sh_l[ours],
                stacked(d_layer(f"self_attn.{theirs}.weight")), dtype)

    if cfg.is_moe:
        out_layers["router"] = make(
            (L, d, E), sh_l["router"],
            stacked(t_layer("block_sparse_moe.gate.weight")), dtype)
        moe = {"w_gate": ("w1", (L, E, d, ff), qw_col, qs_col),
               "w_up": ("w3", (L, E, d, ff), qw_col, qs_col),
               "w_down": ("w2", (L, E, ff, d), qw_row, qs_row)}
        for name, (hf, shape, qw, qs) in moe.items():
            if quant:
                out_layers[name] = make(
                    packed_shape(shape), sh_l[name],
                    stacked(expert(hf, qw)), np.int8)
                out_layers[name + "_scale"] = make(
                    scale_shape(shape), sh_l[name + "_scale"],
                    stacked(expert(hf, qs)), np.float32)
            else:
                out_layers[name] = make(shape, sh_l[name],
                                        stacked(expert(hf, t_layer)), dtype)
    else:
        mlp = {"w_gate": ("mlp.gate_proj.weight", (L, d, ff)),
               "w_up": ("mlp.up_proj.weight", (L, d, ff))}
        for name, (suffix, shape) in mlp.items():
            if quant:
                out_layers[name] = make(packed_shape(shape), sh_l[name],
                                        stacked(qw_col(suffix)), np.int8)
                out_layers[name + "_scale"] = make(
                    scale_shape(shape), sh_l[name + "_scale"],
                    stacked(qs_col(suffix)), np.float32)
            else:
                out_layers[name] = make(shape, sh_l[name],
                                        stacked(t_layer(suffix)), dtype)
        if quant:
            out_layers["w_down"] = make(
                packed_shape((L, ff, d)), sh_l["w_down"],
                stacked(qw_row("mlp.down_proj.weight")), np.int8)
            out_layers["w_down_scale"] = make(
                scale_shape((L, ff, d)), sh_l["w_down_scale"],
                stacked(qs_row("mlp.down_proj.weight")), np.float32)
        else:
            out_layers["w_down"] = make(
                (L, ff, d), sh_l["w_down"],
                stacked(t_layer("mlp.down_proj.weight")), dtype)

    embed_key = "model.embed_tokens.weight"
    out: Params = {
        "embed": make((V, d), shardings["embed"],
                      lambda nidx: ckpt.slice(embed_key, nidx), dtype),
        "final_norm": make((d,), shardings["final_norm"],
                           lambda nidx: ckpt.slice("model.norm.weight", nidx),
                           dtype),
        "layers": out_layers,
    }
    if not cfg.tie_word_embeddings:
        head_key = ("lm_head.weight" if "lm_head.weight" in ckpt
                    else embed_key)   # checkpoint ties silently

        def head_fetch(nidx):
            si, so = nidx
            return ckpt.slice(head_key, (so, si)).T

        if int4:
            # Vocab-sharded head is column-class (full input dim per shard).
            def head_q4(nidx):
                si, so = nidx       # si over the packed input dim (full)
                w = ckpt.slice(head_key, (so, slice(None))).T
                wq, scale = quantize_tensor_int4(np.ascontiguousarray(w), gs)
                scale_cache[(head_key, 0, so.start, so.stop)] = scale
                return wq[si, :]

            def head_scale4(nidx):
                sg, so = nidx
                key = (head_key, 0, so.start, so.stop)
                if key in scale_cache:
                    return scale_cache.pop(key)[sg]
                raw = ckpt.slice(head_key, (so, slice(None)))
                return int4_group_scale(
                    np.ascontiguousarray(raw.T), gs)[sg]

            out["lm_head"] = make(packed_shape((d, V)),
                                  shardings["lm_head"], head_q4, np.int8)
            out["lm_head_scale"] = make(scale_shape((d, V)),
                                        shardings["lm_head_scale"],
                                        head_scale4, np.float32)
        elif quant:
            def head_q(nidx):
                si, so = nidx
                w = ckpt.slice(head_key, (so, slice(None))).T
                wq, scale = quantize_tensor(np.ascontiguousarray(w))
                scale_cache[(head_key, 0, so.start, so.stop)] = scale
                return wq[si, :]

            def head_scale(nidx):
                (so,) = nidx
                key = (head_key, 0, so.start, so.stop)
                if key in scale_cache:
                    return scale_cache.pop(key)
                return _scale_from(key,
                                   ckpt.slice(head_key, (so, slice(None))))

            out["lm_head"] = make((d, V), shardings["lm_head"], head_q,
                                  np.int8)
            out["lm_head_scale"] = make((V,), shardings["lm_head_scale"],
                                        head_scale, np.float32)
        else:
            out["lm_head"] = make((d, V), shardings["lm_head"], head_fetch,
                                  dtype)

    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(out))
    local_bytes = sum(
        sum(s.data.size * s.data.dtype.itemsize for s in x.addressable_shards)
        for x in jax.tree.leaves(out))
    logger.info("loaded %s streamed: %.2f GB global, %.2f GB on this host",
                cfg.name, n_bytes / 1e9, local_bytes / 1e9)
    return out


def load_weights(path: str, cfg: ModelConfig,
                 shardings: Optional[Any] = None,
                 dtype: Optional[jnp.dtype] = None) -> Params:
    """Load a local HF checkpoint into the stacked-layer params pytree of
    models/llama.py. ``shardings`` is an optional matching pytree of
    NamedShardings (parallel.sharding.param_shardings /
    parallel.pp.pp_param_shardings) — with it, the load STREAMS: each
    process reads only its addressable shards' byte ranges from the
    safetensors (see _load_streamed), so per-host RSS is ~shard bytes, not
    model bytes. Without shardings (single device), the full stacked pytree
    is built host-side and uploaded."""
    if cfg.hc_mult > 1 or cfg.q_lora_rank:
        raise ValueError(
            f"{cfg.name}: no loader for a checkpoint with residual streams "
            "or a low-rank query: none could be fetched to read the tensor "
            "names from (they are ASSUMED in "
            "perfbench/configs/xing4.0-29b-a4b-bf16.json); it is served "
            "with random weights")
    ckpt = _Checkpoint(path)
    dtype = dtype or cfg.jnp_dtype
    if cfg.pos_embedding == "learned":
        # OPT-class checkpoints (different HF tensor names, small models):
        # full host load; sharded placement still works via device_put with
        # the matching shardings pytree.
        return _place(_load_opt_host(ckpt, cfg), cfg, dtype, shardings)
    if cfg.state_kind == "kda":
        raise ValueError(
            f"{cfg.name}: no loader for a kimi_linear checkpoint: none could "
            "be fetched to read its tensor names from (they are ASSUMED in "
            "perfbench/configs/kimi-linear-48b-a3b-bf16.json); it is served "
            "with random weights")
    if cfg.is_mla:
        if shardings is not None:
            raise ValueError(f"{cfg.name}: a latent-attention model loads "
                             "onto one device (no sharded placement)")
        return _place(_load_deepseek_host(ckpt, cfg), cfg, dtype, None)
    if cfg.has_state:
        if shardings is not None:
            raise ValueError(f"{cfg.name}: a state model loads onto one "
                             "device (no sharded placement)")
        return _place(_load_granite_hybrid_host(ckpt, cfg), cfg, dtype, None)
    if shardings is not None:
        return _load_streamed(ckpt, cfg, shardings, dtype)
    L = cfg.num_layers

    def stack(keys_fn, transpose=True) -> np.ndarray:
        """Stack per-layer tensors into one [L, ...] array without holding
        more than one extra layer copy."""
        first = ckpt.get_t(keys_fn(0)) if transpose else ckpt.get(keys_fn(0))
        out = np.empty((L,) + first.shape, dtype=first.dtype)
        out[0] = first
        for l in range(1, L):
            out[l] = ckpt.get_t(keys_fn(l)) if transpose else ckpt.get(keys_fn(l))
        return out

    pre = "model.layers.{}."
    layers: Params = {
        "input_norm": stack(lambda l: pre.format(l) + "input_layernorm.weight",
                            transpose=False),
        "post_attn_norm": stack(
            lambda l: pre.format(l) + "post_attention_layernorm.weight",
            transpose=False),
        "wq": stack(lambda l: pre.format(l) + "self_attn.q_proj.weight"),
        "wk": stack(lambda l: pre.format(l) + "self_attn.k_proj.weight"),
        "wv": stack(lambda l: pre.format(l) + "self_attn.v_proj.weight"),
        "wo": stack(lambda l: pre.format(l) + "self_attn.o_proj.weight"),
    }
    if cfg.attention_bias:
        for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"),
                             ("bv", "v_proj")):
            layers[ours] = stack(
                lambda l, t=theirs: pre.format(l) + f"self_attn.{t}.bias",
                transpose=False)
    if cfg.qk_norm:
        layers["q_norm"] = stack(
            lambda l: pre.format(l) + "self_attn.q_norm.weight", transpose=False)
        layers["k_norm"] = stack(
            lambda l: pre.format(l) + "self_attn.k_norm.weight", transpose=False)
    if cfg.is_moe:
        E = cfg.num_experts
        layers["router"] = stack(
            lambda l: pre.format(l) + "block_sparse_moe.gate.weight")

        def stack_experts(w_name: str) -> np.ndarray:
            first = ckpt.get_t(
                pre.format(0) + f"block_sparse_moe.experts.0.{w_name}.weight")
            out = np.empty((L, E) + first.shape, dtype=first.dtype)
            for l in range(L):
                for e in range(E):
                    out[l, e] = ckpt.get_t(
                        pre.format(l)
                        + f"block_sparse_moe.experts.{e}.{w_name}.weight")
            return out

        layers["w_gate"] = stack_experts("w1")
        layers["w_up"] = stack_experts("w3")
        layers["w_down"] = stack_experts("w2")
    else:
        layers["w_gate"] = stack(lambda l: pre.format(l) + "mlp.gate_proj.weight")
        layers["w_up"] = stack(lambda l: pre.format(l) + "mlp.up_proj.weight")
        layers["w_down"] = stack(lambda l: pre.format(l) + "mlp.down_proj.weight")

    params: Params = {
        "embed": ckpt.get("model.embed_tokens.weight"),
        "final_norm": ckpt.get("model.norm.weight"),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in ckpt:
            params["lm_head"] = ckpt.get_t("lm_head.weight")
        else:   # checkpoint ties even though config doesn't say so
            params["lm_head"] = np.ascontiguousarray(params["embed"].T)

    return _place(params, cfg, dtype, None)


def _place(params: Params, cfg: ModelConfig, dtype,
           shardings: Optional[Any]) -> Params:
    """Quantize (host-side, so the device never sees full-precision weights)
    + dtype-convert + upload, optionally into a sharded placement."""
    if cfg.quantization:
        from ..ops.quant import quantize_params
        params = quantize_params(params, cfg.quantization,
                                 cfg.quant_group_size)

    def put(path_, x):
        # Dtype conversion stays HOST-side (numpy + ml_dtypes): handing host
        # arrays to device_put lets a sharded placement upload only each
        # device's shard, instead of committing the full tensor to device 0
        # first and resharding device-to-device.
        name = path_[-1].key if hasattr(path_[-1], "key") else str(path_[-1])
        if (x.dtype == np.int8 or name.endswith("_scale")
                or name == "router_bias"):
            # int8 weights, f32 scales and the f32 choice bias as they are
            return np.ascontiguousarray(x)
        if name in ("A_log", "dt_bias", "D"):
            # a state layer's recurrence vectors stay float32
            return np.ascontiguousarray(np.asarray(x, np.float32))
        return np.ascontiguousarray(np.asarray(x, dtype=dtype))

    params = jax.tree_util.tree_map_with_path(put, params)
    out = (jax.device_put(params, shardings) if shardings is not None
           else jax.tree.map(jax.device_put, params))
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(out))
    logger.info("loaded %s: %.2f GB as %s", cfg.name, n_bytes / 1e9, dtype)
    return out


def _load_deepseek_host(ckpt: _Checkpoint, cfg: ModelConfig) -> Params:
    """deepseek_v3 / kimi_vl HF checkpoint -> the ``dense_layers`` +
    ``layers`` tree of models/llama.py (host numpy). A kimi_vl checkpoint
    keeps the decoder under ``language_model.``; its ``vision_tower.*`` and
    ``multi_modal_projector.*`` tensors are skipped (text only), with one
    log line.

    The rope columns of ``q_proj`` and ``kv_a_proj_with_mqa`` are
    DE-INTERLEAVED here ([0, 2, 4, ..., 1, 3, 5, ...]): the published
    modeling code rotates interleaved pairs (x[2i], x[2i+1]) by doing this
    same permutation on the activations at run time; on the weights it is
    free, and the decoder's half-split RoPE then turns the same pairs.
    ``kv_b_proj`` [nh * (nope + v), r] is split per head into ``w_uk``
    [nh, r, nope] and ``w_uv`` [nh, r, v]."""
    names = list(ckpt._index)
    root = "language_model." if any(
        n.startswith("language_model.") for n in names) else ""
    keep = (root,) if root else ("model.", "lm_head.")
    skipped = [n for n in names if not n.startswith(keep)]
    if skipped:
        logger.info("%s: skipped %d tensors outside the language model "
                    "(vision tower, projector: text only), e.g. %s",
                    cfg.name, len(skipped), skipped[0])
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    deint = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    pre = root + "model.layers.{}."

    def attn(l: int) -> Params:
        p = pre.format(l)
        wq = ckpt.get_t(p + "self_attn.q_proj.weight")         # [d, nh*(n+r)]
        wq = wq.reshape(wq.shape[0], nh, nope + rope)
        wq = np.concatenate([wq[..., :nope], wq[..., nope:][..., deint]], -1)
        w_kva = ckpt.get_t(p + "self_attn.kv_a_proj_with_mqa.weight")
        w_kva = np.concatenate([w_kva[:, :r], w_kva[:, r:][:, deint]], -1)
        kv_b = ckpt.get(p + "self_attn.kv_b_proj.weight").reshape(
            nh, nope + vd, r)
        return {
            "input_norm": ckpt.get(p + "input_layernorm.weight"),
            "post_attn_norm": ckpt.get(p + "post_attention_layernorm.weight"),
            "wq": wq.reshape(wq.shape[0], -1),
            "w_kva": w_kva,
            "kv_norm": ckpt.get(p + "self_attn.kv_a_layernorm.weight"),
            "w_uk": np.ascontiguousarray(kv_b[:, :nope].transpose(0, 2, 1)),
            "w_uv": np.ascontiguousarray(kv_b[:, nope:].transpose(0, 2, 1)),
            "wo": ckpt.get_t(p + "self_attn.o_proj.weight"),
        }

    def swiglu(prefix: str, names=("w_gate", "w_up", "w_down")) -> Params:
        return {ours: ckpt.get_t(f"{prefix}{theirs}.weight")
                for ours, theirs in zip(names, ("gate_proj", "up_proj",
                                                "down_proj"))}

    def layer(l: int) -> Params:
        p = pre.format(l) + "mlp."
        out = attn(l)
        if l < cfg.num_dense_layers:
            return {**out, **swiglu(p)}
        out["router"] = ckpt.get_t(p + "gate.weight").astype(np.float32)
        out["router_bias"] = np.asarray(
            ckpt.get(p + "gate.e_score_correction_bias"), np.float32)
        experts = [swiglu(f"{p}experts.{e}.") for e in range(cfg.num_experts)]
        for k in ("w_gate", "w_up", "w_down"):
            out[k] = np.stack([e[k] for e in experts])
        if cfg.num_shared_experts:
            out.update(swiglu(p + "shared_experts.",
                              ("ws_gate", "ws_up", "ws_down")))
        return out

    def stacked(ls) -> Params:
        per = [layer(l) for l in ls]
        return {k: np.stack([p[k] for p in per]) for k in per[0]}

    Ld = cfg.num_dense_layers
    params: Params = {
        "embed": ckpt.get(root + "model.embed_tokens.weight"),
        "final_norm": ckpt.get(root + "model.norm.weight"),
        "layers": stacked(range(Ld, cfg.num_layers)),
    }
    if Ld:
        params["dense_layers"] = stacked(range(Ld))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = ckpt.get_t(root + "lm_head.weight")
    return params


def _load_granite_hybrid_host(ckpt: _Checkpoint, cfg: ModelConfig) -> Params:
    """granitemoehybrid HF checkpoint -> the ``layers`` (attention) +
    ``ssm_layers`` (state) tree of models/llama.py (host numpy), each stack
    in the order its kind appears in ``cfg.layer_types``. Every layer's
    ``shared_mlp.input_linear`` [2 ff, d] is split into ``w_gate`` | ``w_up``
    (the published code chunks it in that order); the conv's weight
    [channels, 1, K] is stored [K, channels]; ``mamba.in_proj``'s columns [z
    | x B C | dt], in the published order, are split into ``w_z``, ``w_xbc``
    and ``w_dt`` (models.llama._init_state_layers says why)."""
    ff = cfg.intermediate_size
    pre = "model.layers.{}."

    def mlp(p: str) -> Params:
        w_in = ckpt.get_t(p + "shared_mlp.input_linear.weight")  # [d, 2 ff]
        return {
            "input_norm": ckpt.get(p + "input_layernorm.weight"),
            "post_attn_norm": ckpt.get(p + "post_attention_layernorm.weight"),
            "w_gate": w_in[:, :ff], "w_up": w_in[:, ff:],
            "w_down": ckpt.get_t(p + "shared_mlp.output_linear.weight"),
        }

    def layer(l: int) -> Params:
        p = pre.format(l)
        if cfg.layer_types[l] == "attention":
            return {**mlp(p), **{
                ours: ckpt.get_t(f"{p}self_attn.{theirs}_proj.weight")
                for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                                     ("wo", "o"))}}
        m = p + "mamba."
        in_proj = ckpt.get_t(m + "in_proj.weight")       # [d, di + C + H]
        return {
            **mlp(p),
            "w_z": in_proj[:, :cfg.mamba_d_inner],
            "w_xbc": in_proj[:, cfg.mamba_d_inner:-cfg.mamba_n_heads],
            "w_dt": in_proj[:, -cfg.mamba_n_heads:],
            "conv_w": np.ascontiguousarray(
                ckpt.get(m + "conv1d.weight")[:, 0, :].T),
            "conv_b": ckpt.get(m + "conv1d.bias"),
            "dt_bias": ckpt.get(m + "dt_bias"),
            "A_log": ckpt.get(m + "A_log"),
            "D": ckpt.get(m + "D"),
            "ssm_norm": ckpt.get(m + "norm.weight"),
            "w_out": ckpt.get_t(m + "out_proj.weight"),
        }

    def stacked(kind: str) -> Params:
        per = [layer(l) for l, t in enumerate(cfg.layer_types) if t == kind]
        return {k: np.stack([p[k] for p in per]) for k in per[0]}

    params: Params = {
        "embed": ckpt.get("model.embed_tokens.weight"),
        "final_norm": ckpt.get("model.norm.weight"),
        "layers": stacked("attention"),
        "ssm_layers": stacked("mamba"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = ckpt.get_t("lm_head.weight")
    return params


def _load_opt_host(ckpt: _Checkpoint, cfg: ModelConfig) -> Params:
    """OPT HF checkpoint -> shared-decoder pytree (host numpy). Tensor names
    per HF OPTForCausalLM: note the per-layer PRE-MLP norm is called
    ``final_layer_norm`` inside each layer, distinct from the decoder-level
    ``model.decoder.final_layer_norm``."""
    L = cfg.num_layers
    pre = "model.decoder.layers.{}."

    def stack(suffix, transpose=True):
        first = (ckpt.get_t if transpose else ckpt.get)(pre.format(0) + suffix)
        out = np.empty((L,) + first.shape, first.dtype)
        out[0] = first
        for l in range(1, L):
            out[l] = (ckpt.get_t if transpose
                      else ckpt.get)(pre.format(l) + suffix)
        return out

    layers: Params = {
        "input_norm": stack("self_attn_layer_norm.weight", transpose=False),
        "input_norm_b": stack("self_attn_layer_norm.bias", transpose=False),
        "post_attn_norm": stack("final_layer_norm.weight", transpose=False),
        "post_attn_norm_b": stack("final_layer_norm.bias", transpose=False),
        "wq": stack("self_attn.q_proj.weight"),
        "wk": stack("self_attn.k_proj.weight"),
        "wv": stack("self_attn.v_proj.weight"),
        "wo": stack("self_attn.out_proj.weight"),
        "w_up": stack("fc1.weight"),
        "w_down": stack("fc2.weight"),
    }
    if cfg.attention_bias:
        layers["bq"] = stack("self_attn.q_proj.bias", transpose=False)
        layers["bk"] = stack("self_attn.k_proj.bias", transpose=False)
        layers["bv"] = stack("self_attn.v_proj.bias", transpose=False)
    if cfg.linear_bias:
        layers["bo"] = stack("self_attn.out_proj.bias", transpose=False)
        layers["b_up"] = stack("fc1.bias", transpose=False)
        layers["b_down"] = stack("fc2.bias", transpose=False)

    params: Params = {
        "embed": ckpt.get("model.decoder.embed_tokens.weight"),
        "pos_embed": ckpt.get("model.decoder.embed_positions.weight"),
        "final_norm": ckpt.get("model.decoder.final_layer_norm.weight"),
        "final_norm_b": ckpt.get("model.decoder.final_layer_norm.bias"),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in ckpt:
            params["lm_head"] = ckpt.get_t("lm_head.weight")
        else:
            params["lm_head"] = np.ascontiguousarray(params["embed"].T)
    return params


def resolve_model(model_url: str, name: Optional[str] = None):
    """The reference's ``modelURL`` semantics (HF id OR local path,
    ``values-01-minimal-example3.yaml:8,22-30``): a local directory with
    config.json -> (config_from_hf, weights+tokenizer from it); otherwise a
    preset name -> (preset config, random init, byte tokenizer)."""
    if os.path.isdir(model_url) and os.path.exists(
            os.path.join(model_url, "config.json")):
        cfg = config_from_hf(model_url, name)
        return cfg, model_url, model_url
    from ..config import get_model_config
    return get_model_config(model_url), None, None
