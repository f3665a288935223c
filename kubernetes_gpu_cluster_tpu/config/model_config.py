"""Model architecture configs for the decoder-only families the framework serves.

The reference served OPT-125M, Qwen-7B, Qwen2.5-7B, Qwen3-4B and Qwen3-14B via
vLLM images (reference ``values-01-minimal-example*.yaml``: modelURL fields), and
the north-star configs add TinyLlama-1.1B, Llama-3-8B/70B and Mixtral-8x7B
(BASELINE.json). One config dataclass covers all of these families:

- llama-class dense (Llama 1/2/3, TinyLlama, Qwen2/2.5 via ``attention_bias``,
  Qwen3 via ``qk_norm``, OPT-like models are served through the llama graph
  with learned-rope disabled — see models/registry.py)
- mixtral-class sparse MoE via ``num_experts``/``num_experts_per_tok``
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


STATE_KINDS = ("mamba", "kda")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    # HF ``rope_scaling`` (llama3 / linear / yarn), stored as a sorted (key,
    # value) tuple so the frozen config stays hashable; see
    # ops/rope.scaled_inv_freq.
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # Qwen2/2.5 use bias on q/k/v projections (not o).
    attention_bias: bool = False
    # Qwen3 applies RMSNorm to q and k per-head before RoPE.
    qk_norm: bool = False
    # MoE. num_experts == 0 means dense MLP. Mixtral class: softmax over the
    # top-k logits, every layer an expert layer of width intermediate_size.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # DeepSeek-V3 class (kimi_vl's language model): routed experts of width
    # ``moe_intermediate_size`` beside ``num_shared_experts`` always-on ones
    # (one SwiGLU of their summed width), after ``first_k_dense_replace``
    # leading dense layers of width intermediate_size. The router scores by
    # ``scoring_func`` over ALL experts, chooses by score + a per-expert
    # correction bias, weighs by the raw score, normalises
    # (``norm_topk_prob``) and scales (``routed_scaling_factor``).
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    first_k_dense_replace: int = 0
    scoring_func: str = "softmax"     # | "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Latent attention (MLA), on when kv_lora_rank > 0: the cache holds one
    # row [c (kv_lora_rank) | k_pe (qk_rope_head_dim)] a token a layer and no
    # V; q/k heads are qk_nope_head_dim + qk_rope_head_dim wide, v heads
    # v_head_dim. ``head_dim`` is then the q/k width (softmax scale).
    kv_lora_rank: int = 0
    # deepseek_v3's low-rank query: q = RMSNorm_q(x W_qa) W_qb through a
    # latent of this width (0: one full-rank ``wq``).
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # OPT-class decoder knobs (reference values-01-minimal-example.yaml:4-8
    # serves facebook/opt-125m). Defaults describe the llama class.
    norm_type: str = "rmsnorm"        # "rmsnorm" | "layernorm" (w/ bias)
    # "rope" | "learned" (+2 OPT offset) | "none" (granitemoehybrid's
    # "nope": the state layers carry the order)
    pos_embedding: str = "rope"
    mlp_type: str = "swiglu"          # "swiglu" | "mlp" (fc1/act/fc2, biased)
    mlp_act: str = "silu"             # "mlp" type only: "relu" | "gelu"
    # OPT puts biases on the attention out-projection and the MLP.
    linear_bias: bool = False
    # Serving dtype for weights/activations; fp32 accumulation on the MXU.
    dtype: str = "bfloat16"
    # Weight-only quantization of the big matmuls ("int8", "int4" or None):
    # shrinks the HBM weight-streaming bytes that bound decode to 1/2 and
    # ~1/4 of bf16 respectively (ops/quant.py). int4 packs two nibbles per
    # byte with group-wise scales; int8 is per-output-channel.
    quantization: Optional[str] = None
    # int4 only: input-dim rows per scale group (per-output-channel alone is
    # too coarse at 4 bits). Must divide every matmul input dim
    # (hidden/ff/nh*hd) and align with row-shard boundaries under tp.
    quant_group_size: int = 128
    max_model_len: int = 4096
    # Typed layers: one entry a layer, the layer's MIXER: "attention",
    # "mamba" (a Mamba-2 state mixer: granitemoehybrid) or "kda" (a gated
    # delta-rule state mixer: kimi_linear); None: every layer is attention.
    # The layer's MLP is independent of it: dense in the leading
    # ``num_dense_layers`` and in a model without experts, else experts.
    # The pattern is the leading dense layers, whole repetitions of
    # ``layer_period`` (what the layer scan scans) and a tail shorter than
    # a period (``layer_sections``). An attention layer holds pages (K|V,
    # or latent rows), a state layer one fixed slot of recurrent state a
    # sequence (engine/kv_cache.py).
    layer_types: Optional[tuple] = None
    # The state mixer's sizes (``mamba_*`` of the HF config): heads x head
    # width = d_inner; one B and one C of ``mamba_d_state`` a group; a
    # causal depthwise conv of ``mamba_d_conv`` taps over [x | B | C]; the
    # segment scan's chunk.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # The delta-rule mixer's sizes (``linear_attn_config`` of kimi_linear):
    # heads x head width for q, k and v alike; the two low-rank pairs (the
    # decay gate's and the output gate's) pass through ``kda_head_dim``; a
    # causal depthwise conv of ``kda_d_conv`` taps over each of q, k, v;
    # the segment form's chunk.
    kda_n_heads: int = 0
    kda_head_dim: int = 0
    kda_d_conv: int = 4
    kda_chunk_size: int = 64
    # The share of the routed experts this process holds: ``experts_held``
    # experts from id ``experts_first`` on (0 held: every one). The router
    # scores all ``num_experts``; the routed sum runs over the chosen
    # experts that are held, the others' weights are never allocated and
    # nothing stands in for what they would add (models.llama._moe_mlp).
    experts_first: int = 0
    experts_held: int = 0
    # Granite's four scalars: h0 = embed * embedding_multiplier; every
    # residual add takes residual_multiplier * branch; the softmax scale is
    # attention_multiplier (None: head_dim ** -0.5); logits / logits_scaling.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # Manifold-constrained hyper-connections (arXiv:2512.24880; xing4_0's
    # ``hc_*`` / ``mhc_*`` keys): the residual is ``hc_mult`` parallel
    # streams; every sublayer reads a learned mix of them and writes back
    # through a learned map that ``hc_sinkhorn_iters`` rounds of column and
    # row normalisation (``hc_eps`` in the denominators) make doubly
    # stochastic, its logits clamped to ``hc_res_clamp`` before the exp
    # (ops/hyper_conn.py). 1: the plain residual.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # DeepSeek Sparse Attention (glm_moe_dsa's ``index_*`` keys), on when
    # ``index_topk`` > 0: a lightning indexer (``index_n_heads`` heads of
    # ``index_head_dim``, ONE key a token) scores every earlier token, and
    # attention runs over the ``index_topk`` best of them only
    # (ops/dsa.py). ``indexer_types`` says, a layer, who chooses: "full"
    # layers hold an indexer, an index-key pool layer beside their latent
    # pages, and choose; "shared" layers attend over the choice of the
    # nearest "full" layer before them.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Optional[tuple] = None
    # Generation by diffusion over blocks (sdar_moe), on when
    # ``block_length`` > 1: attention is block-causal (key j visible to
    # query i iff j // B <= i // B), a sequence holds an OPEN BLOCK of B
    # positions beyond its committed length, each an id or masked (the
    # input embedding of a masked position is ``mask_token_id``'s); a
    # denoising pass runs the block over the pages, writes nothing, and
    # transfers every masked position whose confidence exceeds
    # ``confidence_threshold`` if there are at least
    # ``block_length / denoising_steps`` of them, else that many of the
    # most confident (``remasking`` "low_confidence_dynamic", the one
    # rule served); a whole block's K/V reach the pages in its commit,
    # which rides the next block's first denoising pass (``row_width``).
    # 1: an autoregressive model, whose open block is its next position.
    block_length: int = 1
    denoising_steps: int = 1
    confidence_threshold: float = 0.9
    remasking: str = "low_confidence_dynamic"
    mask_token_id: int = 0

    @property
    def row_width(self) -> int:
        """Positions a running sequence takes of a step program's pass: one
        token, or a block model's two blocks, [the block awaiting its
        commit | the open block] (engine/block.py)."""
        return 2 * self.block_length if self.block_length > 1 else 1

    def __post_init__(self):
        if self.block_length > 1:
            B, n = self.block_length, self.denoising_steps
            if B & (B - 1) or n < 1 or B % n:
                raise ValueError(
                    f"{self.name}: block_length {B} must be a power of two "
                    f"and a multiple of denoising_steps {n}")
            if self.remasking != "low_confidence_dynamic":
                raise ValueError(
                    f"{self.name}: remasking {self.remasking!r}: only "
                    "'low_confidence_dynamic' is served")
            if not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(
                    f"{self.name}: mask_token_id {self.mask_token_id} is "
                    f"outside the vocabulary of {self.vocab_size}")
            if (self.is_mla or self.layer_types is not None
                    or self.max_model_len % B):
                raise ValueError(
                    f"{self.name}: block_length {B} is served over K and V "
                    "pages of attention layers alone, and max_model_len "
                    "must be a multiple of it")
        if self.index_topk:
            types = self.indexer_types or ()
            if (len(types) != self.num_layers or types[0] != "full"
                    or set(types) - {"full", "shared"}):
                raise ValueError(
                    f"{self.name}: index_topk {self.index_topk} needs "
                    f"indexer_types, 'full' or 'shared' for each of the "
                    f"{self.num_layers} layers, the first of them 'full' "
                    f"(a 'shared' layer attends over the choice of the "
                    f"'full' layer before it); got {types!r}")
            if not (self.is_mla and self.q_lora_rank
                    and self.layer_types is None):
                raise ValueError(
                    f"{self.name}: the indexer reads the query latent of "
                    "latent attention (q_lora_rank) and chooses among "
                    "latent pages; every layer an attention layer")
        if self.hc_mult > 1 and self.residual_multiplier != 1.0:
            raise ValueError(
                f"{self.name}: residual_multiplier "
                f"{self.residual_multiplier} with hc_mult {self.hc_mult}: "
                "the stream maps carry the branch's weight; a scalar beside "
                "them is not implemented")
        if not 1 <= self.hc_mult <= 8:
            raise ValueError(
                f"{self.name}: hc_mult {self.hc_mult}: the stream mixers "
                "hold 1 to 8 residual streams")
        if not 0 <= self.experts_first <= (
                self.num_experts - self.num_local_experts):
            raise ValueError(
                f"{self.name}: experts {self.experts_first} to "
                f"{self.experts_first + self.num_local_experts - 1} held, "
                f"of {self.num_experts}")
        if self.layer_types is None:
            return
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"{self.name}: layer_types names {len(self.layer_types)} "
                f"layers, num_layers is {self.num_layers}")
        bad = set(self.layer_types) - {"attention", *STATE_KINDS}
        if bad:
            raise ValueError(f"{self.name}: layer_types {sorted(bad)} are "
                             "none of 'attention', 'mamba', 'kda'")
        if len(set(self.layer_types) & set(STATE_KINDS)) > 1:
            raise ValueError(
                f"{self.name}: state layers of two kinds in one model: the "
                "slot pool has one layout")
        if self.state_kind == "mamba" and self.mamba_n_groups != 1:
            raise ValueError(
                f"{self.name}: mamba_n_groups {self.mamba_n_groups}: the "
                "state mixer shares one B and one C among all heads")

    @property
    def state_kind(self) -> Optional[str]:
        """The kind of the model's state layers ("mamba", "kda"), or None."""
        for kind in self.layer_types or ():
            if kind in STATE_KINDS:
                return kind
        return None

    @property
    def has_state(self) -> bool:
        """Whether some layer is a state layer (recurrent state slots beside
        the pages)."""
        return self.state_kind is not None

    @property
    def layer_sections(self) -> tuple:
        """The stack as the layer scan runs it: ``(types, repeats, dense)``
        a section, in order. The leading dense layers first (their own
        shortest period), then the shortest run of layer types whose whole
        repetitions cover the layers that follow, then what is left, shorter
        than that period, once. A homogeneous model: one section of
        ("attention",); granite-4.0-h-micro: 4 x [5 state, 1 attention, 4
        state]; kimi-linear: the dense KDA layer, 6 x [KDA, KDA, MLA, KDA],
        [KDA, MLA]."""
        types = tuple(self.layer_types
                      or ("attention",) * max(self.num_layers, 1))

        def period(run):
            for n in range(1, len(run) + 1):
                k = len(run) // n
                if run[:n] * k == run[:n * k]:
                    return n, k
            return len(run), 1

        nd = self.num_dense_layers
        sections = []
        if nd:
            n, k = period(types[:nd])
            sections.append((types[:n], k, True))
            if types[n * k:nd]:
                sections.append((types[n * k:nd], 1, True))
        body = types[nd:]
        n, k = period(body)
        dense = not self.is_moe
        sections.append((body[:n], k, dense))
        if body[n * k:]:
            sections.append((body[n * k:], 1, dense))
        return tuple(sections)

    @property
    def layer_period(self) -> tuple:
        """The period of the layers behind the leading dense ones."""
        at = 0
        for types, repeats, _ in self.layer_sections:
            if at >= self.num_dense_layers:
                return types
            at += len(types) * repeats
        return ()

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages: the depth of the page pools."""
        if self.layer_types is None:
            return self.num_layers
        return self.layer_types.count("attention")

    @property
    def num_state_layers(self) -> int:
        return self.num_layers - self.num_kv_layers

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the depthwise conv: [x | B | C]."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_shape(self) -> tuple:
        """What a slot holds of the recurrent state in one state layer,
        float32, lane-dense: Mamba-2's [N, d_inner] (``ops/ssm.py``), the
        delta rule's [heads * d_k, d_v] (``ops/kda.py``)."""
        if self.state_kind == "kda":
            return (self.kda_n_heads * self.kda_head_dim, self.kda_head_dim)
        return (self.mamba_d_state, self.mamba_d_inner)

    @property
    def state_conv_shape(self) -> tuple:
        """... and of the conv's last inputs, in the model's dtype:
        [taps - 1, channels] ([x | B | C]; [q | k | v])."""
        if self.state_kind == "kda":
            return (self.kda_d_conv - 1,
                    3 * self.kda_n_heads * self.kda_head_dim)
        return (self.mamba_d_conv - 1, self.mamba_conv_dim)

    @property
    def num_local_experts(self) -> int:
        """Routed experts whose weights this process holds."""
        return self.experts_held or self.num_experts

    @property
    def attn_scale(self) -> float:
        """The softmax scale: granite's multiplier, else head_dim^-1/2, times
        YaRN's m^2 where the rotation is YaRN-scaled (deepseek_v3: m from
        ``mscale_all_dim``)."""
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        from ..ops.rope import yarn_attn_factor
        return self.head_dim ** -0.5 * yarn_attn_factor(self.rope_scaling_dict)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_per_kv(self) -> int:
        assert self.num_heads % self.num_kv_heads == 0
        return self.num_heads // self.num_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def num_dense_layers(self) -> int:
        """Leading dense layers, run before the scan over the expert layers."""
        return self.first_k_dense_replace if self.is_moe else 0

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def kv_row_dim(self) -> int:
        """Elements one token holds in one layer of ONE pool: the latent row
        [c | k_pe], or the flattened K (= V) heads."""
        if self.is_mla:
            return self.kv_lora_rank + self.qk_rope_head_dim
        return self.num_kv_heads * self.head_dim

    @property
    def kv_row_padded(self) -> int:
        """The row as the pool stores it. A latent row is padded to whole
        128-lane tiles (a DMA slice must be whole HBM tiles: PERF.md, PR 25);
        K|V rows are stored as they are."""
        return -(-self.kv_row_dim // 128) * 128 if self.is_mla else self.kv_row_dim

    @property
    def index_layers(self) -> tuple:
        """The layers that hold an indexer and index keys, in order: the
        depth of the index-key pool and of the ``indexer`` weight stack."""
        return tuple(i for i, t in enumerate(self.indexer_types or ())
                     if t == "full") if self.index_topk else ()

    @property
    def kv_pools(self) -> int:
        """Pools of rows the cache keeps: K and V, or the one latent pool."""
        return 1 if self.is_mla else 2

    @property
    def rope_scaling_dict(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def _p(name, **kw) -> ModelConfig:
    return ModelConfig(name=name, **kw)


def _yarn(factor: float, original: int, **kw) -> tuple:
    """deepseek_v3's YaRN block as ``ModelConfig.rope_scaling`` holds it."""
    return tuple(sorted({
        "type": "yarn", "factor": factor,
        "original_max_position_embeddings": original, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1, **kw}.items()))


# YaRN where 16 rope dims and 512 positions show it: the ramp runs over
# pairs 0 to 3 of 8 and m^2 is 1.296.
_YARN_DEBUG = _yarn(factor=4.0, original=64)


MODEL_PRESETS: dict[str, ModelConfig] = {
    # Tiny configs for tests / CI (CPU mesh) — the fake-backend analogue of the
    # reference's opt-125m smoke model (values-01-minimal-example.yaml:7-8).
    "debug-tiny": _p(
        "debug-tiny", vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32, max_model_len=512,
        dtype="float32",
    ),
    "debug-moe": _p(
        "debug-moe", vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32, max_model_len=512,
        num_experts=4, num_experts_per_tok=2, dtype="float32",
    ),
    # kimi-vl-a3b's block at a size the CPU tests can afford: latent
    # attention, 1 dense + 3 expert layers, sigmoid router with a choice
    # bias, 2 shared experts.
    # sdar_moe's block at a size the CPU tests can afford: qk-norm GQA, a
    # softmax router over 8 experts top-2 with no shared expert and no
    # dense layer, generation by diffusion over blocks of 4.
    "debug-block-moe": _p(
        "debug-block-moe", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=32, qk_norm=True, rope_theta=1000000.0, rms_norm_eps=1e-6,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=64,
        block_length=4, denoising_steps=4, mask_token_id=511,
        max_model_len=512, dtype="float32",
    ),
    "debug-mla-moe": _p(
        "debug-mla-moe", vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=4, num_heads=4, num_kv_heads=4, head_dim=48,
        kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=64, num_shared_experts=2,
        first_k_dense_replace=1, scoring_func="sigmoid",
        routed_scaling_factor=2.446, rope_theta=800000.0,
        max_model_len=512, dtype="float32",
    ),
    # xing4.0's block at a size the CPU tests can afford: debug-mla-moe's
    # widths with four residual streams mixed around every sublayer, the
    # query through a latent of 48, YaRN on the 16 rope dims, 2 dense + 2
    # expert layers.
    "debug-hc-mla-moe": _p(
        "debug-hc-mla-moe", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=4, num_heads=4, num_kv_heads=4,
        head_dim=48, kv_lora_rank=64, q_lora_rank=48, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=64,
        num_shared_experts=1, first_k_dense_replace=2,
        scoring_func="sigmoid", routed_scaling_factor=2.0,
        rope_theta=10000.0, rope_scaling=_YARN_DEBUG, rms_norm_eps=1e-6,
        hc_mult=4, max_model_len=512, dtype="float32",
    ),
    # glm-5.2's block at a size the CPU tests can afford: debug-hc-mla-moe's
    # latent attention (one residual stream, plain rope) behind a learned
    # top-16 choice: 2 index heads of 32 (16 of them rotated), indexers in
    # layers 0 and 3, 1 dense + 4 expert layers. 16 is small enough that
    # every form chooses, a fresh chunk included. (A scaling factor of 3:
    # with glm's 2.5 a greedy stream of chip_smoke's rehearsal meets the
    # end-of-sequence id, one of only 512.)
    "debug-dsa-mla-moe": _p(
        "debug-dsa-mla-moe", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=5, num_heads=4, num_kv_heads=4,
        head_dim=48, kv_lora_rank=64, q_lora_rank=48, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=64,
        num_shared_experts=1, first_k_dense_replace=1,
        scoring_func="sigmoid", routed_scaling_factor=3.0,
        rope_theta=8000000.0, rms_norm_eps=1e-6, index_topk=16,
        index_n_heads=2, index_head_dim=32,
        indexer_types=("full", "shared", "shared", "full", "shared"),
        max_model_len=512, dtype="float32",
    ),
    # granite-4.0-h-micro's block at a size the CPU tests can afford: 2
    # periods of [2 state, 1 attention, 1 state] (the attention layer INSIDE
    # the period), a scan chunk of 8, no positional encoding, the four
    # multipliers all away from 1.
    "debug-ssm-hybrid": _p(
        "debug-ssm-hybrid", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=8, num_heads=4, num_kv_heads=2,
        head_dim=32, pos_embedding="none", tie_word_embeddings=True,
        layer_types=("mamba", "mamba", "attention", "mamba") * 2,
        mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
        mamba_chunk_size=8, embedding_multiplier=3.0,
        residual_multiplier=0.5, attention_multiplier=0.25,
        logits_scaling=2.0, max_model_len=512, dtype="float32",
    ),
    # kimi-linear's block at a size the CPU tests can afford, in the shape
    # of its pattern: one leading dense KDA layer, two periods of [KDA, KDA,
    # MLA, KDA], a ragged [KDA, MLA] tail; NoPE latent attention; 16 experts
    # top-4 + 1 shared; a chunk of two sub-chunks.
    "debug-kda-hybrid": _p(
        "debug-kda-hybrid", vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=11, num_heads=4, num_kv_heads=4,
        head_dim=48, kv_lora_rank=64, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, pos_embedding="none",
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=64,
        num_shared_experts=1, first_k_dense_replace=1,
        scoring_func="sigmoid", routed_scaling_factor=2.446,
        layer_types=(("kda",) + ("kda", "kda", "attention", "kda") * 2
                     + ("kda", "attention")),
        kda_n_heads=4, kda_head_dim=32, kda_chunk_size=32,
        max_model_len=512, dtype="float32",
    ),
    # The reference's minimal-example model (values-01-minimal-example.yaml:8).
    "opt-125m": _p(
        "opt-125m", vocab_size=50272, hidden_size=768, intermediate_size=3072,
        num_layers=12, num_heads=12, num_kv_heads=12, head_dim=64,
        max_model_len=2048, tie_word_embeddings=True, attention_bias=True,
        norm_type="layernorm", pos_embedding="learned", mlp_type="mlp",
        mlp_act="relu", linear_bias=True,
    ),
    # BASELINE.json config 1.
    "tinyllama-1.1b": _p(
        "tinyllama-1.1b", vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=32, num_kv_heads=4, head_dim=64,
        rope_theta=10000.0, max_model_len=2048,
    ),
    # BASELINE.json configs 2/3.
    "llama-3-8b": _p(
        "llama-3-8b", vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_model_len=8192,
    ),
    # BASELINE.json config 5.
    "llama-3-70b": _p(
        "llama-3-70b", vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_model_len=8192,
    ),
    # Reference models (values-01-minimal-example4/5/7/8/9.yaml).
    "qwen2.5-7b": _p(
        "qwen2.5-7b", vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, attention_bias=True,
        max_model_len=4096,
    ),
    "qwen3-4b": _p(
        "qwen3-4b", vocab_size=151936, hidden_size=2560, intermediate_size=9728,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, qk_norm=True,
        tie_word_embeddings=True, max_model_len=4096,
    ),
    "qwen3-14b": _p(
        "qwen3-14b", vocab_size=151936, hidden_size=5120, intermediate_size=17408,
        num_layers=40, num_heads=40, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, rms_norm_eps=1e-6, qk_norm=True,
        max_model_len=4096,
    ),
    # BASELINE.json config 4.
    "mixtral-8x7b": _p(
        "mixtral-8x7b", vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, max_model_len=8192,
        num_experts=8, num_experts_per_tok=2,
    ),
    # JetLM/SDAR-30B-A3B-Chat (``sdar_moe``): qwen3_moe's decoder (qk-norm
    # GQA, 128 routed experts of width 768 top-8 by softmax with
    # norm_topk_prob, no shared expert, no dense layer) that generates by
    # diffusion over blocks of 4 (``block_diffusion_generate``). The block
    # length, the steps, the rule and the threshold are the family's
    # convention, not config.json's (perfbench/configs/
    # sdar-30b-a3b-chat-bf16.json: ``assumed``).
    "sdar-30b-a3b-chat": _p(
        "sdar-30b-a3b-chat", vocab_size=151936, hidden_size=2048,
        intermediate_size=6144, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, qk_norm=True, rope_theta=1000000.0, rms_norm_eps=1e-6,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
        norm_topk_prob=True, block_length=4, denoising_steps=4,
        confidence_threshold=0.9, mask_token_id=151669, max_model_len=4096,
    ),
    # The language model of moonshotai/Kimi-VL-A3B-Instruct (config.json's
    # text_config, a deepseek_v3 decoder): MLA, 64 routed experts top-6 with
    # sigmoid scores and a noaux_tc choice bias, 2 shared experts, one
    # leading dense layer. Text only: the vision tower is not served.
    "kimi-vl-a3b": _p(
        "kimi-vl-a3b", vocab_size=163840, hidden_size=2048,
        intermediate_size=11264, num_layers=27, num_heads=16,
        num_kv_heads=16, head_dim=192, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=64, num_experts_per_tok=6, moe_intermediate_size=1408,
        num_shared_experts=2, first_k_dense_replace=1,
        scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.446, rope_theta=800000.0,
        rms_norm_eps=1e-5, max_model_len=4096,
    ),
    # ibm-granite/granite-4.0-h-micro (granitemoehybrid): 4 periods of
    # [5 state, 1 attention, 4 state]; Mamba-2 mixers of 64 heads x 64 with
    # state 128; GQA attention with no positional encoding and a softmax
    # scale of 1/64; one SwiGLU of 8192 after every mixer (num_local_experts
    # 0: the "shared" MLP is the only one); tied head.
    "granite-4.0-h-micro": _p(
        "granite-4.0-h-micro", vocab_size=100352, hidden_size=2048,
        intermediate_size=8192, num_layers=40, num_heads=32, num_kv_heads=8,
        head_dim=64, pos_embedding="none", tie_word_embeddings=True,
        rms_norm_eps=1e-5,
        layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=256,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0,
        max_model_len=4096,
    ),
    # moonshotai/Kimi-Linear-48B-A3B-Instruct (kimi_linear, arXiv:2510.26692):
    # 20 KDA (gated delta-rule) layers of 32 heads x 128 and 7 latent
    # attention layers (kimi-vl's MLA widths over 32 heads) with NO
    # positional encoding, 1-based layers 4, 8, ..., 24 and 27; layer 1 has a
    # dense SwiGLU of 9216, layers 2-27 256 routed experts of 1024 top-8
    # (sigmoid, renormalised, x 2.446) and 1 shared; untied head. Its 98 GB
    # of bf16 weights are served as a share: ``--hf-overrides`` names the
    # depth, the experts held and the vocabulary slice.
    "kimi-linear-48b-a3b": _p(
        "kimi-linear-48b-a3b", vocab_size=163840, hidden_size=2304,
        intermediate_size=9216, num_layers=27, num_heads=32,
        num_kv_heads=32, head_dim=192, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        pos_embedding="none", num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=1024, num_shared_experts=1,
        first_k_dense_replace=1, scoring_func="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.446,
        rms_norm_eps=1e-5,
        layer_types=(("kda",) + ("kda", "kda", "attention", "kda") * 6
                     + ("kda", "attention")),
        kda_n_heads=32, kda_head_dim=128, kda_d_conv=4, kda_chunk_size=64,
        max_model_len=4096,
    ),
    # XingChen-AGI/Xing4.0-29B-A4B (xing4_0): a deepseek_v3 decoder (latent
    # attention with a low-rank query, 64 sigmoid-routed experts of 1024
    # top-4 x 2 + 1 shared, two leading dense layers of 9216, YaRN x 64 over
    # 4096 on the 64 rope dims) whose residual is FOUR streams mixed by
    # manifold-constrained hyper-connections around every attention and every
    # MLP. 59 GB of bf16 weights: ``--hf-overrides`` names the depth one
    # chip holds. Its multi-token-prediction module is not served.
    "xing4.0-29b-a4b": _p(
        "xing4.0-29b-a4b", vocab_size=131072, hidden_size=3584,
        intermediate_size=9216, num_layers=40, num_heads=32,
        num_kv_heads=32, head_dim=192, kv_lora_rank=512, q_lora_rank=768,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=64, num_experts_per_tok=4, moe_intermediate_size=1024,
        num_shared_experts=1, first_k_dense_replace=2,
        scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.0, rope_theta=10000.0,
        rope_scaling=_yarn(factor=64.0, original=4096), rms_norm_eps=1e-6,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_res_clamp=(-30.0, 30.0), max_model_len=4096,
    ),
    # zai-org/GLM-5.2 (glm_moe_dsa): a deepseek_v3 decoder (latent attention
    # behind a low-rank query, 256 sigmoid-routed experts of 2048 top-8 x 2.5
    # + 1 shared, three leading dense layers of 12288, plain rope at theta
    # 8e6) with DeepSeek Sparse Attention: indexers of 32 heads x 128 in
    # layers 0-2 and every fourth layer from 6 on, whose top-2048 choice the
    # three layers behind each share. 1.5 TB of bf16 weights:
    # ``--hf-overrides`` names the share one chip holds. Its
    # multi-token-prediction module is not served.
    "glm-5.2": _p(
        "glm-5.2", vocab_size=154880, hidden_size=6144,
        intermediate_size=12288, num_layers=78, num_heads=64,
        num_kv_heads=64, head_dim=256, kv_lora_rank=512, q_lora_rank=2048,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
        num_shared_experts=1, first_k_dense_replace=3,
        scoring_func="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, rope_theta=8000000.0, rms_norm_eps=1e-5,
        index_topk=2048, index_n_heads=32, index_head_dim=128,
        indexer_types=tuple("full" if i < 3 or (i - 3) % 4 == 3 else "shared"
                            for i in range(78)),
        max_model_len=4096,
    ),
}


# Shape keys of an HF ``config.json`` and the ModelConfig field each sets:
# read by engine/weights.config_from_hf and by ``--hf-overrides`` (vLLM's
# name), which changes SHAPE only, e.g. the depth one pipeline stage holds.
HF_SHAPE_KEYS: dict[str, str] = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "max_position_embeddings": "max_model_len",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_routed_experts": "num_experts",
    "n_shared_experts": "num_shared_experts",
    "moe_intermediate_size": "moe_intermediate_size",
    "first_k_dense_replace": "first_k_dense_replace",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    # Not HF's: the share of the routed experts this process holds.
    "experts_first": "experts_first",
    "experts_held": "experts_held",
    # Not HF's, and no field: the first of the published layers this
    # process holds (``num_hidden_layers`` of them: one pipeline stage).
    "layers_from": "layers_from",
}


def apply_hf_overrides(cfg: ModelConfig, overrides: dict) -> ModelConfig:
    """``--hf-overrides '{"num_hidden_layers": 9}'``: HF shape keys laid
    over a config (``layers_from`` k: the layers held are the published
    ones from k on, not from 0). Anything that is not a whole-number shape
    key is refused by name."""
    fields = {}
    for key, val in overrides.items():
        if key not in HF_SHAPE_KEYS:
            raise ValueError(
                f"--hf-overrides: {key!r} is not a shape key "
                f"(one of {sorted(HF_SHAPE_KEYS)})")
        if isinstance(val, bool) or not isinstance(val, int) or val < 0:
            raise ValueError(
                f"--hf-overrides: {key} must be a whole number, not {val!r}")
        fields[HF_SHAPE_KEYS[key]] = val
    start = fields.pop("layers_from", 0)
    depth = fields.get("num_layers")
    if start:
        # A window of the published layers (one pipeline stage): the
        # leading dense layers are those of the published ones inside it,
        # and every per-layer list keeps the entries of the layers held.
        depth = fields.setdefault("num_layers", cfg.num_layers - start)
        if depth < 1 or start + depth > cfg.num_layers:
            raise ValueError(
                f"--hf-overrides: layers {start} to {start + depth - 1} are "
                f"not among {cfg.name}'s {cfg.num_layers}")
        if "first_k_dense_replace" in fields:
            raise ValueError(
                "--hf-overrides: layers_from says which of the leading "
                "dense layers are held; first_k_dense_replace beside it "
                "is refused")
        fields["first_k_dense_replace"] = max(
            cfg.first_k_dense_replace - start, 0)
        if cfg.layer_types is not None:
            fields["layer_types"] = cfg.layer_types[start:start + depth]
    elif (cfg.layer_types is not None and depth is not None
            and depth != cfg.num_layers):
        period, nd = cfg.layer_period, cfg.num_dense_layers
        if depth < nd or (depth - nd) % len(period):
            raise ValueError(
                f"--hf-overrides: num_hidden_layers {depth} is not "
                + (f"{nd} leading dense layers and " if nd else "")
                + f"whole periods of {cfg.name}'s layer pattern "
                f"({len(period)} layers: {', '.join(period)})")
        fields["layer_types"] = (cfg.layer_types[:nd]
                                 + period * ((depth - nd) // len(period)))
    if cfg.indexer_types is not None:
        fields["indexer_types"] = cfg.indexer_types[start:][
            :fields.get("num_layers", cfg.num_layers)]
    cfg = cfg.replace(**fields)
    if cfg.is_mla:
        cfg = cfg.replace(head_dim=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.num_dense_layers >= cfg.num_layers > 0 and cfg.is_moe:
        raise ValueError(
            f"--hf-overrides: {cfg.num_layers} layers leave no expert layer "
            f"after {cfg.num_dense_layers} leading dense ones")
    return cfg


def get_model_config(name: str, **overrides) -> ModelConfig:
    """Look up a preset by name (case-insensitive; HF-style ids are mapped to
    presets by their basename, e.g. ``TinyLlama/TinyLlama-1.1B-Chat-v1.0``)."""
    key = name.lower()
    if key in MODEL_PRESETS:
        cfg = MODEL_PRESETS[key]
        return cfg.replace(**overrides) if overrides else cfg
    base = key.rsplit("/", 1)[-1]
    for preset_key, cfg in MODEL_PRESETS.items():
        if preset_key.replace(".", "").replace("-", "") in base.replace(".", "").replace("-", ""):
            return cfg.replace(**overrides) if overrides else cfg
    raise KeyError(
        f"unknown model {name!r}; known presets: {sorted(MODEL_PRESETS)}. "
        "To serve a model without a preset, pre-stage its HF checkpoint "
        "locally and pass the absolute directory path (config.json supplies "
        "the architecture; supported families: llama/qwen2/qwen3/mixtral/opt)")
