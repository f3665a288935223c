"""Pipeline parallelism: a shard_map circular pipeline over the ``pp`` axis.

The reference ran PP=2 across nodes by handing vLLM a Ray cluster
(``pipelineParallelSize: 2`` + ``raySpec.headNode`` — reference
``values-01-minimal-example4.yaml:16-23,42-46``; concept at
``old_README.md:1615-1625``). TPU-native, there is no actor framework: all
hosts run one SPMD program, stacked layer weights are sharded over the mesh's
``pp`` axis on the layer axis (each stage holds ``L/S`` contiguous layers and
the matching slab of the paged KV pool), and microbatched hidden states rotate
stage-to-stage with `lax.ppermute` — the circular-pipeline schedule from the
public scaling-book recipe. PP composes with manual TP/EP: inside the
shard_map body the model runs with ``tp_axis``/``ep_axis`` set, so attention/
MLP psums ride ICI while the stage-boundary ppermute crosses hosts over DCN.

Schedule: M microbatches, S stages, M+S-1 ticks. At tick t, stage s computes
microbatch ``t - s`` when ``0 <= t-s < M`` (inactive ticks run on garbage and
their KV writes are masked into the scrap page, so the cache stays exact).
Stage 0 injects embeddings; stage S-1 accumulates outputs, broadcast at the
end with a psum over ``pp``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..config import ModelConfig
from ..engine.kv_cache import KVCache
from ..models import llama as model_lib
from ..models.llama import StepMeta
from ..ops.attention import NO_KERNELS, Kernels


def _layer_specs(cfg: ModelConfig) -> dict:
    """PartitionSpecs for the stacked per-layer params: layer axis over ``pp``,
    Megatron column/row sharding over ``tp``, expert axis over ``ep``.
    Mirrors parallel/sharding.py but in manual (shard_map) mode, where the
    layer axis carries the pipeline stage."""
    specs = {
        "input_norm": P("pp"),
        "post_attn_norm": P("pp"),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
    }
    if cfg.attention_bias:
        specs["bq"] = P("pp", "tp")
        specs["bk"] = P("pp", "tp")
        specs["bv"] = P("pp", "tp")
    if cfg.qk_norm:
        specs["q_norm"] = P("pp")
        specs["k_norm"] = P("pp")
    if cfg.norm_type == "layernorm":      # OPT-class LayerNorm biases
        specs["input_norm_b"] = P("pp")
        specs["post_attn_norm_b"] = P("pp")
    if cfg.linear_bias:                   # OPT-class out/MLP biases
        specs["bo"] = P("pp")
        specs["b_up"] = P("pp", "tp")
        specs["b_down"] = P("pp")
    if cfg.is_moe:
        specs["router"] = P("pp")
        specs["w_gate"] = P("pp", "ep", None, "tp")
        specs["w_up"] = P("pp", "ep", None, "tp")
        specs["w_down"] = P("pp", "ep", "tp", None)
    else:
        if cfg.mlp_type != "mlp":
            specs["w_gate"] = P("pp", None, "tp")
        specs["w_up"] = P("pp", None, "tp")
        specs["w_down"] = P("pp", "tp", None)
    if cfg.quantization == "int4":
        # Group scales [*, n_groups, out] (ops/quant.py int4 layout): the
        # out axis shards like the weight's out axis; the group axis
        # partitions the INPUT dim, so row-sharded weights (wo, w_down)
        # shard it over tp (group/shard alignment per engine/weights.py).
        specs["wq_scale"] = P("pp", None, "tp")
        specs["wk_scale"] = P("pp", None, "tp")
        specs["wv_scale"] = P("pp", None, "tp")
        specs["wo_scale"] = P("pp", "tp", None)
        if cfg.is_moe:
            specs["w_gate_scale"] = P("pp", "ep", None, "tp")
            specs["w_up_scale"] = P("pp", "ep", None, "tp")
            specs["w_down_scale"] = P("pp", "ep", "tp", None)
        else:
            if cfg.mlp_type != "mlp":
                specs["w_gate_scale"] = P("pp", None, "tp")
            specs["w_up_scale"] = P("pp", None, "tp")
            specs["w_down_scale"] = P("pp", "tp", None)
    elif cfg.quantization:
        # int8 scales shard like their weight's OUT axis (cf. sharding.py).
        specs["wq_scale"] = P("pp", "tp")
        specs["wk_scale"] = P("pp", "tp")
        specs["wv_scale"] = P("pp", "tp")
        specs["wo_scale"] = P("pp")
        if cfg.is_moe:
            specs["w_gate_scale"] = P("pp", "ep", "tp")
            specs["w_up_scale"] = P("pp", "ep", "tp")
            specs["w_down_scale"] = P("pp", "ep")
        else:
            if cfg.mlp_type != "mlp":
                specs["w_gate_scale"] = P("pp", "tp")
            specs["w_up_scale"] = P("pp", "tp")
            specs["w_down_scale"] = P("pp")
    return specs


def param_pp_specs(cfg: ModelConfig) -> dict:
    """Full param-pytree specs. Embedding/head replicated (small next to the
    layer stack; vocab-sharding them under manual mode is a later
    optimization)."""
    specs = {
        "embed": P(),
        "final_norm": P(),
        "layers": _layer_specs(cfg),
    }
    if cfg.norm_type == "layernorm":
        specs["final_norm_b"] = P()
    if cfg.pos_embedding == "learned":
        specs["pos_embed"] = P()
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P()
        if cfg.quantization:
            specs["lm_head_scale"] = P()
    return specs


KV_PP_SPEC = P("pp", None, None, "tp")  # [L, P, ps, n_kv*hd], heads over tp


def pp_param_shardings(mesh: Mesh, cfg: ModelConfig):
    """NamedSharding pytree for engine-owned params under the pipeline mesh
    (layer axis over ``pp``, Megatron tp inside stages). The engine places
    params with these BEFORE stepping so the shard_map body never repartitions
    weights. ``is_leaf`` guards PartitionSpec's tuple ancestry from tree
    descent."""
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pp_specs(cfg),
                        is_leaf=lambda x: isinstance(x, P))


def pp_kv_sharding(mesh: Mesh):
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, KV_PP_SPEC)


def validate_pp_mesh(mesh: Mesh, cfg: ModelConfig) -> None:
    S, tp, ep = mesh.shape["pp"], mesh.shape["tp"], mesh.shape["ep"]
    if cfg.num_layers % S != 0:
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by pp={S}")
    if cfg.num_heads % tp != 0:
        raise ValueError(f"num_heads={cfg.num_heads} not divisible by tp={tp}")
    if cfg.num_kv_heads % tp != 0:
        raise ValueError(
            f"manual TP inside the pipeline requires num_kv_heads ({cfg.num_kv_heads}) "
            f"divisible by tp={tp}")
    if cfg.is_moe and cfg.num_experts % ep != 0:
        raise ValueError(f"num_experts={cfg.num_experts} not divisible by ep={ep}")


def build_pp_mapped(mesh: Mesh, cfg: ModelConfig, kind: str,
                    kernels: Kernels = NO_KERNELS):
    """The un-jitted shard_map pipeline: ``mapped(params, kv_k, kv_v,
    tokens_mb, meta_mb) -> (hidden_mb [M, N, d], kv_k, kv_v)``. Composable
    inside a larger jitted program — the engine's decode window wraps it in
    its substep scan (sampling stays outside the shard_map, where params'
    replicated final_norm/lm_head make logits a plain GSPMD matmul).
    ``kernels``: the engine's choice; inside the shard_map body a kernel
    runs at the stage's per-shard geometry (``tp_mesh`` is None under pp)."""
    assert kind in ("prefill", "decode", "prefill_hist")
    validate_pp_mesh(mesh, cfg)
    S = mesh.shape["pp"]
    perm = [(i, (i + 1) % S) for i in range(S)]

    if kind == "prefill_hist":
        return _build_pp_hist_mapped(mesh, cfg, S, perm, kernels)

    def local_fn(params, kv_k, kv_v, tokens_mb, meta_mb):
        rank = jax.lax.axis_index("pp")
        M, N = tokens_mb.shape
        d = params["embed"].shape[1]
        dtype = params["embed"].dtype

        def tick(carry, t):
            buf, kvk, kvv, outputs = carry
            mb = jnp.clip(t - rank, 0, M - 1)
            active = jnp.logical_and(t - rank >= 0, t - rank < M)
            tokens = tokens_mb[mb]
            # Inactive ticks write their K/V into the scrap page (slot 0).
            slots = jnp.where(active, meta_mb.slot_mapping[mb], 0)
            if kind == "prefill":
                meta = StepMeta(
                    seg_ids=meta_mb.seg_ids[mb], positions=meta_mb.positions[mb],
                    slot_mapping=slots, logits_indices=meta_mb.logits_indices[mb])
            else:
                meta = StepMeta(
                    positions=meta_mb.positions[mb], slot_mapping=slots,
                    page_tables=meta_mb.page_tables[mb],
                    context_lens=meta_mb.context_lens[mb])
            h_in = jnp.where(
                rank == 0,
                model_lib._embed(params, cfg, tokens,
                                 meta.positions).astype(dtype), buf)
            _, kv_new, h_out = model_lib.forward(
                params, cfg, tokens, meta, KVCache(k=kvk, v=kvv), kernels,
                hidden_in=h_in, tp_axis="tp", ep_axis="ep")
            contrib = jnp.where(jnp.logical_and(rank == S - 1, active),
                                h_out, jnp.zeros_like(h_out))
            outputs = outputs.at[mb].add(contrib)
            buf = jax.lax.ppermute(h_out, "pp", perm)
            return (buf, kv_new.k, kv_new.v, outputs), None

        init = (jnp.zeros((N, d), dtype), kv_k, kv_v,
                jnp.zeros((M, N, d), dtype))
        (buf, kvk, kvv, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(M + S - 1))
        # Outputs live on the last stage only; broadcast to every rank.
        outputs = jax.lax.psum(outputs, "pp")
        return outputs, kvk, kvv

    # P() for the whole meta: every field it carries is replicated.
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_pp_specs(cfg), KV_PP_SPEC, KV_PP_SPEC, P(), P()),
        out_specs=(P(), KV_PP_SPEC, KV_PP_SPEC),
        check_vma=False,
    )


def _build_pp_hist_mapped(mesh: Mesh, cfg: ModelConfig, S: int, perm,
                          kernels: Kernels):
    """Pipelined CHUNKED prefill (VERDICT r4 #6: the history path used to
    run as plain GSPMD, making XLA all-gather the pp-sharded layer stack on
    every long-prompt chunk). The chunk is split into M sub-chunk
    microbatches along the token axis; sub-chunk j attends to the POOL with
    ``hist_lens[j] = hist_len + j*sub`` — exact, because in the circular
    pipeline stage s processes sub-chunk j-1 at tick (j-1)+s, committing its
    stage-s KV to the local pool shard before sub-chunk j arrives at tick
    j+s. In-chunk causality within a sub-chunk is the ordinary
    history-attention mask. Signature: ``mapped(params, kv_k, kv_v,
    tokens_mb [M, sub], meta_mb, page_table [W], hist_lens [M]) ->
    (hidden_mb [M, sub, d], kv_k, kv_v)``."""

    def local_fn(params, kv_k, kv_v, tokens_mb, meta_mb, page_table,
                 hist_lens):
        rank = jax.lax.axis_index("pp")
        M, _ = tokens_mb.shape
        d = params["embed"].shape[1]
        dtype = params["embed"].dtype

        def tick(carry, t):
            buf, kvk, kvv, outputs = carry
            mb = jnp.clip(t - rank, 0, M - 1)
            active = jnp.logical_and(t - rank >= 0, t - rank < M)
            tokens = tokens_mb[mb]
            slots = jnp.where(active, meta_mb.slot_mapping[mb], 0)
            meta = StepMeta(
                seg_ids=meta_mb.seg_ids[mb], positions=meta_mb.positions[mb],
                slot_mapping=slots, logits_indices=meta_mb.logits_indices[mb],
                chunk_page_table=page_table, hist_len=hist_lens[mb])
            h_in = jnp.where(
                rank == 0,
                model_lib._embed(params, cfg, tokens,
                                 meta.positions).astype(dtype), buf)
            _, kv_new, h_out = model_lib.forward(
                params, cfg, tokens, meta, KVCache(k=kvk, v=kvv), kernels,
                hidden_in=h_in, tp_axis="tp", ep_axis="ep")
            contrib = jnp.where(jnp.logical_and(rank == S - 1, active),
                                h_out, jnp.zeros_like(h_out))
            outputs = outputs.at[mb].add(contrib)
            buf = jax.lax.ppermute(h_out, "pp", perm)
            return (buf, kv_new.k, kv_new.v, outputs), None

        N = tokens_mb.shape[1]
        init = (jnp.zeros((N, d), dtype), kv_k, kv_v,
                jnp.zeros((M, N, d), dtype))
        (buf, kvk, kvv, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(M + S - 1))
        outputs = jax.lax.psum(outputs, "pp")
        return outputs, kvk, kvv

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_pp_specs(cfg), KV_PP_SPEC, KV_PP_SPEC, P(),
                  P(), P(), P()),
        out_specs=(P(), KV_PP_SPEC, KV_PP_SPEC),
        check_vma=False,
    )


def build_pp_forward(mesh: Mesh, cfg: ModelConfig, kind: str,
                     kernels: Kernels = NO_KERNELS):
    """Jitted standalone pipelined forward: ``fn(params, kv, tokens_mb,
    meta_mb) -> (hidden_mb, new_kv)`` where every meta field carries a leading
    microbatch axis ``[M, ...]`` and ``hidden_mb`` is the raw last-stage
    hidden state ``[M, N, d]`` (N = flattened tokens T for prefill, batch B
    for decode). The caller applies final-norm/logits/sampling (see
    :func:`pp_logits`). The serving engine uses :func:`build_pp_mapped`
    directly instead, fusing sampling into its step program."""
    mapped = build_pp_mapped(mesh, cfg, kind, kernels)

    @partial(jax.jit, donate_argnums=(1,))
    def fn(params, kv: KVCache, tokens_mb, meta_mb):
        outputs, kvk, kvv = mapped(params, kv.k, kv.v, tokens_mb, meta_mb)
        return outputs, KVCache(k=kvk, v=kvv)

    return fn


def pp_logits(params, cfg: ModelConfig, hidden: jax.Array,
              logits_indices=None) -> jax.Array:
    """Final norm + logits for pipeline output hidden states.

    hidden: [N, d] raw last-stage hidden for one microbatch. For prefill pass
    ``logits_indices`` [B] to select each sequence's last token first.
    """
    if logits_indices is not None:
        hidden = hidden[logits_indices]
    normed = model_lib._norm(cfg, hidden, params, "final_norm")
    return model_lib.compute_logits(params, cfg, normed)
