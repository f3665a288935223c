"""LLMEngine: the single-host serving engine (continuous batching over jit).

This is the component the reference delegated wholesale to vLLM CUDA images
(SURVEY §0 consequence 2). Responsibilities:

- owns model params, the paged KV cache (donated through every step so XLA
  updates it in place), and the scheduler;
- compiles one XLA program per (kind, bucketed shape) and reuses it across the
  serving lifetime — the jit-cache discipline that replaces vLLM's CUDA-graph
  capture;
- fuses sampling into the step program so only sampled token ids (B int32)
  cross device->host per step.

Parallelism: the engine runs its step under an optional device mesh with
tensor-parallel sharding (parallel/mesh.py, parallel/sharding.py). DP
replication happens one level up (multiple engine pods behind the router,
as in reference values-01-minimal-example2.yaml), PP in parallel/pp.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sanitize import build_step_sanitizer
from ..config import EngineConfig, cache_kind_refusal
from ..utils.math import next_power_of_2
from ..models import llama as model_lib
from ..observability import Observability, StepClock
from ..models.llama import StepMeta
from ..ops.attention import Kernels
from ..ops.sampling import (apply_logit_bias, apply_penalties, build_counts,
                            bump_counts, gated_top_logprobs, row_sample_keys,
                            sample_and_logprobs, spec_verify_sample,
                            token_logprobs)
from ..resilience.faults import inject as _inject_fault
from ..utils import cdiv, get_logger
from .kv_cache import (KVCache, KVPageIO, KVTransferPrograms,
                       allocate_kv_cache, build_kv_swapper,
                       default_state_slots, derive_num_pages,
                       kv_cache_bytes_per_token, kv_cache_dtype,
                       kv_row_padding_share, state_bytes_per_seq)
from .mixed_batch import (mixed_row_bucket, mixed_steps_of_prompt,
                          padding_mixed_batch)
from .sampling_params import LOGIT_BIAS_CAP, SamplingParams
from .scheduler import CannotChain, ScheduledBatch, Scheduler, _bucket
from .sequence import FinishReason, Sequence, SequenceStatus
from . import block as block_steps

logger = get_logger("engine")


def _maybe_bias(logits, bias_ids, bias_vals):
    """Sparse additive logit_bias under a runtime cond (bias-free batches —
    the common case — skip the scatter; they pass a cached -1 dummy).
    Applied BEFORE penalties/temperature (OpenAI: 'prior to sampling')."""
    return jax.lax.cond(
        jnp.any(bias_ids >= 0),
        lambda l: apply_logit_bias(l, bias_ids, bias_vals),
        lambda l: l, logits)


@dataclasses.dataclass
class EngineStats:
    """Aggregate serving counters, consumed by serving.metrics (/metrics).
    Latency distributions (TTFT, step time, …) live in the engine's
    Observability histograms — the host-side sample deques and quantile()
    this class used to carry were superseded and removed with them."""
    tokens_generated: int = 0
    requests_finished: int = 0
    prefill_tokens: int = 0
    steps: int = 0


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    prompt_token_ids: list[int]
    output_token_ids: list[int]
    finished: bool
    finish_reason: Optional[str] = None
    new_token_ids: Optional[list[int]] = None  # tokens produced this step
    new_logprobs: Optional[list[float]] = None  # chosen-token logprobs, ditto
    output_logprobs: Optional[list[float]] = None  # full per-token record
    # OpenAI logprobs=N alternatives: per new token, [(token_id, logprob)]
    # of the N most likely tokens (N = SamplingParams.top_logprobs).
    new_top_logprobs: Optional[list[list[tuple[int, float]]]] = None
    output_top_logprobs: Optional[list[list[tuple[int, float]]]] = None
    # The clock of the program that produced new_token_ids: ONE object for
    # all its rows' outputs (observability/phases.py); None: no program did.
    clock: Optional[StepClock] = None


def _prefill_penalties(cfg, logits, int_t, prompt_lens, presence, frequency):
    """Presence/frequency penalties at the PREFILL sampling point. A
    recompute-preemption re-prefill carries the sequence's generated tokens
    IN the batch (prompt + outputs re-prefilled together), so the output
    histogram is built on-device from the batch itself: tokens at positions
    >= the row's prompt_len are outputs. Fresh admissions have no output
    tokens and penalize nothing. Gated by a runtime cond — penalty-free
    batches (the common case) skip the [B, V] scatter."""
    any_pen = jnp.any((presence != 0.0) | (frequency != 0.0))

    def penalize(l):
        tokens, seg_ids, positions = int_t[0], int_t[1], int_t[2]
        row = jnp.clip(seg_ids, 0, l.shape[0] - 1)
        out_mask = ((seg_ids >= 0)
                    & (positions >= jnp.take(prompt_lens, row)))
        counts = jnp.zeros((l.shape[0], cfg.vocab_size), jnp.int32)
        counts = counts.at[row, tokens].add(out_mask.astype(jnp.int32))
        return apply_penalties(l, counts, presence, frequency)

    return jax.lax.cond(any_pen, penalize, lambda l: l, logits)


def _pack_int_b(batch: ScheduledBatch) -> np.ndarray:
    """The per-row int buffer of the prefill, chunk and mixed programs:
    [B, 5] = (logits_indices, top_k, seed, prompt_len, top_n), and for a
    state model the slots after them, one column a kind the batch has
    (segments', then rows'), so that they ride the upload that is made
    anyway; last, where the batch has decode rows (mixed), ``tok_src``."""
    cols = [batch.logits_indices, batch.top_k, batch.seed, batch.prompt_lens,
            batch.top_n]
    cols += [c for c in (batch.seg_slots, batch.row_slots, batch.tok_src)
             if c is not None]
    return np.stack(cols, axis=1)


def _pack_float_b(batch: ScheduledBatch):
    """The per-row float buffer of every step program: [B, 4]."""
    return jnp.asarray(np.stack(
        [batch.temperature, batch.top_p, batch.presence, batch.frequency],
        axis=1))


def _chained_tokens(tokens, prev, src):
    """Decode rows' input tokens: where ``src`` names a row, the token the
    step dispatched before this one sampled for it (``prev``, that step's
    last-token output, still on the device); the host's elsewhere. A step
    with no predecessor passes zeros and -1 everywhere."""
    return jnp.where(src >= 0, jnp.take(prev, jnp.maximum(src, 0)), tokens)


def _last_tokens(tokens, width: int):
    """A step's newest token a row as ``[width]``, whatever its row bucket:
    what ``_chained_tokens`` of the next step reads, one shape for every
    predecessor, so that a step kind stays one program."""
    return jnp.zeros(width, jnp.int32).at[:tokens.shape[0]].set(tokens)


def _slot_columns(cfg, int_b, *names, chunk: bool = False) -> dict:
    """``_pack_int_b``'s slot columns back as StepMeta fields, in its order;
    nothing for a model without state layers. ``chunk``: the segment part
    is ONE sequence's chunk, so only the column's first entry is a segment
    (the forward computes a final state for every entry it is handed)."""
    if not cfg.has_state:
        return {}
    cols = {name: int_b[:, 5 + i] for i, name in enumerate(names)}
    if chunk:
        cols["seg_slots"] = cols["seg_slots"][:1]
    return cols


def resolve_shardings(mesh, model_cfg):
    """(params_sharding, kv_sharding) for a serving mesh — the one place
    that picks between GSPMD Megatron layouts (parallel/sharding.py) and the
    manual pipeline layout (parallel/pp.py: layer axis over ``pp``, Megatron
    tp inside stages — the engine-side integration the reference got from
    Ray + vLLM, reference values-01-minimal-example4.yaml:16-23). Used by
    the engine at init AND by weight loading, so checkpoints stream straight
    into their sharded placement (engine/weights._load_streamed)."""
    if mesh is None:
        return None, None
    if mesh.shape.get("pp", 1) > 1:
        from ..parallel.pp import (pp_kv_sharding, pp_param_shardings,
                                   validate_pp_mesh)
        validate_pp_mesh(mesh, model_cfg)
        return pp_param_shardings(mesh, model_cfg), pp_kv_sharding(mesh)
    from ..parallel.sharding import kv_cache_sharding, param_shardings
    return param_shardings(mesh, model_cfg), kv_cache_sharding(mesh, model_cfg)


class LLMEngine:
    def __init__(self, config: EngineConfig, params=None,
                 eos_token_id: Optional[int] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 use_pallas: Optional[bool] = None,
                 draft_params=None):
        if config.cache.page_size is None:
            # Backend-derived default (see CacheConfig.page_size).
            ps = 128 if jax.default_backend() == "tpu" else 16
            config = dataclasses.replace(
                config, cache=dataclasses.replace(config.cache, page_size=ps))
        self.config = config
        self.model_config = config.model
        refusal = cache_kind_refusal(
            config, mesh.shape if mesh is not None else None)
        if refusal is not None:
            raise ValueError(refusal)
        self.eos_token_id = eos_token_id
        self.mesh = mesh
        self.pp_size = mesh.shape.get("pp", 1) if mesh is not None else 1
        self.sp_size = mesh.shape.get("sp", 1) if mesh is not None else 1
        ep = mesh.shape.get("ep", 1) if mesh is not None else 1
        if ep > 1 and not config.model.is_moe:
            # ep on a dense model would silently replicate all work across
            # the axis — N chips for ~1 chip of throughput.
            raise ValueError(
                f"ep={ep} requires an MoE model; {config.model.name} is dense")
        if self.sp_size > 1:
            # Sequence parallelism scales PREFILL (ring attention over sp);
            # decode runs GSPMD with the batch replicated over sp. The
            # pipeline composes with tp/ep, not sp (two shard_map regimes).
            if self.pp_size > 1:
                raise ValueError("sp and pp cannot combine in one mesh")
            bad = [b for b in config.scheduler.prefill_buckets
                   if b % self.sp_size]
            if bad:
                raise ValueError(
                    f"prefill buckets {bad} not divisible by sp={self.sp_size}"
                    " (ring attention shards the token axis)")
        self.kernels = self._resolve_use_pallas(use_pallas)
        self._key = jax.random.key(config.seed)

        params_sharding, kv_sharding = resolve_shardings(mesh, config.model)
        if mesh is not None and self.pp_size > 1:
            logger.info("pipeline-parallel serving: %s", dict(mesh.shape))

        if params is None:
            logger.info("initializing random weights for %s", config.model.name)
            params = model_lib.init_params(config.model, jax.random.key(config.seed))
        if params_sharding is not None:
            params = jax.device_put(params, params_sharding)
        self.params = jax.block_until_ready(params)

        # The pool is sized from what is free ONCE THE WEIGHTS ARE RESIDENT
        # (random-init and loaded alike): sizing first hands 0.9 of an empty
        # chip to the pool and the weights then have nowhere to go.
        # Under a mesh this reads the first local device, which holds 1/tp
        # of the weights, against the UNSHARDED bytes per page — each chip
        # stores only 1/tp of a page when kv heads divide tp, so a tp mesh's
        # pool is sized conservatively (about 1/tp of what would fit).
        hbm_free = _device_free_memory(resident=_resident_bytes(self.params))
        # A state model's slots come before the pages: a seat each and the
        # scrap slot, a fixed size whatever the contexts (64 seats of
        # granite-4.0-h-micro: 4.97 GB, beside 6.38 GB of weights).
        num_state_slots = default_state_slots(
            config.model, config.scheduler.max_num_seqs)
        state_bytes = num_state_slots * state_bytes_per_seq(config.model)
        if hbm_free is not None:
            # ... and once the largest step program's own workspace is set
            # aside: hbm_utilization applies to what the POOL can have.
            hbm_free -= step_workspace_bytes(config) + state_bytes
        num_pages = derive_num_pages(
            config.model, config.cache, config.effective_max_len,
            config.scheduler.max_num_seqs, hbm_free)
        # Cap: no point holding more pages than max_num_seqs full sequences.
        cap = (config.scheduler.max_num_seqs *
               cdiv(config.effective_max_len, config.cache.page_size) + 1)
        num_pages = min(num_pages, cap)
        logger.info("KV cache: %d pages x %d tokens (page pool; %s bytes "
                    "were free for it once the weights were resident and "
                    "%d were set aside for a step's workspace)",
                    num_pages, config.cache.page_size, hbm_free,
                    step_workspace_bytes(config))

        # One Observability per engine, shared with the scheduler: lifecycle
        # trace events, step-phase attribution, and the /metrics histograms
        # all accumulate here (serving.metrics renders it; /debug/trace
        # exports it).
        self.obs = Observability()
        if config.model.experts_held:   # the gauges are over this share
            self.obs.moe_held = (config.model.experts_first,
                                 config.model.experts_held)
        self.scheduler = Scheduler(config, num_pages, obs=self.obs,
                                   num_state_slots=num_state_slots)
        if self.scheduler.qos is not None:
            # Per-tier SLO trackers + served counters (bounded label set:
            # the configured tier names). Tiers without their own budget
            # grade against the operator's admission default — the same
            # bar the global tracker and per-tier admission fall back to.
            # QoS off leaves the scrape byte-identical to the tier-less
            # engine.
            self.obs.configure_qos_tiers(
                config.scheduler.qos_tiers,
                self.scheduler.qos.default_tier,
                fallback_budget_ms=config.resilience.default_ttft_budget_ms)

        self.kv_cache = allocate_kv_cache(config.model, config.cache, num_pages,
                                          kv_sharding, num_state_slots)
        if state_bytes:
            logger.info("state slots: %d x %d bytes (%d state layers; "
                        "slot 0 is scrap)", num_state_slots,
                        state_bytes_per_seq(config.model),
                        config.model.num_state_layers)

        # The one-deep device queue: every step program also returns its
        # newest token a row as [_last_width], and takes its predecessor's
        # such output (or these zeros), so that a step dispatched behind an
        # unfetched one reads its decode rows' input tokens on the device.
        # One width, the largest row bucket there is, keeps each step kind
        # ONE program whatever precedes it. A block model's programs hand
        # on their rows' open blocks instead (engine/block.py: ``hand_on``).
        sc = config.scheduler
        self._last_width = max(sc.decode_buckets[-1],
                               _bucket(sc.max_num_seqs, sc.decode_buckets))
        B = config.model.block_length
        self._no_pred = jnp.zeros(
            self._last_width if B == 1
            else (self._last_width, block_steps.state_width(B)), jnp.int32)
        self._prefill_fn = self._build_prefill_fn()
        # Two compiled window programs: all-greedy batches (the common
        # serving case) never trace sampling at all — argmax only. Selection
        # happens HOST-side per batch from its SamplingParams; a runtime
        # lax.cond inside the scan would keep the sampling subgraph in the
        # program and its cost on the critical path.
        self._decode_fn = self._build_decode_fn(greedy=False)
        self._decode_fn_greedy = self._build_decode_fn(greedy=True)
        # Chunked-prefill history attention has no pipelined variant yet:
        # under pp it runs as plain GSPMD over the pp-sharded params (XLA
        # gathers the layer stack — correct, slow, and rare: only prompts
        # longer than max_prefill_tokens take this path; parity locked in by
        # tests/test_parallel.py::test_pp_engine_chunked_prefill).
        self._prefill_hist_fn = self._build_prefill_hist_fn()
        # Mixed prefill/decode step program (stall-free batching). No pp/sp
        # variant exists: the pipelined layer regime and ring attention both
        # replace the kernels this path splits the token axis between, so
        # those meshes keep the legacy prefill-else-decode policy.
        # A block model's window and mixed step (engine/block.py): W passes
        # over the rows' open blocks, and one pass beside a chunk.
        self._block_window_fn, self._block_mixed_fn = (
            block_steps.build_block_fns(self)
            if config.model.block_length > 1 else (None, None))
        if self.pp_size == 1 and self.sp_size == 1:
            self._mixed_fn = self._build_mixed_fn()
        else:
            self._mixed_fn = None
            if self.scheduler.mixed_enabled:
                logger.warning(
                    "mixed batching disabled: no mixed forward path under "
                    "pp=%d/sp=%d meshes", self.pp_size, self.sp_size)
                self.scheduler.mixed_enabled = False
        if self.scheduler.mixed_enabled:
            # Surface configurations that silently leave mixing inert: the
            # bow-out probes in build_mixed_batch read ~0 on
            # kgct_mixed_step_ratio with no other signal.
            sc = config.scheduler
            budget = sc.decode_priority_token_budget
            if budget is not None and budget < 2:
                raise ValueError(
                    f"decode_priority_token_budget={budget} can never fit a "
                    "decode row plus a chunk token; mixing would never engage")
            if budget is not None and budget < sc.max_num_seqs + 1:
                logger.warning(
                    "mixed batching: decode_priority_token_budget=%d is below"
                    " max_num_seqs+1=%d — a full batch's decode rows alone "
                    "exhaust it, so high-occupancy steps keep the legacy "
                    "policy", budget, sc.max_num_seqs + 1)
            if sc.max_num_seqs > sc.decode_buckets[-1]:
                logger.warning(
                    "mixed batching: max_num_seqs=%d exceeds the decode "
                    "bucket grid (max %d); steps with more running sequences"
                    " than the grid covers keep the legacy policy",
                    sc.max_num_seqs, sc.decode_buckets[-1])
        # Speculative decoding: pure-decode steps become batched draft
        # verification (engine/spec/). Single-mesh and GSPMD-tp regimes
        # only, like the mixed path — under pp the layer stack is sharded
        # outside the flat forward and under sp ring attention replaces
        # the paged layout it splits on.
        if self.scheduler.spec_enabled and (self.pp_size > 1
                                            or self.sp_size > 1):
            logger.warning(
                "spec decode disabled: no spec-verify forward path under "
                "pp=%d/sp=%d meshes", self.pp_size, self.sp_size)
            self.scheduler.spec_enabled = False
        self._spec_verify_fn = (self._build_spec_verify_fn()
                                if self.scheduler.spec_enabled else None)
        # Spec×mixed composition: mixed steps carry verify slices when both
        # features survived their mesh gating. Without the combined program
        # the scheduler keeps the pre-composition behavior (spec on
        # pure-decode steps, plain mixed otherwise).
        if self.scheduler.spec_enabled and self._mixed_fn is not None:
            self._spec_mixed_fn = self._build_spec_mixed_fn()
        else:
            self._spec_mixed_fn = None
            self.scheduler.spec_mixed_enabled = False
        if self.scheduler.spec_enabled:
            sc = config.scheduler
            if sc.spec_draft_model:
                # Two-model speculation: install the draft-model runner
                # over the scheduler's n-gram proposer. This assignment is
                # the ONE sanctioned installation site; afterwards the
                # engine/scheduler touch draft state only through the
                # proposer seam (KGCT017 draft-state-boundary).
                from .spec.draft_model import build_draft_runner
                self.scheduler.spec_proposer = build_draft_runner(
                    config, sc.spec_draft_model, params=draft_params,
                    jit_enabled=not config.enforce_eager)
            ctrl = self.scheduler.spec_controller
            self.obs.spec_current_k = (ctrl.current_k if ctrl is not None
                                       else sc.effective_spec_k_max)
        self.stats = EngineStats()
        self.step_count = 0
        # The step in flight: the record of the one program dispatched and
        # not yet fetched, of whatever kind (see _step()).
        self._inflight: Optional[dict] = None
        # Set by import_request: a sequence joined ``running`` (and pages
        # were written) outside schedule(), between two steps. The next
        # step is then scheduled with nothing in flight (chain break
        # "stale"), as the loop always did after an import.
        self._batch_stale = False
        self._deferred_release: list[Sequence] = []
        # Streamed fleet-prefix imports in flight (begin_prefix_import):
        # handle -> {pages, token_ids, filled}. Pages are released on
        # commit/abort; the serving layer owns abort-on-failure.
        self._prefix_imports: dict[str, dict] = {}
        self._prefix_import_seq = 0
        # Width of the host->device output-token resync buffer for the
        # penalty histogram (outputs are bounded by the model length).
        self._out_cap = config.effective_max_len
        # Recycled device buffers for the sampled decode program, per padded
        # batch size: counts cycle donated through windows over the same rows
        # and return to the pool when a window's successor takes none
        # (contents only read under rebuild/penalty
        # conds, so staleness is harmless); the -1-filled out_tokens dummy is
        # not donated and lives forever.
        self._counts_pool: dict[int, Any] = {}
        self._dummy_out: dict[int, Any] = {}
        self._dummy_bias: dict[int, Any] = {}
        # Runtime sanitizers (KGCT_SANITIZE=1, analysis/sanitize.py):
        # step-output NaN/vocab guard + KV-slot shadow for the spec-decode
        # rollback contract. None when off — every hook is one is-None
        # test and outputs are byte-identical with the sanitizer absent.
        self._sanitizer = build_step_sanitizer(config.cache.page_size)
        if self._sanitizer is not None:
            self.scheduler.release_guard = self._sanitizer.on_release
        # Two-tier KV cache (CacheConfig.swap_space_gb > 0): host-DRAM page
        # pool + batched jitted gather/scatter. The scheduler preempts by
        # swap instead of recompute, and the prefix cache spills evicted
        # pages for a second-chance restore. None when off — every call
        # site degrades to today's single-tier behavior byte-identically.
        # One gather/scatter pair serves BOTH transfer seams (host-tier
        # swap and cross-replica handoff): a decode replica with
        # swap_space_gb > 0 compiles one family, not two identical copies.
        self._kv_programs = KVTransferPrograms(
            jit_enabled=not config.enforce_eager, kv_sharding=kv_sharding)
        self.swapper = build_kv_swapper(
            config.model, config.cache, self.kv_cache,
            get_kv=lambda: self.kv_cache, set_kv=self._set_kv_cache,
            obs=self.obs, jit_enabled=not config.enforce_eager,
            kv_sharding=kv_sharding, programs=self._kv_programs)
        if self.swapper is not None:
            self.scheduler.attach_swapper(self.swapper)
            if self.scheduler.prefix_cache is not None:
                self.scheduler.prefix_cache.attach_swapper(self.swapper)
            if self._sanitizer is not None:
                # The KV-slot shadow learns that a swapped-in slot is
                # committed history (stale spec slots died with the swap).
                self.swapper.on_restored = self._sanitizer.on_swap_restore
        # Disaggregated prefill/decode: the KV export/import seam. Both
        # jitted transfer programs compile lazily — engines that never hand
        # KV between replicas never pay for them (kv_cache.KVPageIO).
        self.kv_io = KVPageIO(
            get_kv=lambda: self.kv_cache, set_kv=self._set_kv_cache,
            programs=self._kv_programs)
        # Black-box flight recorder: periodic state snapshots (queue depths,
        # KV occupancy both tiers) ride Observability.on_step; the source is
        # O(1) attribute reads, never a device sync (KGCT012).
        self.obs.flight.set_snapshot_source(self._flight_snapshot)
        logger.info("engine ready: %s", self.runtime_info())

    def runtime_info(self) -> dict:
        """What this engine actually runs on and with — the device as JAX
        reports it, the kernels that were proved at construction, and the
        pool. Logged once at start-up and served on /health, so a result
        can always be tied to the device and the kernels behind it (a CPU
        run or an XLA-attention run must never pass for the chip)."""
        dev = jax.devices()[0]
        info = {
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "dtype": self.model_config.dtype,
            "quantization": self.model_config.quantization,
            "use_pallas": self.kernels.use_pallas,
            "use_pallas_hist": self.kernels.use_pallas_hist,
            "num_pages": self.scheduler.allocator.num_pages,
            "page_size": self.config.cache.page_size,
            # The bytes a cached token really holds (all layers, padding of
            # a latent row included) and the share of them that is padding.
            "weight_bytes": sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(self.params)),
            "kv_layout": (("latent" if self.model_config.is_mla else "k|v")
                          + ("+state" if self.model_config.has_state
                             else "")),
            "kv_bytes_per_token": kv_cache_bytes_per_token(
                self.model_config, self.config.cache),
            "kv_row_padding_share": round(
                kv_row_padding_share(self.model_config), 4),
        }
        if self.model_config.has_state:
            # The second kind of memory: layers of each kind, the slots and
            # what they hold (scrap slot included), a sequence's share.
            alloc = self.scheduler.allocator
            info.update(
                kv_layers=self.model_config.num_kv_layers,
                state_layers=self.model_config.num_state_layers,
                state_slots=alloc.num_state_slots,
                state_bytes_per_seq=state_bytes_per_seq(self.model_config),
                state_bytes=alloc.num_state_slots
                * state_bytes_per_seq(self.model_config))
        if self.model_config.experts_held:
            # A share of the routed experts, by configuration: which ones,
            # of how many the router scores.
            info.update(experts_held=self.model_config.experts_held,
                        experts_first=self.model_config.experts_first,
                        experts_published=self.model_config.num_experts)
        if self.model_config.index_topk:
            # A third kind of row: index keys, in the layers that choose.
            m = self.model_config
            info.update(
                index_topk=m.index_topk, indexer_layers=len(m.index_layers),
                index_cache_bytes=int(self.kv_cache.idx.size
                                      * self.kv_cache.idx.dtype.itemsize))
        if self.model_config.hc_mult > 1:
            # The residual is this many streams, mixed around every sublayer.
            info["residual_streams"] = self.model_config.hc_mult
        if self.model_config.block_length > 1:
            # Generation by diffusion over blocks: a pass yields 0 to
            # block_length tokens a row.
            m = self.model_config
            info.update(block_length=m.block_length,
                        denoising_steps=m.denoising_steps,
                        remasking=m.remasking,
                        confidence_threshold=m.confidence_threshold)
        if self.pallas_disabled_reason is not None:
            info["pallas_disabled_reason"] = self.pallas_disabled_reason
        return info

    def _flight_snapshot(self) -> dict:
        sched = self.scheduler
        alloc = sched.allocator
        snap = {"waiting": len(sched.waiting), "running": len(sched.running),
                "swapped": len(sched.swapped), "step": self.step_count,
                "kv_pages_free": alloc.num_free,
                "kv_pages_total": alloc.num_pages}
        if alloc.num_state_slots:
            snap["state_slots_free"] = alloc.num_free_slots
            snap["state_slots_total"] = alloc.num_state_slots
        if self.swapper is not None:
            snap["host_pages_in_use"] = self.swapper.host.num_in_use
            snap["host_pages_total"] = self.swapper.host.num_pages
        return snap

    def compiled_step_variants(self) -> int:
        """Total jit-cache entries across every step program: the number of
        distinct SHAPES the step programs have met so far. An entry that was
        loaded from the persistent compilation cache counts like one that
        was compiled, and the eager one-op programs the host issues between
        steps are not seen at all; the compilations themselves are counted
        where they happen (``kgct_xla_compile_*``, utils/compile_cache.py).
        The same count the tier-1 compile guard bounds
        (tests/test_compile_guard.py), exported as
        ``kgct_jit_compiles_total``: a steady-state serving process holds
        this flat, so growth under constant traffic means new shapes keep
        arriving."""
        fns = [self._prefill_fn, self._prefill_hist_fn, self._mixed_fn,
               self._decode_fn, self._decode_fn_greedy, self._spec_verify_fn,
               self._spec_mixed_fn, self._block_window_fn,
               self._block_mixed_fn]
        # The shared pair counts once: swapper and kv_io both run it.
        fns += [self._kv_programs._gather_fn, self._kv_programs._scatter_fn]
        total = sum(fn._cache_size() for fn in fns
                    if fn is not None and hasattr(fn, "_cache_size"))
        # The draft model's decode/prefill programs (read through the
        # proposer seam): the compile guard and the jit-compiles gauge
        # must cover the second model's family too.
        proposer = self.scheduler.spec_proposer
        if proposer is not None and hasattr(proposer, "compiled_variants"):
            total += proposer.compiled_variants()
        return total

    def _set_kv_cache(self, kv: KVCache) -> None:
        """Swap-in rebinding seam: the scatter donates the pool, so the
        swapper must rebind the engine's reference from its own result —
        the same discipline every step program follows (KGCT004)."""
        self.kv_cache = kv

    def _resolve_use_pallas(self, use_pallas: Optional[bool]) -> Kernels:
        """Decide the kernel path ONCE, at init, from static facts — backend,
        mesh sharding, lane alignment — and PROVE it: on a TPU every kernel
        the engine is eligible to use is compiled here at the geometry it
        will serve, and a kernel that does not compile fails construction
        with the compiler's message. There is no fallback: a refused kernel
        that degraded to a warning once put XLA gather attention into the
        record as the system. The two eligibility decisions below (heads
        not divisible by tp, lane not 128-aligned) stay explicit choices;
        their reason is kept in ``pallas_disabled_reason`` and reported on
        /health.

        The result is the ONE value (``ops.attention.Kernels``) every step
        program hands to the forward pass; nothing below decides again. It
        also says where the kernels run per shard (a GSPMD mesh; under pp
        they already run inside the pipeline's own shard_map), what replaces
        fresh-prompt attention under sp, and whether the experts lie whole
        on one device."""
        self.pallas_disabled_reason: Optional[str] = None
        cfg = self.model_config
        if use_pallas is None and jax.default_backend() == "tpu":
            tp = self.mesh.shape.get("tp", 1) if self.mesh is not None else 1
            lane = cfg.kv_row_padded // tp
            if cfg.num_heads % tp or cfg.num_kv_heads % tp:
                self.pallas_disabled_reason = (
                    f"heads ({cfg.num_heads} q / {cfg.num_kv_heads} kv) not "
                    f"divisible by tp={tp}")
            elif lane % 128:
                self.pallas_disabled_reason = (
                    f"per-shard KV lane dim {lane} (n_kv*hd/tp) is not "
                    "128-aligned")
            use_pallas = self.pallas_disabled_reason is None
            if use_pallas:
                # Under a mesh the kernels run per-shard inside shard_map —
                # the tp wrappers (ops.attention.*_tp) for GSPMD serving, or
                # the pipeline's own shard_map body for pp>1 — so the probes
                # compile the kernels at the PER-SHARD head geometry each
                # device will actually build.
                self._probe_pallas_compile(tp)
            else:
                logger.warning(
                    "Pallas kernels disabled: %s; using XLA attention",
                    self.pallas_disabled_reason)
        use_pallas = bool(use_pallas)   # None: not asked for, not on a TPU
        ring = None
        if self.sp_size > 1:
            # Ring attention over the sp axis (parallel/sp.py): each device
            # holds T/sp tokens and K/V blocks rotate by ppermute. Heads
            # stay replicated inside the ring body — sp is the long-context
            # axis, tp the weight axis; they compose at the GSPMD level
            # (matmuls), not inside attention. The mesh stays for the
            # post-scan KV write kernel.
            from ..parallel.sp import build_ring_prefill
            ring = build_ring_prefill(
                self.mesh, cfg.num_kv_heads,
                cfg.num_heads // cfg.num_kv_heads, cfg.head_dim ** -0.5)
        gspmd = self.mesh is not None and self.pp_size == 1
        return Kernels(
            use_pallas=use_pallas,
            use_pallas_hist=use_pallas and self._hist_kernel_eligible(),
            tp_mesh=self.mesh if use_pallas and gspmd else None,
            ring_prefill=ring,
            grouped_experts=self._grouped_experts,
            block=cfg.block_length)

    def _hist_kernel_eligible(self) -> bool:
        """Where the Pallas history-prefill kernel can serve: meshless
        engines call it directly; GSPMD tp meshes route it through the tp
        shard_map wrapper. Under pp the pool's layer axis is pp-sharded
        (outside the wrapper's specs) and under sp the tp-only wrapper
        would replicate the whole chunk's history attention across the sp
        group — both keep the XLA path."""
        return self.pp_size == 1 and self.sp_size == 1

    def _probe_pallas_compile(self, tp: int = 1) -> None:
        """Compile every Pallas kernel this engine will run ON THE CHIP
        before committing to it, at the per-shard head geometry and the
        LARGEST shapes the scheduler can dispatch (top decode bucket, full
        page-table width, top prefill bucket): Mosaic refuses a kernel for
        VMEM or tiling only at jit-compile time, and a small probe can pass
        where B=64 / T=2048 is refused. Abstract shapes — nothing is
        allocated. Raises with the compiler's message on failure."""
        from ..ops.pallas.flash_prefill import flash_ragged_prefill
        from ..ops.pallas.flash_prefill_hist import flash_prefill_history
        from ..ops.pallas.kv_write import kv_write
        from ..ops.pallas.paged_decode import pallas_paged_decode

        cfg = self.model_config
        sc = self.config.scheduler
        nh, nkv, hd = cfg.num_heads // tp, cfg.num_kv_heads // tp, cfg.head_dim
        ps = self.config.cache.page_size
        pps = cdiv(self.config.effective_max_len, ps)
        B, T = sc.decode_buckets[-1], sc.prefill_buckets[-1]
        scale = hd ** -0.5

        def arr(shape, dtype=cfg.jnp_dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        i32 = jnp.int32
        # Stacked [L, P, ps, kd] pool with a dynamic layer index — the
        # variant serving runs; P does not shape the kernel.
        pool = arr((2, 2, ps, cfg.kv_row_padded // tp),
                   kv_cache_dtype(cfg, self.config.cache))
        compiled = []

        def probe(name, fn, *args):
            try:
                jax.jit(fn).lower(*args).compile()
            except Exception as e:
                raise RuntimeError(
                    f"Pallas kernel {name} failed to compile at the served "
                    f"geometry (heads {nh}q/{nkv}kv x {hd}, page_size {ps}, "
                    f"pages/seq {pps}, B={B}, T={T}): {e}") from e
            compiled.append(name)

        if self._grouped_experts:
            # The grouped expert matmuls of the largest step (a full prefill
            # bucket beside a full decode bucket), over the whole stack's
            # groups and in the rows models.llama.experts_grouped lays out.
            from ..ops.pallas.grouped_matmul import (grouped_matmul,
                                                     padded_rows)
            held = cfg.num_local_experts
            rows = padded_rows((T + B) * cfg.num_experts_per_tok, held)
            d, ff = cfg.hidden_size, cfg.expert_width
            # One stack's groups a call: a typed model's expert layers lie
            # in two (its attention layers', its state layers').
            for stack, n_layers in model_lib.layer_stacks(cfg).items():
                if stack.startswith("dense_"):
                    continue
                groups = n_layers * held
                for name, k, n in (("up", d, ff), ("down", ff, d)):
                    probe(f"grouped_matmul[{stack}.{name}]", grouped_matmul,
                          arr((rows, k)), arr((groups, k, n)),
                          arr((groups,), i32))
        if cfg.has_state:
            # The state update of a full decode bucket, in place in a pool
            # of the served slot shape (its depth and slot count shape no
            # block).
            f32, (N, di) = jnp.float32, cfg.state_shape
            state_pool = arr((cfg.num_state_layers, 2) + cfg.state_shape, f32)
            if cfg.state_kind == "kda":
                from ..ops.pallas.kda_chunk import kda_chunk
                from ..ops.pallas.kda_update import kda_update
                H, hd = cfg.kda_n_heads, cfg.kda_head_dim
                probe("kda_update", kda_update, state_pool, arr((), i32),
                      arr((B,), i32), arr((B, H, hd), f32), arr((B, H), f32),
                      *(arr((B, H, hd), f32),) * 3)
                # The chunked form over a full prefill bucket.
                probe("kda_chunk",
                      lambda *a: kda_chunk(*a, -2, cfg.kda_chunk_size),
                      *(arr((T, H, hd), f32),) * 4, arr((T, H), f32),
                      arr((T,), i32), arr((1,), i32), arr((N, di), f32))
            else:
                from ..ops.pallas.ssm_chunk import ssm_chunk
                from ..ops.pallas.ssm_update import ssm_update
                probe("ssm_update", ssm_update, state_pool, arr((), i32),
                      arr((B,), i32), arr((B, di), f32), arr((B, di), f32),
                      arr((B, N), f32), arr((B, N), f32))
                # The chunked scan over a full prefill bucket.
                H, P = cfg.mamba_n_heads, cfg.mamba_d_head
                probe("ssm_chunk",
                      lambda *a: ssm_chunk(*a, -2, cfg.mamba_chunk_size),
                      arr((T, H, P)), arr((T, H), f32), arr((T, H), f32),
                      arr((T, N)), arr((T, N)), arr((T,), i32),
                      arr((1,), i32), arr((N, di), f32))
            # The conv stage of a full prefill bucket, read out of the
            # widest mixed step's projection.
            from ..ops.pallas.conv_segments import conv_segments
            K1, C = cfg.state_conv_shape
            split = model_lib.state_conv_split(cfg)
            probe("conv_segments",
                  lambda *a: conv_segments(*a, split),
                  arr((T + B, C)), arr((T,), i32), arr((K1, C)),
                  arr((K1 + 1, C)), arr((C,)))
        if cfg.hc_mult > 1:
            # The stream mixers over a full decode bucket and over the
            # widest mixed step.
            from ..ops import hyper_conn
            from ..ops.pallas.hc_mix import hc_post, hc_pre
            hc, n, d = hyper_conn.settings(cfg), cfg.hc_mult, cfg.hidden_size
            f32 = jnp.float32
            for rows in (B, T + B):
                probe(f"hc_pre[T={rows}]",
                      lambda x, phi, a, b: hc_pre(x, phi, a, b, hc),
                      arr((rows, n * d)), arr((n * d, hyper_conn.COLS)),
                      arr((3,), f32), arr((hyper_conn.COLS,), f32))
                probe(f"hc_post[T={rows}]", hc_post, arr((rows, n * d)),
                      arr((rows, d)), arr((rows, hyper_conn.COLS), f32))
        if cfg.is_mla:
            self._probe_latent_kernels(probe, arr, pool, B, T, pps)
            logger.info("Pallas kernels compiled at the served geometry: %s",
                        ", ".join(compiled))
            return
        blk = {"block": cfg.block_length} if cfg.block_length > 1 else {}
        if blk:
            # A block model's rows run ``block_attend`` (two blocks of
            # query positions a row), never the one-token decode kernel.
            from ..ops.pallas.block_attend import block_attend
            S = cfg.row_width
            probe("block_attend",
                  lambda q, k, v, kp, vp, tb, ctx, lyr, wide: block_attend(
                      q, k, v, kp, vp, tb, ctx, scale, layer=lyr,
                      block=cfg.block_length, wide=wide),
                  arr((B * S, nh, hd)), arr((B * S, nkv, hd)),
                  arr((B * S, nkv, hd)), pool, pool, arr((B, pps), i32),
                  arr((B,), i32), arr((1,), i32), arr((B,), i32))
        else:
            probe("paged_decode",
                  lambda q, kp, vp, tb, ctx, kc, vc, lyr:
                  pallas_paged_decode(q, kp, vp, tb, ctx, kc, vc, scale,
                                      layer=lyr),
                  arr((B, nh, hd)), pool, pool, arr((B, pps), i32),
                  arr((B,), i32), arr((B, nkv, hd)), arr((B, nkv, hd)),
                  arr((1,), i32))
        if self.sp_size == 1:   # ring attention replaces it under sp
            probe("flash_prefill",
                  lambda q, k, v, seg, pos: flash_ragged_prefill(
                      q, k, v, seg, pos, scale, **blk),
                  arr((T, nh, hd)), arr((T, nkv, hd)), arr((T, nkv, hd)),
                  arr((T,), i32), arr((T,), i32))
        if self._hist_kernel_eligible():
            probe("flash_prefill_hist",
                  lambda q, k, v, seg, pos, kp, vp, pt, hl, lyr:
                  flash_prefill_history(q, k, v, seg, pos, kp, vp, pt, hl,
                                        scale, layer=lyr, **blk),
                  arr((T, nh, hd)), arr((T, nkv, hd)), arr((T, nkv, hd)),
                  arr((T,), i32), arr((T,), i32), pool, pool,
                  arr((pps,), i32), arr((), i32), arr((), i32))
        # The post-scan KV write, at the pool's real depth (L shapes its
        # VMEM blocks) for the largest decode and prefill flushes.
        layers = cfg.num_kv_layers // self.pp_size
        deep_pool = arr((layers, 2, ps, nkv * hd), pool.dtype)
        for n in (B * cfg.row_width, T):
            rows = arr((layers, n, nkv * hd))
            probe(f"kv_write[T={n}]", kv_write, deep_pool, deep_pool,
                  rows, rows, arr((n,), i32))
        logger.info("Pallas kernels compiled at the served geometry: %s",
                    ", ".join(compiled))

    def _probe_latent_kernels(self, probe, arr, pool, B, T, pps) -> None:
        """The kernels a latent-attention model runs, at its geometry: the
        shared-row decode and chunk-with-history kernels over the one pool,
        the materialised prefill (192-wide q/k against 128-wide v), and the
        one-pool page write at the pool's real depth."""
        from ..ops.pallas.flash_prefill import flash_ragged_prefill
        from ..ops.pallas.flash_prefill_hist import (
            flash_prefill_history_shared)
        from ..ops.pallas.kv_write import kv_write
        from ..ops.pallas.latent_decode import latent_paged_decode

        cfg = self.model_config
        nh, R, i32 = cfg.num_heads, cfg.kv_row_padded, jnp.int32
        scale = cfg.attn_scale
        if cfg.index_topk:
            # A sparse-attention model attends over chosen rows in XLA
            # (ops/dsa.py): of the latent kernels it runs the materialised
            # prefill of packed prompts and the page writes, its index-key
            # pool's among them.
            self._probe_page_writes(probe, arr, pool, B, T)
            return
        probe("latent_paged_decode",
              lambda q, kp, tb, ctx, cur, lyr: latent_paged_decode(
                  q, kp, tb, ctx, cur, scale, layer=lyr),
              arr((B, nh, R)), pool, arr((B, pps), i32), arr((B,), i32),
              arr((B, 1, R)), arr((1,), i32))
        probe("flash_prefill",
              lambda q, k, v, seg, pos: flash_ragged_prefill(
                  q, k, v, seg, pos, scale),
              arr((T, nh, cfg.head_dim)), arr((T, nh, cfg.head_dim)),
              arr((T, nh, cfg.v_head_dim)), arr((T,), i32), arr((T,), i32))
        probe("latent_prefill_hist",
              lambda q, rows, seg, pos, kp, pt, hl, lyr:
              flash_prefill_history_shared(q, rows, seg, pos, kp, pt, hl,
                                           scale, layer=lyr),
              arr((T, nh, R)), arr((T, 1, R)), arr((T,), i32),
              arr((T,), i32), pool, arr((pps,), i32), arr((), i32),
              arr((), i32))
        self._probe_page_writes(probe, arr, pool, B, T)

    def _probe_page_writes(self, probe, arr, pool, B, T) -> None:
        """A latent model's materialised prefill and its one-pool page
        writes at the pools' real depths: the latent pool's, and a
        sparse-attention model's index-key pool's."""
        from ..ops.pallas.flash_prefill import flash_ragged_prefill
        from ..ops.pallas.kv_write import kv_write

        cfg = self.model_config
        nh, i32 = cfg.num_heads, jnp.int32
        if cfg.index_topk:
            probe("flash_prefill",
                  lambda q, k, v, seg, pos: flash_ragged_prefill(
                      q, k, v, seg, pos, cfg.attn_scale),
                  arr((T, nh, cfg.head_dim)), arr((T, nh, cfg.head_dim)),
                  arr((T, nh, cfg.v_head_dim)), arr((T,), i32),
                  arr((T,), i32))
        for name, L, kd in (
                ("kv_write", cfg.num_kv_layers, cfg.kv_row_padded),
                ("index_kv_write", len(cfg.index_layers),
                 cfg.index_head_dim)):
            for n in (B, T) if L else ():
                probe(f"{name}[T={n}]",
                      lambda p, rows, slots: kv_write(p, None, rows, None,
                                                      slots),
                      arr((L, 2, pool.shape[2], kd), pool.dtype),
                      arr((L, n, kd)), arr((n,), i32))

    @property
    def _grouped_experts(self) -> bool:
        """Whether the step programs may run their experts by grouped
        dispatch (``Kernels.grouped_experts``): an expert model
        whose expert tensors lie whole on ONE device and are not quantized.
        Under any mesh (GSPMD shards them P(None, 'ep', None, 'tp'); the pp
        shard_map slices them) the grouped kernel, a custom call with no
        partitioning rule, would be handed an all-gathered stack: those keep
        dense dispatch. The one test for the kernel's start-up probe AND its
        use, so that what was not probed is not run."""
        return (self.model_config.is_moe and self.mesh is None
                and self.model_config.quantization is None)

    @property
    def _reports_expert_load(self) -> bool:
        """Prefill, chunk and mixed steps of an expert model on one device
        return the real routed pairs of each expert of each layer beside
        their tokens (a step of thousands of tokens says how even the
        routing is; a decode window's few hundred pairs say little and its
        scan would have to carry the count)."""
        return self.model_config.is_moe and self.mesh is None

    # -- jitted step programs ----------------------------------------------

    def _maybe_jit(self, fn, donate_argnums=()):
        """jit unless ``enforce_eager`` (parity with vllm --enforce-eager):
        eager mode runs the step op-by-op — no compile cache, no donation —
        for debugging numerics/shape issues. Always slower."""
        if self.config.enforce_eager:
            return fn
        return jax.jit(fn, donate_argnums=donate_argnums)

    def _build_prefill_fn(self):
        """Inputs arrive as TWO packed buffers (one int, one float) — each
        host->device upload is a round trip on remote-attached TPUs, so the
        step interface is packed tight: int_t [4, T] (tokens, seg_ids,
        positions, slot_mapping), int_b [B, 5] (logits_indices, top_k, seed,
        prompt_len, top_n), float_b [B, 4] (temperature, top_p, presence,
        frequency).

        Under a pp mesh the same interface runs the circular pipeline of
        parallel/pp.py instead of the flat forward — the scheduler/step loop
        is oblivious to pp."""
        cfg = self.model_config
        kernels = self.kernels

        if self.pp_size > 1:
            from ..parallel.pp import build_pp_mapped, pp_logits
            mapped = build_pp_mapped(self.mesh, cfg, "prefill", kernels)

            def fwd(params, kv, int_t, logits_indices, moe_load=None):
                # The whole ragged prefill batch rides the pipeline as ONE
                # microbatch (M=1): the scheduler packs sequences into a
                # single flat [T] buffer, and splitting it would let a
                # sequence straddle microbatches, breaking in-batch
                # attention. S-1 bubble ticks per prefill is the cost;
                # decode — the steady state — microbatches properly.
                meta_mb = StepMeta(
                    seg_ids=int_t[1][None], positions=int_t[2][None],
                    slot_mapping=int_t[3][None],
                    logits_indices=logits_indices[None])
                hidden_mb, kvk, kvv = mapped(params, kv.k, kv.v,
                                             int_t[0][None], meta_mb)
                return (pp_logits(params, cfg, hidden_mb[0], logits_indices),
                        KVCache(k=kvk, v=kvv))
        else:
            def fwd(params, kv, int_t, logits_indices, moe_load=None,
                    seg_slots=None):
                meta = StepMeta(seg_ids=int_t[1], positions=int_t[2],
                                slot_mapping=int_t[3],
                                logits_indices=logits_indices,
                                seg_slots=seg_slots)
                hidden, kv, _ = model_lib.forward(
                    params, cfg, int_t[0], meta, kv, kernels,
                    moe_load=moe_load)
                return model_lib.compute_logits(params, cfg, hidden,
                                                 kernels), kv

        reports_load = self._reports_expert_load
        last_width = self._last_width

        def prefill_step(params, kv: KVCache, int_t, int_b, float_b,
                         bias_ids, bias_vals, key):
            # int_b: [B, 5] = (logits_indices, top_k, seed, prompt_len,
            # top_n); a state model's has a 6th column, each segment's slot.
            load = [] if reports_load else None
            logits, kv = fwd(params, kv, int_t, int_b[:, 0], load,
                             **_slot_columns(cfg, int_b, "seg_slots"))
            logits = _maybe_bias(logits, bias_ids, bias_vals)
            logits = _prefill_penalties(cfg, logits, int_t, int_b[:, 3],
                                        float_b[:, 2], float_b[:, 3])
            pos_next = jnp.take(int_t[2], int_b[:, 0]) + 1
            keys = row_sample_keys(key, int_b[:, 2], pos_next)
            next_tokens, lps, tids, tlps = sample_and_logprobs(
                logits, keys, float_b[:, 0], int_b[:, 1], float_b[:, 1],
                row_keys=True, with_top=jnp.any(int_b[:, 4] > 0))
            return (next_tokens, lps, tids, tlps,
                    _last_tokens(next_tokens, last_width), kv, *(load or ()))

        return self._maybe_jit(prefill_step, donate_argnums=(1,))

    def _build_prefill_hist_fn(self):
        """Chunked-prefill step: one sequence's chunk attending to its pool
        history (``StepMeta.chunk_page_table``/``hist_len``). Extra inputs
        vs prefill: page_table [1, pages_bucket] and hist_len scalar.
        Compiled lazily — engines that never see a long prompt never pay for
        it. pp meshes run the PIPELINED history path
        (parallel/pp._build_pp_hist_mapped): the chunk is microbatched into
        sub-chunks with per-sub-chunk history lengths, keeping the layer
        stack sharded — no all-gather of the pp-sharded params (VERDICT r4
        #6; previously this ran as plain GSPMD and XLA gathered the whole
        stack per chunk)."""
        cfg = self.model_config
        kernels = self.kernels
        if not kernels.use_pallas_hist:
            # Where the history kernel is ineligible (sp meshes, and the
            # pipeline's chunked path below) the WHOLE chunked program runs
            # the XLA references, page write included. No chip has run
            # either regime, so whether their write may take the kernel is
            # open (ROADMAP D5g).
            kernels = kernels.xla_only()

        if self.pp_size > 1:
            from ..parallel.pp import build_pp_mapped, pp_logits
            S = self.pp_size
            mapped = build_pp_mapped(self.mesh, cfg, "prefill_hist", kernels)

            def hist_fwd(params, kv, int_t, int_b, page_table, hist_len,
                         moe_load=None):
                T = int_t.shape[1]
                M = S if T % S == 0 else 1
                sub = T // M
                meta_mb = StepMeta(
                    seg_ids=int_t[1].reshape(M, sub),
                    positions=int_t[2].reshape(M, sub),
                    slot_mapping=int_t[3].reshape(M, sub),
                    logits_indices=jnp.zeros((M,) + int_b[:, 0].shape,
                                             jnp.int32))
                hist_lens = hist_len + jnp.arange(M, dtype=jnp.int32) * sub
                h_mb, kvk, kvv = mapped(params, kv.k, kv.v,
                                        int_t[0].reshape(M, sub), meta_mb,
                                        page_table[0], hist_lens)
                logits = pp_logits(params, cfg, h_mb.reshape(T, -1),
                                   logits_indices=int_b[:, 0])
                return logits, KVCache(k=kvk, v=kvv)
        else:
            def hist_fwd(params, kv, int_t, int_b, page_table, hist_len,
                         moe_load=None):
                meta = StepMeta(seg_ids=int_t[1], positions=int_t[2],
                                slot_mapping=int_t[3],
                                logits_indices=int_b[:, 0],
                                chunk_page_table=page_table[0],
                                hist_len=hist_len,
                                **_slot_columns(cfg, int_b, "seg_slots",
                                                chunk=True))
                hidden, kv, _ = model_lib.forward(
                    params, cfg, int_t[0], meta, kv, kernels,
                    moe_load=moe_load)
                return model_lib.compute_logits(params, cfg, hidden,
                                                 kernels), kv

        reports_load = self._reports_expert_load
        last_width = self._last_width

        def prefill_hist_step(params, kv: KVCache, int_t, int_b, float_b,
                              page_table, hist_len, out_tokens,
                              bias_ids, bias_vals, key):
            load = [] if reports_load else None
            logits, kv = hist_fwd(params, kv, int_t, int_b, page_table,
                                  hist_len, load)
            logits = _maybe_bias(logits, bias_ids, bias_vals)
            # EXACT penalties on the chunked path: earlier chunks' token ids
            # live in the pool as vectors, not ids, so the histogram comes
            # from a HOST resync (out_tokens [B, cap], -1-padded — the host
            # always knows the full output history) instead of the in-batch
            # count the non-chunked program uses. Gated: penalty-free
            # batches upload a cached dummy and skip the scatter.
            presence, frequency = float_b[:, 2], float_b[:, 3]
            logits = jax.lax.cond(
                jnp.any((presence != 0.0) | (frequency != 0.0)),
                lambda l: apply_penalties(
                    l, build_counts(out_tokens, cfg.vocab_size),
                    presence, frequency),
                lambda l: l, logits)
            pos_next = jnp.take(int_t[2], int_b[:, 0]) + 1
            keys = row_sample_keys(key, int_b[:, 2], pos_next)
            next_tokens, lps, tids, tlps = sample_and_logprobs(
                logits, keys, float_b[:, 0], int_b[:, 1], float_b[:, 1],
                row_keys=True, with_top=jnp.any(int_b[:, 4] > 0))
            return (next_tokens, lps, tids, tlps,
                    _last_tokens(next_tokens, last_width), kv, *(load or ()))

        return self._maybe_jit(prefill_hist_step, donate_argnums=(1,))

    def _build_mixed_fn(self):
        """Mixed prefill/decode step (stall-free batching): ONE program
        runs a budgeted chunk of the queue-head prompt AND every running
        sequence's decode token. Compiled per (prefill bucket, row bucket,
        history width) — the same bounded bucket grid as the pure paths
        (tests/test_compile_guard.py pins the bound). Penalties use the
        host-resync histogram (out_tokens) like the chunked path: a mixed
        step rides behind an unfetched step only when no penalised row has
        tokens in flight (_chain_break). Sampling rows cover the decode rows plus the chunk's last
        token; the engine discards the chunk row's sample when the chunk is
        partial (KV committed, prompt unfinished)."""
        cfg = self.model_config
        kernels = self.kernels
        reports_load = self._reports_expert_load
        last_width = self._last_width

        def mixed_step(params, kv: KVCache, prev, int_t, int_b, float_b,
                       chunk_page_table, hist_len, page_tables, context_lens,
                       out_tokens, bias_ids, bias_vals, key):
            # int_t: [4, Tp_bucket + R_pad]; int_b: [R_pad, 5..] =
            # (logits_indices, top_k, seed, prompt_len, top_n[, slots],
            # tok_src). prev: [last_width], the last-token output of the
            # step dispatched before this one (zeros when there is none):
            # the decode rows whose newest token the host has not fetched
            # take it from there.
            Tp = int_t.shape[1] - int_b.shape[0]
            tokens = int_t[0].at[Tp:].set(
                _chained_tokens(int_t[0, Tp:], prev, int_b[:, -1]))
            meta = StepMeta(
                seg_ids=int_t[1], positions=int_t[2], slot_mapping=int_t[3],
                logits_indices=int_b[:, 0],
                chunk_page_table=chunk_page_table[0], hist_len=hist_len,
                page_tables=page_tables, context_lens=context_lens,
                **_slot_columns(cfg, int_b, "seg_slots", "row_slots",
                                chunk=True))
            load = [] if reports_load else None
            hidden, kv, _ = model_lib.forward(
                params, cfg, tokens, meta, kv, kernels, moe_load=load)
            logits = model_lib.compute_logits(params, cfg, hidden, kernels)
            logits = _maybe_bias(logits, bias_ids, bias_vals)
            presence, frequency = float_b[:, 2], float_b[:, 3]
            logits = jax.lax.cond(
                jnp.any((presence != 0.0) | (frequency != 0.0)),
                lambda l: apply_penalties(
                    l, build_counts(out_tokens, cfg.vocab_size),
                    presence, frequency),
                lambda l: l, logits)
            pos_next = jnp.take(int_t[2], int_b[:, 0]) + 1
            keys = row_sample_keys(key, int_b[:, 2], pos_next)
            next_tokens, lps, tids, tlps = sample_and_logprobs(
                logits, keys, float_b[:, 0], int_b[:, 1], float_b[:, 1],
                row_keys=True, with_top=jnp.any(int_b[:, 4] > 0))
            return (next_tokens, lps, tids, tlps,
                    _last_tokens(next_tokens, last_width), kv, *(load or ()))

        return self._maybe_jit(mixed_step, donate_argnums=(1,))

    def _build_spec_verify_fn(self):
        """Speculative-verification step: ONE program runs every running
        sequence's [last token, k drafts] slice (a row part S tokens wide) —
        history attention against the paged pool, an S x S causal block per
        row, multi-token KV append — and applies the lossless accept/
        resample rule over the per-position logits
        (ops.sampling.spec_verify_sample). Compiled per decode-bucketed row
        count at token width R_pad * S; S = k + 1 is config-static, so the
        variant family stays inside the bounded bucket grid
        (tests/test_compile_guard.py pins it). Penalties use the
        host-resynced histogram (out_tokens) like the chunked/mixed paths —
        spec steps are synchronous, so the host always knows the full
        output history — and the verifier advances the counts with each
        accepted token, matching the decode window's per-substep bump."""
        cfg = self.model_config
        kernels = self.kernels
        V = cfg.vocab_size

        def spec_step(params, kv: KVCache, int_t, int_b, float_b,
                      page_tables, context_lens, out_tokens,
                      bias_ids, bias_vals, key):
            # int_t: [4, R_pad*S]; int_b: [R_pad, 3] = (top_k, seed, top_n).
            R_pad = page_tables.shape[0]
            S = int_t.shape[1] // R_pad
            # No segment part: int_t[1] (row ids) is the sanitizer's.
            meta = StepMeta(positions=int_t[2], slot_mapping=int_t[3],
                            page_tables=page_tables,
                            context_lens=context_lens)
            hidden, kv, _ = model_lib.forward(
                params, cfg, int_t[0], meta, kv, kernels, row_width=S)
            # Verification needs logits over EVERY draft position, so the
            # vocab projection runs on all R_pad*S rows (the one place the
            # engine pays more than B logit rows; amortized by acceptance).
            logits = model_lib.compute_logits(params, cfg, hidden, kernels)
            logits = _maybe_bias(logits, jnp.repeat(bias_ids, S, axis=0),
                                 jnp.repeat(bias_vals, S, axis=0))
            logits = logits.reshape(R_pad, S, V)
            drafts = int_t[0].reshape(R_pad, S)[:, 1:]
            presence, frequency = float_b[:, 2], float_b[:, 3]
            counts = jax.lax.cond(
                jnp.any((presence != 0.0) | (frequency != 0.0)),
                lambda ot: build_counts(ot, V),
                lambda ot: jnp.zeros((R_pad, V), jnp.int32), out_tokens)
            toks, n_acc, lps, tids, tlps = spec_verify_sample(
                logits, drafts, context_lens, key, int_b[:, 1],
                float_b[:, 0], int_b[:, 0], float_b[:, 1],
                presence, frequency, counts,
                with_top=jnp.any(int_b[:, 2] > 0))
            return toks, n_acc, lps, tids, tlps, kv

        return self._maybe_jit(spec_step, donate_argnums=(1,))

    def _build_spec_mixed_fn(self):
        """Spec×mixed step: ONE program runs a
        budgeted chunk of the queue-head prompt AND every running
        sequence's verify slice. The verify half follows the spec program
        exactly (lossless accept/resample over all draft positions, counts
        advanced per accepted token); the chunk half follows the mixed
        program exactly (history attention, host-resync penalties, one
        sampled row riding device row R_pad). ``S = k + 1`` is a jit
        STATIC argument — each ladder rung compiles its own (prefill
        bucket, row bucket, history width) family, bounded like every
        other grid (tests/test_compile_guard.py)."""
        cfg = self.model_config
        kernels = self.kernels
        V = cfg.vocab_size

        def spec_mixed_step(params, kv: KVCache, S, int_t, logits_idx,
                            int_b, float_b, chunk_page_table, hist_len,
                            page_tables, context_lens, out_tokens,
                            bias_ids, bias_vals, key):
            # int_t: [4, Tp + R_pad*S]; int_b: [R_pad+1, 3] =
            # (top_k, seed, top_n); logits_idx: [R_pad*S + 1].
            R_pad = page_tables.shape[0]
            # The verify slices' entries of int_t[1] carry row ids for the
            # sanitizer's slot map; the forward reads the chunk's only.
            meta = StepMeta(
                seg_ids=int_t[1], positions=int_t[2], slot_mapping=int_t[3],
                logits_indices=logits_idx,
                chunk_page_table=chunk_page_table[0], hist_len=hist_len,
                page_tables=page_tables, context_lens=context_lens)
            hidden, kv, _ = model_lib.forward(
                params, cfg, int_t[0], meta, kv, kernels, row_width=S)
            logits = model_lib.compute_logits(params, cfg, hidden, kernels)
            logits = _maybe_bias(
                logits,
                jnp.concatenate([jnp.repeat(bias_ids[:R_pad], S, axis=0),
                                 bias_ids[R_pad:R_pad + 1]], axis=0),
                jnp.concatenate([jnp.repeat(bias_vals[:R_pad], S, axis=0),
                                 bias_vals[R_pad:R_pad + 1]], axis=0))
            spec_logits = logits[:R_pad * S].reshape(R_pad, S, V)
            Tp = int_t.shape[1] - R_pad * S
            drafts = int_t[0][Tp:].reshape(R_pad, S)[:, 1:]
            presence_s, frequency_s = float_b[:R_pad, 2], float_b[:R_pad, 3]
            counts = jax.lax.cond(
                jnp.any((presence_s != 0.0) | (frequency_s != 0.0)),
                lambda ot: build_counts(ot, V),
                lambda ot: jnp.zeros((R_pad, V), jnp.int32),
                out_tokens[:R_pad])
            any_top = jnp.any(int_b[:, 2] > 0)
            toks_s, n_acc, lps_s, tids_s, tlps_s = spec_verify_sample(
                spec_logits, drafts, context_lens, key, int_b[:R_pad, 1],
                float_b[:R_pad, 0], int_b[:R_pad, 0], float_b[:R_pad, 1],
                presence_s, frequency_s, counts, with_top=any_top)
            # Chunk row: the mixed path's single sampled row, on the
            # chunk's last-token logits.
            cl = logits[R_pad * S:]
            presence_c, frequency_c = (float_b[R_pad:, 2],
                                       float_b[R_pad:, 3])
            cl = jax.lax.cond(
                jnp.any((presence_c != 0.0) | (frequency_c != 0.0)),
                lambda l: apply_penalties(
                    l, build_counts(out_tokens[R_pad:], V),
                    presence_c, frequency_c),
                lambda l: l, cl)
            pos_next = jnp.take(int_t[2], logits_idx[R_pad * S:]) + 1
            keys_c = row_sample_keys(key, int_b[R_pad:, 1], pos_next)
            tok_c, lp_c, tid_c, tlp_c = sample_and_logprobs(
                cl, keys_c, float_b[R_pad:, 0], int_b[R_pad:, 0],
                float_b[R_pad:, 1], row_keys=True, with_top=any_top)
            # Assemble [R_pad+1, ...]: the chunk's one token rides column 0
            # of its row; columns past it are padding the host never reads
            # (its emit count is pinned to 1).
            pad_cols = ((0, 0), (0, S - 1))
            toks = jnp.concatenate(
                [toks_s, jnp.pad(tok_c[:, None], pad_cols)], axis=0)
            lps = jnp.concatenate(
                [lps_s, jnp.pad(lp_c[:, None], pad_cols)], axis=0)
            tids = jnp.concatenate(
                [tids_s, jnp.pad(tid_c[:, None], pad_cols + ((0, 0),))],
                axis=0)
            tlps = jnp.concatenate(
                [tlps_s, jnp.pad(tlp_c[:, None], pad_cols + ((0, 0),))],
                axis=0)
            return toks, n_acc, lps, tids, tlps, kv

        if self.config.enforce_eager:
            return spec_mixed_step
        return jax.jit(spec_mixed_step, static_argnums=(2,),
                       donate_argnums=(1,))

    def _build_decode_fn(self, greedy: bool = False):
        """Multi-step decode: W autoregressive steps inside one XLA program.
        Sampled tokens feed back on-device through a lax.scan; per-sub-step
        positions/slots/context-lens are recomputed from the page tables, so
        only one host->device upload and one [B, W] download happen per
        window. This is what keeps continuous batching fast when the host
        round-trip is the bottleneck (and it always is: TPU decode steps are
        ~ms, host syncs are not free anywhere).

        ``greedy=True`` compiles the argmax-only variant (see __init__)."""
        cfg = self.model_config
        kernels = self.kernels
        W = self.config.scheduler.decode_window
        ps = self.config.cache.page_size
        max_len = self.config.effective_max_len

        if self.pp_size > 1:
            from ..parallel.pp import build_pp_mapped, pp_logits
            S = self.pp_size
            mapped = build_pp_mapped(self.mesh, cfg, "decode", kernels)

            def fwd(params, kv, tokens, meta):
                # Split the batch into M microbatches (M = pp when the padded
                # batch divides evenly, else 1 — shapes are static per
                # bucket, so M resolves at trace time); each substep runs the
                # M+S-1-tick circular pipeline, and sampling happens outside
                # the shard_map on the reassembled [B] hidden states.
                B = tokens.shape[0]
                M = S if B % S == 0 else 1
                meta_mb = StepMeta(
                    positions=meta.positions.reshape(M, B // M),
                    slot_mapping=meta.slot_mapping.reshape(M, B // M),
                    page_tables=meta.page_tables.reshape(M, B // M, -1),
                    context_lens=meta.context_lens.reshape(M, B // M))
                hidden_mb, kvk, kvv = mapped(params, kv.k, kv.v,
                                             tokens.reshape(M, B // M),
                                             meta_mb)
                return (pp_logits(params, cfg, hidden_mb.reshape(B, -1)),
                        KVCache(k=kvk, v=kvv))
        else:
            def fwd(params, kv, tokens, meta):
                hidden, kv, _ = model_lib.forward(params, cfg, tokens, meta,
                                                  kv, kernels)
                return model_lib.compute_logits(params, cfg, hidden,
                                                 kernels), kv

        V = cfg.vocab_size
        last_width = self._last_width

        def window_tables(int_b):
            # A state model's int_b ends with one more column: each row's
            # state slot, which every substep of the window updates.
            if cfg.has_state:
                return int_b[:, 6:-1], int_b[:, -1]
            return int_b[:, 6:], None

        def substep_meta(page_tables, pos, row_slots=None):
            # Window substeps past the model length cap produce tokens the
            # host discards — but their KV writes still happen on device.
            # Route them to the scrap page (page 0) instead of clamping
            # into the sequence's real pages, where the write would wrap
            # (pos % ps) and overwrite earlier KV.
            pos_c = jnp.minimum(pos, max_len - 1)
            page_idx = pos_c // ps
            page = jnp.take_along_axis(page_tables, page_idx[:, None],
                                       axis=1)[:, 0]
            in_range = pos < max_len
            slot = jnp.where(in_range, page * ps + pos_c % ps, pos % ps)
            return StepMeta(positions=pos_c, slot_mapping=slot,
                            page_tables=page_tables, context_lens=pos_c + 1,
                            row_slots=row_slots)

        def decode_window_greedy(params, kv: KVCache, prev, int_b,
                                 float_b, key):
            # prev: [last_width], the last-token output of the step
            # dispatched before this one, still on the device: a row whose
            # newest token the host has not fetched takes it from there
            # (tok_src), with no host round trip.
            # int_b: [B, pps+6] = (positions, top_k, seed, top_n, token,
            # tok_src, page_table...[, state slot]), float_b: [B, 4] =
            # (temperature, top_p,
            # presence, frequency). Slots/context lens are recomputed per
            # sub-step from positions + page tables. The greedy program
            # ignores the sampling columns — it is only dispatched for
            # all-greedy, penalty-free, bias-free batches.
            tokens0 = _chained_tokens(int_b[:, 4], prev, int_b[:, 5])
            positions0 = int_b[:, 0]
            any_top = jnp.any(int_b[:, 3] > 0)
            page_tables, row_slots = window_tables(int_b)

            def substep(carry, i):
                kv, tokens, pos = carry
                logits, kv = fwd(params, kv, tokens,
                                 substep_meta(page_tables, pos, row_slots))
                next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                lps = token_logprobs(logits, next_tokens)
                tids, tlps = gated_top_logprobs(logits, any_top)
                return ((kv, next_tokens, pos + 1),
                        (next_tokens, lps, tids, tlps))

            (kv, _, _), (toks, lps, tids, tlps) = jax.lax.scan(
                substep, (kv, tokens0, positions0), jnp.arange(W))
            # [B, W] / [B, W, K]
            return (toks.T, lps.T, tids.transpose(1, 0, 2),
                    tlps.transpose(1, 0, 2),
                    _last_tokens(toks[-1], last_width), kv)

        def decode_window_sampled(params, kv: KVCache, prev, int_b,
                                  float_b, key, counts, out_tokens, rebuild,
                                  bias_ids, bias_vals):
            # Sampled variant adds per-request seed + presence/frequency
            # penalties (vLLM semantics: over generated tokens only). counts
            # [B, V] i32 is the device-resident output-token histogram: it
            # is REBUILT from host-known output ids (out_tokens, -1-padded)
            # when the batch composition changed, and CARRIED (donated
            # through the chain) across speculatively chained windows — so
            # penalties see the in-flight window's tokens the host hasn't
            # downloaded yet.
            tokens0 = _chained_tokens(int_b[:, 4], prev, int_b[:, 5])
            positions0 = int_b[:, 0]
            top_k = int_b[:, 1]
            seed = int_b[:, 2]
            any_top = jnp.any(int_b[:, 3] > 0)
            page_tables, row_slots = window_tables(int_b)
            temperature = float_b[:, 0]
            top_p = float_b[:, 1]
            presence = float_b[:, 2]
            frequency = float_b[:, 3]
            any_pen = jnp.any((presence != 0.0) | (frequency != 0.0))
            counts = jax.lax.cond(
                rebuild, lambda c: build_counts(out_tokens, V),
                lambda c: c, counts)

            def substep(carry, i):
                kv, counts, tokens, pos = carry
                logits, kv = fwd(params, kv, tokens,
                                 substep_meta(page_tables, pos, row_slots))
                logits = _maybe_bias(logits, bias_ids, bias_vals)
                logits = jax.lax.cond(
                    any_pen,
                    lambda l: apply_penalties(l, counts, presence, frequency),
                    lambda l: l, logits)
                keys = row_sample_keys(key, seed, pos + 1)
                next_tokens, lps, tids, tlps = sample_and_logprobs(
                    logits, keys, temperature, top_k, top_p, row_keys=True,
                    with_top=any_top)
                counts = jax.lax.cond(
                    any_pen, lambda c: bump_counts(c, next_tokens),
                    lambda c: c, counts)
                return ((kv, counts, next_tokens, pos + 1),
                        (next_tokens, lps, tids, tlps))

            (kv, counts, _, _), (toks, lps, tids, tlps) = jax.lax.scan(
                substep, (kv, counts, tokens0, positions0), jnp.arange(W))
            return (toks.T, lps.T, tids.transpose(1, 0, 2),
                    tlps.transpose(1, 0, 2),
                    _last_tokens(toks[-1], last_width), kv, counts)

        if greedy:
            return self._maybe_jit(decode_window_greedy, donate_argnums=(1,))
        # counts (arg 6) rides the chain donated, like the KV pool.
        return self._maybe_jit(decode_window_sampled, donate_argnums=(1, 6))

    # -- public API ---------------------------------------------------------

    def add_request(self, request_id: str, prompt_token_ids: list[int],
                    params: Optional[SamplingParams] = None,
                    hold_kv: bool = False,
                    arrival_t0: Optional[float] = None,
                    resume_outputs: Optional[list[int]] = None) -> None:
        """``hold_kv``: disaggregated-prefill mode — when the request
        finishes (normally with max_tokens=1 on a prefill replica), its
        committed KV pages are HELD for :meth:`export_held` instead of
        released; the caller owns the export-or-discard.

        ``arrival_t0``: backdated ``time.monotonic()`` arrival stamp — a
        decode replica whose handoff pull failed admits the request only
        AFTER the pull burned its wall time, and that wait is part of the
        client-observed TTFT/queue-wait span the SLO gauges exist to
        catch.

        ``resume_outputs``: token-replay resume (mid-stream failover with
        no migrated KV): the tokens a dead replica already generated are
        pre-seeded as OUTPUT history, so admission replays prompt+outputs
        through the recompute-preemption prefill path (``all_token_ids``)
        and decoding continues from the next position. max_tokens/penalty
        accounting see the replayed tokens as outputs (the prompt/output
        boundary is preserved), and for greedy or seeded sampling the
        continuation is byte-identical to the uninterrupted run (sample
        keys derive from (seed, position), engine-independent). Raises
        ValueError when the replayed history already satisfies a stop
        condition — there is nothing left to generate."""
        params = params or SamplingParams()
        if params.logit_bias:
            # Out-of-vocab ids would be silently dropped by the device
            # scatter — reject with a signal instead (OpenAI/vLLM 400).
            V = self.model_config.vocab_size
            bad = [t for t in params.logit_bias if t >= V]
            if bad:
                raise ValueError(
                    f"logit_bias token ids {bad[:5]} out of range for "
                    f"vocab_size {V}")
        if self.model_config.block_length > 1:
            block_steps.refuse_request(params)
        seq = Sequence(request_id, prompt_token_ids, params,
                       eos_token_id=self.eos_token_id,
                       block_length=self.model_config.block_length)
        seq.hold_kv = hold_kv
        if arrival_t0 is not None:
            seq.arrival_time = min(arrival_t0, seq.arrival_time)
        if resume_outputs:
            for tok in resume_outputs:
                seq.append_token(int(tok))
            if seq.check_stop(self.config.effective_max_len) is not None:
                raise ValueError(
                    f"resume history of {len(resume_outputs)} tokens "
                    "already satisfies a stop condition; nothing to resume")
        self.obs.on_arrival(seq)
        try:
            self.scheduler.add(seq)
        except Exception:
            # Admission rejected (e.g. prompt exceeds the KV pool): close
            # the just-opened trace span or /debug/trace renders this
            # request as running forever.
            self.obs.on_finish(seq, FinishReason.ABORT)
            raise

    def abort_request(self, request_id: str) -> bool:
        # A sequence in the step in flight still has device KV writes
        # pending against its pages: finish it but defer the release of
        # pages and slot until that step is fetched. (A prompt between two
        # chunks is still in ``waiting``.)
        if self._inflight is not None:
            for seq in self._inflight["batch"].seqs:
                if seq.request_id == request_id and not seq.is_finished:
                    seq.status = SequenceStatus.FINISHED
                    seq.finish_reason = FinishReason.ABORT
                    for queue in (self.scheduler.running,
                                  self.scheduler.waiting):
                        if seq in queue:
                            queue.remove(seq)
                    self._inflight["zombies"].add(request_id)
                    self._deferred_release.append(seq)
                    self.stats.requests_finished += 1
                    self.obs.on_finish(seq, FinishReason.ABORT)
                    return True
        if self.scheduler.abort(request_id):
            # Aborted sequences never reach _process_window's finish
            # accounting — count them here or kgct_requests_finished_total
            # drifts from kgct_requests_total.
            self.stats.requests_finished += 1
            return True
        if request_id in self.scheduler.held:
            # A held prefill whose exporter died between finish and export
            # (kv_handoff pull timeout/disconnect): the sequence already
            # counted as finished — only the parked pages remain, and no
            # other abort path scans ``held``, so without this they would
            # leak until the pool drains.
            self.discard_held(request_id)
            return True
        return False

    def has_unfinished_requests(self) -> bool:
        # A step in flight must be fetched even if every sequence finished
        # (what they hold is released then).
        return self.scheduler.has_work() or self._inflight is not None

    # -- disaggregated prefill/decode (KV handoff seam) ----------------------

    def _require_kv_wire(self, what: str) -> None:
        """The KV wire paths (handoff, migration, prefix export/import,
        spill) frame K and V page pairs; a latent-page model has one pool,
        a state model a slot of recurrent state beside its pages: both are
        refused by name, here and at start (cache_kind_refusal)."""
        if self.model_config.is_mla:
            raise ValueError(
                f"{what} with {self.model_config.name}: the KV wire paths "
                "frame K and V page pairs, not latent pages")
        if self.model_config.has_state:
            raise ValueError(
                f"{what} with {self.model_config.name}: the KV wire paths "
                "frame pages of K and V, not a sequence's recurrent state")

    def _export_state(self, seq: Sequence, k_np, v_np) -> dict:
        """The serialized cross-replica sequence state, built from
        COMMITTED quantities only: the sequence's host-known token/logprob
        history and the already-fetched committed-page buffers. Nothing
        from an in-flight window (device-resident sampled tokens, window
        scratch) may enter this dict — the KGCT014 lint rule polices the
        export path statically."""
        return {
            "model": self.model_config.name,
            "page_size": self.config.cache.page_size,
            "dtype": str(self.kv_cache.k.dtype),
            "prompt_token_ids": list(seq.prompt_token_ids),
            "output_token_ids": list(seq.output_token_ids),
            "output_logprobs": list(seq.output_logprobs),
            "output_top_logprobs": [
                [[int(t), float(lp)] for t, lp in top]
                for top in seq.output_top_logprobs],
            "sampling": seq.params.to_state(),
            "k": k_np, "v": v_np,
        }

    def export_held(self, request_id: str) -> dict:
        """Serialize a held finished prefill (``add_request(hold_kv=True)``)
        into one contiguous host-buffer state dict: the sequence's committed
        KV pages (positions [0, num_tokens-1) — the last sampled token's KV
        is written by the decode side's first step, exactly like swap
        restore) plus the generation state a decode replica needs to resume
        byte-identically. Pages are released here; raises KeyError when
        nothing is held under ``request_id`` (capacity-terminated or
        already exported) — the caller degrades to local recompute."""
        self._require_kv_wire("KV export (handoff)")
        seq = self.scheduler.held.pop(request_id, None)
        if seq is None:
            raise KeyError(f"no held KV for request {request_id!r}")
        ps = self.config.cache.page_size
        n = cdiv(seq.num_tokens - 1, ps)
        k_np, v_np = self.kv_io.export_pages(seq.pages[:n])
        # Gather fetched above; only now may the pages return to the pool
        # (KGCT010 ordering).
        self.scheduler.allocator.free(seq.pages)
        seq.pages = []
        return self._export_state(seq, k_np, v_np)

    def export_running(self, request_id: str) -> dict:
        """Live migration: snapshot a RUNNING sequence mid-decode into the
        same wire state :meth:`export_held` produces — committed KV pages
        (positions [0, num_tokens-1); the next decode step on the importing
        side writes the last token's KV, exactly like swap restore) plus
        the full host-known generation and sampling state — and retire it
        locally (FinishReason.MIGRATE: terminal, but no client-facing
        finish — the stream continues on the peer). For greedy and seeded
        sampling the imported continuation is byte-identical to the
        uninterrupted run.

        Safe against the device queue: a sequence in the step in flight
        becomes a ZOMBIE (its already-sampled, not-yet-fetched tokens are
        discarded — the peer regenerates them deterministically) and its
        pages are released only when that step has been fetched, since the
        dispatched program still writes into them.
        The gather itself serializes after the in-flight program on the
        device stream and reads only committed positions' pages, which the
        window never touches below position num_tokens-1.

        Raises KeyError when no RUNNING sequence owns ``request_id`` and
        RuntimeError when nothing is committed yet — the caller degrades
        to the wait-it-out drain path."""
        self._require_kv_wire("KV export (migration)")
        seq = self.scheduler.find_running(request_id)
        if seq is None:
            raise KeyError(f"no running sequence {request_id!r}")
        ps = self.config.cache.page_size
        n = cdiv(seq.num_tokens - 1, ps)
        if n < 1 or n > len(seq.pages) or not seq.output_token_ids:
            raise RuntimeError(
                f"{request_id!r} has no committed KV to migrate")
        k_np, v_np = self.kv_io.export_pages(seq.pages[:n])
        state = self._export_state(seq, k_np, v_np)
        state["mid_stream"] = True
        # Retire locally. Only now (gather fetched) may pages be released
        # (KGCT010); a sequence in the step in flight defers the release
        # to that step's fetch (pending device writes target its pages).
        self.scheduler.running.remove(seq)
        seq.status = SequenceStatus.FINISHED
        seq.finish_reason = FinishReason.MIGRATE
        inflight = self._inflight
        if inflight is not None and seq in inflight["batch"].seqs:
            inflight["zombies"].add(request_id)
            self._deferred_release.append(seq)
        elif seq.pages:
            self.scheduler.allocator.free(seq.pages)
            seq.pages = []
        self.stats.requests_finished += 1
        self.obs.on_finish(seq, FinishReason.MIGRATE)
        self.obs.tracer.emit("migrate", request_id, side="export", pages=n,
                             tokens=len(state["output_token_ids"]))
        return state

    def discard_held(self, request_id: str) -> None:
        """Release a held prefill whose export never happened (client died
        between finish and export). Idempotent."""
        seq = self.scheduler.held.pop(request_id, None)
        if seq is not None:
            self.scheduler._release(seq)

    def import_request(self, request_id: str, prompt_token_ids: list[int],
                       params: SamplingParams, state: dict
                       ) -> list[RequestOutput]:
        """Admit a prefill-replica export as COMMITTED history: allocate
        pages, scatter the transferred KV in (kv_cache.KVPageIO — the
        swap-in path, no prefill replay), and join ``running`` directly so
        the next decode batch carries the sequence as if it prefilled here.
        Returns the RequestOutput carrying the already-generated token(s)
        so the serving layer streams them to the client. Raises on any
        mismatch or capacity shortfall — the caller falls back to local
        recompute (``add_request``), which is byte-identical, just slower."""
        self._require_kv_wire("KV import")
        # Serving-layer stamp of when the decode replica began the handoff
        # (pull start): now - t0 is the replica-observed TTFT — remote
        # prefill + transfer + import — the client-facing span.
        ttft_t0 = state.pop("_ttft_t0", None)
        # Mid-stream migration state (export_running): the client already
        # received its first token on the exporting replica, so no TTFT
        # sample fires here; the serialized sampling snapshot is forensic
        # (the caller derives params from the original request body).
        mid_stream = bool(state.pop("mid_stream", False))
        state.pop("sampling", None)
        ps = self.config.cache.page_size
        if state.get("model") != self.model_config.name:
            raise ValueError(f"handoff model {state.get('model')!r} != "
                             f"{self.model_config.name!r}")
        if state.get("page_size") != ps:
            raise ValueError(f"handoff page_size {state.get('page_size')} "
                             f"!= {ps}")
        if list(state["prompt_token_ids"]) != list(prompt_token_ids):
            raise ValueError("handoff prompt does not match the request")
        # Convert EVERYTHING the post-allocation path consumes up front —
        # malformed state must raise before any pages are allocated, or a
        # hostile/buggy peer could leak device pages per rejected handoff.
        try:
            out_ids = [int(t) for t in state["output_token_ids"]]
            lps = [float(x) for x in (state.get("output_logprobs") or [])]
            tops = [[(int(t), float(p)) for t, p in row]
                    for row in (state.get("output_top_logprobs") or [])]
        except (TypeError, ValueError) as e:
            raise ValueError(f"malformed handoff output state: {e}") from e
        if not out_ids:
            raise ValueError("handoff carries no generated token")
        k_np, v_np = state["k"], state["v"]
        num_tokens = len(prompt_token_ids) + len(out_ids)
        need = cdiv(num_tokens - 1, ps)
        L, _, _, kd = self.kv_cache.k.shape
        if tuple(k_np.shape) != (L, need, ps, kd) or k_np.shape != v_np.shape:
            raise ValueError(f"handoff KV shape {tuple(k_np.shape)} != "
                             f"{(L, need, ps, kd)}")
        if str(k_np.dtype) != str(self.kv_cache.k.dtype):
            raise ValueError(f"handoff KV dtype {k_np.dtype} != "
                             f"{self.kv_cache.k.dtype}")
        sched = self.scheduler
        if len(sched.running) >= sched.max_num_seqs:
            raise RuntimeError("no batch seat for imported sequence")
        if not sched.allocator.can_allocate(need):
            raise RuntimeError(
                f"no KV pages for imported sequence (want {need}, "
                f"free {sched.allocator.num_free})")
        seq = Sequence(request_id, prompt_token_ids, params,
                       eos_token_id=self.eos_token_id)
        pages = sched.allocator.allocate(need)
        try:
            self.kv_io.import_pages(pages, k_np, v_np)
        except Exception:
            sched.allocator.free(pages)
            raise
        seq.pages = pages
        seq.num_prefilled = seq.num_prompt_tokens
        seq.prefix_checked = True
        want_lps = params.logprobs
        want_top = params.top_logprobs
        for j, tok in enumerate(out_ids):
            lp = lps[j] if want_lps and j < len(lps) else None
            top = tops[j] if want_top and j < len(tops) else None
            seq.append_token(tok, lp, top)
        seq.status = SequenceStatus.RUNNING
        sched.running.append(seq)
        self.obs.on_arrival(seq)
        self.obs.on_scheduled(seq, 1)
        if ttft_t0 is not None and not mid_stream:
            # step() never fires on_first_token for an imported sequence
            # (append_token above already stamped first_token_time), so the
            # TTFT sample — histogram + SLO attainment window + the goodput
            # gate on_finish applies — lands here with the true span.
            self.obs.on_handoff_first_token(
                seq, max(time.monotonic() - ttft_t0, 0.0))
        self.obs.tracer.emit("migrate" if mid_stream else "handoff",
                             request_id, side="import",
                             pages=need, tokens=len(out_ids))
        if self._sanitizer is not None:
            # The KV-slot shadow learns the imported slots are committed
            # history — same contract as a swap restore.
            self._sanitizer.on_swap_restore(seq)
        reason = seq.check_stop(self.config.effective_max_len)
        if reason is not None:
            sched.finish(seq, reason)
            self.stats.requests_finished += 1
        else:
            # The step in flight predates this sequence: the next one is
            # scheduled once it is fetched (see ``_batch_stale``). A
            # sequence that finished AT import left ``running``
            # net-unchanged, so no break (a prefill-heavy max_tokens=1
            # storm would otherwise pay a schedule round-trip per import
            # on the decode replica).
            self._batch_stale = True
        return [RequestOutput(
            request_id=request_id,
            prompt_token_ids=list(prompt_token_ids),
            output_token_ids=list(seq.output_token_ids),
            finished=seq.is_finished,
            finish_reason=(seq.finish_reason.value
                           if seq.finish_reason else None),
            new_token_ids=out_ids,
            new_logprobs=(list(lps) if want_lps else None),
            output_logprobs=(list(seq.output_logprobs)
                             if want_lps else None),
            new_top_logprobs=(list(seq.output_top_logprobs)
                              if want_top else None),
            output_top_logprobs=(list(seq.output_top_logprobs)
                                 if want_top else None))]

    # -- fleet-wide prefix cache (global KV reuse over the handoff seam) -----

    def prefix_peek(self, token_ids: list[int]) -> int:
        """Tokens already covered by the LOCAL prefix cache (either tier) —
        the pull gate's "what would a local admission reuse anyway" input.
        Read-only; safe from the worker seam."""
        return self.scheduler.prefix_peek(token_ids)

    def export_prefix(self, token_ids: list[int],
                      skip_tokens: int = 0) -> dict:
        """Serve a peer's fleet-cache fetch: the longest cached prefix of
        ``token_ids`` — live entries gathered through the ``KVPageIO``
        seam, host-tier spills READ IN PLACE from the host pool (never
        restored into the device pool, no LRU touch, no counters: a
        peer's fetch must not perturb the owner's cache or its locality
        telemetry) — assembled into one contiguous host buffer.

        ``skip_tokens``: what the puller already holds locally (page-
        aligned; floored if not). Only pages BEYOND it are exported —
        the delta the roofline gate actually priced — though the chain
        walk still runs from token 0 (chained digests commit to the
        whole prefix). Raises KeyError when prefix caching is off,
        nothing matches, or the match does not extend past
        ``skip_tokens`` — the serving layer answers 404 and the peer
        recomputes locally. Capped at ``len(token_ids) - 1`` like
        admission reuse, so the importer always keeps >= 1 token to
        prefill."""
        self._require_kv_wire("prefix export")
        pc = self.scheduler.prefix_cache
        if pc is None:
            raise KeyError("prefix caching is off on this replica")
        ps = self.config.cache.page_size
        skip_pages = max(int(skip_tokens), 0) // ps
        entries, matched = pc.export_walk(token_ids, len(token_ids) - 1)
        dev_pages = [p for kind, p in entries if kind == "dev"]
        try:
            if matched <= skip_pages * ps:
                raise KeyError(
                    "no cached prefix beyond the peer's local coverage"
                    if matched else "no cached prefix for this prompt")
            send = entries[skip_pages:]
            L, _, _, kd = self.kv_cache.k.shape
            k_np = np.empty((L, len(send), ps, kd), self.kv_cache.k.dtype)
            v_np = np.empty_like(k_np)
            dev_ix = [i for i, (kind, _) in enumerate(send)
                      if kind == "dev"]
            if dev_ix:
                # One batched gather for the live slices; the fetch
                # completes inside export_pages, before the forked
                # references are released below (KGCT010).
                dk, dv = self.kv_io.export_pages(
                    [send[i][1] for i in dev_ix])
                k_np[:, dev_ix] = dk
                v_np[:, dev_ix] = dv
            host_ix = [i for i, (kind, _) in enumerate(send)
                       if kind == "host"]
            if host_ix:
                hk, hv = self.swapper.host.get(
                    [send[i][1] for i in host_ix])
                k_np[:, host_ix] = hk
                v_np[:, host_ix] = hv
        finally:
            # Gather completed (or the walk is being abandoned) — either
            # way the forked device references must not outlive this call.
            if dev_pages:
                self.scheduler.allocator.free(dev_pages)
        return {
            "model": self.model_config.name,
            "page_size": ps,
            "dtype": str(self.kv_cache.k.dtype),
            "matched_tokens": matched,
            "start_tokens": skip_pages * ps,
            "prompt_token_ids": list(token_ids[:matched]),
            "k": k_np, "v": v_np,
        }

    def _validate_prefix_header(self, header: dict) -> tuple:
        """Shared header validation of the streamed prefix import: returns
        (token_ids, n_pages) or raises ValueError. Everything the
        post-allocation path consumes converts up front, like
        import_request — a malformed peer frame must never leak pages."""
        ps = self.config.cache.page_size
        if header.get("model") != self.model_config.name:
            raise ValueError(f"prefix import model {header.get('model')!r} "
                             f"!= {self.model_config.name!r}")
        if header.get("page_size") != ps:
            raise ValueError(f"prefix import page_size "
                             f"{header.get('page_size')} != {ps}")
        if str(header.get("dtype")) != str(self.kv_cache.k.dtype):
            raise ValueError(f"prefix import dtype {header.get('dtype')} "
                             f"!= {self.kv_cache.k.dtype}")
        try:
            ids = [int(t) for t in header["prompt_token_ids"]]
            matched = int(header["matched_tokens"])
            start = int(header.get("start_tokens", 0))
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(f"malformed prefix import header: {e}") from e
        if matched < ps or matched % ps or len(ids) != matched:
            raise ValueError(
                f"prefix import carries {matched} matched tokens over "
                f"{len(ids)} ids (need a page-aligned, page-covered match)")
        if start < 0 or start % ps or start >= matched:
            raise ValueError(
                f"prefix import start_tokens {start} invalid for "
                f"{matched} matched tokens")
        return ids, start // ps, (matched - start) // ps

    def begin_prefix_import(self, header: dict) -> str:
        """Open a STREAMED prefix import: validate the wire header,
        allocate the destination pages, and hand back an opaque handle.
        The serving layer then scatters the pulled pages in bounded chunks
        (:meth:`import_prefix_chunk`) as they arrive off the socket — each
        chunk is one worker op, so decode steps for other requests
        interleave with the transfer instead of stalling behind one blob —
        and finally registers the chain (:meth:`commit_prefix_import`).
        This begin/chunk/commit seam is the ONLY sanctioned way remote
        prefix bytes enter the KV pool (KGCT016)."""
        self._require_kv_wire("prefix import")
        pc = self.scheduler.prefix_cache
        if pc is None:
            raise ValueError("prefix caching is off on this replica")
        ids, start_page, need = self._validate_prefix_header(header)
        alloc = self.scheduler.allocator
        if not alloc.can_allocate(need):
            raise RuntimeError(
                f"no KV pages for prefix import (want {need}, "
                f"free {alloc.num_free})")
        self._prefix_import_seq += 1
        handle = f"pfimp-{self._prefix_import_seq}"
        self._prefix_imports[handle] = {
            "pages": alloc.allocate(need), "token_ids": ids,
            "start_page": start_page, "filled": 0}
        return handle

    def import_prefix_chunk(self, handle: str, k_np: np.ndarray,
                            v_np: np.ndarray) -> None:
        """Scatter one chunk of pulled pages into the next slice of the
        handle's destination pages (kv_cache.KVPageIO — schedule-time
        semantics: runs on the worker thread between steps, never racing a
        dispatched program)."""
        st = self._prefix_imports.get(handle)
        if st is None:
            raise ValueError(f"unknown prefix import handle {handle!r}")
        ps = self.config.cache.page_size
        L, _, _, kd = self.kv_cache.k.shape
        n = k_np.shape[1] if k_np.ndim == 4 else -1
        if (n < 1 or tuple(k_np.shape) != (L, n, ps, kd)
                or k_np.shape != v_np.shape
                or str(k_np.dtype) != str(self.kv_cache.k.dtype)
                or st["filled"] + n > len(st["pages"])):
            self.abort_prefix_import(handle)
            raise ValueError(
                f"prefix import chunk shape {tuple(k_np.shape)} invalid "
                f"at offset {st['filled']}/{len(st['pages'])} pages")
        self.kv_io.import_pages(
            st["pages"][st["filled"]:st["filled"] + n], k_np, v_np)
        st["filled"] += n

    def commit_prefix_import(self, handle: str) -> int:
        """Close a streamed import: every destination page must be filled;
        the chain registers into the prefix cache (the cache forks its own
        reference per new digest) and the import's references are released
        — pages whose digest was registered concurrently by a local
        prefill simply return to the pool (dedupe). Returns the matched
        token count now serveable from the local cache."""
        st = self._prefix_imports.pop(handle, None)
        if st is None:
            raise ValueError(f"unknown prefix import handle {handle!r}")
        pc = self.scheduler.prefix_cache
        if st["filled"] != len(st["pages"]):
            self.scheduler.allocator.free(st["pages"])
            raise ValueError(
                f"prefix import truncated: {st['filled']}/"
                f"{len(st['pages'])} pages arrived")
        pc.register(st["token_ids"], st["pages"],
                    start_page=st["start_page"])
        self.scheduler.allocator.free(st["pages"])
        return len(st["token_ids"])

    def abort_prefix_import(self, handle: str) -> None:
        """Release a streamed import that will not complete (peer died,
        bound exceeded, chunk mismatch). Idempotent."""
        st = self._prefix_imports.pop(handle, None)
        if st is not None:
            self.scheduler.allocator.free(st["pages"])

    def accept_remote_spill(self, digest_hex: str, k_np: np.ndarray,
                            v_np: np.ndarray) -> bool:
        """Receive one remote-spilled prefix page into the local HOST tier
        (kv_cache.PrefixCache.accept_host_entry): host memory only — a
        peer's cold prefix never takes device pages until a local lookup
        actually second-chances it. False when the host tier is off/full
        or the frame does not match this pool's geometry."""
        self._require_kv_wire("remote spill")
        pc = self.scheduler.prefix_cache
        if pc is None:
            return False
        ps = self.config.cache.page_size
        L, _, _, kd = self.kv_cache.k.shape
        if (tuple(k_np.shape) != (L, 1, ps, kd)
                or k_np.shape != v_np.shape
                or str(k_np.dtype) != str(self.kv_cache.k.dtype)):
            return False
        try:
            digest = bytes.fromhex(digest_hex)
        except ValueError:
            return False
        return pc.accept_host_entry(digest, k_np, v_np)

    def enable_fleet_spill(self, sink) -> bool:
        """Arm the remote-spill eviction rung: ``sink(digest_hex, k_np,
        v_np) -> bool`` receives each evicted page the local host tier
        could not take (called on the worker thread mid-eviction, so it
        must only enqueue — the serving layer's bounded spill queue pushes
        to peers asynchronously). The gather runs through the KVPageIO
        seam and completes before the eviction frees the page (KGCT010).
        False when prefix caching is off."""
        self._require_kv_wire("fleet spill")
        pc = self.scheduler.prefix_cache
        if pc is None:
            return False

        def hook(digest: bytes, page: int) -> bool:
            try:
                k_np, v_np = self.kv_io.export_pages([page])
                return bool(sink(digest.hex(), k_np, v_np))
            except Exception:
                logger.exception("fleet spill hook failed; dropping page")
                return False

        pc.fleet_spill = hook
        return True

    def step(self) -> list[RequestOutput]:
        """One iteration of the loop: dispatch the next step program, then
        fetch the one in flight (``_step``). Every program's time is kept on
        its own record, not here (``_record``, ``_retired``)."""
        # Chaos site: KGCT_FAULT=step_stall:delay=N sleeps here, simulating a
        # hung device dispatch for the watchdog to catch. One is-armed check
        # when no spec is set — free on the hot path.
        _inject_fault("step_stall")
        # The phases' parent in a profiler capture: what of "kgct.step" no
        # phase covers (the key split, the drains, a window's unphased
        # parts) is the step's own time there. ``launched``: the number this
        # iteration's launch gets if it schedules a program; ``retired``:
        # the program it fetches.
        nxt = self.step_count + 1
        with self.obs.phases.span(
                "step", launched=nxt,
                retired=nxt if self._inflight is None
                else self._inflight["step"]):
            outs = self._step()
        self.stats.steps += 1
        return outs

    def _step(self) -> list[RequestOutput]:
        """Run one engine iteration: dispatch the NEXT step program, then
        fetch and post-process the one in flight.

        The device queue is one deep for every step kind whose inputs the
        host can derive without its predecessor's tokens: a decode window,
        a mixed step, a prefill or a chunk is scheduled from "tokens in
        flight a sequence" (``Sequence.sched_tokens``) and dispatched
        BEHIND the step in flight; its decode rows read their input token
        from that step's device-resident last-token output
        (``_chained_tokens``; a block model's rows their open block from
        its final state, engine/block.py). So the device-to-host download
        of step n, its post-processing, the hand-over of its tokens and the
        scheduling of step n+2 all run under step n+1, and an admission or
        a finish no longer empties the queue. A row found finished in step
        n rides n+1, already dispatched, as a zombie and no further; a row
        whose ``max_tokens`` falls inside n is left out of n+1 (a block
        model's cannot be told beforehand, a pass yields 0 to B tokens: it
        rides n+1). What a finished
        sequence holds is released when the last dispatched program that
        carries it has been fetched (``_drain_deferred``).

        The chain breaks, and the next step is scheduled only once this one
        is fetched, for speculation (the next drafts depend on the accepted
        tokens), a batch that needs a preemption, an import since the last
        schedule, and penalties whose histogram the host must rebuild: each
        counted by reason (``kgct_chain_breaks_total``)."""
        t_iter = time.monotonic()
        inflight = self._inflight
        outs: list[RequestOutput] = []
        if inflight is None:
            inflight, outs = self._launch(None)
            if inflight is None:
                return outs
            self._mark_in_flight(inflight)
        successor, drained = self._launch(inflight)
        self._inflight = successor
        inflight["t_iter"] = t_iter
        return outs + drained + self._retire(inflight, successor)

    def _chain_break(self, pred: dict) -> Optional[str]:
        """Why no step may be scheduled while ``pred`` is in flight, read
        from what the engine holds; None when one may. (A block model
        takes neither speculation nor penalties: its chain breaks when
        stale, and for want of pages in ``schedule``.)"""
        sched = self.scheduler
        if sched.spec_enabled:
            # Draft verification IS the speculation, and a chained window
            # would pin the engine in legacy decode after n-gram matches
            # appear (schedule() re-checks eligibility between steps).
            return "spec"
        if self._batch_stale:
            return "stale"
        if any(s.inflight_tokens and (s.params.presence_penalty
                                      or s.params.frequency_penalty)
               for s in sched.running):
            # A penalised row's newest tokens are on the chip. Only the
            # window over the very same rows carries their histogram there
            # (``counts``, donated along); any other batch rebuilds it from
            # the output tokens the host knows.
            same_rows = (pred["kind"] == "decode"
                         and pred["counts"] is not None
                         and not pred["zombies"]
                         and not sched.waiting and not sched.swapped
                         and pred["batch"].seqs == sched.running
                         and not any(
                             s.finishes_in_flight(
                                 self.config.effective_max_len)
                             for s in sched.running))
            if not same_rows:
                return "penalties"
        return None

    def _launch(self, pred: Optional[dict]
                ) -> tuple[Optional[dict], list[RequestOutput]]:
        """Schedule the next batch and dispatch its program, behind
        ``pred`` when a step is in flight. Returns the record of the step
        now in flight (None: nothing to run, a chain break, or a
        speculative step, which runs to its end here) and the outputs that
        are due at once."""
        t_launch = time.monotonic()
        # What this launch spends (schedule, host_prep, device_dispatch) is
        # filed under the program it launches, not under the one the same
        # iteration fetches.
        phases = self.obs.phases.start_step()
        ph = self.obs.phases.phase
        behind = pred is not None
        reason = self._chain_break(pred) if behind else None
        batch = None
        if reason is None:
            self.obs.step_launching = self.step_count + 1
            try:
                with ph("schedule"):
                    batch = self.scheduler.schedule(behind=behind)
            except CannotChain as e:
                reason = e.reason
        if reason is not None:
            self.obs.on_chain_break(reason)
            return None, []
        if not behind:
            self._batch_stale = False
        outs = self._drain_terminally_finished()
        if batch is None:
            return None, outs
        self.step_count += 1
        self._key, step_key = jax.random.split(self._key)
        with ph("host_prep"):
            float_b = _pack_float_b(batch)
        self.obs.on_step_dispatched(batch.kind, behind)
        rec = self._record(batch, pred, t_launch, phases)
        if batch.kind in ("spec", "spec_mixed"):
            self.obs.on_chain_break("spec")
            step = (self._step_spec if batch.kind == "spec"
                    else self._step_spec_mixed)
            return None, outs + step(rec, float_b, step_key)
        if self._sanitizer is not None:
            self._sanitizer.on_step_dispatch(batch.seqs)
        prev = self._no_pred if pred is None else pred["last"]
        if batch.kind == "decode":
            self._dispatch_window(rec, prev, float_b, step_key, pred)
        else:
            self._dispatch_prefill(rec, prev, float_b, step_key)
        return rec, outs

    def _record(self, batch: ScheduledBatch, pred: Optional[dict],
                t_launch: float, phases: list) -> dict:
        """The record of the program about to be dispatched for ``batch``,
        the ONE place it is made for all five step bodies: its own number
        (the ``step_count`` it was given), kind, real rows, the tokens it
        computes and its bucket's size, whether and behind which program
        it is queued, and the first of its stamps. The dispatchers add
        what the fetch needs and ``t_dispatched``; ``_fetching`` adds
        ``t_wait``, ``t_ready`` and ``clock`` (what its rows' outputs take
        along of its stamps); ``_retired`` adds ``t_retired`` and hands it
        to ``Observability.on_step``."""
        kind, rows, padded = batch.kind, batch.num_seqs, len(batch.tokens)
        if batch.block is not None:
            # A block program: positions computed over its passes; the
            # tokens it TRANSFERRED are known when it retires
            # (``block.retire`` writes them over this).
            passes = (self.config.scheduler.decode_window
                      if kind == "decode" else 1)
            width = passes * self.model_config.row_width
            # (a mixed step's head, last in ``seqs``, has no row)
            tokens = (batch.prefill_token_count
                      + max(rows - (kind == "mixed"), 0) * width)
            padded = (batch.prefill_token_count
                      + len(batch.temperature) * width)
        elif kind == "decode":
            window = self.config.scheduler.decode_window
            tokens, padded = rows * window, padded * window
        elif kind == "prefill":
            tokens = int(np.sum(batch.seg_ids >= 0))
        elif kind == "mixed":
            tokens = batch.prefill_token_count + rows - 1
        else:
            # verify slices of S tokens a row; spec_mixed: behind the chunk
            S = batch.spec_S or padded // batch.page_tables.shape[0]
            tokens = ((rows * S) if kind == "spec"
                      else batch.prefill_token_count + (rows - 1) * S)
        return {"step": self.step_count, "kind": kind, "batch": batch,
                "rows": rows, "tokens": tokens, "padded_tokens": padded,
                "behind": pred is not None,
                "pred": None if pred is None else pred["step"],
                "t_launch": t_launch, "t_iter": t_launch, "phases": phases}

    @contextlib.contextmanager
    def _fetching(self, rec: dict):
        """The fetch of the program of ``rec``, as the phase
        ``device_fetch``: block until it is done, stamping both ends of the
        wait (they are also the worker's turns into and out of
        ``device_wait``), then let the caller copy what it needs. Async
        dispatch means the device COMPUTE completes inside this sync, so
        what follows ``t_ready`` is the device->host copy alone:
        ``transfer_s``, the TTFT decomposition's "first_fetch" (the compute
        is its "prefill" share)."""
        turn = self.obs.phases.worker_turn
        with self.obs.phases.phase("device_fetch", rec):
            rec["t_wait"] = t = time.monotonic()
            turn("device_wait", t)
            rec["toks"].block_until_ready()
            rec["t_ready"] = t = time.monotonic()
            turn("host", t)
            rec["clock"] = StepClock(rec["step"], t)
            yield
        rec["transfer_s"] = time.monotonic() - rec["t_ready"]

    def _retired(self, rec: dict, outs: list[RequestOutput], **extra) -> None:
        """Post-processing of the program of ``rec`` is done: the last
        stamp, what it committed, and the record goes to the accounts."""
        rec["t_retired"] = rec["clock"].t_retired = time.monotonic()
        rec["new_tokens"] = sum(len(o.new_token_ids or []) for o in outs)
        rec.update(extra)
        self.obs.on_step(rec)

    def warm_full_window(self) -> None:
        """Dispatch the greedy decode window over padding rows alone at the
        largest row bucket, and wait for it: the program a server runs from
        the moment its seats are full is then traced, lowered and loaded (or
        compiled) before the first request, not under every open stream at
        once (6 s without a token on 64 streams at kimi-linear: PERF.md
        section 6, PR 36). Padding rows write the scrap page and the scrap
        slot; no sequence, counter or random key of the engine is touched.
        The serving CLI calls it before it listens; a library user may."""
        sc = self.config.scheduler
        t0 = time.monotonic()
        batch = self.scheduler.decode_batch(
            [], _bucket(sc.max_num_seqs, sc.decode_buckets))
        prev = self._no_pred
        for _ in range(2):
            # the second behind the first: its cache and ``prev`` are a
            # step's outputs, as every window's under load
            rec = self._record(batch, None, t0, [])
            self._dispatch_window(rec, prev, _pack_float_b(batch),
                                  jax.random.key(0), None)
            prev = rec["last"]
        jax.block_until_ready(prev)
        logger.info("the window at %d rows met before the first request: "
                    "%.1f s", len(batch.tokens), time.monotonic() - t0)

    def warm_mixed_steps(self) -> None:
        """Dispatch, over padding alone, the mixed steps that prompts of
        ``SchedulerConfig.warm_prompt_lens`` tokens ride beside full seats,
        and wait for them. A chunk's step program is one a (chunk rung,
        width of its history table): a prompt of a few tokens meets the
        smallest rung at width one (the default list: its first use stands
        every open stream still for 2-3 s with a warm compile cache and
        ~30 s without: PERF.md section 6, PR 37), a prompt of several
        chunks one program a width its context grows through (most of a
        minute each for a sparse-attention model, inside requests that
        outlast a benchmark's pre-roll: PERF.md section 6, PR 46). The list
        is the operator's: every program is a compile at a cold start and a
        load at a warm one. As ``warm_full_window``: the scrap page and the
        scrap slot, no sequence, counter or random key of the engine. No
        mixed step (pp, sp, ``mixed_batch_enabled`` off) or a speculative
        one in its place: nothing to meet."""
        sc = self.config.scheduler
        if (self._mixed_fn is None or not self.scheduler.mixed_enabled
                or self.scheduler.spec_enabled):
            return
        t0 = time.monotonic()
        steps = list(dict.fromkeys(
            step for n in sc.warm_prompt_lens
            for step in mixed_steps_of_prompt(self.scheduler, n)))
        prev = self._no_pred
        for Tp, width in steps:
            batch = padding_mixed_batch(
                self.scheduler, Tp,
                mixed_row_bucket(sc.max_num_seqs, Tp, sc),
                width)
            for _ in range(2):
                # the second behind the first, as in warm_full_window
                rec = self._record(batch, None, t0, [])
                self._dispatch_prefill(rec, prev, _pack_float_b(batch),
                                       jax.random.key(0))
                prev = rec["last"]
            jax.block_until_ready(prev)
        logger.info("%d mixed steps (chunk rung, history width) %s beside "
                    "%d rows met before the first request: %.1f s",
                    len(steps), steps, sc.max_num_seqs,
                    time.monotonic() - t0)

    def _dispatch_prefill(self, rec: dict, prev, float_b, step_key) -> None:
        """Dispatch a prefill, a chunk with history or a mixed step; what
        its fetch needs goes into the step's record ``rec``. A partial
        chunk's sampled row is meaningless (KV committed, prompt
        unfinished): it goes through the zombie set, so ``_process_window``
        skips it with no output, no stats, no stop checks."""
        ph = self.obs.phases.phase
        batch = rec["batch"]
        if batch.block is not None:     # a block model's pass beside a chunk
            return block_steps.dispatch(self, rec, prev, float_b, step_key)
        mixed = batch.kind == "mixed"
        with ph("host_prep"):
            int_t = jnp.asarray(np.stack(
                [batch.tokens, batch.seg_ids, batch.positions,
                 batch.slot_mapping]))
            int_b = jnp.asarray(_pack_int_b(batch))
            bias_ids, bias_vals = self._bias_arrays(batch)
            if batch.hist_len is not None:
                page_tables = jnp.asarray(batch.page_tables)
                out_tokens = self._penalty_out_tokens(batch)
            if mixed:
                self._count_chosen(
                    batch.context_lens[batch.context_lens > 0])
                chunk_pt = jnp.asarray(batch.chunk_page_table)
                context_lens = jnp.asarray(batch.context_lens)
        if mixed:
            self.stats.prefill_tokens += batch.prefill_token_count
            with ph("device_dispatch", rec):
                (toks, lps, tids, tlps, last, self.kv_cache,
                 *load) = self._mixed_fn(
                    self.params, self.kv_cache, prev, int_t, int_b, float_b,
                    chunk_pt, jnp.int32(batch.hist_len), page_tables,
                    context_lens, out_tokens, bias_ids, bias_vals, step_key)
        elif batch.hist_len is not None:
            # Chunked prefill (solo): the chunk attends to pool history.
            self.stats.prefill_tokens += rec["tokens"]
            with ph("device_dispatch", rec):
                (toks, lps, tids, tlps, last, self.kv_cache,
                 *load) = self._prefill_hist_fn(
                    self.params, self.kv_cache, int_t, int_b, float_b,
                    page_tables, jnp.int32(batch.hist_len), out_tokens,
                    bias_ids, bias_vals, step_key)
        else:
            self.stats.prefill_tokens += sum(
                s.num_tokens for s in batch.seqs)
            with ph("device_dispatch", rec):
                (toks, lps, tids, tlps, last, self.kv_cache,
                 *load) = self._prefill_fn(
                    self.params, self.kv_cache, int_t, int_b, float_b,
                    bias_ids, bias_vals, step_key)
        zombies = {batch.seqs[-1].request_id} if batch.partial else set()
        if self.model_config.block_length > 1:
            # A block model's prefill samples nothing (the prompt's tail
            # opens the first block) and touches no open block: it hands
            # on its predecessor's final state.
            zombies, last = {s.request_id for s in batch.seqs}, prev
        rec.update(t_dispatched=time.monotonic(), toks=toks, lps=lps,
                   tids=tids, tlps=tlps, last=last, load=load, counts=None,
                   zombies=zombies)

    def _retire(self, step: dict,
                successor: Optional[dict]) -> list[RequestOutput]:
        """Fetch the tokens of ``step`` (the wait for the device, under its
        ``successor`` when one was dispatched), commit them, and release
        what no dispatched program can write any more. A block program's
        passes are replayed over the host's copy of its rows' open blocks
        (engine/block.py) where another's tokens are appended."""
        ph = self.obs.phases.phase
        # The fetch and the post-processing serve THIS program, whatever
        # the iteration launched before it came here.
        self.obs.phases.file_under(step["phases"])
        batch = step["batch"]
        window = step["kind"] == "decode"
        block = batch.block is not None
        with self._fetching(step):
            if block:
                fetched = block_steps.fetch(self, step)
            else:
                toks = np.asarray(step["toks"])
                lps = np.asarray(step["lps"])
                top_i = top_l = None
                if any(s.params.top_logprobs for s in batch.seqs):
                    # Alternatives ride the device outputs unconditionally;
                    # the device->host TRANSFER happens only when someone
                    # asked.
                    top_i = np.asarray(step["tids"])
                    top_l = np.asarray(step["tlps"])
                if not window:
                    toks, lps = toks[:, None], lps[:, None]
                    if top_i is not None:
                        top_i, top_l = top_i[:, None], top_l[:, None]
                    self.obs.on_expert_load(
                        step["load"], model_lib.grouped_dispatch(
                            len(batch.tokens), self.model_config,
                            self.kernels))
        if self._sanitizer is not None:
            self._sanitizer.on_step_retire()
        for seq in batch.seqs:
            seq.inflight_tokens = seq.inflight_passes = 0
            seq.inflight_row = -1
        carried = (frozenset() if successor is None
                   else frozenset(map(id, successor["batch"].seqs)))
        with ph("postproc"):
            if block:
                outputs, extra = block_steps.replay(self, step, fetched,
                                                    carried)
            else:
                outputs = self._process_window(
                    step, toks, lps, carried, top_ids=top_i, top_lps=top_l)
            if successor is not None:
                successor["zombies"].update(
                    s.request_id for s in successor["batch"].seqs
                    if s.is_finished)
                self._mark_in_flight(successor)
            counts = step["counts"]
            if counts is not None:
                # (None too where a successor over the same rows took the
                # histogram along, donated: ``_dispatch_window``)
                self._counts_pool[counts.shape[0]] = counts
            self._drain_deferred(carried)
        if not block:       # (``replay`` counted what a block program did)
            extra = self._routed(step["tokens"])
            if window:
                extra["mode"] = "greedy" if step["greedy"] else "sampled"
            elif step["kind"] == "mixed":
                extra.update(prefill_tokens=batch.prefill_token_count,
                             decode_tokens=batch.num_seqs - 1)
        self._retired(step, outputs, **extra)
        return outputs

    def _mark_in_flight(self, step: dict) -> None:
        """Tell the sequences of ``step``, now the only unfetched program,
        what it holds for them: how many tokens, and the row of its
        last-token output with the newest; for a block program how many
        passes, and the row of its final state with the sequence's open
        block. Rows of finished sequences and a partial chunk's (the
        zombies) are sampled nothing that counts."""
        batch = step["batch"]
        n = (self.config.scheduler.decode_window
             if step["kind"] == "decode" else 1)
        for row, seq in batch.device_seq_rows():
            if seq.request_id in step["zombies"]:
                continue
            seq.inflight_row = row
            if batch.block is not None:
                seq.inflight_passes = n
            else:
                seq.inflight_tokens = n

    def _routed(self, tokens: int) -> dict:
        """``on_step``'s count of (token, expert) pairs a step of ``tokens``
        real tokens sent through the expert layers; empty for a dense
        model."""
        m = self.model_config
        if not m.is_moe:
            return {}
        return {"routed_pairs": tokens * m.num_experts_per_tok
                * (m.num_layers - m.num_dense_layers)}

    def _step_spec(self, rec: dict, float_b, step_key) -> list[RequestOutput]:
        """Execute one speculative-verification step and commit its
        results: every row advances by ``accepted + 1`` tokens (the
        accepted draft prefix plus the resample-or-bonus token), appended
        through the regular stop-check loop so EOS/max_tokens mid-window
        truncate exactly as in the decode path. Spec steps are synchronous
        (the next step's drafts depend on this one's accepted tokens), so
        finished rows release pages immediately. Rejected drafts need NO
        device-side rollback: their KV slots sit past the new committed
        length and the next step's append overwrites them before any read
        (the verifier module documents the invariant; tests pin it)."""
        ph = self.obs.phases.phase
        batch = rec["batch"]
        R_pad = batch.page_tables.shape[0]
        S = len(batch.tokens) // R_pad
        # Chaos site: KGCT_FAULT=kv_commit_stomp corrupts one KV write slot
        # BEFORE the upload, so the device really would stomp committed
        # history — the KV shadow (KGCT_SANITIZE=1) must catch it here.
        if _inject_fault("kv_commit_stomp"):
            _stomp_committed_slot(batch, self.config.cache.page_size, S)
        if self._sanitizer is not None:
            self._sanitizer.on_spec_dispatch(batch)
        with ph("host_prep"):
            int_t = jnp.asarray(np.stack(
                [batch.tokens, batch.seg_ids, batch.positions,
                 batch.slot_mapping]))
            int_b = jnp.asarray(np.stack(
                [batch.top_k, batch.seed, batch.top_n], axis=1))
            page_tables = jnp.asarray(batch.page_tables)
            context_lens = jnp.asarray(batch.context_lens)
            out_tokens = self._penalty_out_tokens(batch)
            bias_ids, bias_vals = self._bias_arrays(batch)
        with ph("device_dispatch", rec):
            (toks, n_acc, lps, tids, tlps,
             self.kv_cache) = self._spec_verify_fn(
                self.params, self.kv_cache, int_t, int_b, float_b,
                page_tables, context_lens, out_tokens, bias_ids, bias_vals,
                step_key)
        rec.update(t_dispatched=time.monotonic(), toks=toks, zombies=set())
        with self._fetching(rec):
            toks_np = np.asarray(toks)
            n_acc_np = np.asarray(n_acc)
            lps_np = np.asarray(lps)
            top_i = top_l = None
            if any(s.params.top_logprobs for s in batch.seqs):
                top_i = np.asarray(tids)
                top_l = np.asarray(tlps)
        B = batch.num_seqs
        emit = np.minimum(n_acc_np + 1, S)
        # Acceptance metrics count REAL proposals only: rows short of k
        # were padded with filler drafts (lossless but not "drafted" in
        # any operator-meaningful sense), so both the drafted and the
        # accepted tallies clamp to draft_lens — kgct_spec_acceptance_ratio
        # measures the proposer, not the padding.
        draft_lens = batch.draft_lens[:B]
        drafted = int(draft_lens.sum())
        accepted = int(np.minimum(n_acc_np[:B], draft_lens).sum())
        greedy = bool(np.all(batch.temperature[:B] <= 0))
        self._observe_spec_outcome(drafted, accepted)
        if self._sanitizer is not None:
            # Before _process_window appends tokens: rejected-draft slots
            # (past each row's accepted prefix) become stale in the shadow.
            self._sanitizer.on_spec_commit(batch, emit)
        with ph("postproc"):
            outs = self._process_window(rec, toks_np, lps_np,
                                        top_ids=top_i, top_lps=top_l,
                                        emit_counts=emit)
        self._retired(rec, outs, mode="greedy" if greedy else "sampled",
                      drafted_tokens=drafted, accepted_tokens=accepted,
                      draft_s=batch.draft_time_s)
        return outs

    def _observe_spec_outcome(self, drafted: int, accepted: int) -> None:
        """Feed the acceptance-adaptive controller (no-op when static k)
        and mirror its decision to the kgct_spec_current_k gauge."""
        ctrl = self.scheduler.spec_controller
        if ctrl is None:
            return
        ctrl.observe(drafted, accepted)
        self.obs.spec_current_k = ctrl.current_k

    def _step_spec_mixed(self, rec: dict, float_b,
                         step_key) -> list[RequestOutput]:
        """Execute one spec×mixed step: every running row advances by
        ``accepted + 1`` tokens (the spec path's commit) AND the queue-head
        prompt advances by one budgeted chunk (the mixed path's commit) —
        one dispatched program. Synchronous like both parents; the chunk
        row's sampled token is the sequence's first generated token on a
        final chunk (zombie-discarded while partial), and rejected draft
        slots roll back by the same overwrite-before-read contract the
        pure spec step pins."""
        ph = self.obs.phases.phase
        batch = rec["batch"]
        chunk_seq = batch.seqs[-1]
        decode_seqs = batch.seqs[:-1]
        D = len(decode_seqs)
        R_pad = batch.page_tables.shape[0]
        S = batch.spec_S
        Tp = len(batch.tokens) - R_pad * S
        if _inject_fault("kv_commit_stomp"):
            _stomp_committed_slot(batch, self.config.cache.page_size, S,
                                  token_start=Tp)
        if self._sanitizer is not None:
            # Verify slices only: the chunk half's writes target
            # uncommitted prompt positions by design (KGCT005's static
            # scope), exactly like the plain mixed step.
            self._sanitizer.on_spec_dispatch(batch, seqs=decode_seqs,
                                             token_start=Tp)
        with ph("host_prep"):
            int_t = jnp.asarray(np.stack(
                [batch.tokens, batch.seg_ids, batch.positions,
                 batch.slot_mapping]))
            logits_idx = jnp.asarray(batch.logits_indices)
            int_b = jnp.asarray(np.stack(
                [batch.top_k, batch.seed, batch.top_n], axis=1))
            chunk_pt = jnp.asarray(batch.chunk_page_table)
            page_tables = jnp.asarray(batch.page_tables)
            context_lens = jnp.asarray(batch.context_lens)
            out_tokens = self._penalty_out_tokens(batch)
            bias_ids, bias_vals = self._bias_arrays(batch)
        self.stats.prefill_tokens += batch.prefill_token_count
        with ph("device_dispatch", rec):
            (toks, n_acc, lps, tids, tlps,
             self.kv_cache) = self._spec_mixed_fn(
                self.params, self.kv_cache, S, int_t, logits_idx, int_b,
                float_b, chunk_pt, jnp.int32(batch.hist_len), page_tables,
                context_lens, out_tokens, bias_ids, bias_vals, step_key)
        zombies = {chunk_seq.request_id} if batch.partial else set()
        rec.update(t_dispatched=time.monotonic(), toks=toks, zombies=zombies)
        with self._fetching(rec):
            toks_np = np.asarray(toks)
            n_acc_np = np.asarray(n_acc)
            lps_np = np.asarray(lps)
            top_i = top_l = None
            if any(s.params.top_logprobs for s in batch.seqs):
                top_i = np.asarray(tids)
                top_l = np.asarray(tlps)
        # Host row view: the D real verify rows, then the chunk's device
        # row (R_pad) — matching batch.seqs order for _process_window.
        sel = list(range(D)) + [R_pad]
        toks_np = toks_np[sel]
        lps_np = lps_np[sel]
        if top_i is not None:
            top_i = top_i[sel]
            top_l = top_l[sel]
        emit = np.ones(D + 1, np.int64)
        emit[:D] = np.minimum(n_acc_np[:D] + 1, S)
        draft_lens = batch.draft_lens[:D]
        drafted = int(draft_lens.sum())
        accepted = int(np.minimum(n_acc_np[:D], draft_lens).sum())
        greedy = bool(np.all(batch.temperature <= 0))
        self._observe_spec_outcome(drafted, accepted)
        if self._sanitizer is not None:
            self._sanitizer.on_spec_commit(batch, emit)
        with ph("postproc"):
            outs = self._process_window(rec, toks_np, lps_np,
                                        top_ids=top_i, top_lps=top_l,
                                        emit_counts=emit)
        self._retired(rec, outs, mode="greedy" if greedy else "sampled",
                      prefill_tokens=batch.prefill_token_count,
                      decode_tokens=int(emit[:D].sum()),
                      drafted_tokens=drafted, accepted_tokens=accepted,
                      draft_s=batch.draft_time_s)
        return outs

    def _bias_arrays(self, batch: ScheduledBatch):
        """(bias_ids [B, 300] i32 -1-padded, bias_vals [B, 300] f32) for the
        device-side logit_bias scatter; cached -1/0 dummies when no request
        in the batch carries a bias."""
        B = len(batch.temperature)
        if not any(seq.params.logit_bias for seq in batch.seqs):
            if B not in self._dummy_bias:
                self._dummy_bias[B] = (
                    jnp.full((B, LOGIT_BIAS_CAP), -1, jnp.int32),
                    jnp.zeros((B, LOGIT_BIAS_CAP), jnp.float32))
            return self._dummy_bias[B]
        ids = np.full((B, LOGIT_BIAS_CAP), -1, np.int32)
        vals = np.zeros((B, LOGIT_BIAS_CAP), np.float32)
        for s, seq in batch.device_seq_rows():
            lb = seq.params.logit_bias
            if lb:   # validated <= LOGIT_BIAS_CAP at SamplingParams init
                for j, (tok, bias) in enumerate(lb.items()):
                    ids[s, j] = tok
                    vals[s, j] = bias
        return jnp.asarray(ids), jnp.asarray(vals)

    def _penalty_out_tokens(self, batch: ScheduledBatch):
        """[B, out_cap] -1-padded output-token ids for the device-side
        penalty histogram resync; the cached -1 dummy when no request in the
        batch has penalties (the program's cond never reads it then)."""
        B = len(batch.temperature)
        if not (np.any(batch.presence) or np.any(batch.frequency)):
            if B not in self._dummy_out:
                self._dummy_out[B] = jnp.full((B, self._out_cap), -1,
                                              jnp.int32)
            return self._dummy_out[B]
        out = np.full((B, self._out_cap), -1, np.int32)
        for s, seq in batch.device_seq_rows():
            ids = seq.output_token_ids[:self._out_cap]
            out[s, :len(ids)] = ids
        return jnp.asarray(out)

    def _dispatch_window(self, rec: dict, prev, float_b, step_key,
                         pred: Optional[dict]) -> None:
        """Dispatch a decode window; what its fetch needs goes into the
        step's record ``rec``. Behind a window over the very same rows the
        penalty histogram rides along on the device (``counts``, donated:
        it already holds the tokens in flight)."""
        ph = self.obs.phases.phase
        batch = rec["batch"]
        if batch.block is not None:     # a block model's W passes
            return block_steps.dispatch(self, rec, prev, float_b, step_key)
        if self._sanitizer is not None:
            self._sanitizer.on_decode_dispatch(
                batch.seqs, batch.positions,
                self.config.scheduler.decode_window)
        with ph("host_prep"):
            int_b = jnp.asarray(np.concatenate(
                [np.stack([batch.positions, batch.top_k, batch.seed,
                           batch.top_n, batch.tokens, batch.tok_src],
                          axis=1), batch.page_tables]
                + ([] if batch.row_slots is None
                   else [batch.row_slots[:, None]]), axis=1))
        greedy = (bool(np.all(batch.temperature <= 0))
                  and not np.any(batch.presence)
                  and not np.any(batch.frequency)
                  and not any(s.params.logit_bias for s in batch.seqs))
        if greedy:
            with ph("device_dispatch", rec):
                (dev_out, dev_lp, dev_tid, dev_tlp, last,
                 self.kv_cache) = self._decode_fn_greedy(
                    self.params, self.kv_cache, prev, int_b, float_b,
                    step_key)
            counts = None
        else:
            B = len(batch.temperature)
            any_pen = bool(np.any(batch.presence) or np.any(batch.frequency))
            counts = None
            if (pred is not None and pred["counts"] is not None
                    and pred["counts"].shape[0] == B
                    and pred["batch"].seqs == batch.seqs):
                counts, pred["counts"] = pred["counts"], None
            rebuild = counts is None and any_pen
            if counts is None:
                counts = self._counts_pool.pop(B, None)
                if counts is None:
                    counts = jnp.zeros((B, self.model_config.vocab_size),
                                       jnp.int32)
            if rebuild:
                # A window over other rows than its predecessor's, with
                # penalties active: re-sync the histogram from host-known
                # output tokens (``_chain_break`` saw to it that none of a
                # penalised row's are still in flight). A successor over
                # the same rows carries the device-resident counts instead
                # (they already include the in-flight window's tokens), and
                # penalty-free sampled batches (the common case) skip the
                # host assembly + upload + scatter entirely — counts stay a
                # device zero-fill that apply_penalties never reads.
                out_tokens = self._penalty_out_tokens(batch)
            elif B in self._dummy_out:
                out_tokens = self._dummy_out[B]
            else:
                out_tokens = self._dummy_out.setdefault(
                    B, jnp.full((B, self._out_cap), -1, jnp.int32))
            with ph("host_prep"):
                bias_ids, bias_vals = self._bias_arrays(batch)
            with ph("device_dispatch", rec):
                (dev_out, dev_lp, dev_tid, dev_tlp, last, self.kv_cache,
                 counts) = self._decode_fn(
                    self.params, self.kv_cache, prev, int_b, float_b,
                    step_key, counts, out_tokens, jnp.asarray(rebuild),
                    bias_ids, bias_vals)
        self._count_chosen(batch.positions[:len(batch.seqs)] + 1,
                           self.config.scheduler.decode_window)
        rec.update(t_dispatched=time.monotonic(), toks=dev_out, lps=dev_lp,
                   tids=dev_tid, tlps=dev_tlp, last=last, load=(),
                   zombies=set(), counts=counts, greedy=greedy)

    def _count_chosen(self, contexts: np.ndarray, steps: int = 1) -> None:
        """A sparse-attention model's decode rows, from the lengths the
        host holds: the tokens each row could see over ``steps`` steps of
        growing context, and the ``index_topk`` of them at most it attended
        to."""
        topk = self.model_config.index_topk
        if topk and len(contexts):
            ctx = (np.asarray(contexts, np.int64)[:, None]
                   + np.arange(steps)[None, :])
            self.obs.dsa_visible_tokens += int(ctx.sum())
            self.obs.dsa_chosen_tokens += int(np.minimum(ctx, topk).sum())

    def _process_window(self, rec: dict, next_tokens: np.ndarray,
                        logprobs: np.ndarray,
                        carried: frozenset = frozenset(),
                        top_ids: Optional[np.ndarray] = None,
                        top_lps: Optional[np.ndarray] = None,
                        emit_counts: Optional[np.ndarray] = None,
                        ) -> list[RequestOutput]:
        """``rec``: the record of the program whose tokens these are (its
        batch, its zombies, its number for the first-token event and its
        clock for the frames). next_tokens/logprobs: [B_pad, W]. Append
        window tokens per sequence
        until a stop condition fires; tokens generated past the stop are
        discarded.
        Its ``zombies`` (request ids finished in an earlier step of the chain,
        and a partial chunk's row) are skipped. ``carried``: the ``id`` of
        every sequence that the successor, already dispatched, has a row
        for: one of them that finishes here keeps its pages and slot until
        that program is fetched (it still writes them).
        ``emit_counts`` [B_pad] caps the usable columns per row (spec steps:
        accepted drafts + 1; slots past the first rejection are garbage).
        """
        # Chaos site: KGCT_FAULT=nan_step_output poisons the fetched
        # logprobs — the corruption class the KGCT_SANITIZE step-output
        # guard must catch before any client sees it.
        batch, zombies = rec["batch"], rec["zombies"]
        if _inject_fault("nan_step_output"):
            logprobs = np.full_like(np.asarray(logprobs, np.float32), np.nan)
        if self._sanitizer is not None:
            self._sanitizer.check_outputs(
                next_tokens, logprobs, emit_counts,
                self.model_config.vocab_size, len(batch.seqs))
        outputs = []
        for s, seq in enumerate(batch.seqs):
            if seq.request_id in zombies:
                continue
            had_first = seq.first_token_time is not None
            want_lps = seq.params.logprobs
            want_top = (seq.params.top_logprobs if top_ids is not None else 0)
            new_tokens: list[int] = []
            new_lps: list[float] = []
            new_tops: list[list[tuple[int, float]]] = []
            width = (next_tokens.shape[1] if emit_counts is None
                     else int(emit_counts[s]))
            for j, (token, lp) in enumerate(zip(next_tokens[s][:width],
                                                logprobs[s][:width])):
                token = int(token)
                # Per-request gating: the device computes logprobs
                # unconditionally (negligible next to sampling), but the
                # host records them only for requests that asked.
                top = None
                if want_top:
                    top = [(int(t), float(v)) for t, v in
                           zip(top_ids[s, j, :want_top],
                               top_lps[s, j, :want_top])]
                    # OpenAI/vLLM: the SAMPLED token is always present (up
                    # to N+1 entries) even when it fell outside the top N.
                    if token not in (t for t, _ in top):
                        top.append((token, float(lp)))
                    new_tops.append(top)
                seq.append_token(token, float(lp) if want_lps else None, top)
                new_tokens.append(token)
                if want_lps:
                    new_lps.append(float(lp))
                reason = seq.check_stop(self.config.effective_max_len)
                if reason is not None:
                    self._finish_row(seq, reason, carried)
                    break
            self.stats.tokens_generated += len(new_tokens)
            if not had_first and seq.first_token_time is not None:
                # TTFT decomposition: under async dispatch the device
                # compute completes inside the fetch sync, so "first_fetch"
                # is the copy alone, from the program's t_ready on.
                self.obs.on_first_token(seq, fetch_s=rec["transfer_s"],
                                        step=rec["step"])
            if seq.is_finished:
                self.stats.requests_finished += 1
            outputs.append(RequestOutput(
                request_id=seq.request_id,
                prompt_token_ids=seq.prompt_token_ids,
                output_token_ids=list(seq.output_token_ids),
                finished=seq.is_finished,
                finish_reason=seq.finish_reason.value if seq.finish_reason else None,
                new_token_ids=new_tokens,
                new_logprobs=new_lps if want_lps else None,
                output_logprobs=(list(seq.output_logprobs)
                                 if want_lps else None),
                new_top_logprobs=new_tops if want_top else None,
                output_top_logprobs=(list(seq.output_top_logprobs)
                                     if seq.params.top_logprobs else None),
                clock=rec["clock"]))
        return outputs

    def _finish_row(self, seq: Sequence, reason: FinishReason,
                    carried: frozenset) -> None:
        """``seq`` met a stop condition in the program being retired. If
        its successor, already dispatched, has a row for it (``carried``),
        what it holds stays its own until that one is fetched
        (``_drain_deferred``)."""
        if id(seq) not in carried:
            return self.scheduler.finish(seq, reason)
        seq.status = SequenceStatus.FINISHED
        seq.finish_reason = reason
        if seq in self.scheduler.running:
            self.scheduler.running.remove(seq)
        self._deferred_release.append(seq)
        self.obs.on_finish(seq, reason)

    def _drain_terminally_finished(self) -> list[RequestOutput]:
        """Sequences the scheduler finished on its own (grown past pool
        capacity, no forward step possible) still owe the client a finished
        RequestOutput — without this, generate()/a server handler waits on a
        request that will never emit again."""
        outs = []
        for seq in self.scheduler.terminally_finished:
            self.stats.requests_finished += 1
            outs.append(RequestOutput(
                request_id=seq.request_id,
                prompt_token_ids=seq.prompt_token_ids,
                output_token_ids=list(seq.output_token_ids),
                finished=True,
                finish_reason=seq.finish_reason.value if seq.finish_reason else None,
                new_token_ids=[],
                output_logprobs=(list(seq.output_logprobs)
                                 if seq.params.logprobs else None),
                output_top_logprobs=(list(seq.output_top_logprobs)
                                     if seq.params.top_logprobs else None)))
        self.scheduler.terminally_finished.clear()
        return outs

    def _drain_deferred(self, carried: frozenset = frozenset()) -> None:
        """Release what finished sequences still hold, but for those that
        the program in flight has a row for (``carried``: their turn comes
        when that one is fetched)."""
        held_back = [s for s in self._deferred_release if id(s) in carried]
        for seq in self._deferred_release:
            if id(seq) in carried:
                continue
            if (seq.hold_kv and seq.pages
                    and seq.finish_reason != FinishReason.ABORT):
                # Disaggregated prefill finishing in a step that had a
                # successor queued (max_tokens > 1 holds): the export seam owns the
                # release, exactly like the scheduler.finish hold path.
                self.scheduler.held[seq.request_id] = seq
                continue
            self.scheduler._release(seq)    # pages, and a state slot
        self._deferred_release[:] = held_back

    # -- convenience --------------------------------------------------------

    def generate(self, prompts: list[list[int]],
                 params=None) -> list[RequestOutput]:
        """Synchronous batch generation (offline / test path). ``params``:
        one SamplingParams for all prompts, or a list of one per prompt."""
        plist = (list(params) if isinstance(params, (list, tuple))
                 else [params] * len(prompts))
        if len(plist) != len(prompts):
            raise ValueError(f"got {len(plist)} SamplingParams for "
                             f"{len(prompts)} prompts")
        for i, (p, sp) in enumerate(zip(prompts, plist)):
            self.add_request(f"req-{i}", p, sp)
        final: dict[str, RequestOutput] = {}
        while self.has_unfinished_requests():
            for out in self.step():
                if out.finished:
                    final[out.request_id] = out
        return [final[f"req-{i}"] for i in range(len(prompts))]


def _stomp_committed_slot(batch, page_size: int, S: int,
                          token_start: int = 0) -> None:
    """Chaos helper (``KGCT_FAULT=kv_commit_stomp``): redirect row 0's
    first draft KV write to the sequence's position-0 slot — a REAL write
    into committed history (``num_tokens - 1 > 0`` guarantees position 0
    is committed). The KGCT_SANITIZE KV shadow must refuse the dispatch;
    with the sanitizer off this genuinely corrupts context, which is the
    point — the harness validates the detector, not a simulation of it.
    ``token_start``: where the verify slices begin on the token axis
    (spec×mixed offsets them past the prefill chunk)."""
    if not batch.seqs:
        return
    seq = batch.seqs[0]
    if seq.num_tokens < 2 or not seq.pages:
        return
    batch.slot_mapping[token_start + (1 if S > 1 else 0)] = \
        seq.pages[0] * page_size


def _resident_bytes(tree) -> int:
    """Bytes of ``tree``'s arrays that lie on the first addressable device
    (all of them on one device, a shard of each under a mesh)."""
    dev = jax.local_devices()[0]
    return sum(s.data.nbytes for x in jax.tree.leaves(tree)
               for s in getattr(x, "addressable_shards", ())
               if s.device == dev)


SETTLE_SLACK_BYTES = 64 << 20   # probes' programs and such: 20 MB on a v5e
SETTLE_TIMEOUT_S = 3.0
SETTLE_PERIOD_S = 0.05


def _device_free_memory(resident: Optional[int] = None) -> Optional[int]:
    """Free HBM bytes on the first addressable device. The CPU backend
    keeps no memory statistics -> None -> test-sized pool
    (kv_cache.derive_num_pages). On an accelerator a missing statistic is
    an error: guessing there would size a 38 GB pool on a 16 GB chip.

    ``resident``: the bytes the caller knows to be on the device for good
    (the weights). In six server starts of fifteen on the v5e the float32
    draw of a 163,840 x 2048 head, 1.34 GB that the weight init had dropped,
    was still counted when the last weight was ready, and the pool, a
    static shape of every step program, came out at 1999 or 2049 pages by
    chance: a warm compile cache then missed every program. So while the
    bytes in use stand more than a slack over ``resident``, the read is
    repeated for up to SETTLE_TIMEOUT_S, each time after a collection of
    Python's reference cycles and a trivial transfer (for a runtime that
    frees when it next touches the device); what still stands then is
    taken to be resident too (another model on the device), and said."""
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        in_use = int(stats.get("bytes_in_use", 0))
        t_end = time.monotonic() + SETTLE_TIMEOUT_S
        while (resident is not None
               and in_use > resident + SETTLE_SLACK_BYTES):
            if time.monotonic() > t_end:
                logger.warning(
                    "%d bytes in use beside %d of weights did not go within "
                    "%.0f s: sized around them", in_use - resident, resident,
                    SETTLE_TIMEOUT_S)
                break
            gc.collect()
            time.sleep(SETTLE_PERIOD_S)
            jax.device_put(np.int32(0), dev).block_until_ready()
            in_use = int(dev.memory_stats().get("bytes_in_use", 0))
        return int(stats["bytes_limit"]) - in_use
    if dev.platform == "cpu":
        return None
    raise RuntimeError(
        f"{dev.device_kind} ({dev.platform}) reports no memory_stats(); "
        "cannot size the KV pool — set CacheConfig.num_pages explicitly")


def step_workspace_bytes(config: EngineConfig) -> int:
    """Upper estimate of the HBM one step program needs BESIDE the weights
    and the pool — set aside before the pool takes its share, or the first
    full prefill bucket is a compile-time OOM (qwen3-4b on a 16 GB v5e: the
    pool at 0.90 of everything free left 0.58 GB, the 2048-token chunk
    program needs more). Counted at the largest shapes the scheduler
    dispatches, unsharded (conservative under a mesh):

    - the K/V (or latent) rows the layer scan hands the post-scan pool write;
    - the widest layer intermediates in f32 plus their model-dtype copy
      (MLP gate/up/act over ``ff`` — a Mixtral-class model is counted at
      dense dispatch, EVERY expert over every token, which bounds its
      grouped path too; a latent-attention model at the grouped layout's
      real rows — and q/k/v/attention-out over the heads);
    - residual-stream copies (of every stream a model with hyper-connections
      carries, and a token's float32 coefficient rows); and
    - the ``[rows, vocab]`` f32 sampling buffers (logits, penalties
      histogram, sort/top-k scratch) at the top decode bucket."""
    m, sc = config.model, config.scheduler
    # mixed step width (a block model's rows are two blocks of positions)
    B = sc.decode_buckets[-1] * m.row_width
    T = sc.prefill_buckets[-1] + B
    it = m.jnp_dtype.itemsize
    kd = m.kv_row_padded
    kv_rows = (m.kv_pools * m.num_kv_layers * T * kd
               * kv_cache_dtype(m, config.cache).itemsize)
    if m.is_mla:
        # Grouped dispatch (models.llama.experts_grouped): the T*k routed
        # pairs' rows and their gate/up/act over the expert width, the
        # float32 down-projection before the combine; then the shared
        # experts and a leading dense layer over T tokens.
        pairs = T * m.num_experts_per_tok
        mlp = (pairs * (m.expert_width * (4 + 4 + it)
                        + m.hidden_size * (it + 4 + 4))
               + T * max(m.intermediate_size,
                         m.num_shared_experts * m.expert_width) * (4 + 4 + it))
        # q, the absorbed q and the latent output at the padded row width.
        attn = T * m.num_heads * (m.head_dim + 2 * kd) * (4 + it)
        if m.index_topk:
            # A "full" layer (ops/dsa.py): a block of queries' index scores
            # a head and of attention scores a head over the widest chunk's
            # candidates, float32, twice (the scores and what is made of
            # them); every query's scores, the mask from them and the
            # mask carried; the rows' gathered index keys and chosen rows.
            cand = sc.prefill_buckets[-1] + next_power_of_2(
                config.effective_max_len)
            attn += (2 * 64 * cand * 4 * (m.index_n_heads + m.num_heads)
                     + T * cand * (4 + 4 + 2)
                     + B * (config.effective_max_len
                            * (m.index_head_dim * it + 4 * m.index_n_heads)
                            + m.index_topk * kd * (it + 4)))
    else:
        if m.is_moe and m.moe_intermediate_size:
            # qwen3_moe's class at grouped dispatch: the routed pairs' rows
            # as in the latent branch; no shared expert, no dense layer.
            pairs = T * m.num_experts_per_tok
            mlp = pairs * (m.expert_width * (4 + 4 + it)
                           + m.hidden_size * (it + 4 + 4))
        else:
            mlp = (max(m.num_experts, 1) * T * m.intermediate_size
                   * (4 + 4 + it))
        attn = T * (m.num_heads * m.head_dim + kd) * 2 * (4 + it)
    resid = 4 * T * m.hc_mult * m.hidden_size * 4
    if m.hc_mult > 1:
        resid += 4 * T * 128 * 4
    sampling = 8 * B * m.vocab_size * 4
    state = 0
    if m.state_kind == "mamba":
        # One state layer's chunked scan at a time: the [chunks, heads, Q, Q]
        # decay-masked products (mask, decay, product), the projection and
        # the conv over T, and, for every segment a packed prefill may hold
        # (a decode bucket's worth), its final state with the chunk it was
        # gathered from; then all layers' conv rows.
        Q, di = m.mamba_chunk_size, m.mamba_d_inner
        state = (3 * T * m.mamba_n_heads * Q * 4
                 + 2 * T * (di + m.mamba_conv_dim) * (4 + it)
                 + B * (3 * m.mamba_d_state + 2 * Q) * di * 4)
    elif m.state_kind == "kda":
        # One KDA layer's chunked form at a time (ops/kda.py): q, k, v, g,
        # their running sums and decayed copies per token in float32 (a
        # dozen [T, H, d] arrays), the pairwise decay of the sub-chunks'
        # diagonal blocks ([T, 16, H, d]), the [T, H, Q] Gram matrices and
        # the solve, every chunk's incoming state; the conv over T; for
        # every segment a packed prefill may hold, its final state with the
        # chunk it was gathered from.
        Q, HD = m.kda_chunk_size, m.kda_n_heads * m.kda_head_dim
        state = (T * HD * 4 * (12 + 16) + 4 * T * m.kda_n_heads * Q * 4
                 + (T // Q + 1) * HD * m.kda_head_dim * 4
                 + 2 * T * 3 * HD * (4 + it)
                 + B * (3 * m.kda_head_dim + 4 * Q) * HD * 4)
    if m.has_state:     # all layers' conv rows
        state += m.num_state_layers * 2 * B * math.prod(
            m.state_conv_shape) * it
    return kv_rows + mlp + attn + resid + sampling + state


def device_memory_stats() -> list[tuple[int, int]]:
    """(bytes_limit, bytes_in_use) of every addressable device, in device
    order — the ``kgct_hbm_bytes_{limit,in_use}`` gauges read the first,
    /health reports all (a tp mesh must show its shards balanced). (0, 0)
    where the backend keeps no statistics (CPU), so a fresh scrape is
    nan-free by construction; reading the runtime's counters is a
    host-side C call, never a device sync."""
    out = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        out.append((int(stats.get("bytes_limit", 0) or 0),
                    int(stats.get("bytes_in_use", 0) or 0)))
    return out
