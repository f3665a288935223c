"""Serving benchmark — prints ONE JSON line for the driver.

Primary metric (BASELINE.json north-star config 2): steady-state decode
tokens/sec/chip on **Llama-3-8B int4 (W4A16)** under continuous batching,
measured on whatever backend is default (the driver runs this on the real
TPU chip). The 8B int8 config runs alongside as the quant-ladder A/B (the
r1-r5 line), a TinyLlama-1.1B bf16 config as the continuity line with
rounds 1-4, and every config's JSON carries:

- prefill throughput + TTFT p50/p95 over THREE fresh-batch trials (one trial
  collapses all samples onto the per-step boundaries; see VERDICT r4 weak #2)
- greedy AND sampled (temperature=1.0, top_k=50, top_p=0.95) decode rates —
  serving traffic is not greedy, so the sampled path is measured, not assumed
- a roofline block: modeled HBM bytes/token and FLOPs/token against the
  chip's peak HBM bandwidth and bf16 matmul throughput (``hbm_bw_util``,
  ``mfu``) so "is this fast?" has an arithmetic answer, not a vibe
- for the primary config, a sustained-load phase: Poisson arrivals at ~70%
  of measured decode capacity, reporting TTFT under load — the north star's
  "p50 TTFT under continuous batching" taken literally

Measurement discipline (r1 finding: never time XLA compilation): every
figure is collected AFTER a warmup phase that triggers every jit compile.
Decode throughput hides the host<->device round trip via speculative window
chaining; TTFT/prefill include it (``ttft_breakdown`` attributes the split,
``host_rt_ms`` reports the trip as measured on the run's own device).

vs_baseline: the reference publishes no numbers (BASELINE.md "published:
{}"); the bar is a SELF-CHOSEN representative single-A100 vLLM decode
throughput per model class, labeled as such in the output.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import jax
import numpy as np

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.utils import cdiv

# SELF-CHOSEN comparison bars, not measured or published numbers: vLLM-class
# single-A100 decode throughput per model class (batch ~64 / ~32 for 8B).
A100_VLLM_TOKS_PER_S = {
    "tinyllama-1.1b": 6000.0,   # ~1B class
    "debug-tiny": 6000.0,       # CPU smoke path, ~1B bar for continuity
    "llama-3-8b": 1500.0,       # 8B class (BASELINE.json config 2)
    "llama-3-70b": 200.0,       # 70B class, per-chip share of an 8xA100 node
    "mixtral-8x7b": 800.0,      # MoE 47B-total/13B-active class
}

# Chip peaks for the roofline (TPU v5e public specs); overridable when the
# driver runs on different hardware. MXU peak is the bf16 number even for
# int8 serving: W8A16 converts inside the dot, the MACs are bf16.
CHIP_HBM_GBPS = float(os.environ.get("KGCT_CHIP_HBM_GBPS", 819.0))
CHIP_TFLOPS_BF16 = float(os.environ.get("KGCT_CHIP_TFLOPS_BF16", 197.0))

PROMPT_LEN = int(os.environ.get("KGCT_BENCH_PROMPT", 128))
# None = the engine's backend-derived page size (128 on TPU, 16 on CPU), so
# the bench measures the SHIPPED default config.
PAGE = (int(os.environ["KGCT_BENCH_PAGE"])
        if os.environ.get("KGCT_BENCH_PAGE") else None)
# Substeps per XLA program. Re-tuned in r4 after the kernel optimizations
# shortened per-substep device time: at matched token budgets W=48 beat
# W=32 in every interleaved pair — the fixed per-window host round trip
# amortizes worse once substeps got faster. (Measured on the r4 device
# attachment; not re-measured since — S0 re-seats it.)
DECODE_WINDOW = int(os.environ.get("KGCT_BENCH_WINDOW", 48))
# Prefill token budget per step — measured operating point after the
# segment-aware k-window prefill kernel (r4); see PARITY.md "TTFT lever".
PREFILL_BUDGET = int(os.environ.get("KGCT_BENCH_PREFILL_BUDGET", 4096))
WARMUP_WINDOWS = 3
BENCH_WINDOWS = int(os.environ.get("KGCT_BENCH_WINDOWS", 12))
PREFILL_TRIALS = 3
SAMPLED_WINDOWS = int(os.environ.get("KGCT_BENCH_SAMPLED_WINDOWS", 6))
LOAD_REQUESTS = int(os.environ.get("KGCT_BENCH_LOAD_REQS", 160))
LOAD_MAX_NEW = 128
LOAD_UTILIZATION = float(os.environ.get("KGCT_BENCH_LOAD_UTIL", 0.7))
# Overload phase: offered load ABOVE capacity, TTFT-budget admission control
# on — measures that shedding keeps admitted requests' TTFT inside budget
# while shed clients retry per Retry-After (the PR-2 QoS contract).
OVERLOAD_UTILIZATION = float(os.environ.get("KGCT_BENCH_OVERLOAD_UTIL", 1.3))
OVERLOAD_REQUESTS = int(os.environ.get("KGCT_BENCH_OVERLOAD_REQS", 64))
OVERLOAD_TTFT_BUDGET_MS = float(
    os.environ.get("KGCT_BENCH_TTFT_BUDGET_MS", 1000.0))
# Stall-free mixed prefill/decode batching (engine/mixed_batch.py). Default
# ON for the bench: the sustained-load phase is the north-star TTFT
# measurement and mixing is the scheduler-level fix it exists to validate;
# KGCT_BENCH_MIXED=0 runs the legacy prefill-else-decode policy (A/B).
MIXED_BATCH = os.environ.get("KGCT_BENCH_MIXED", "1") != "0"
# Speculative decoding phase (engine/spec/): greedy decode over a
# repetitive-suffix workload (the n-gram proposer's home turf), a three-way
# A/B on identically-seeded engines — off / n-gram / draft-MODEL (with
# acceptance-adaptive k) — reporting acceptance ratio, accepted tokens per
# spec step, and the draft-over-ngram speedup headline; plus a spec×mixed
# arm measuring chat TTFT with speculation AND mixed batching on against
# mixed-only (spec must no longer forfeit the stall-free TTFT win).
# KGCT_BENCH_SPEC=0 skips the phase; KGCT_BENCH_SPEC_K sets the draft
# length; KGCT_BENCH_SPEC_DRAFT names the draft preset (default: the
# target preset itself — same arch and seed, the oracle-draft harness
# ceiling; real small-draft checkpoints are a TPU-round story);
# KGCT_BENCH_SPEC_MIXED=0 skips the composition arm.
SPEC_BENCH = os.environ.get("KGCT_BENCH_SPEC", "1") != "0"
SPEC_K = int(os.environ.get("KGCT_BENCH_SPEC_K", 4))
SPEC_BATCH = int(os.environ.get("KGCT_BENCH_SPEC_BATCH", 4))
SPEC_MAX_NEW = int(os.environ.get("KGCT_BENCH_SPEC_MAX_NEW", 96))
SPEC_DRAFT = os.environ.get("KGCT_BENCH_SPEC_DRAFT", "")
SPEC_MIXED_BENCH = os.environ.get("KGCT_BENCH_SPEC_MIXED", "1") != "0"
SPEC_CHAT_PROBES = int(os.environ.get("KGCT_BENCH_SPEC_CHAT_PROBES", 6))
# Prefix-reuse phase (engine/kv_cache.PrefixCache): a shared-system-prompt
# workload — cold requests with unique prompts vs warm requests sharing a
# page-aligned prefix — showing warm-prefix TTFT collapsing toward the
# cost of prefilling only the unique tail. KGCT_BENCH_PREFIX=0 skips.
PREFIX_BENCH = os.environ.get("KGCT_BENCH_PREFIX", "1") != "0"
PREFIX_REQS = int(os.environ.get("KGCT_BENCH_PREFIX_REQS", 6))
PREFIX_TAIL = int(os.environ.get("KGCT_BENCH_PREFIX_TAIL", 16))
# KV-swap phase (engine/kv_cache two-tier cache): a session workload
# oversubscribed ~KGCT_BENCH_SWAP_OVERSUB x the HBM page pool, A/B
# swap-preemption (host-DRAM tier) vs recompute-preemption on
# identically-seeded engines, reporting resumed-session TTFT (preempt ->
# next emitted token) and preemption counts. KGCT_BENCH_SWAP=0 skips.
SWAP_BENCH = os.environ.get("KGCT_BENCH_SWAP", "1") != "0"
SWAP_SESSIONS = int(os.environ.get("KGCT_BENCH_SWAP_SESSIONS", 8))
SWAP_OVERSUB = float(os.environ.get("KGCT_BENCH_SWAP_OVERSUB", 2.0))
SWAP_MAX_NEW = int(os.environ.get("KGCT_BENCH_SWAP_MAX_NEW", 48))
# Router phase (serving/router.py prefix-affinity): a shared-prefix SESSION
# workload replayed through the REAL router over >= 2 in-process engine
# replicas, A/B least-inflight vs prefix-affinity on identically-seeded
# engines. Least-inflight scatters a session's repeat requests across
# replicas (each replica must re-prefill the shared prefix before its own
# cache warms); affinity routes them to the ring owner whose cache is
# already hot — the phase reports warm-request TTFT and per-replica
# prefix-cache hit ratios for both arms. Always runs debug-tiny engines
# (the phase measures ROUTING locality, not model speed, and on TPU the
# primary config's pool must not be re-instantiated N more times).
# KGCT_BENCH_ROUTER=0 skips.
ROUTER_BENCH = os.environ.get("KGCT_BENCH_ROUTER", "1") != "0"
ROUTER_REPLICAS = int(os.environ.get("KGCT_BENCH_ROUTER_REPLICAS", 2))
# Sessions deliberately coprime with the replica count: least-inflight's
# round-robin tie-break then alternates each session across replicas
# (the scatter the affinity policy exists to fix); an equal multiple would
# park session s on replica s % N by accident and hide the effect.
ROUTER_SESSIONS = int(os.environ.get("KGCT_BENCH_ROUTER_SESSIONS",
                                     ROUTER_REPLICAS + 1))
ROUTER_ROUNDS = int(os.environ.get("KGCT_BENCH_ROUTER_ROUNDS", 3))
# Disaggregation phase (serving/handoff.py + router prefill pool): a MIXED
# long-prefill/long-decode workload A/B'd through the real serving stack —
# 1 prefill + 1 decode replica (role-split, KV-page handoff) vs 2 colocated
# replicas, all identically seeded. Mixed batching is OFF in both arms so
# the colocated arm exhibits the full prefill/decode interference
# disaggregation removes (the DistServe regime; mixed batching only BOUNDS
# it). Sustained decode TPOT p95 and TTFT p50 come from ONE router scrape
# per arm (the relabeled per-replica histograms). Always debug-tiny
# engines, like the router phase. KGCT_BENCH_DISAGG=0 skips.
DISAGG_BENCH = os.environ.get("KGCT_BENCH_DISAGG", "1") != "0"
DISAGG_DECODE_SESSIONS = int(os.environ.get("KGCT_BENCH_DISAGG_SESSIONS", 3))
DISAGG_DECODE_ROUNDS = int(os.environ.get("KGCT_BENCH_DISAGG_ROUNDS", 2))
DISAGG_PREFILLS = int(os.environ.get("KGCT_BENCH_DISAGG_PREFILLS", 6))
DISAGG_MAX_NEW = int(os.environ.get("KGCT_BENCH_DISAGG_MAX_NEW", 16))

# Drain phase (session survivability A/B): an oversubscribed streaming
# session workload over 2 replicas behind the router; one replica begins a
# SIGTERM drain mid-stream, once with live KV migration (drain time is
# transfer-bound: push each running sequence to the peer, the router
# splices the resumed streams) and once with migration disabled via the
# migrate_fail chaos site (the pre-migration wait-it-out path: drain time
# is bound by the longest remaining decode). Headline
# ``drain_migrate_over_wait_seconds`` = migrate-arm drain seconds /
# wait-arm drain seconds. Always debug-tiny engines. KGCT_BENCH_DRAIN=0
# skips.
DRAIN_BENCH = os.environ.get("KGCT_BENCH_DRAIN", "1") != "0"
DRAIN_SESSIONS = int(os.environ.get("KGCT_BENCH_DRAIN_SESSIONS", 6))
DRAIN_MAX_NEW = int(os.environ.get("KGCT_BENCH_DRAIN_MAX_NEW", 48))

# Fleet-cache phase (serving/fleet_cache.py — global prefix cache over the
# handoff substrate): shared-prefix sessions warmed on an OWNER replica and
# then forced onto a NON-OWNER (the router's affinity-overflow case: the
# owner is over-bound, the pick lands elsewhere and carries the
# x-kgct-prefix-source hint). A/B on identically-seeded replica pairs:
# fleet cache ON pulls the owner's cached prefix into the non-owner's
# cache (streamed import, roofline-gated); OFF recomputes the full prefix.
# Headline ``fleet_prefix_pull_over_recompute_ttft`` = pull-arm warm TTFT
# p50 / recompute-arm's (< 1 = pulling beats re-prefilling). Always
# debug-tiny engines, like every multi-replica phase.
# KGCT_BENCH_FLEET_CACHE=0 skips.
FLEET_BENCH = os.environ.get("KGCT_BENCH_FLEET_CACHE", "1") != "0"
FLEET_SESSIONS = int(os.environ.get("KGCT_BENCH_FLEET_SESSIONS", 3))
# Shared-prefix length: long enough that the recompute arm's full prefill
# clearly exceeds one localhost pull + tail chunk on CPU.
FLEET_SHARED = int(os.environ.get("KGCT_BENCH_FLEET_SHARED", 384))

# Multi-tenant QoS phase (engine/qos.py): a mixed chat+batch workload at
# SATURATION — batch-tier jobs hold every scheduler seat while short
# interactive requests arrive one at a time — A/B'd on identically-seeded
# engines with QoS tiers on vs off. Off, each chat request queues until a
# whole batch job finishes; on, priority make-room preemption (swap-backed)
# and fair-share promotion admit it immediately. Headline
# ``qos_chat_ttft_protected_ratio`` = chat p95 TTFT with QoS / without
# (< 1 = protected). The phase also runs the per-tier ADMISSION ledger
# under a deterministic tenant_flood chaos burst, reporting the per-tier
# shed split (the overload must attribute to the batch tier alone).
# KGCT_BENCH_QOS=0 skips.
QOS_BENCH = os.environ.get("KGCT_BENCH_QOS", "1") != "0"
QOS_BATCH_SEQS = int(os.environ.get("KGCT_BENCH_QOS_BATCH", 4))
QOS_CHAT_REQS = int(os.environ.get("KGCT_BENCH_QOS_CHAT_REQS", 6))
QOS_BATCH_MAX_NEW = int(os.environ.get("KGCT_BENCH_QOS_BATCH_MAX_NEW", 48))
QOS_CHAT_MAX_NEW = int(os.environ.get("KGCT_BENCH_QOS_CHAT_MAX_NEW", 8))

# The stdout contract bench.py guarantees (also the --help epilog, and what
# tests/test_bench_contract.py pins): everything before the last line is
# free-form noise; the LAST non-empty stdout line is the result.
OUTPUT_CONTRACT = """\
Output contract (the driver's official record depends on it):

  The LAST non-empty line of stdout is the benchmark result — exactly one
  single-line JSON object (json.dumps, no embedded newlines), written and
  flushed after everything else. All logging goes to stderr; any earlier
  stdout noise is flushed before the result so interleaving cannot split
  the line. Consumers must parse ONLY that last line (parse_result_line()
  implements this), never scan stdout for something JSON-shaped.

  The result line is BOUNDED to RESULT_LINE_MAX bytes: capture harnesses
  keep only a stdout TAIL (the r5 record kept 2000 chars and decapitated
  an oversized result line into "parsed": null). When the full result
  would exceed the bound, the bulky per-config detail ("configs") moves to
  stderr as a FULL_RESULT line and the stdout result keeps every headline
  field plus "configs_on_stderr": true.
"""

# The driver's transcript tail window is 2000 chars (BENCH_r05.json);
# bound the result line well under it so a tail capture can never cut the
# line's head off again. tests/test_bench_contract.py pins this.
RESULT_LINE_MAX = 1600


def _mk_engine(model_name: str, quant, batch: int, max_new: int,
               window: int, budget: int, page_slack: int = 3):
    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    # Ceil-divide: a floor here under-provisions the pool whenever the page
    # size doesn't divide the sequence budget (fatal with page_slack=0).
    pages_per_seq = cdiv(PROMPT_LEN + max_new, page) + page_slack
    cfg = EngineConfig(
        model=get_model_config(model_name).replace(quantization=quant),
        cache=CacheConfig(page_size=page, num_pages=batch * pages_per_seq + 1),
        scheduler=SchedulerConfig(
            max_num_seqs=batch, max_prefill_tokens=budget,
            decode_buckets=(batch,), prefill_buckets=(budget,),
            decode_window=window, mixed_batch_enabled=MIXED_BATCH))
    return LLMEngine(cfg, eos_token_id=None)


def _add_batch(engine, rng, vocab, tag, batch, max_new, **samp):
    samp.setdefault("temperature", 0.0)
    params = SamplingParams(max_tokens=max_new, **samp)
    t = time.perf_counter()
    for i in range(batch):
        prompt = rng.integers(1, vocab, PROMPT_LEN).tolist()
        engine.add_request(f"{tag}-{i}", prompt, params)
    return t


def _drain(engine, tag, batch):
    for i in range(batch):
        engine.abort_request(f"{tag}-{i}")
    while engine.has_unfinished_requests():
        engine.step()


def _measure_host_rt_s() -> float:
    """Median host<->device round trip for a tiny dispatched op; part of
    every synchronous step's TTFT, reported separately."""
    x = jax.numpy.zeros((1,), jax.numpy.float32)
    f = jax.jit(lambda a: a + 1)
    f(x).block_until_ready()  # compile outside the timing
    ts = []
    for _ in range(5):
        t = time.perf_counter()
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t)
    return sorted(ts)[len(ts) // 2]


def _median(xs, default=float("nan")):
    # ADVICE r4: never IndexError into an empty list and mask the real
    # misconfiguration (e.g. all requests finished during warmup).
    return sorted(xs)[len(xs) // 2] if xs else default


def _percentile(xs, q, default=float("nan")):
    if not xs:
        return default
    xs = sorted(xs)
    return xs[min(int(len(xs) * q), len(xs) - 1)]


# --------------------------------------------------------------------------
# Roofline model
# --------------------------------------------------------------------------

def _weight_stream_bytes(mcfg, quant) -> int:
    """Modeled HBM bytes to stream every matmul weight once (one decode
    step), at the quant ladder's REAL storage layout (ops/quant.py):
    bf16/f32 at dtype bytes per weight; int8 at 1 B/w plus one f32 scale
    per output channel; int4 at 0.5 B/w (two nibbles packed per byte) plus
    one f32 scale per (input group, output channel) — the scale overhead is
    what keeps int4 at ~0.53x int8, not an idealized 0.5x. MoE streams ALL
    expert weights (at serving batch sizes every expert is hit)."""
    h, inter = mcfg.hidden_size, mcfg.intermediate_size
    nh, nkv, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim
    L, V = mcfg.num_layers, mcfg.vocab_size
    dtype_bytes = 2 if mcfg.dtype == "bfloat16" else 4
    n_exp = max(mcfg.num_experts, 1)
    gs = mcfg.quant_group_size
    # (in_dim, out_dim, count) per streamed matmul class — matches
    # ops/quant.QUANT_LAYER_KEYS plus lm_head.
    mats = [(h, nh * hd, L), (h, nkv * hd, 2 * L), (nh * hd, h, L),
            (h, inter, 2 * L * n_exp), (inter, h, L * n_exp)]
    if not mcfg.tie_word_embeddings:
        mats.append((h, V, 1))
    total = 0
    for din, dout, count in mats:
        if quant == "int4":
            per = din * dout // 2 + 4 * (din // gs) * dout
        elif quant == "int8":
            per = din * dout + 4 * dout
        else:
            per = din * dout * dtype_bytes
        total += per * count
    return total


def _roofline(mcfg, quant, batch: int, ctx: int) -> dict:
    """Modeled per-step HBM traffic and per-token matmul FLOPs for decode at
    context length ``ctx``. Weight-streaming accounting matches
    ops/quant.QUANT_LAYER_KEYS storage exactly (packed bytes + scales; see
    _weight_stream_bytes); embeddings/norms stream at the serving dtype.
    MoE streams ALL expert weights per step (at serving batch sizes every
    expert is hit) but only num_experts_per_tok experts contribute
    per-token FLOPs."""
    h, inter = mcfg.hidden_size, mcfg.intermediate_size
    nh, nkv, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim
    L, V = mcfg.num_layers, mcfg.vocab_size

    attn_p = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    mlp_unit = 3 * h * inter
    active_exp = mcfg.num_experts_per_tok if mcfg.is_moe else 1
    layer_active = attn_p + active_exp * mlp_unit       # flops: routed only

    # Per decode step: every matmul weight streams once (batch amortizes);
    # each sequence reads its KV history and writes one slot.
    kv_token_bytes = 2 * L * nkv * hd * 2               # bf16 KV
    weight_stream = _weight_stream_bytes(mcfg, quant)
    step_bytes = weight_stream + batch * kv_token_bytes * ctx
    # Per-token matmul FLOPs (2 per MAC) + attention score/value FLOPs.
    flops_per_token = 2 * (L * layer_active + V * h) + 4 * L * nh * hd * ctx
    return {
        "weight_stream_bytes": int(weight_stream),
        "kv_bytes_per_step": int(batch * kv_token_bytes * ctx),
        "step_bytes": int(step_bytes),
        "flops_per_token": int(flops_per_token),
    }


def _roofline_prefill(mcfg, quant, T: int) -> dict:
    """Modeled ragged-prefill step of ``T`` flattened prompt tokens — the
    arithmetic target TTFT optimization regresses against (ROADMAP item #5:
    the roofline used to model decode only while prefill was the weak
    phase).

    FLOPs: every matmul runs over all T tokens (2 FLOPs/MAC, routed experts
    only for MoE) plus causal attention score+value FLOPs (~T^2/2 valid
    pairs). Logits project only the B sampled rows, not T — excluded from
    FLOPs, like the decode model excludes sampling (the head WEIGHT still
    counts in the byte stream: it is read every sampling step). Bytes: the
    weight stream (every matmul weight once per step — amortized over T,
    which is why prefill is compute-bound where decode is
    weight-streaming-bound) plus the step's KV writes; activations are
    omitted (VMEM-resident at these shapes).
    ``flops_per_byte`` makes the regime explicit: compared against the
    chip's peak FLOPs/peak bandwidth ratio (~240 on v5e), prefill at
    budget-sized T sits far above it — any TTFT prefill-phase time beyond
    ``compute_bound_ms`` is overhead (padding, layout, host), not physics.
    """
    h, inter = mcfg.hidden_size, mcfg.intermediate_size
    nh, nkv, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim
    L = mcfg.num_layers

    attn_p = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    mlp_unit = 3 * h * inter
    active_exp = mcfg.num_experts_per_tok if mcfg.is_moe else 1
    layer_active = attn_p + active_exp * mlp_unit

    matmul_flops = 2 * T * L * layer_active
    attn_flops = 4 * L * nh * hd * (T * T) // 2     # causal: ~half the pairs
    flops_step = matmul_flops + attn_flops
    kv_token_bytes = 2 * L * nkv * hd * 2           # bf16 KV
    bytes_step = _weight_stream_bytes(mcfg, quant) + T * kv_token_bytes
    return {
        "tokens_modeled": int(T),
        "flops_per_step": int(flops_step),
        "flops_per_token": int(flops_step // max(T, 1)),
        "bytes_per_step": int(bytes_step),
        "flops_per_byte": round(flops_step / bytes_step, 1),
        "compute_bound_ms": round(
            flops_step / (CHIP_TFLOPS_BF16 * 1e12) * 1e3, 3),
        "hbm_bound_ms": round(bytes_step / (CHIP_HBM_GBPS * 1e9) * 1e3, 3),
    }


def _utilization(model_acct: dict, toks_per_s: float, batch: int) -> dict:
    steps_per_s = toks_per_s / batch
    hbm_gbps = steps_per_s * model_acct["step_bytes"] / 1e9
    mfu = toks_per_s * model_acct["flops_per_token"] / (CHIP_TFLOPS_BF16 * 1e12)
    return {
        "hbm_gbps": round(hbm_gbps, 1),
        "hbm_bw_util": round(hbm_gbps / CHIP_HBM_GBPS, 3),
        "mfu": round(mfu, 4),
    }


# --------------------------------------------------------------------------
# Measurement phases
# --------------------------------------------------------------------------

def _measure_prefill_ttft(engine, rng, vocab, batch, max_new, host_rt_s):
    """PREFILL_TRIALS fresh-batch prefill trials; TTFT samples pooled across
    trials so the percentiles stop being a 2-step boundary artifact. The
    LAST trial's batch is left running for the decode phase."""
    trial_rates, ttfts = [], []
    breakdown = None
    for t in range(PREFILL_TRIALS):
        tag = f"bench{t}"
        t_submit = _add_batch(engine, rng, vocab, tag, batch, max_new)
        first_token_at = {}
        steps = 0
        t0 = time.perf_counter()
        while engine.scheduler.waiting:
            outs = engine.step()
            steps += 1
            now = time.perf_counter()
            for o in outs:
                if o.new_token_ids and o.request_id not in first_token_at:
                    first_token_at[o.request_id] = now
        wall = time.perf_counter() - t0
        trial_rates.append(batch * PROMPT_LEN / wall)
        ttfts.extend(t - t_submit for t in first_token_at.values())
        breakdown = {
            "host_rt_ms": round(host_rt_s * 1e3, 1),
            "prefill_steps": steps,
            "prefill_wall_ms": round(wall * 1e3, 1),
            "est_prefill_compute_ms": round(
                max(wall - steps * host_rt_s, 0.0) * 1e3, 1),
        }
        if t < PREFILL_TRIALS - 1:
            _drain(engine, tag, batch)
    return {
        "prefill_tokens_per_sec": round(_median(trial_rates), 1),
        "prefill_trials": PREFILL_TRIALS,
        "ttft_p50_ms": round(_percentile(ttfts, 0.50) * 1e3, 1),
        "ttft_p95_ms": round(_percentile(ttfts, 0.95) * 1e3, 1),
        "ttft_breakdown": breakdown,
    }, f"bench{PREFILL_TRIALS - 1}"


def _measure_decode(engine, n_windows, phases=3):
    """Steady-state decode: one priming step so the speculative window chain
    is in flight, then ``phases`` consecutive phases whose MEDIAN rate is
    reported (a median over temporally-close phases keeps one bad window
    from defining the number)."""
    outs = engine.step()
    phase_rates = []
    per_phase = max(1, n_windows // phases)
    for _ in range(phases):
        new_tokens = 0
        t0 = time.perf_counter()
        for _ in range(per_phase):
            outs = engine.step()
            if not outs:
                break
            new_tokens += sum(len(o.new_token_ids or []) for o in outs)
        elapsed = time.perf_counter() - t0
        if new_tokens:
            phase_rates.append(new_tokens / elapsed)
        if not outs:
            break
    return _median(phase_rates)


def _measure_sampled_decode(engine, rng, vocab, batch, max_new):
    """Fresh batch at temperature=1.0/top_k=50/top_p=0.95 — compiles and
    measures the SAMPLED decode program (real serving traffic is not
    greedy; r4's headline silently assumed it was)."""
    tag = "sampled"
    _add_batch(engine, rng, vocab, tag, batch, max_new,
               temperature=1.0, top_k=50, top_p=0.95)
    while engine.scheduler.waiting:
        engine.step()
    engine.step()   # first sampled window: compile + prime
    rate = _measure_decode(engine, SAMPLED_WINDOWS, phases=2)
    _drain(engine, tag, batch)
    return rate


def _measure_sustained(engine, rng, vocab, batch, rate_rps):
    """Poisson arrivals at ``rate_rps`` until LOAD_REQUESTS complete their
    first token. TTFT is measured from the scheduled ARRIVAL time (includes
    host/queueing delay — admission fairness under steady load), throughput
    over the whole phase."""
    n = LOAD_REQUESTS
    params = SamplingParams(temperature=0.0, max_tokens=LOAD_MAX_NEW)
    gaps = rng.exponential(1.0 / rate_rps, n)
    arrivals = np.cumsum(gaps)
    first_at, submitted = {}, 0
    new_tokens = 0
    start = time.perf_counter()
    while len(first_at) < n:
        now = time.perf_counter() - start
        while submitted < n and arrivals[submitted] <= now:
            prompt = rng.integers(1, vocab, PROMPT_LEN).tolist()
            engine.add_request(f"load-{submitted}", prompt, params)
            submitted += 1
        if engine.has_unfinished_requests():
            outs = engine.step()
            t_now = time.perf_counter() - start
            for o in outs:
                new_tokens += len(o.new_token_ids or [])
                if o.new_token_ids and o.request_id not in first_at:
                    first_at[o.request_id] = t_now
        elif submitted < n:
            time.sleep(min(arrivals[submitted] - now, 0.05))
    wall = time.perf_counter() - start
    for i in range(n):
        engine.abort_request(f"load-{i}")
    while engine.has_unfinished_requests():
        engine.step()
    ttfts = [first_at[f"load-{i}"] - arrivals[i] for i in range(n)
             if f"load-{i}" in first_at]
    return {
        "offered_rate_rps": round(rate_rps, 2),
        "n_requests": n,
        "ttft_p50_ms": round(_percentile(ttfts, 0.50) * 1e3, 1),
        "ttft_p95_ms": round(_percentile(ttfts, 0.95) * 1e3, 1),
        "throughput_tokens_per_sec": round(new_tokens / wall, 1),
    }


def _measure_overload(engine, rng, vocab, rate_rps, budget_ms):
    """Poisson arrivals ABOVE decode capacity with TTFT-budget admission
    control (resilience.AdmissionController — the same control loop the API
    server runs). A shed client honors Retry-After: it re-attempts after the
    advised backoff, up to ``max_retries`` times, then counts as dropped.
    Reports the shed/delivered split and whether ADMITTED requests kept
    their TTFT — the acceptance bar is that overload degrades the shed
    count, not the admitted requests' latency."""
    from kubernetes_gpu_cluster_tpu.resilience import AdmissionController

    n = OVERLOAD_REQUESTS
    max_retries = 2
    adm = AdmissionController(engine, default_budget_ms=budget_ms)
    # SLO layer reads for THIS phase: grade attainment against the same
    # budget admission control sheds on, over a window that starts here
    # (the sustained phase's samples would dilute the overload readout).
    engine.obs.slo.ttft_budget_ms = budget_ms
    engine.obs.slo.clear()
    params = SamplingParams(temperature=0.0, max_tokens=LOAD_MAX_NEW)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, n))
    attempt_at = list(arrivals)          # next admission attempt per request
    retries = [0] * n
    pending = set(range(n))              # not yet admitted or dropped
    submit_at: dict = {}                 # i -> admission time
    first_at: dict = {}                  # i -> first-token time
    dropped: set = set()
    start = time.perf_counter()
    while len(first_at) + len(dropped) < n:
        now = time.perf_counter() - start
        for i in sorted(pending):
            if attempt_at[i] > now:
                continue
            retry_after = adm.check(None)
            if retry_after is None:
                prompt = rng.integers(1, vocab, PROMPT_LEN).tolist()
                engine.add_request(f"over-{i}", prompt, params)
                submit_at[i] = now
                pending.discard(i)
            elif retries[i] >= max_retries:
                dropped.add(i)
                pending.discard(i)
            else:
                retries[i] += 1
                attempt_at[i] = now + retry_after
        if engine.has_unfinished_requests():
            outs = engine.step()
            t_now = time.perf_counter() - start
            for o in outs:
                if (o.new_token_ids and o.request_id.startswith("over-")
                        and o.request_id not in first_at):
                    first_at[o.request_id] = t_now
        elif pending:
            nxt = min(attempt_at[i] for i in pending)
            time.sleep(min(max(nxt - now, 0.0), 0.05))
    # Re-key first-token times by request index for the TTFT join.
    first_by_i = {int(rid.split("-")[1]): t for rid, t in first_at.items()}
    for i in range(n):
        engine.abort_request(f"over-{i}")
    while engine.has_unfinished_requests():
        engine.step()
    # TTFT measured from the ADMITTED attempt (the request whose budget the
    # controller accepted), which is the QoS the 429 contract protects.
    ttfts = [first_by_i[i] - submit_at[i] for i in first_by_i]
    violations = sum(1 for t in ttfts if t * 1e3 > budget_ms)
    return {
        "offered_rate_rps": round(rate_rps, 2),
        "ttft_budget_ms": budget_ms,
        "n_requests": n,
        "delivered": len(first_by_i),
        "dropped_after_retries": len(dropped),
        "shed_attempts": adm.shed_total,
        "retried_clients": sum(1 for r in retries if r > 0),
        # None, not NaN, when everything was shed: json.dumps emits a bare
        # NaN token strict parsers reject — the exact guaranteed-last-line
        # regression the PR-1 emit contract exists to prevent.
        "ttft_p50_ms": (round(_percentile(ttfts, 0.50) * 1e3, 1)
                        if ttfts else None),
        "ttft_p95_ms": (round(_percentile(ttfts, 0.95) * 1e3, 1)
                        if ttfts else None),
        "ttft_budget_violations": violations,
        # The rolling SLO gauges the autoscaler (ROADMAP 4(b)) will consume,
        # read engine-side at phase end: attainment over the phase's
        # admitted requests and budget-meeting goodput. BENCH_r06 captures
        # attainment alongside raw TTFT.
        "slo_ttft_attainment_ratio": round(engine.obs.slo.attainment(), 3),
        "slo_goodput_tokens_per_sec": round(
            engine.obs.slo.goodput_tokens_per_sec(), 1),
    }


def _measure_spec(model_name: str, quant, rng) -> dict:
    """Speculative-decoding phase: greedy decode over a repetitive-suffix
    workload (prompts built from a short repeated pattern, so prompt-lookup
    drafts hit), a three-way A/B on engines with IDENTICAL weights (same
    config seed): off ("base"), n-gram ("spec"), and draft-MODEL with
    acceptance-adaptive k ("draft"). Reports per arm the acceptance ratio,
    accepted draft tokens per spec step (the >1.0 bar that makes a verify
    step beat a plain decode step in tokens), and decode tokens/sec; the
    draft arm adds the adaptive controller's live k and movement counts.

    Draft-model caveat (CPU): the default draft is the TARGET preset at
    the SAME seed — an oracle draft (acceptance ~1.0) that validates the
    two-model machinery and the adaptive ceiling, but whose per-token
    draft cost equals the target's, so `spec_draft_over_ngram_speedup`
    measures harness overhead, not the production win. The production
    ratio needs a genuinely small draft (KGCT_BENCH_SPEC_DRAFT, e.g.
    tinyllama-1.1b drafting for llama-3-8b) and real checkpoints — the
    BENCH_r06 TPU round (ROADMAP item 1(b)). Runs after the main config's
    engine is freed — on-chip, extra model instantiations must not
    overlap the big serving pool."""
    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    pattern = rng.integers(1, 200, 12).tolist()
    reps = cdiv(PROMPT_LEN, len(pattern))
    prompts = [(pattern * reps)[:PROMPT_LEN] for _ in range(SPEC_BATCH)]
    params = SamplingParams(max_tokens=SPEC_MAX_NEW, temperature=0.0)
    draft_name = SPEC_DRAFT or model_name
    out = {"k": SPEC_K, "batch": SPEC_BATCH, "max_new": SPEC_MAX_NEW,
           "draft_model": draft_name}

    arms = (("base", False, None), ("spec", True, None),
            ("draft", True, draft_name))
    for label, spec, draft in arms:
        pages_per_seq = cdiv(PROMPT_LEN + SPEC_MAX_NEW + SPEC_K, page) + 2
        cfg = EngineConfig(
            model=get_model_config(model_name).replace(quantization=quant),
            cache=CacheConfig(page_size=page,
                              num_pages=SPEC_BATCH * pages_per_seq + 1),
            scheduler=SchedulerConfig(
                max_num_seqs=SPEC_BATCH, max_prefill_tokens=PREFILL_BUDGET,
                decode_buckets=(SPEC_BATCH,), prefill_buckets=(PREFILL_BUDGET,),
                decode_window=DECODE_WINDOW, mixed_batch_enabled=False,
                spec_decode_enabled=spec, num_speculative_tokens=SPEC_K,
                spec_draft_model=draft, spec_adaptive_k=draft is not None))
        engine = LLMEngine(cfg, eos_token_id=None)
        # Warmup pass compiles every program this workload touches (the
        # measurement discipline: never time XLA compilation).
        for i, p in enumerate(prompts):
            engine.add_request(f"warm-{i}", list(p), params)
        while engine.has_unfinished_requests():
            engine.step()
        for i, p in enumerate(prompts):
            engine.add_request(f"m-{i}", list(p), params)
        while engine.scheduler.waiting:
            engine.step()
        steps0 = engine.stats.steps
        drafted0 = engine.obs.spec_drafted_tokens
        accepted0 = engine.obs.spec_accepted_tokens
        spec_steps0 = engine.obs.step_kind_counts["spec"]
        new_tokens = 0
        t0 = time.perf_counter()
        while engine.has_unfinished_requests():
            new_tokens += sum(len(o.new_token_ids or [])
                              for o in engine.step())
        wall = time.perf_counter() - t0
        out[label] = {
            "decode_tokens_per_sec": round(new_tokens / wall, 1),
            "decode_steps": engine.stats.steps - steps0,
        }
        if spec:
            drafted = engine.obs.spec_drafted_tokens - drafted0
            accepted = engine.obs.spec_accepted_tokens - accepted0
            n_spec = engine.obs.step_kind_counts["spec"] - spec_steps0
            out[label].update({
                "spec_steps": n_spec,
                "drafted_tokens": drafted,
                "accepted_tokens": accepted,
                "acceptance_ratio": (round(accepted / drafted, 3)
                                     if drafted else None),
                "accepted_tokens_per_spec_step": (round(accepted / n_spec, 2)
                                                  if n_spec else None),
            })
        ctrl = engine.scheduler.spec_controller
        if ctrl is not None:
            out[label]["adaptive_k"] = {
                "current_k": ctrl.current_k, "ladder": list(ctrl.ladder),
                "steps_down": ctrl.num_steps_down,
                "steps_up": ctrl.num_steps_up,
            }
        del engine
        gc.collect()
    base, spec, draft = out["base"], out["spec"], out["draft"]
    out["speedup"] = (round(spec["decode_tokens_per_sec"]
                            / base["decode_tokens_per_sec"], 3)
                      if base["decode_tokens_per_sec"] else None)
    out["spec_draft_over_ngram_speedup"] = (
        round(draft["decode_tokens_per_sec"]
              / spec["decode_tokens_per_sec"], 3)
        if spec["decode_tokens_per_sec"] else None)
    if SPEC_MIXED_BENCH:
        out["spec_mixed"] = _measure_spec_mixed(model_name, quant, rng)
    return out


def _measure_spec_mixed(model_name: str, quant, rng) -> dict:
    """Spec×mixed composition arm: chat TTFT with speculation AND mixed
    batching on, against mixed-only. Before the composition landed,
    enabling spec forfeited the stall-free TTFT win (spec rows and a
    prefill chunk could not share a device step); now the mixed step
    carries every running row's verify slice plus the budgeted chunk, so
    chat TTFT with both on must sit within noise of mixed-only at the
    same load — that non-regression IS the result, with the spec arm's
    decode acceleration riding along for free.

    Load shape: SPEC_BATCH repetitive long-decode sessions saturate the
    batch (the n-gram proposer's home turf, so verify slices are real),
    then SPEC_CHAT_PROBES short chat prompts arrive serially; each
    probe's TTFT is measured while the sessions keep decoding, and the
    sessions' decode progress per step is reported alongside — the
    composition's actual win is BOTH columns at once (chat TTFT parity
    with mixed-only while the sessions advance accepted+1 tokens per
    step instead of one). The step token budget is sized for the verify
    slices (chat_len + batch*(k+1) — the operator guidance: a budget
    tuned for 1-token decode rows would starve the chunk once rows widen
    to S tokens)."""
    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    pattern = rng.integers(1, 200, 8).tolist()
    sess_len = max(64, min(PROMPT_LEN, 256))
    reps = cdiv(sess_len, len(pattern))
    sess_prompts = [(pattern * reps)[:sess_len] for _ in range(SPEC_BATCH)]
    chat_len = 48
    sess_new, chat_new = 512, 8
    budget = chat_len + SPEC_BATCH * (SPEC_K + 1)
    out = {"sessions": SPEC_BATCH, "chat_probes": SPEC_CHAT_PROBES}

    for label, spec in (("mixed_only", False), ("spec_mixed", True)):
        pages_per_seq = cdiv(sess_len + sess_new + SPEC_K, page) + 2
        cfg = EngineConfig(
            model=get_model_config(model_name).replace(quantization=quant),
            cache=CacheConfig(
                page_size=page,
                num_pages=(SPEC_BATCH + 2) * pages_per_seq + 1),
            scheduler=SchedulerConfig(
                max_num_seqs=SPEC_BATCH + 2, max_prefill_tokens=chat_len,
                decode_priority_token_budget=budget,
                decode_buckets=(1, 2, 4, max(8, SPEC_BATCH + 2)),
                prefill_buckets=(chat_len, 2 * chat_len),
                decode_window=DECODE_WINDOW, mixed_batch_enabled=True,
                spec_decode_enabled=spec, num_speculative_tokens=SPEC_K))
        engine = LLMEngine(cfg, eos_token_id=None)
        sess_params = SamplingParams(max_tokens=sess_new, temperature=0.0)
        chat_params = SamplingParams(max_tokens=chat_new, temperature=0.0)
        # Warmup: one session + one chat probe compile the families.
        engine.add_request("warm-s", list(sess_prompts[0]), sess_params)
        for _ in range(8):
            engine.step()
        engine.add_request("warm-c", rng.integers(1, 200, chat_len).tolist(),
                           chat_params)
        while engine.has_unfinished_requests():
            engine.step()
        # Saturate decode, then probe chat TTFT serially mid-decode.
        for i, p in enumerate(sess_prompts):
            engine.add_request(f"s-{i}", list(p), sess_params)
        for _ in range(SPEC_BATCH + 8):
            engine.step()

        def probe(rid):
            prompt = rng.integers(1, 200, chat_len).tolist()
            t0 = time.perf_counter()
            engine.add_request(rid, prompt, chat_params)
            ttft = None
            while ttft is None and engine.has_unfinished_requests():
                for o in engine.step():
                    if o.request_id == rid and o.new_token_ids:
                        ttft = time.perf_counter() - t0
                        break
            engine.abort_request(rid)
            return ttft if ttft is not None else float("nan")

        # Two unmeasured probes compile the chunk-bearing step families
        # against the NOW-draftable session batch (warm-c above ran before
        # the sessions existed, so the spec×mixed shape first appears
        # here).
        for i in range(2):
            probe(f"warm-probe-{i}")
        # EVERY reported quantity is a measured-window delta over one
        # consistent baseline (warmup + warm probes excluded), and session
        # tokens are read off the Sequence OBJECTS — a session that
        # finishes mid-window keeps its token history, where a
        # running-set re-scan would silently drop it.
        kinds0 = dict(engine.obs.step_kind_counts)
        drafted0 = engine.obs.spec_drafted_tokens
        accepted0 = engine.obs.spec_accepted_tokens
        sess_seqs = [s for s in engine.scheduler.running
                     if s.request_id.startswith("s-")]
        sess_tokens0 = sum(len(s.output_token_ids) for s in sess_seqs)
        t0_probe = time.perf_counter()
        ttfts = [probe(f"chat-{i}") for i in range(SPEC_CHAT_PROBES)]
        probe_wall = time.perf_counter() - t0_probe
        sess_tokens = sum(len(s.output_token_ids)
                          for s in sess_seqs) - sess_tokens0
        kinds = {k: engine.obs.step_kind_counts[k] - kinds0[k]
                 for k in kinds0}
        arm = {
            "chat_ttft_p50_ms": round(_percentile(ttfts, 0.5) * 1e3, 2),
            "mixed_steps": kinds["mixed"] + kinds["spec_mixed"],
            # The throughput half of the composition: how fast the decode
            # sessions advanced WHILE chat probes were in flight
            # (spec×mixed rows commit accepted+1 per step; mixed-only
            # rows commit one).
            "session_tokens_per_sec": (round(sess_tokens / probe_wall, 1)
                                       if probe_wall > 0 else None),
        }
        if spec:
            drafted = engine.obs.spec_drafted_tokens - drafted0
            accepted = engine.obs.spec_accepted_tokens - accepted0
            arm["spec_mixed_steps"] = kinds["spec_mixed"]
            arm["spec_steps"] = kinds["spec"] + kinds["spec_mixed"]
            arm["acceptance_ratio"] = (round(accepted / drafted, 3)
                                       if drafted else None)
        out[label] = arm
        del engine
        gc.collect()
    base = out["mixed_only"]["chat_ttft_p50_ms"]
    out["chat_ttft_spec_over_mixed"] = (
        round(out["spec_mixed"]["chat_ttft_p50_ms"] / base, 3)
        if base else None)
    return out


def _ttft_once(engine, rid, prompt, params) -> float:
    """Submit one request on an idle engine, return its TTFT, drain."""
    t0 = time.perf_counter()
    engine.add_request(rid, prompt, params)
    ttft = None
    while engine.has_unfinished_requests() and ttft is None:
        outs = engine.step()
        now = time.perf_counter()
        for o in outs:
            if o.request_id == rid and o.new_token_ids:
                ttft = now - t0
                break
    engine.abort_request(rid)
    while engine.has_unfinished_requests():
        engine.step()
    return ttft if ttft is not None else float("nan")


def _measure_prefix_reuse(model_name: str, quant, rng) -> dict:
    """prefix_reuse phase (ROADMAP item 2's done-criterion): the
    shared-system-prompt workload that motivates cross-request KV reuse.
    One request at a time on a prefix-caching engine:

    - COLD wave: unique prompts -> every prefix lookup misses, full-prompt
      prefill TTFT.
    - one seeding request with the shared prefix, then the WARM wave:
      requests sharing that page-aligned prefix + a unique tail -> the
      cached pages become chunked-prefill history and only the tail
      prefills, so TTFT collapses toward first-new-token cost.

    Both programs (full prefill, history-chunk) are compiled in a discarded
    warmup pair first — never time XLA compilation. Like the spec phase,
    this builds its own small engine after run_config freed the big one."""
    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    shared_len = max(PROMPT_LEN // page, 1) * page      # page-aligned prefix
    tail = PREFIX_TAIL
    n = PREFIX_REQS
    vocab_cap = 200                                      # safe for any vocab
    max_new = 4
    full_len = shared_len + tail
    # A bucket ladder FINER than the full prompt: a warm request prefills
    # only its tail, and the collapse is only visible if that tail lands in
    # a small compiled bucket instead of padding back up to the cold shape.
    ladder = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)
    top = next((b for b in ladder if b >= full_len), full_len)
    buckets = tuple(b for b in ladder if b < full_len) + (top,)
    pages_per_seq = cdiv(full_len + max_new, page) + 1
    cfg = EngineConfig(
        model=get_model_config(model_name).replace(quantization=quant),
        cache=CacheConfig(
            page_size=page,
            # Pool holds the live request + every cached prompt of the cold
            # wave (the CachingPageAllocator only evicts under pressure).
            num_pages=(2 * n + 4) * pages_per_seq + 1),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_prefill_tokens=top,
            decode_buckets=(1, 2), prefill_buckets=buckets,
            decode_window=4, mixed_batch_enabled=False,
            enable_prefix_caching=True))
    engine = LLMEngine(cfg, eos_token_id=None)
    params = SamplingParams(max_tokens=max_new, temperature=0.0)

    def prompt_of(prefix_seed: int, tail_seed: int) -> list:
        p_rng = np.random.default_rng(prefix_seed)
        t_rng = np.random.default_rng(tail_seed)
        return (p_rng.integers(1, vocab_cap, shared_len).tolist()
                + t_rng.integers(1, vocab_cap, tail).tolist())

    # Warmup pair: compiles the full-prefill AND the cached-history
    # (chunked) programs; TTFTs discarded.
    _ttft_once(engine, "warm-a", prompt_of(10_000, 1), params)
    _ttft_once(engine, "warm-b", prompt_of(10_000, 2), params)

    pc = engine.scheduler.prefix_cache
    hits0, misses0 = pc.hits, pc.misses
    cold = [_ttft_once(engine, f"cold-{i}", prompt_of(20_000 + i, i), params)
            for i in range(n)]
    _ttft_once(engine, "seed", prompt_of(30_000, 100), params)
    warm = [_ttft_once(engine, f"warm-{i}",
                       prompt_of(30_000, 200 + i), params)
            for i in range(n)]
    cold_p50 = _median([t for t in cold if t == t])
    warm_p50 = _median([t for t in warm if t == t])
    return {
        "n_requests": n,
        "shared_prefix_tokens": shared_len,
        "tail_tokens": tail,
        "ttft_cold_p50_ms": round(cold_p50 * 1e3, 1),
        "ttft_warm_p50_ms": round(warm_p50 * 1e3, 1),
        "warm_over_cold": (round(warm_p50 / cold_p50, 3)
                           if cold_p50 and cold_p50 == cold_p50 else None),
        "cache_hits": pc.hits - hits0,
        "cache_misses": pc.misses - misses0,
    }


def _measure_swap(model_name: str, quant, rng) -> dict:
    """kv_swap phase (ROADMAP item 2's host-offload criterion): a session
    workload oversubscribed ~SWAP_OVERSUB x the device page pool, so the
    scheduler must preempt, A/B'd on identically-seeded engines:

    - swap arm: host-DRAM tier on — victims' committed KV moves to host and
      readmission is a scatter + direct decode resume;
    - recompute arm: single-tier baseline — victims re-prefill from scratch.

    The headline is resumed-session TTFT: the wall gap between a session's
    preemption (its "preempt" trace event — the same clock the step loop's
    token timestamps use) and its NEXT emitted token. Swap replaces the
    re-prefill with a memcpy, so its gap should sit measurably below the
    recompute arm's at >= 2x oversubscription. Wave 1 of each arm is a
    discarded compile warmup (never time XLA compilation)."""
    from kubernetes_gpu_cluster_tpu.engine.kv_cache import (
        kv_cache_bytes_per_page)

    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    n = SWAP_SESSIONS
    prompt_len = max(PROMPT_LEN // page, 1) * page
    max_new = SWAP_MAX_NEW
    pages_per_seq = cdiv(prompt_len + max_new, page)
    # Oversubscribed pool: all n sessions need ~SWAP_OVERSUB x what fits.
    num_pages = max(int(n * pages_per_seq / SWAP_OVERSUB), pages_per_seq) + 1
    mcfg = get_model_config(model_name).replace(quantization=quant)
    # Host tier sized to hold every session at once — the phase measures
    # swap value, not host-pool pressure.
    swap_gb = (n * pages_per_seq * kv_cache_bytes_per_page(
        mcfg, CacheConfig(page_size=page)) + (1 << 20)) / (1 << 30)
    buckets = tuple(sorted({1, 2, 4, n // 2, n} - {0}))
    prefill_buckets = tuple(sorted({prompt_len, 2 * prompt_len}))
    out = {}
    for label, gb in (("recompute", 0.0), ("swap", swap_gb)):
        cfg = EngineConfig(
            model=mcfg,
            cache=CacheConfig(page_size=page, num_pages=num_pages,
                              swap_space_gb=gb),
            scheduler=SchedulerConfig(
                max_num_seqs=n, max_prefill_tokens=2 * prompt_len,
                decode_buckets=buckets, prefill_buckets=prefill_buckets,
                decode_window=4, mixed_batch_enabled=False))
        engine = LLMEngine(cfg, eos_token_id=None)
        params = SamplingParams(max_tokens=max_new, temperature=0.0)

        def run_wave(tag: str):
            w_rng = np.random.default_rng(1234)   # same prompts both arms
            for i in range(n):
                engine.add_request(
                    f"{tag}-{i}",
                    w_rng.integers(1, 200, prompt_len).tolist(), params)
            tok_times: dict = {}
            while engine.has_unfinished_requests():
                outs = engine.step()
                now = time.monotonic()     # the trace ring's clock
                for o in outs:
                    if o.new_token_ids:
                        tok_times.setdefault(o.request_id, []).append(now)
            latencies = []
            for e in engine.obs.tracer.events():
                if e.kind == "preempt" and e.request_id.startswith(tag):
                    nxt = [t for t in tok_times.get(e.request_id, ())
                           if t > e.ts]
                    if nxt:
                        latencies.append(nxt[0] - e.ts)
            return latencies

        run_wave("warm")                       # compiles; discarded
        pre0 = dict(engine.scheduler.num_preemptions_by_kind)
        swap0 = dict(engine.obs.swap_pages)    # warm wave swapped too
        t0 = time.perf_counter()
        lat = run_wave("m")
        wall = time.perf_counter() - t0
        kinds = engine.scheduler.num_preemptions_by_kind
        out[label] = {
            "wall_s": round(wall, 3),
            "preemptions": {k: kinds[k] - pre0[k] for k in kinds},
            "resume_ttft_p50_ms": (round(_median(lat) * 1e3, 1)
                                   if lat else None),
            "resumes_observed": len(lat),
        }
        if label == "swap":
            out[label]["swap_out_pages"] = (engine.obs.swap_pages["out"]
                                            - swap0["out"])
            out[label]["swap_in_pages"] = (engine.obs.swap_pages["in"]
                                           - swap0["in"])
        del engine
        gc.collect()
    sw, rc = out["swap"], out["recompute"]
    out["sessions"] = n
    out["oversubscription"] = round(n * pages_per_seq / (num_pages - 1), 2)
    out["resume_ttft_ratio"] = (
        round(sw["resume_ttft_p50_ms"] / rc["resume_ttft_p50_ms"], 3)
        if sw["resume_ttft_p50_ms"] and rc["resume_ttft_p50_ms"] else None)
    out["preemptions"] = {
        "recompute_arm": rc["preemptions"], "swap_arm": sw["preemptions"]}
    return out


def _measure_qos(model_name: str, quant, rng) -> dict:
    """KGCT_BENCH_QOS phase (ROADMAP item 3): multi-tenant overload
    isolation A/B on identically-seeded engines.

    Workload: QOS_BATCH_SEQS batch-tier jobs (long decodes) saturate every
    scheduler seat, with a finished job immediately replaced so the
    pressure never lets up; QOS_CHAT_REQS short interactive requests
    arrive one at a time and their TTFT (add -> first emitted token) is
    measured. QoS OFF, a chat request waits until a whole batch job
    finishes (seat-bound FCFS); QoS ON, the scheduler's priority
    make-room preemption swaps a batch victim out (host KV tier — the
    cheap preemption PR 7 built) and fair-share promotion admits the chat
    request at once. Wave 1 of each arm is a discarded compile warmup.

    The admission block exercises the per-tier ledger: with the batch
    tier's offered load inflated by the deterministic ``tenant_flood``
    chaos site past its max_concurrent budget, batch checks shed 429s
    while interactive checks all admit — the per-tier shed counters must
    attribute the whole overload to the batch tier."""
    from kubernetes_gpu_cluster_tpu.config import QoSTier
    from kubernetes_gpu_cluster_tpu.engine.kv_cache import (
        kv_cache_bytes_per_page)
    from kubernetes_gpu_cluster_tpu.resilience.deadline import (
        AdmissionController)
    from kubernetes_gpu_cluster_tpu.resilience.faults import configure_faults
    from kubernetes_gpu_cluster_tpu.utils.math import next_power_of_2

    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    n_batch = QOS_BATCH_SEQS
    n_chat = QOS_CHAT_REQS
    prompt_len = max(PROMPT_LEN // page, 1) * page
    chat_prompt = page                      # short chat turns
    batch_new, chat_new = QOS_BATCH_MAX_NEW, QOS_CHAT_MAX_NEW
    pages_per_seq = cdiv(prompt_len + batch_new, page)
    # Seats are the bottleneck by construction (max_num_seqs = n_batch);
    # the pool holds every batch job plus a chat request with slack so
    # page pressure never confounds the seat story.
    num_pages = (n_batch + 2) * pages_per_seq + 1
    mcfg = get_model_config(model_name).replace(quantization=quant)
    swap_gb = ((n_batch + 2) * pages_per_seq * kv_cache_bytes_per_page(
        mcfg, CacheConfig(page_size=page)) + (1 << 20)) / (1 << 30)
    tiers = (QoSTier("interactive", weight=4, priority=10,
                     max_concurrent=max(n_chat, 4)),
             QoSTier("batch", weight=1, priority=0, max_concurrent=2))
    buckets = tuple(sorted({1, 2, 4, n_batch, n_batch + 1,
                            next_power_of_2(n_batch + 1)} - {0}))
    prefill_buckets = tuple(sorted({page, prompt_len, 2 * prompt_len}))
    out: dict = {}
    for label in ("qos_off", "qos_on"):
        cfg = EngineConfig(
            model=mcfg,
            cache=CacheConfig(page_size=page, num_pages=num_pages,
                              swap_space_gb=swap_gb),
            scheduler=SchedulerConfig(
                max_num_seqs=n_batch, max_prefill_tokens=2 * prompt_len,
                decode_buckets=buckets, prefill_buckets=prefill_buckets,
                decode_window=4, mixed_batch_enabled=False,
                qos_tiers=tiers if label == "qos_on" else ()))
        engine = LLMEngine(cfg, eos_token_id=None)
        # qos_tier rides the params in BOTH arms: the tier-less arm
        # ignores it (scheduler.qos is None), so the submitted workloads
        # are literally identical.
        batch_params = SamplingParams(max_tokens=batch_new,
                                      temperature=0.0, qos_tier="batch")
        chat_params = SamplingParams(max_tokens=chat_new, temperature=0.0,
                                     qos_tier="interactive")

        def run_wave(tag: str):
            w_rng = np.random.default_rng(97)   # same workload both arms
            nb = 0

            def add_batch_job():
                nonlocal nb
                engine.add_request(
                    f"{tag}-b{nb}",
                    w_rng.integers(1, 200, prompt_len).tolist(),
                    batch_params)
                nb += 1

            for _ in range(n_batch):
                add_batch_job()
            for _ in range(3):                  # batch into steady decode
                if engine.has_unfinished_requests():
                    engine.step()
            ttfts: list = []
            t_add: dict = {}
            added = done = 0
            while done < n_chat:
                if added == done and added < n_chat:
                    # One chat request in flight at a time: each sample
                    # measures admission under full batch saturation.
                    rid = f"{tag}-c{added}"
                    engine.add_request(
                        rid, w_rng.integers(1, 200, chat_prompt).tolist(),
                        chat_params)
                    t_add[rid] = time.monotonic()
                    added += 1
                outs = engine.step()
                now = time.monotonic()
                for o in outs:
                    rid = o.request_id
                    if rid in t_add and o.new_token_ids:
                        ttfts.append(now - t_add.pop(rid))
                    if o.finished:
                        if rid.startswith(f"{tag}-c"):
                            done += 1
                        else:
                            add_batch_job()    # keep the pressure on
            while engine.has_unfinished_requests():
                engine.step()
            return ttfts

        run_wave("warm")                        # compiles; discarded
        t0 = time.perf_counter()
        ttfts = run_wave("m")
        wall = time.perf_counter() - t0
        out[label] = {
            "wall_s": round(wall, 3),
            "chat_ttft_p50_ms": round(_median(ttfts) * 1e3, 1),
            "chat_ttft_p95_ms": round(_percentile(ttfts, 0.95) * 1e3, 1),
            "chat_requests": len(ttfts),
            "preemptions": dict(engine.scheduler.num_preemptions_by_kind),
        }
        if label == "qos_on":
            # Per-tier admission ledger under a deterministic flood: the
            # batch tier's offered load is inflated past its
            # max_concurrent budget; every batch check must shed and
            # every interactive check must admit.
            adm = AdmissionController(engine)
            adm.configure_tiers(tiers, "interactive")
            configure_faults("tenant_flood:value=8")
            try:
                checks = {"interactive": 0, "batch": 0}
                for i in range(12):
                    tier = "batch" if i % 2 else "interactive"
                    checks[tier] += 1
                    adm.check(None, tier=tier)
            finally:
                configure_faults(None)
            out["admission"] = {
                "checks": checks,
                "shed_by_tier": dict(adm.shed_by_tier),
            }
        del engine
        gc.collect()
    on, off = out["qos_on"], out["qos_off"]
    out["batch_seqs"] = n_batch
    out["qos_chat_ttft_protected_ratio"] = (
        round(on["chat_ttft_p95_ms"] / off["chat_ttft_p95_ms"], 3)
        if on["chat_ttft_p95_ms"] and off["chat_ttft_p95_ms"] else None)
    return out


def _measure_router() -> dict:
    """KGCT_BENCH_ROUTER phase: cache-aware fleet routing A/B through the
    real serving stack — N in-process replicas (api_server.build_server on
    real sockets, prefix caching on) behind serving/router.Router, replaying
    a shared-prefix session workload:

    - ROUTER_SESSIONS sessions, each with its own page-aligned shared
      prefix; ROUTER_ROUNDS rounds issue one request per session
      (prefix + a unique tail), sequentially — the steady inflight=0 state
      where least-inflight's tie-break round-robins and scatters sessions.
    - arm "least_inflight": the pre-affinity policy. A session's round-2
      request lands on the OTHER replica (cold: full-prefix prefill).
    - arm "prefix_affinity": bounded-load ring routing on the prompt
      prefix — every round after the first lands on the owner replica
      whose cache holds the prefix (warm: tail-only prefill).

    Both arms run identically-seeded engines and identical prompts; each
    replica is warmed DIRECTLY (bypassing the router) with a discarded
    prefix-reuse pair so the full-prefill AND cached-history programs are
    compiled everywhere before measurement (never time XLA compilation).
    Headline: affinity warm-request TTFT p50 / least-inflight's, plus
    per-replica prefix-cache hit ratios showing locality concentrate."""
    import asyncio

    import aiohttp
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
    from kubernetes_gpu_cluster_tpu.serving.router import Router

    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    shared_len = max(PROMPT_LEN // page, 1) * page
    tail = 16
    full_len = shared_len + tail
    vocab_cap = 200
    ladder = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)
    top = next((b for b in ladder if b >= full_len), full_len)
    buckets = tuple(b for b in ladder if b < full_len) + (top,)
    pages_per_seq = cdiv(full_len + 4, page) + 1

    def engine_config():
        return EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(
                page_size=page,
                num_pages=(2 * (ROUTER_SESSIONS + 1) + 4) * pages_per_seq + 1),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_prefill_tokens=top,
                decode_buckets=(1, 2), prefill_buckets=buckets,
                decode_window=4, mixed_batch_enabled=False,
                enable_prefix_caching=True))

    def prompt_of(prefix_seed: int, tail_seed: int) -> list:
        p_rng = np.random.default_rng(prefix_seed)
        t_rng = np.random.default_rng(tail_seed)
        return (p_rng.integers(1, vocab_cap, shared_len).tolist()
                + t_rng.integers(1, vocab_cap, tail).tolist())

    def scrape(text: str, name: str) -> float:
        for line in text.splitlines():
            if line.startswith(name + " "):
                return float(line.rpartition(" ")[2])
        return 0.0

    async def run_arm(policy: str) -> dict:
        runners, urls = [], []
        for _ in range(ROUTER_REPLICAS):
            srv = build_server(engine_config(), None, "debug-tiny")
            runner = aioweb.AppRunner(srv.build_app())
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            runners.append(runner)
            urls.append(f"http://127.0.0.1:{runner.addresses[0][1]}")
        # Affinity window clamped to the shared prefix: with a short
        # KGCT_BENCH_PROMPT the default 32-token window would fold each
        # request's UNIQUE tail into the key, silently un-sticking the
        # sessions and reporting a misleading "affinity does not help".
        router = Router(urls, health_interval_s=9999,
                        routing_policy=policy,
                        affinity_prefix_len=min(32, shared_len))
        rrunner = aioweb.AppRunner(router.build_app())
        await rrunner.setup()
        rsite = aioweb.TCPSite(rrunner, "127.0.0.1", 0)
        await rsite.start()
        router_url = f"http://127.0.0.1:{rrunner.addresses[0][1]}"

        out: dict = {"policy": policy}
        try:
            async with aiohttp.ClientSession() as sess:
                async def complete(base: str, prompt: list) -> float:
                    t0 = time.perf_counter()
                    async with sess.post(
                            f"{base}/v1/completions",
                            json={"prompt": prompt, "max_tokens": 1,
                                  "temperature": 0.0}) as resp:
                        assert resp.status == 200, await resp.text()
                        await resp.read()
                    return time.perf_counter() - t0

                # Direct per-replica warmup (discarded): compiles full
                # prefill, cached-history chunk, and decode everywhere.
                for i, url in enumerate(urls):
                    await complete(url, prompt_of(90_000 + i, 0))
                    await complete(url, prompt_of(90_000 + i, 1))

                before = []
                for url in urls:
                    async with sess.get(f"{url}/metrics") as resp:
                        before.append(await resp.text())

                cold, warm = [], []
                for rnd in range(ROUTER_ROUNDS):
                    for s in range(ROUTER_SESSIONS):
                        dt = await complete(
                            router_url,
                            prompt_of(50_000 + s, 1000 * rnd + s))
                        (cold if rnd == 0 else warm).append(dt)

                per_replica = []
                for i, url in enumerate(urls):
                    async with sess.get(f"{url}/metrics") as resp:
                        text = await resp.text()
                    hits = (scrape(text, "kgct_prefix_cache_hits_total")
                            - scrape(before[i],
                                     "kgct_prefix_cache_hits_total"))
                    misses = (scrape(text, "kgct_prefix_cache_misses_total")
                              - scrape(before[i],
                                       "kgct_prefix_cache_misses_total"))
                    served = (scrape(text, "kgct_requests_total")
                              - scrape(before[i], "kgct_requests_total"))
                    per_replica.append({
                        "requests": int(served),
                        "cache_hits": int(hits),
                        "cache_misses": int(misses),
                        "hit_ratio": (round(hits / (hits + misses), 3)
                                      if hits + misses else None),
                        # The per-replica SLO gauge the fleet autoscaler
                        # reads (one scrape per replica, same surface).
                        "slo_ttft_attainment_ratio": scrape(
                            text, "kgct_slo_ttft_attainment_ratio"),
                    })
                # Fleet-merged trace: ONE download of the router's
                # /debug/trace must hold the router's own spans AND engine
                # lifecycle spans from the replicas, correlated on the
                # router-minted request ids (the acceptance contract; the
                # summary rides the stderr FULL_RESULT, not the headline).
                async with sess.get(f"{router_url}/debug/trace") as resp:
                    tdoc = await resp.json()
                ids_by_pid: dict = {}
                for e in tdoc["traceEvents"]:
                    if e.get("cat") == "request" and e.get("id"):
                        ids_by_pid.setdefault(e["pid"], set()).add(e["id"])
                router_ids = ids_by_pid.get(1, set())
                out["merged_trace"] = {
                    "processes": len({e.get("pid")
                                      for e in tdoc["traceEvents"]}),
                    "router_requests": len(router_ids),
                    "replicas_sharing_ids": sum(
                        1 for pid, ids in ids_by_pid.items()
                        if pid != 1 and ids & router_ids),
                }
                out.update({
                    "ttft_cold_p50_ms": round(_median(cold) * 1e3, 1),
                    "ttft_warm_p50_ms": round(_median(warm) * 1e3, 1),
                    "per_replica": per_replica,
                })
                if policy == "prefix-affinity":
                    reqs = router.affinity_requests_total
                    out["affinity_hit_ratio"] = (
                        round(router.affinity_hits_total / reqs, 3)
                        if reqs else None)
                    out["ring_remaps"] = router.ring_remaps_total
        finally:
            await rrunner.cleanup()
            for runner in runners:
                await runner.cleanup()
        return out

    out: dict = {
        "replicas": ROUTER_REPLICAS,
        "sessions": ROUTER_SESSIONS,
        "rounds": ROUTER_ROUNDS,
        "shared_prefix_tokens": shared_len,
        "tail_tokens": tail,
    }
    for label, policy in (("least_inflight", "least-inflight"),
                          ("prefix_affinity", "prefix-affinity")):
        out[label] = asyncio.run(run_arm(policy))
        gc.collect()
    li, aff = out["least_inflight"], out["prefix_affinity"]
    out["warm_ttft_ratio"] = (
        round(aff["ttft_warm_p50_ms"] / li["ttft_warm_p50_ms"], 3)
        if li["ttft_warm_p50_ms"] else None)
    return out


def _measure_fleet_cache() -> dict:
    """KGCT_BENCH_FLEET_CACHE phase: fleet-wide KV reuse A/B through the
    real serving stack — an OWNER replica whose prefix cache holds each
    session's shared prefix and a NON-OWNER replica the sessions are
    forced onto (requests go DIRECTLY to the non-owner carrying the
    x-kgct-prefix-source hint the router's overflow path would set,
    which also exercises the --peer-pool allowlist):

    - arm "pull" (fleet cache on): the non-owner pulls the owner's cached
      prefix pages over /internal/fetch_prefix, streams them into its own
      cache, and prefills only the unique tail;
    - arm "recompute" (fleet cache off): the hint is ignored and the
      non-owner re-prefills the whole prefix — today's behavior.

    Both arms run identically-seeded engines and identical prompts; both
    replicas are warmed directly (full-prefill + cached-history programs
    compiled everywhere, plus one discarded pulled session in the pull
    arm so the transfer scatter's compile is not timed). Headline:
    pull-arm warm TTFT p50 / recompute-arm's."""
    import asyncio

    import aiohttp
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
    from kubernetes_gpu_cluster_tpu.serving.errors import PREFIX_SOURCE_HEADER

    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    shared_len = max(FLEET_SHARED // page, 1) * page
    tail = 16
    full_len = shared_len + tail
    vocab_cap = 200
    ladder = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)
    top = next((b for b in ladder if b >= full_len), full_len)
    buckets = tuple(b for b in ladder if b < full_len) + (top,)
    pages_per_seq = cdiv(full_len + 4, page) + 1

    def engine_config():
        # max_num_seqs also CAPS the page pool (the engine never holds
        # more pages than max_num_seqs full sequences): 8 seats keep the
        # cap above every warmed session's cached chain, so the owner's
        # cache is not evicting session prefixes before their pull.
        return EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(
                page_size=page,
                num_pages=(2 * (FLEET_SESSIONS + 3) + 4) * pages_per_seq + 1),
            scheduler=SchedulerConfig(
                max_num_seqs=8, max_prefill_tokens=top,
                decode_buckets=(1, 2, 4, 8), prefill_buckets=buckets,
                decode_window=4, mixed_batch_enabled=False,
                enable_prefix_caching=True))

    def prompt_of(prefix_seed: int, tail_seed: int) -> list:
        p_rng = np.random.default_rng(prefix_seed)
        t_rng = np.random.default_rng(tail_seed)
        return (p_rng.integers(1, vocab_cap, shared_len).tolist()
                + t_rng.integers(1, vocab_cap, tail).tolist())

    def scrape(text: str, name: str) -> float:
        for line in text.splitlines():
            if line.startswith(name + " "):
                return float(line.rpartition(" ")[2])
        return 0.0

    async def run_arm(fleet_on: bool, integrity: bool = True) -> dict:
        runners = []

        async def serve(**kw):
            srv = build_server(engine_config(), None, "debug-tiny", **kw)
            runner = aioweb.AppRunner(srv.build_app())
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            runners.append(runner)
            return f"http://127.0.0.1:{runner.addresses[0][1]}"

        out: dict = {"fleet_cache": fleet_on, "integrity": integrity}
        try:
            owner_url = await serve(fleet_prefix_cache=fleet_on,
                                    integrity_checks=integrity)
            puller_url = await serve(fleet_prefix_cache=fleet_on,
                                     integrity_checks=integrity,
                                     peer_pool=[owner_url])
            async with aiohttp.ClientSession() as sess:
                async def complete(base, prompt, hint=None):
                    headers = ({PREFIX_SOURCE_HEADER: hint} if hint else {})
                    t0 = time.perf_counter()
                    async with sess.post(
                            f"{base}/v1/completions",
                            json={"prompt": prompt, "max_tokens": 1,
                                  "temperature": 0.0},
                            headers=headers) as resp:
                        assert resp.status == 200, await resp.text()
                        await resp.read()
                    return time.perf_counter() - t0

                # Compile warmup on BOTH replicas: full prefill + the
                # cached-history tail chunk (discarded local session).
                for url in (owner_url, puller_url):
                    await complete(url, prompt_of(90_000, 0))
                    await complete(url, prompt_of(90_000, 1))
                # Pull-path warmup (discarded session): the transfer
                # scatter's first compile must not land in a measured TTFT.
                await complete(owner_url, prompt_of(91_000, 0))
                await complete(puller_url, prompt_of(91_000, 1),
                               hint=owner_url)

                # Warm each session's prefix on the OWNER, then force the
                # session's next request onto the NON-owner with the hint.
                warm = []
                for s in range(FLEET_SESSIONS):
                    await complete(owner_url, prompt_of(60_000 + s, 0))
                for s in range(FLEET_SESSIONS):
                    warm.append(await complete(
                        puller_url, prompt_of(60_000 + s, 1000 + s),
                        hint=owner_url))
                async with sess.get(f"{puller_url}/metrics") as resp:
                    text = await resp.text()
                out.update({
                    "warm_ttft_p50_ms": round(_median(warm) * 1e3, 1),
                    "pulls_ok": int(scrape(
                        text,
                        'kgct_fleet_prefix_pulls_total{outcome="ok"}')),
                    "pulls_skipped": int(scrape(
                        text,
                        'kgct_fleet_prefix_pulls_total{outcome="skipped"}')),
                    "pulled_bytes": int(scrape(
                        text, 'kgct_fleet_prefix_bytes_total{dir="pull"}')),
                    "prefix_cache_hit_ratio": scrape(
                        text, "kgct_prefix_cache_hit_ratio"),
                })
        finally:
            for runner in reversed(runners):
                await runner.cleanup()
        return out

    out: dict = {
        "sessions": FLEET_SESSIONS,
        "shared_prefix_tokens": shared_len,
        "tail_tokens": tail,
    }
    # Third arm: the pull path with the wire-integrity layer off — the
    # checksum cost (encode-side CRC folds + decode-side re-verify) is
    # the only difference, so the ratio IS the integrity overhead on the
    # wire path. Droppable: dashboards treat an absent ratio as "not
    # measured", never as 1.0.
    for label, fleet_on, integrity in (("recompute", False, True),
                                       ("pull", True, True),
                                       ("pull_integrity_off", True, False)):
        out[label] = asyncio.run(run_arm(fleet_on, integrity))
        gc.collect()
    pull, rec = out["pull"], out["recompute"]
    out["fleet_prefix_pull_over_recompute_ttft"] = (
        round(pull["warm_ttft_p50_ms"] / rec["warm_ttft_p50_ms"], 3)
        if rec["warm_ttft_p50_ms"] else None)
    off = out["pull_integrity_off"]
    out["kv_integrity_overhead_ratio"] = (
        round(pull["warm_ttft_p50_ms"] / off["warm_ttft_p50_ms"], 3)
        if off["warm_ttft_p50_ms"] else None)
    return out


def _hist_buckets(text: str, family: str, replicas=None) -> dict:
    """Cumulative bucket counts {le: count} for ``family`` summed over the
    router-relabeled per-replica series (all label sets, e.g. the TTFT
    histogram's outcome children), optionally restricted to ``replicas``
    (URLs)."""
    buckets: dict = {}
    prefix = family + "_bucket{"
    for line in text.splitlines():
        if not line.startswith(prefix):
            continue
        labels, _, value = line[len(prefix):].partition("} ")
        kv = dict(p.split("=", 1) for p in labels.split(",") if "=" in p)
        le = kv.get('le', '"+Inf"').strip('"')
        if replicas is not None and kv.get("replica", "").strip('"') \
                not in replicas:
            continue
        try:
            buckets[le] = buckets.get(le, 0.0) + float(value)
        except ValueError:
            continue
    return buckets


def _hist_delta(before: str, after: str, family: str,
                replicas=None) -> dict:
    """Measured-window bucket deltas {le: after - before} for ``family``,
    keeping buckets whose first sample landed inside the window (absent
    from the before-scrape). One parse per scrape text."""
    after_b = _hist_buckets(after, family, replicas)
    delta = {le: after_b.get(le, 0.0) - n
             for le, n in _hist_buckets(before, family, replicas).items()}
    for le, n in after_b.items():
        delta.setdefault(le, n)
    return delta


def _bucket_quantile(delta: dict, q: float):
    """Quantile (seconds) from cumulative-bucket DELTAS by linear
    interpolation inside the crossing bucket; None on an empty window."""
    def le_key(le):
        return math.inf if le == "+Inf" else float(le)
    items = sorted(delta.items(), key=lambda kv: le_key(kv[0]))
    total = items[-1][1] if items else 0.0
    if total <= 0:
        return None
    target = q * total
    prev_le, prev_n = 0.0, 0.0
    for le, n in items:
        if n >= target:
            hi = le_key(le)
            if hi is math.inf:
                return prev_le
            frac = ((target - prev_n) / (n - prev_n)) if n > prev_n else 1.0
            return prev_le + frac * (hi - prev_le)
        prev_le, prev_n = le_key(le), n
    return prev_le


def _measure_disagg() -> dict:
    """KGCT_BENCH_DISAGG phase: disaggregated prefill/decode A/B through
    the real serving stack on a MIXED workload —

    - arm "colocated": 2 role="both" replicas behind the router; every
      replica interleaves long prefills with its decode steps, so decode
      inter-token latency absorbs the prefill stalls (mixed batching is
      OFF in both arms to expose the full interference that DistServe-
      style disaggregation removes rather than bounds);
    - arm "disagg": 1 role="prefill" + 1 role="decode" replica; the router
      routes completions to the decode pool with an x-kgct-prefill-url
      header, the decode replica pulls the prefilled KV (one contiguous
      buffer) and resumes decode directly — its device steps are decode-
      only, so TPOT stays flat while prefills land elsewhere.

    Workload: DISAGG_DECODE_SESSIONS decode-heavy sessions (short prompt,
    DISAGG_MAX_NEW tokens) run concurrently with DISAGG_PREFILLS long-
    prompt/1-token prefill-heavy requests. Sustained decode TPOT p95 and
    TTFT p50 are read from ONE router scrape per arm (delta of the
    relabeled per-replica histograms over the measured window; the
    prefill-heavy requests emit one token and thus never enter the TPOT
    histogram — the p95 is pure decode-session TPOT). Headline:
    ``disagg_tpot_over_colocated`` = disagg TPOT p95 / colocated's."""
    import asyncio

    import aiohttp
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
    from kubernetes_gpu_cluster_tpu.serving.router import Router

    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    short_len = 2 * page
    long_len = 8 * page
    vocab_cap = 200
    ladder = (32, 64, 128, 256, 512, 1024, 2048, 4096)
    top = next((b for b in ladder if b >= long_len), long_len)
    buckets = tuple(b for b in ladder if b < long_len) + (top,)
    pages_per_seq = cdiv(long_len + DISAGG_MAX_NEW + 4, page) + 1

    def engine_config():
        return EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(
                page_size=page,
                num_pages=4 * (DISAGG_DECODE_SESSIONS + DISAGG_PREFILLS)
                * pages_per_seq + 1),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_prefill_tokens=top,
                decode_buckets=(1, 2, 4), prefill_buckets=buckets,
                decode_window=4, mixed_batch_enabled=False))

    def prompt_of(seed: int, length: int) -> list:
        return np.random.default_rng(seed).integers(
            1, vocab_cap, length).tolist()

    async def run_arm(disagg: bool) -> dict:
        runners = []

        async def serve(role):
            srv = build_server(engine_config(), None, "debug-tiny",
                               role=role)
            runner = aioweb.AppRunner(srv.build_app())
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            runners.append(runner)
            return f"http://127.0.0.1:{runner.addresses[0][1]}"

        if disagg:
            prefill_urls = [await serve("prefill")]
            decode_urls = [await serve("decode")]
        else:
            prefill_urls = None
            decode_urls = [await serve("both"), await serve("both")]
        router = Router(decode_urls, health_interval_s=9999,
                        prefill_urls=prefill_urls)
        rrunner = aioweb.AppRunner(router.build_app())
        await rrunner.setup()
        rsite = aioweb.TCPSite(rrunner, "127.0.0.1", 0)
        await rsite.start()
        router_url = f"http://127.0.0.1:{rrunner.addresses[0][1]}"

        out: dict = {"arm": "disagg" if disagg else "colocated",
                     "decode_replicas": decode_urls,
                     "prefill_replicas": prefill_urls or []}
        try:
            async with aiohttp.ClientSession() as sess:
                async def complete(prompt, max_tokens):
                    async with sess.post(
                            f"{router_url}/v1/completions",
                            json={"prompt": prompt,
                                  "max_tokens": max_tokens,
                                  "temperature": 0.0}) as resp:
                        assert resp.status == 200, await resp.text()
                        await resp.read()

                async def scrape_router() -> str:
                    async with sess.get(f"{router_url}/metrics") as resp:
                        return await resp.text()

                async def complete_at(base, prompt, max_tokens):
                    async with sess.post(
                            f"{base}/v1/completions",
                            json={"prompt": prompt,
                                  "max_tokens": max_tokens,
                                  "temperature": 0.0}) as resp:
                        assert resp.status == 200, await resp.text()
                        await resp.read()

                # Warmup, same work in both arms:
                #  1. DIRECT per-replica long+short — every pod compiles
                #     both prompt-length prefill buckets and the decode
                #     window independent of the router's tie-break
                #     rotation (routed warmup would send every long to one
                #     colocated pod and every short to the other, leaving
                #     each a bucket family to JIT inside the measured
                #     window and biasing the A/B);
                #  2. one long+short THROUGH the router — compiles the
                #     disagg handoff gather/scatter pair on both sides of
                #     the seam (plain extra traffic in the colocated arm);
                #  3. a concurrent burst of short sessions — compiles the
                #     larger decode batch buckets at the same concurrency
                #     the measured window drives (the disagg decode pod
                #     takes ALL sessions; a colocated pod roughly half).
                for i, u in enumerate(decode_urls + (prefill_urls or [])):
                    await complete_at(u, prompt_of(9_000 + i, long_len), 1)
                    await complete_at(u, prompt_of(9_100 + i, short_len),
                                      DISAGG_MAX_NEW)
                await complete(prompt_of(9_200, long_len), 1)
                await complete(prompt_of(9_300, short_len), DISAGG_MAX_NEW)
                await asyncio.gather(
                    *(complete(prompt_of(9_400 + s, short_len),
                               DISAGG_MAX_NEW)
                      for s in range(DISAGG_DECODE_SESSIONS)))
                before = await scrape_router()

                t0 = time.perf_counter()

                async def decode_session(s: int):
                    for r in range(DISAGG_DECODE_ROUNDS):
                        await complete(
                            prompt_of(1_000 * s + r, short_len),
                            DISAGG_MAX_NEW)

                async def prefill_storm():
                    for i in range(DISAGG_PREFILLS):
                        await complete(prompt_of(5_000 + i, long_len), 1)

                await asyncio.gather(
                    *(decode_session(s)
                      for s in range(DISAGG_DECODE_SESSIONS)),
                    prefill_storm())
                wall = time.perf_counter() - t0
                after = await scrape_router()

            decode_set = {u for u in decode_urls}
            tpot_d = _hist_delta(before, after, "kgct_tpot_seconds",
                                 decode_set)
            # TTFT from the DECODE pool only, like TPOT: in the disagg arm
            # a handoff request samples TTFT on BOTH pools — partial
            # (arrival-at-prefill -> first token) on the prefill replica,
            # end-to-end (pull + remote prefill + import) on the decode
            # replica — and only the latter compares with the colocated
            # arm's full TTFT.
            ttft_d = _hist_delta(before, after, "kgct_ttft_seconds",
                                 decode_set)
            tpot_p95 = _bucket_quantile(tpot_d, 0.95)
            ttft_p50 = _bucket_quantile(ttft_d, 0.50)
            out.update({
                "wall_s": round(wall, 3),
                "decode_tpot_p95_ms": (round(tpot_p95 * 1e3, 2)
                                       if tpot_p95 is not None else None),
                "ttft_p50_ms": (round(ttft_p50 * 1e3, 2)
                                if ttft_p50 is not None else None),
            })
            if disagg:
                handoffs = 0.0
                for line in after.splitlines():
                    if line.startswith("kgct_disagg_handoffs_total{") \
                            and 'side="import"' in line \
                            and 'outcome="ok"' in line:
                        handoffs += float(line.rpartition(" ")[2])
                out["handoffs_ok"] = int(handoffs)
        finally:
            await rrunner.cleanup()
            for runner in runners:
                await runner.cleanup()
        return out

    out: dict = {
        "decode_sessions": DISAGG_DECODE_SESSIONS,
        "decode_rounds": DISAGG_DECODE_ROUNDS,
        "prefill_requests": DISAGG_PREFILLS,
        "max_new": DISAGG_MAX_NEW,
        "long_prompt_tokens": long_len,
        "short_prompt_tokens": short_len,
    }
    for label, disagg in (("colocated", False), ("disagg", True)):
        out[label] = asyncio.run(run_arm(disagg))
        gc.collect()
    co, dis = out["colocated"], out["disagg"]
    out["tpot_p95_ratio"] = (
        round(dis["decode_tpot_p95_ms"] / co["decode_tpot_p95_ms"], 3)
        if dis.get("decode_tpot_p95_ms") and co.get("decode_tpot_p95_ms")
        else None)
    out["ttft_p50_ratio"] = (
        round(dis["ttft_p50_ms"] / co["ttft_p50_ms"], 3)
        if dis.get("ttft_p50_ms") and co.get("ttft_p50_ms") else None)
    return out


def _measure_drain() -> dict:
    """KGCT_BENCH_DRAIN phase: drain-with-migration vs wait-it-out A/B.

    Both arms run the same oversubscribed streaming session workload (more
    concurrent sessions than one replica's batch seats) over 2 role="both"
    replicas behind the real router, then begin a SIGTERM drain on one
    replica while every session is mid-stream:

    - arm "migrate": the draining replica live-migrates each running
      sequence's committed KV to the router-named peer and severs the
      relay; the router splices the resumed streams (parked-KV import on
      the peer), so the drain completes as soon as the pushes do —
      TRANSFER-bound;
    - arm "wait": the ``migrate_fail`` chaos site fails every export, so
      each sequence degrades to the pre-migration wait-it-out path and the
      drain completes only when the longest in-flight decode does —
      DECODE-bound.

    Reported per arm: drain wall seconds (begin_drain -> drain task done)
    and the count of client streams that still completed end-to-end (the
    survivability contract: BOTH arms must deliver every stream; only the
    drain time differs). Headline ``drain_migrate_over_wait_seconds`` =
    migrate drain seconds / wait drain seconds."""
    import asyncio

    import aiohttp
    from aiohttp import web as aioweb

    from kubernetes_gpu_cluster_tpu.resilience.faults import configure_faults
    from kubernetes_gpu_cluster_tpu.serving.api_server import build_server
    from kubernetes_gpu_cluster_tpu.serving.router import Router

    on_tpu = jax.default_backend() == "tpu"
    page = PAGE if PAGE is not None else (128 if on_tpu else 16)
    prompt_len = 2 * page
    vocab_cap = 200
    seats = max(2, (DRAIN_SESSIONS + 1) // 2)   # per-replica seats < sessions:
                                                # the post-migration survivor
                                                # is oversubscribed and queues
    ladder = (32, 64, 128, 256, 512, 1024)
    top = next((b for b in ladder if b >= prompt_len), prompt_len)
    buckets = tuple(b for b in ladder if b < prompt_len) + (top,)
    pages_per_seq = cdiv(prompt_len + DRAIN_MAX_NEW + 4, page) + 1

    def engine_config():
        return EngineConfig(
            model=get_model_config("debug-tiny"),
            cache=CacheConfig(page_size=page,
                              num_pages=2 * DRAIN_SESSIONS * pages_per_seq
                              + 1),
            scheduler=SchedulerConfig(
                max_num_seqs=seats, max_prefill_tokens=top,
                decode_buckets=(1, 2, 4, 8), prefill_buckets=buckets,
                decode_window=4, mixed_batch_enabled=False))

    def prompt_of(seed: int) -> list:
        return np.random.default_rng(seed).integers(
            1, vocab_cap, prompt_len).tolist()

    async def run_arm(migrate: bool) -> dict:
        runners, servers = [], []

        async def serve():
            srv = build_server(engine_config(), None, "debug-tiny")
            runner = aioweb.AppRunner(srv.build_app())
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            runners.append(runner)
            servers.append(srv)
            return f"http://127.0.0.1:{runner.addresses[0][1]}"

        urls = [await serve(), await serve()]
        router = Router(urls, health_interval_s=9999)
        rrunner = aioweb.AppRunner(router.build_app())
        await rrunner.setup()
        rsite = aioweb.TCPSite(rrunner, "127.0.0.1", 0)
        await rsite.start()
        router_url = f"http://127.0.0.1:{rrunner.addresses[0][1]}"
        out: dict = {"arm": "migrate" if migrate else "wait"}
        try:
            async with aiohttp.ClientSession() as sess:
                # Warmup: compile the prefill bucket + decode windows on
                # both replicas (direct), then the migration seam's
                # import path stays cold — its cost IS part of the A/B.
                for i, u in enumerate(urls):
                    async with sess.post(
                            f"{u}/v1/completions",
                            json={"prompt": prompt_of(9_000 + i),
                                  "max_tokens": 8,
                                  "temperature": 0.0}) as resp:
                        assert resp.status == 200, await resp.text()
                        await resp.read()

                started = [asyncio.Event() for _ in range(DRAIN_SESSIONS)]

                async def session(s: int) -> bool:
                    """One streamed completion; True iff the client saw a
                    complete stream ([DONE], no error frame)."""
                    saw_done, saw_error = False, False
                    async with sess.post(
                            f"{router_url}/v1/completions",
                            json={"prompt": prompt_of(s),
                                  "max_tokens": DRAIN_MAX_NEW,
                                  "temperature": 0.0,
                                  "stream": True}) as resp:
                        assert resp.status == 200, await resp.text()
                        async for line in resp.content:
                            text = line.decode("utf-8", "replace").strip()
                            if text.startswith("data:"):
                                started[s].set()
                                payload = text[5:].strip()
                                if payload == "[DONE]":
                                    saw_done = True
                                elif '"error"' in payload:
                                    saw_error = True
                    return saw_done and not saw_error

                tasks = [asyncio.create_task(session(s))
                         for s in range(DRAIN_SESSIONS)]
                await asyncio.gather(*(e.wait() for e in started))
                if not migrate:
                    configure_faults("migrate_fail")
                t0 = time.perf_counter()
                drain_task = servers[0].begin_drain()
                assert drain_task is not None
                await drain_task
                out["drain_seconds"] = round(time.perf_counter() - t0, 3)
                complete = await asyncio.gather(*tasks)
                out["complete_streams"] = sum(complete)
                out["sessions"] = DRAIN_SESSIONS
                mig = servers[0].migration.migrations
                out["migrations_push_ok"] = mig.get(("push", "ok"), 0)
                out["migrations_push_fallback"] = mig.get(
                    ("push", "fallback"), 0)
                out["failovers"] = dict(router.failovers_total)
        finally:
            configure_faults(None)
            await rrunner.cleanup()
            for runner in runners:
                await runner.cleanup()
        return out

    out: dict = {"sessions": DRAIN_SESSIONS, "max_new": DRAIN_MAX_NEW,
                 "prompt_tokens": prompt_len, "seats_per_replica": seats}
    for label, migrate in (("wait", False), ("migrate", True)):
        out[label] = asyncio.run(run_arm(migrate))
        gc.collect()
    mig, wait = out["migrate"], out["wait"]
    out["drain_migrate_over_wait_seconds"] = (
        round(mig["drain_seconds"] / wait["drain_seconds"], 3)
        if mig.get("drain_seconds") and wait.get("drain_seconds") else None)
    return out


# --------------------------------------------------------------------------
# Per-config driver
# --------------------------------------------------------------------------

def run_config(model_name: str, quant, batch: int, *, sustained: bool,
               host_rt_s: float, rng, window: int = None, budget: int = None,
               n_windows: int = None, page_slack: int = 3,
               max_new: int = None) -> dict:
    window = window or DECODE_WINDOW
    budget = budget or PREFILL_BUDGET
    n_windows = n_windows or BENCH_WINDOWS
    max_new = max_new or (
        PROMPT_LEN + window * (WARMUP_WINDOWS + n_windows + 4))
    engine = _mk_engine(model_name, quant, batch, max_new, window, budget,
                        page_slack)
    vocab = engine.config.model.vocab_size

    # Warmup: compile prefill + greedy decode programs.
    _add_batch(engine, rng, vocab, "warm", batch, max_new)
    while engine.scheduler.waiting:
        engine.step()
    for _ in range(WARMUP_WINDOWS):
        engine.step()
    if MIXED_BATCH and batch > 1:
        # Compile the MIXED step program at the sustained-phase shape (one
        # fresh prompt riding a near-full decode batch) so its first-use
        # XLA compile cannot land inside the measured load phases and
        # poison the TTFT percentiles the mixing exists to improve. One
        # warm seat is freed first: a final chunk is only admitted when a
        # max_num_seqs seat is open, which is also the only regime where
        # sustained-phase mixing fires. Up to 3 steps: one drains the
        # in-flight decode chain, one runs the mixed step.
        engine.abort_request("warm-0")
        _add_batch(engine, rng, vocab, "warmmix", 1, max_new)
        for _ in range(3):
            if engine.scheduler.waiting:
                engine.step()
        engine.abort_request("warmmix-0")
    _drain(engine, "warm", batch)

    prefill, live_tag = _measure_prefill_ttft(
        engine, rng, vocab, batch, max_new, host_rt_s)
    greedy_rate = _measure_decode(engine, n_windows)
    # Mid-measurement decode context for the roofline. The measured batch is
    # FRESH (last prefill trial): one priming window + half the measured
    # windows — the warmup batch was a different, drained batch.
    ctx_mid = PROMPT_LEN + window * (1 + n_windows // 2)
    _drain(engine, live_tag, batch)

    sampled_rate = (_measure_sampled_decode(engine, rng, vocab, batch, max_new)
                    if SAMPLED_WINDOWS > 0 else float("nan"))

    mcfg = engine.config.model
    acct = _roofline(mcfg, quant, batch, ctx_mid)
    util = _utilization(acct, greedy_rate, batch)
    param_bytes, matmul_bytes = _param_bytes(engine.params)
    # Prefill roofline at the measured operating point: one budget-bounded
    # ragged step (the whole fresh batch when it fits the budget). The
    # measured rate's utilization against the compute bound is prefill's
    # "mfu" — the TTFT arithmetic target.
    pf_tokens = min(budget, batch * PROMPT_LEN)
    pf = _roofline_prefill(mcfg, quant, pf_tokens)
    pf_rate = prefill["prefill_tokens_per_sec"]
    if pf_rate and pf_rate == pf_rate:
        pf["prefill_mfu"] = round(
            pf_rate * pf["flops_per_token"] / (CHIP_TFLOPS_BF16 * 1e12), 4)
        pf["measured_step_ms"] = round(pf_tokens / pf_rate * 1e3, 1)
    # Observability readout: median queue/prefill/first-fetch TTFT split and
    # the per-phase step-time attribution accumulated over the whole run —
    # a TTFT or tok/s regression in a future round decomposes into a phase
    # delta instead of a guess.
    ttft_decomp = engine.obs.ttft_decomposition()
    phase_breakdown = engine.obs.phases.breakdown()
    sampled_ratio = engine.obs.sampled_decode_ratio()
    result = {
        "model": model_name,
        "quantization": quant,
        "batch": batch,
        "decode_window": window,
        "prefill_budget": budget,
        "decode_tokens_per_sec": round(greedy_rate, 1),
        "decode_tokens_per_sec_sampled": (round(sampled_rate, 1)
                                          if sampled_rate == sampled_rate
                                          else None),
        "sampled_over_greedy": (round(sampled_rate / greedy_rate, 3)
                                if sampled_rate == sampled_rate else None),
        # Engine-side counterpart of sampled_over_greedy, accumulated over
        # ALL decode steps of the run INCLUDING the sampled program's compile
        # window (so it reads low here; in a long-running server, where
        # compiles amortize to nothing, the kgct_sampled_decode_ratio gauge
        # converges on the true ratio). The regression guard is
        # sampled_over_greedy above, measured post-warmup.
        "sampled_decode_ratio_obs": (round(sampled_ratio, 3)
                                     if sampled_ratio is not None else None),
        **prefill,
        "ttft_decomposition": ttft_decomp,
        "step_phase_breakdown": phase_breakdown,
        "mixed_batch": MIXED_BATCH,
        # Buffer-size accounting over the UPLOADED params pytree (real
        # device buffer bytes, not modeled): the packed-int4 evidence that
        # no dequantized weight copy was materialized — matmul_weight_bytes
        # under int4 is ~0.53x the int8 figure, and a dequantized [in, out]
        # copy anywhere would show up as a ~2x jump.
        "param_bytes": param_bytes,
        "matmul_weight_bytes": matmul_bytes,
        "roofline": {
            "chip": {"hbm_gbps_peak": CHIP_HBM_GBPS,
                     "tflops_bf16_peak": CHIP_TFLOPS_BF16},
            "decode_ctx_modeled": ctx_mid,
            **{k: acct[k] for k in ("weight_stream_bytes", "kv_bytes_per_step",
                                    "flops_per_token")},
            **util,
            "prefill": pf,
        },
    }
    if sustained and greedy_rate > 0:
        rate_rps = LOAD_UTILIZATION * greedy_rate / LOAD_MAX_NEW
        # Reset the decomposition deques so the sustained phase's split is
        # not diluted by fresh-batch samples — under load, queue wait is the
        # north-star suspect and must be attributed on its own.
        for dq in (engine.obs.ttft_queue_s, engine.obs.ttft_prefill_s,
                   engine.obs.ttft_fetch_s):
            dq.clear()
        kinds_before = dict(engine.obs.step_kind_counts)
        result["sustained_load"] = _measure_sustained(
            engine, rng, vocab, batch, rate_rps)
        result["sustained_load"]["ttft_decomposition"] = (
            engine.obs.ttft_decomposition())
        # Windowed mixed-step ratio for THIS phase (the whole-run gauge is
        # diluted by the fresh-batch phases, which rarely mix).
        deltas = {k: engine.obs.step_kind_counts[k] - kinds_before[k]
                  for k in kinds_before}
        total = sum(deltas.values())
        result["sustained_load"]["mixed_step_ratio"] = (
            round(deltas["mixed"] / total, 3) if total else None)
        over_rps = OVERLOAD_UTILIZATION * greedy_rate / LOAD_MAX_NEW
        # Budget floor: 2x the measured fresh-batch TTFT p50. Admission
        # control sheds QUEUE wait; it cannot (and should not) shed the
        # irreducible prefill compute — a budget below the empty-engine TTFT
        # (e.g. the CPU debug config, where one padded prefill step is
        # seconds) would just report 100% violations of an unachievable bar.
        floor = prefill["ttft_p50_ms"]
        budget_ms = (max(OVERLOAD_TTFT_BUDGET_MS, 2.0 * floor)
                     if floor == floor else OVERLOAD_TTFT_BUDGET_MS)
        result["overload"] = _measure_overload(
            engine, rng, vocab, over_rps, budget_ms)
    # Whole-run mixed-step ratio, read LAST so the sustained/overload phases
    # (where mixing actually engages) are included.
    ratio = engine.obs.mixed_step_ratio()
    result["mixed_step_ratio"] = (round(ratio, 3) if ratio is not None
                                  else None)
    del engine
    gc.collect()
    return result


def _param_bytes(params) -> tuple:
    """(total params pytree bytes, QUANT_LAYER_KEYS+lm_head matmul bytes
    incl. scales) as actually uploaded — sizes come from the live arrays.
    tests/test_quant.py calls this same accounting for its 0.55x A/B, so
    the bench report and the test pin cannot drift."""
    from kubernetes_gpu_cluster_tpu.ops.quant import QUANT_LAYER_KEYS

    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    matmul = 0
    layers = params["layers"]
    for key in QUANT_LAYER_KEYS + ("lm_head",):
        store = params if key == "lm_head" else layers
        for k in (key, key + "_scale"):
            if k in store:
                matmul += store[k].size * store[k].dtype.itemsize
    return int(total), int(matmul)


def assemble_output(results: list[dict], backend: str) -> dict:
    """Fold per-config results into the single driver-facing JSON object.

    Pure (no I/O) so tests can round-trip it through ``json.loads`` — r5's
    official record has ``"parsed": null`` because the result line never made
    it through the driver's parser; the assembly and the emission are now
    separately guaranteed (see ``emit_result``)."""
    primary = results[-1]
    bar = A100_VLLM_TOKS_PER_S.get(primary["model"])
    return {
        "metric": (f"decode_tokens_per_sec_per_chip[{primary['model']}"
                   f"{',' + primary['quantization'] if primary['quantization'] else ''}"
                   f",B={primary['batch']},ctx={PROMPT_LEN}]"),
        "value": primary["decode_tokens_per_sec"],
        "unit": "tokens/s/chip",
        "vs_baseline": (round(primary["decode_tokens_per_sec"] / bar, 3)
                        if bar else None),
        "backend": backend,
        # vs_baseline is normalized against a SELF-CHOSEN constant (the
        # reference publishes no numbers): representative single-A100 vLLM
        # decode throughput for this model class.
        "baseline_bar": {"value": bar,
                         "source": ("chosen constant (A100 vLLM class bar)"
                                    if bar else "no bar defined for model")},
        "decode_window": primary["decode_window"],
        "prefill_budget": primary["prefill_budget"],
        # The primary config's TTFT decomposition (queue / prefill /
        # first-step fetch medians) surfaced top-level for the driver.
        "ttft_decomposition": primary.get("ttft_decomposition"),
        "sampled_over_greedy": primary.get("sampled_over_greedy"),
        "mixed_batch": primary.get("mixed_batch"),
        # Speculative phase headlines (full block in
        # configs[-1].speculative): n-gram acceptance, and the draft-model
        # arm's decode throughput over the n-gram arm's (CPU default pairs
        # the target with an oracle same-arch draft — machinery
        # validation; the production ratio needs a real small draft on
        # chip, ROADMAP 1(b)).
        "spec_acceptance_ratio": (primary.get("speculative", {})
                                  .get("spec", {}).get("acceptance_ratio")),
        "spec_draft_over_ngram_speedup": (
            primary.get("speculative", {})
            .get("spec_draft_over_ngram_speedup")),
        # Prefix-reuse phase headline: warm-prefix TTFT as a fraction of
        # cold TTFT (full block in configs[-1].prefix_reuse).
        "prefix_warm_over_cold_ttft": (primary.get("prefix_reuse", {})
                                       .get("warm_over_cold")),
        # KV-swap phase headlines: resumed-session TTFT under swap as a
        # fraction of recompute-preemption, and the per-arm preemption
        # counts (full block in configs[-1].kv_swap).
        "swap_resume_over_recompute_ttft": (primary.get("kv_swap", {})
                                            .get("resume_ttft_ratio")),
        "preemptions": primary.get("kv_swap", {}).get("preemptions"),
        # Multi-tenant QoS phase headline: chat p95 TTFT under batch
        # saturation with QoS tiers on as a fraction of the tier-less
        # engine's (< 1 = interactive traffic protected; full A/B block
        # incl. per-tier shed attribution in configs[-1].qos).
        "qos_chat_ttft_protected_ratio": (
            primary.get("qos", {}).get("qos_chat_ttft_protected_ratio")),
        # Fleet-routing phase headline: warm-request TTFT through the
        # prefix-affinity router as a fraction of least-inflight's (full
        # A/B block in configs[-1].router_affinity).
        "router_affinity_warm_over_li_ttft": (
            primary.get("router_affinity", {}).get("warm_ttft_ratio")),
        # Fleet-cache phase headline: warm TTFT on a NON-owner replica
        # with the prefix pulled from the ring owner's cache as a
        # fraction of recomputing it (< 1 = remote KV reuse beats
        # re-prefill; full A/B block in configs[-1].fleet_cache).
        "fleet_prefix_pull_over_recompute_ttft": (
            primary.get("fleet_cache", {})
            .get("fleet_prefix_pull_over_recompute_ttft")),
        # Wire-integrity headline: pull-arm warm TTFT with the per-page
        # checksum layer ON as a fraction of the same pull with it OFF
        # (~1.0 = the CRC folds and import-seam re-verify are in the
        # noise; the A/B's third arm in configs[-1].fleet_cache).
        "kv_integrity_overhead_ratio": (
            primary.get("fleet_cache", {})
            .get("kv_integrity_overhead_ratio")),
        # Disaggregation phase headline: sustained decode TPOT p95 through
        # the role-split prefill/decode topology as a fraction of the
        # colocated topology's, from one router scrape per arm (full A/B
        # block in configs[-1].disagg).
        "disagg_tpot_over_colocated": (
            primary.get("disagg", {}).get("tpot_p95_ratio")),
        # Drain phase headline: drain wall seconds with live KV migration
        # as a fraction of the wait-it-out drain's, same oversubscribed
        # streaming workload, every client stream delivered in both arms
        # (full A/B block in configs[-1].drain).
        "drain_migrate_over_wait_seconds": (
            primary.get("drain", {}).get("drain_migrate_over_wait_seconds")),
        # SLO headline: fraction of the overload phase's admitted requests
        # whose TTFT met the admission budget — the attainment read
        # BENCH_r06 captures alongside raw TTFT (full block in
        # configs[-1].overload).
        "slo_ttft_attainment_ratio": (
            primary.get("overload", {}).get("slo_ttft_attainment_ratio")),
        "configs": results,
    }


def parse_result_line(stdout_text: str) -> dict:
    """Parse a bench run's result from its captured stdout — the inverse of
    ``emit_result`` and the ONLY supported way to consume a transcript.
    Takes the last non-empty line (trailing whitespace/newlines tolerated;
    any amount of earlier noise ignored) and json.loads it, raising
    ValueError with context instead of returning None — the r5 official
    record landed ``"parsed": null`` because a driver-side parser failed
    silently."""
    lines = [ln for ln in stdout_text.splitlines() if ln.strip()]
    if not lines:
        raise ValueError("empty bench stdout: no result line to parse")
    last = lines[-1].strip()
    try:
        out = json.loads(last)
    except json.JSONDecodeError as e:
        raise ValueError(
            f"last stdout line is not the bench result JSON "
            f"(contract: see bench.py --help): {last[:200]!r}") from e
    if not isinstance(out, dict):
        raise ValueError(f"bench result line parsed to {type(out).__name__}, "
                         "expected a JSON object")
    return out


def build_arg_parser() -> argparse.ArgumentParser:
    """--help documents the stdout contract; configuration itself stays on
    KGCT_BENCH_* env vars (listed here) so the driver's invocation is just
    ``python bench.py``."""
    p = argparse.ArgumentParser(
        prog="bench.py",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        description=(
            "Serving benchmark: prefill/TTFT, greedy+sampled decode, "
            "roofline (decode + prefill), sustained-load and overload "
            "phases.\n\n" + OUTPUT_CONTRACT),
        epilog=(
            "Configuration (env vars): KGCT_BENCH_MODEL, KGCT_BENCH_QUANT, "
            "KGCT_BENCH_BATCH, KGCT_BENCH_WINDOW, KGCT_BENCH_PREFILL_BUDGET, "
            "KGCT_BENCH_WINDOWS, KGCT_BENCH_SAMPLED_WINDOWS, "
            "KGCT_BENCH_LOAD_REQS, KGCT_BENCH_LOAD_UTIL, "
            "KGCT_BENCH_OVERLOAD_UTIL, KGCT_BENCH_OVERLOAD_REQS, "
            "KGCT_BENCH_TTFT_BUDGET_MS, KGCT_BENCH_MIXED (1=stall-free "
            "mixed prefill/decode batching, default on; 0=legacy "
            "prefill-else-decode), KGCT_BENCH_SPEC (1=speculative-decoding "
            "phase on a repetitive-suffix workload, default on; 0=skip), "
            "KGCT_BENCH_SPEC_K, KGCT_BENCH_SPEC_BATCH, "
            "KGCT_BENCH_SPEC_MAX_NEW, KGCT_BENCH_SPEC_DRAFT (draft-model "
            "preset for the two-model arm; default: the target preset at "
            "the same seed — an oracle draft), KGCT_BENCH_SPEC_MIXED "
            "(1=spec×mixed chat-TTFT composition arm, default on; "
            "0=skip), KGCT_BENCH_SPEC_CHAT_PROBES, "
            "KGCT_BENCH_PREFIX (1=prefix-reuse "
            "phase: cold vs warm shared-prefix TTFT on a prefix-caching "
            "engine, default on; 0=skip), KGCT_BENCH_PREFIX_REQS, "
            "KGCT_BENCH_PREFIX_TAIL, KGCT_BENCH_SWAP (1=kv-swap phase: "
            "oversubscribed session workload, swap-preemption vs "
            "recompute-preemption A/B, default on; 0=skip), "
            "KGCT_BENCH_SWAP_SESSIONS, KGCT_BENCH_SWAP_OVERSUB, "
            "KGCT_BENCH_SWAP_MAX_NEW, KGCT_BENCH_QOS (1=multi-tenant QoS "
            "phase: chat TTFT under batch saturation, tiers on/off A/B on "
            "identically-seeded engines + per-tier shed attribution under "
            "tenant_flood, default on; 0=skip), KGCT_BENCH_QOS_BATCH, "
            "KGCT_BENCH_QOS_CHAT_REQS, KGCT_BENCH_QOS_BATCH_MAX_NEW, "
            "KGCT_BENCH_QOS_CHAT_MAX_NEW, KGCT_BENCH_ROUTER (1=fleet-routing "
            "phase: shared-prefix session workload through the real router "
            "over in-process replicas, least-inflight vs prefix-affinity "
            "A/B, default on; 0=skip), KGCT_BENCH_ROUTER_REPLICAS, "
            "KGCT_BENCH_ROUTER_SESSIONS, KGCT_BENCH_ROUTER_ROUNDS, "
            "KGCT_BENCH_FLEET_CACHE (1=fleet-cache phase: shared-prefix "
            "sessions forced onto a non-owner replica, prefix PULL from "
            "the owner's cache vs full recompute A/B on identically-"
            "seeded replica pairs, default on; 0=skip), "
            "KGCT_BENCH_FLEET_SESSIONS, KGCT_BENCH_FLEET_SHARED, "
            "KGCT_FLEET_BW_GBPS, KGCT_FLEET_FLOPS, "
            "KGCT_BENCH_DISAGG (1=disaggregated prefill/decode phase: "
            "role-split 1 prefill + 1 decode replica with KV-page handoff "
            "vs 2 colocated replicas on a mixed long-prefill/long-decode "
            "workload, sustained decode TPOT p95 + TTFT from one router "
            "scrape per arm, default on; 0=skip), "
            "KGCT_BENCH_DISAGG_SESSIONS, KGCT_BENCH_DISAGG_ROUNDS, "
            "KGCT_BENCH_DISAGG_PREFILLS, KGCT_BENCH_DISAGG_MAX_NEW, "
            "KGCT_BENCH_DRAIN (1=session-survivability phase: "
            "drain-with-live-KV-migration vs wait-it-out drain A/B on an "
            "oversubscribed streaming workload through the router, "
            "default on; 0=skip), KGCT_BENCH_DRAIN_SESSIONS, "
            "KGCT_BENCH_DRAIN_MAX_NEW, "
            "KGCT_BENCH_PROMPT, KGCT_BENCH_PAGE, "
            "KGCT_CHIP_HBM_GBPS, KGCT_CHIP_TFLOPS_BF16. KGCT_BENCH_QUANT "
            "accepts int8 or int4 (the W4A16 dequant-fused path)."))
    return p


# Headline blocks droppable (in order) when the result line must shrink
# further than losing "configs" — the primary metric/value/unit always stay.
_DROPPABLE_HEADLINE = ("ttft_decomposition", "baseline_bar", "mixed_batch",
                       "sampled_over_greedy", "spec_acceptance_ratio",
                       "spec_draft_over_ngram_speedup",
                       "prefix_warm_over_cold_ttft",
                       "swap_resume_over_recompute_ttft", "preemptions",
                       "qos_chat_ttft_protected_ratio",
                       "router_affinity_warm_over_li_ttft",
                       "fleet_prefix_pull_over_recompute_ttft",
                       "kv_integrity_overhead_ratio",
                       "disagg_tpot_over_colocated",
                       "drain_migrate_over_wait_seconds",
                       "slo_ttft_attainment_ratio",
                       "decode_window", "prefill_budget", "vs_baseline")


def compact_result(out: dict, limit: int = RESULT_LINE_MAX) -> dict:
    """Shrink ``out`` until its JSON line fits ``limit`` bytes (the driver
    keeps only a stdout tail — an oversized line gets its HEAD cut off and
    parses to nothing, the BENCH_r05 "parsed": null failure mode). Degrades
    in stages, never fails: drop "configs" (the caller preserves it on
    stderr), then droppable headline blocks, and as a last resort a
    minimal bounded {metric, value, unit} record — a shrunk result always
    beats a decapitated or absent one."""
    line = json.dumps(out)
    if len(line) <= limit:
        return out
    slim = dict(out)
    slim.pop("configs", None)
    slim["configs_on_stderr"] = True
    for key in _DROPPABLE_HEADLINE:
        if len(json.dumps(slim)) <= limit:
            return slim
        slim.pop(key, None)
    if len(json.dumps(slim)) <= limit:
        return slim
    return {"metric": str(out.get("metric"))[:256], "value": out.get("value"),
            "unit": out.get("unit"), "configs_on_stderr": True}


def emit_result(out: dict) -> None:
    """Emit the result as the GUARANTEED last stdout line: json.dumps with
    no embedded newlines AND no more than RESULT_LINE_MAX bytes (a tail
    capture must never decapitate it — see compact_result), everything
    previously buffered flushed first, one write, one flush. All framework
    logging already goes to stderr (utils/logging.py); anything a library
    printed earlier is flushed ahead of the result so interleaving cannot
    split the line. When the full result exceeds the bound, it is emitted
    intact on stderr as a FULL_RESULT line first."""
    slim = compact_result(out)
    if slim is not out:
        sys.stderr.write("FULL_RESULT: " + json.dumps(out) + "\n")
    line = json.dumps(slim)
    # Explicit check, not assert (python -O must not strip the guarantee);
    # unreachable — compact_result's minimal fallback is bounded — but if
    # an invariant ever breaks, fail LOUD before a decapitated record can
    # masquerade as a parse bug downstream.
    if "\n" in line or len(line) > RESULT_LINE_MAX:
        raise RuntimeError(
            f"bench result line violates the stdout contract "
            f"({len(line)} bytes > {RESULT_LINE_MAX} or embedded newline)")
    sys.stderr.flush()
    sys.stdout.flush()
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def main() -> None:
    from kubernetes_gpu_cluster_tpu.utils.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    build_arg_parser().parse_args()   # --help / reject unknown args
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    rng = np.random.default_rng(0)

    if os.environ.get("KGCT_BENCH_MODEL"):
        # Explicit single-config mode (A/B runs, other model families).
        batch = int(os.environ.get("KGCT_BENCH_BATCH",
                                   32 if on_tpu else 8))
        configs = [dict(model_name=os.environ["KGCT_BENCH_MODEL"],
                        quant=os.environ.get("KGCT_BENCH_QUANT") or None,
                        batch=batch, sustained=True)]
    elif on_tpu:
        # Default driver suite: continuity line first (its engine is small),
        # then the 8B int8 r1-r5 line as the quant-ladder A/B, then the
        # PRIMARY 8B int4 config (BASELINE config 2, W4A16) with the
        # sustained-load phase. 8B decode is weight-streaming-bound, so
        # tokens/step scale with batch until HBM runs out; the r5 batch
        # ladder (interleaved probes): B=32 2335 -> B=48 3027 -> B=56 3335
        # -> B=64 3650 tok/s median; B=72 flat (3634), B=80/B=64-at-5-pages
        # OOM by ~1 MB. The fit is an EXACTLY-4-page zero-slack pool
        # (prompt 128 + max_new 384 = 512 tokens/seq; a non-dividing
        # max_new would floor to an under-provisioned pool) + W=28 so 13
        # windows fit the 384-token budget. Slack-0 only risks a graceful
        # chain break at the request tail. r4's +3-slack B=48 OOM'd 17.25G.
        # int4 packs the weight stream to ~0.53x int8 (roofline
        # weight_stream_bytes), so the same B=64 shape should land
        # ~1.5-1.8x the int8 decode rate; it also frees ~3.5 GB of HBM —
        # a B>64 int4 ladder probe is the natural next capture. tinyllama
        # runs twice: B=64 is the r1-r4 continuity line, B=256 the
        # batch-optimal point (same weight-amortization ladder as 8B: 9.9k
        # -> 13.8k (B=128) -> 15.4k (192) -> 16.2k (256) tok/s; B=320
        # fails compile). Larger batches trade fresh-batch TTFT for
        # throughput — both points are reported.
        configs = [dict(model_name="tinyllama-1.1b", quant=None,
                        batch=int(os.environ.get("KGCT_BENCH_BATCH", 64)),
                        sustained=False),
                   dict(model_name="tinyllama-1.1b", quant=None, batch=256,
                        sustained=False, n_windows=9),  # 11-page pool fit
                   dict(model_name="llama-3-8b", quant="int8", batch=64,
                        sustained=False, window=28, budget=2048, n_windows=9,
                        page_slack=0, max_new=384),
                   dict(model_name="llama-3-8b", quant="int4", batch=64,
                        sustained=True, window=28, budget=2048, n_windows=9,
                        page_slack=0, max_new=384)]
    else:
        configs = [dict(model_name="debug-tiny", quant=None,
                        batch=int(os.environ.get("KGCT_BENCH_BATCH", 8)),
                        sustained=True)]

    host_rt_s = _measure_host_rt_s()
    results = [run_config(host_rt_s=host_rt_s, rng=rng, **c) for c in configs]
    if SPEC_BENCH:
        # Speculative phase rides the PRIMARY config's model; it builds its
        # own (small-batch) engines, after run_config freed the big one.
        primary = configs[-1]
        results[-1]["speculative"] = _measure_spec(
            primary["model_name"], primary.get("quant"), rng)
    if PREFIX_BENCH:
        # Prefix-reuse phase: same pattern — own small engine, primary model.
        primary = configs[-1]
        results[-1]["prefix_reuse"] = _measure_prefix_reuse(
            primary["model_name"], primary.get("quant"), rng)
    if SWAP_BENCH:
        # KV-swap phase: same pattern — own small oversubscribed engines.
        primary = configs[-1]
        results[-1]["kv_swap"] = _measure_swap(
            primary["model_name"], primary.get("quant"), rng)
    if QOS_BENCH:
        # Multi-tenant QoS phase: chat-vs-batch overload isolation A/B on
        # identically-seeded engines (own small engines, primary model).
        primary = configs[-1]
        results[-1]["qos"] = _measure_qos(
            primary["model_name"], primary.get("quant"), rng)
    if ROUTER_BENCH:
        # Fleet-routing phase: in-process multi-replica A/B through the
        # real router (always debug-tiny engines; see _measure_router).
        results[-1]["router_affinity"] = _measure_router()
    if FLEET_BENCH:
        # Fleet-cache phase: shared-prefix sessions forced onto a
        # non-owner replica, prefix pull vs full recompute (always
        # debug-tiny engines; see _measure_fleet_cache).
        results[-1]["fleet_cache"] = _measure_fleet_cache()
    if DISAGG_BENCH:
        # Disaggregation phase: role-split prefill/decode pools with KV
        # handoff vs colocated replicas (always debug-tiny engines; see
        # _measure_disagg).
        results[-1]["disagg"] = _measure_disagg()
    if DRAIN_BENCH:
        # Session-survivability phase: drain-with-migration vs wait-it-out
        # on an oversubscribed streaming workload (always debug-tiny
        # engines; see _measure_drain).
        results[-1]["drain"] = _measure_drain()
    emit_result(assemble_output(results, backend))


if __name__ == "__main__":
    main()
