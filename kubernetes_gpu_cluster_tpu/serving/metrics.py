"""Prometheus-format serving metrics (/metrics endpoint).

The reference had NO metrics surface at all — observability was kubectl
transcripts (SURVEY §5 "Metrics/logging/observability: no Prometheus/
Grafana") — so this is framework-over-reference functionality the north star
asks for: tok/s, TTFT under continuous batching, preemptions, KV page
occupancy.

Counters come from engine.EngineStats (filled inside the step loop) and
scheduler/allocator state; latency distributions are REAL histograms
(``_bucket``/``_sum``/``_count`` with outcome labels, rendered by the
engine's Observability) so Prometheus can compute any quantile across
replicas — the two-point host-side summaries this module used to emit
could not aggregate. Text format per the exposition spec, scrapeable
without any client library; nan-free by construction even on a freshly
started server.
"""

from __future__ import annotations

import time

from ..engine.engine import device_memory_stats
from ..engine.kv_cache import (kv_cache_bytes_per_token, kv_row_padding_share,
                               state_bytes_per_seq)
from ..utils.compile_cache import COMPILE_COUNTERS


class Metrics:
    def __init__(self, engine):
        self.engine = engine               # LLMEngine
        self.requests_total = 0
        self.responses_total = 0
        self.response_tokens_total = 0
        self._started = time.monotonic()
        # main() installed them before the first compilation; an embedder
        # that skipped configure_compile_cache() counts from here.
        COMPILE_COUNTERS.install()

    # -- hooks called by the API layer --------------------------------------

    def on_request(self) -> None:
        self.requests_total += 1

    def on_finish(self, n_tokens: int) -> None:
        """HTTP-layer completion: counts responses actually delivered to
        clients (engine-side requests_finished also covers aborts/terminated
        sequences, so the two legitimately differ under churn)."""
        self.responses_total += 1
        self.response_tokens_total += n_tokens

    # -- rendering ----------------------------------------------------------

    def render(self) -> str:
        eng = self.engine
        stats = eng.stats
        sched = eng.scheduler
        alloc = sched.allocator
        lines = [
            "# TYPE kgct_requests_total counter",
            f"kgct_requests_total {self.requests_total}",
            "# TYPE kgct_responses_total counter",
            f"kgct_responses_total {self.responses_total}",
            "# TYPE kgct_response_tokens_total counter",
            f"kgct_response_tokens_total {self.response_tokens_total}",
            "# TYPE kgct_requests_finished_total counter",
            f"kgct_requests_finished_total {stats.requests_finished}",
            "# TYPE kgct_tokens_generated_total counter",
            f"kgct_tokens_generated_total {stats.tokens_generated}",
            "# TYPE kgct_prefill_tokens_total counter",
            f"kgct_prefill_tokens_total {stats.prefill_tokens}",
            "# TYPE kgct_engine_steps_total counter",
            f"kgct_engine_steps_total {stats.steps}",
            # Split by kind (ROADMAP item 2): "swap" preemptions park KV in
            # host DRAM and resume via memcpy, "recompute" ones burn a full
            # re-prefill — the ratio is the two-tier cache's value signal.
            "# TYPE kgct_preemptions_total counter",
            'kgct_preemptions_total{kind="recompute"} %d'
            % sched.num_preemptions_by_kind["recompute"],
            'kgct_preemptions_total{kind="swap"} %d'
            % sched.num_preemptions_by_kind["swap"],
            "# TYPE kgct_num_waiting gauge",
            f"kgct_num_waiting {len(sched.waiting)}",
            "# TYPE kgct_num_running gauge",
            f"kgct_num_running {len(sched.running)}",
            "# TYPE kgct_num_swapped gauge",
            f"kgct_num_swapped {len(sched.swapped)}",
            "# TYPE kgct_kv_pages_total gauge",
            f"kgct_kv_pages_total {alloc.num_pages}",
            "# TYPE kgct_kv_pages_free gauge",
            f"kgct_kv_pages_free {alloc.num_free}",
            # What a cached token really holds over all layers (K and V
            # rows, or one latent row with its padding to whole lane
            # tiles), and the share of a stored row that is padding.
            "# TYPE kgct_kv_bytes_per_token gauge",
            "kgct_kv_bytes_per_token %d" % kv_cache_bytes_per_token(
                eng.model_config, eng.config.cache),
            "# TYPE kgct_kv_row_padding_share gauge",
            "kgct_kv_row_padding_share %.4f"
            % kv_row_padding_share(eng.model_config),
            "# TYPE kgct_uptime_seconds gauge",
            f"kgct_uptime_seconds {time.monotonic() - self._started:.1f}",
        ]
        # A state model's second kind of per-sequence memory: its slots
        # (the scrap slot is neither counted nor free) and what one holds.
        # Zeros for every other model, so the series always exist.
        lines += [
            "# TYPE kgct_state_slots_total gauge",
            f"kgct_state_slots_total {max(alloc.num_state_slots - 1, 0)}",
            "# TYPE kgct_state_slots_free gauge",
            f"kgct_state_slots_free {alloc.num_free_slots}",
            "# TYPE kgct_state_bytes_per_seq gauge",
            f"kgct_state_bytes_per_seq {state_bytes_per_seq(eng.model_config)}",
        ]
        # Prefix-cache reuse (engine/kv_cache.PrefixCache counts lookups;
        # nothing scraped them until now). Emitted unconditionally — zeros
        # when caching is off or nothing was looked up yet — so a fresh
        # scrape is nan-free and dashboards need no existence check.
        pc = sched.prefix_cache
        hits = pc.hits if pc is not None else 0
        misses = pc.misses if pc is not None else 0
        looked = hits + misses
        lines += [
            "# TYPE kgct_prefix_cache_hit_ratio gauge",
            f"kgct_prefix_cache_hit_ratio {hits / looked if looked else 0.0}",
            "# TYPE kgct_prefix_cache_hits_total counter",
            f"kgct_prefix_cache_hits_total {hits}",
            "# TYPE kgct_prefix_cache_misses_total counter",
            f"kgct_prefix_cache_misses_total {misses}",
            # Second-chance restores of host-spilled prefix pages.
            "# TYPE kgct_prefix_cache_host_hits_total counter",
            "kgct_prefix_cache_host_hits_total %d"
            % (pc.host_hits if pc is not None else 0),
        ]
        # Host KV tier occupancy (two-tier cache). Zeros when swap is off —
        # a fresh scrape stays nan-free and dashboards need no existence
        # check, same contract as the prefix-cache series above.
        swapper = getattr(eng, "swapper", None)
        host_total = swapper.host.num_pages if swapper is not None else 0
        host_used = swapper.host.num_in_use if swapper is not None else 0
        lines += [
            "# TYPE kgct_kv_host_pages_total gauge",
            f"kgct_kv_host_pages_total {host_total}",
            "# TYPE kgct_kv_host_pages_in_use gauge",
            f"kgct_kv_host_pages_in_use {host_used}",
        ]
        # Device telemetry (ROADMAP 4(b) autoscaler inputs): HBM occupancy
        # straight from the jax runtime's allocator counters (0/0 on CPU —
        # nan-free), and the jit-cache entry count across every step program
        # (the tier-1 compile guard's number; flat in steady state, growth
        # under constant traffic = new shapes keep arriving). The jit series
        # is a GAUGE despite the _total spelling: it reads the live cache, so
        # jax.clear_caches()/engine rebuild can shrink it — a counter TYPE
        # would make rate() report a phantom compile storm on any reset.
        # It counts SHAPES MET by the step programs: a load from the
        # persistent cache counts, an eager one-op program does not. What
        # XLA compiled is the kgct_xla_compile_* family below, counted by
        # JAX's own monitoring events for every program of the process.
        hbm_limit, hbm_in_use = device_memory_stats()[0]
        cc = COMPILE_COUNTERS
        lines += [
            "# TYPE kgct_hbm_bytes_limit gauge",
            f"kgct_hbm_bytes_limit {hbm_limit}",
            "# TYPE kgct_hbm_bytes_in_use gauge",
            f"kgct_hbm_bytes_in_use {hbm_in_use}",
            "# HELP kgct_jit_compiles_total jit-cache entries of the step "
            "programs: shapes met so far, persistent-cache loads included, "
            "eager one-op programs not seen",
            "# TYPE kgct_jit_compiles_total gauge",
            f"kgct_jit_compiles_total {eng.compiled_step_variants()}",
            "# HELP kgct_xla_compile_requests_total programs handed to the "
            "XLA backend, compiled there or loaded from the persistent cache",
            "# TYPE kgct_xla_compile_requests_total counter",
            f"kgct_xla_compile_requests_total {cc.requests}",
            "# HELP kgct_xla_compile_seconds_total seconds spent in those "
            "requests (compilation, or the cache load)",
            "# TYPE kgct_xla_compile_seconds_total counter",
            f"kgct_xla_compile_seconds_total {round(cc.seconds, 6)}",
            "# HELP kgct_xla_compile_cache_hits_total requests answered by "
            "the persistent compilation cache; requests minus hits were "
            "compiled",
            "# TYPE kgct_xla_compile_cache_hits_total counter",
            f"kgct_xla_compile_cache_hits_total {cc.cache_hits}",
        ]
        # Histograms (TTFT/TPOT/queue-wait/prefill/step/batch-size/e2e),
        # per-phase step-time counters, and the sampled-decode-ratio gauge —
        # all owned by the engine's Observability.
        lines.extend(eng.obs.render_prometheus())
        return "\n".join(lines) + "\n"
