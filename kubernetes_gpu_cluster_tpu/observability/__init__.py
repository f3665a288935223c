"""Observability subsystem: request tracing, phase attribution, histograms.

One ``Observability`` object per engine, shared with its scheduler: the step
loop and scheduler call the ``on_*`` lifecycle hooks; serving/metrics.py
renders the histogram state into /metrics; serving/api_server.py exports the
trace ring via /debug/trace.
Everything here is bounded (rings + fixed-bucket histograms) and lock-free
on the hot path — the engine step loop must never block on observability.

One iteration of the step loop (``LLMEngine.step()``) dispatches program n+1
and then fetches program n (the one-deep device queue, PR 33), so step
accounting goes by PROGRAM, not by iteration: ``on_step`` takes the record
of the program just retired (``step`` its own number, ``kind``, ``rows``,
``tokens``, ``padded_tokens``, ``behind``, and the stamps ``t_launch``,
``t_dispatched``, ``t_wait``, ``t_ready``, ``t_retired`` on
``time.monotonic``; phases.py says what is derived from them), files the
phases that served it under its number in ``/debug/trace`` and fills the
always-on series ``kgct_step_device_seconds``, ``kgct_step_lead_seconds``,
``kgct_device_starved_seconds_total``, ``kgct_steps_retired_total``,
``kgct_step_slow_*``, ``kgct_step_tokens_total``; the worker's three states
(``kgct_worker_seconds_total``) and, per frame written, the delay behind
its program's end and its five stages (``kgct_frame_delay_seconds``,
``kgct_frame_stage_seconds``: ``on_frame``) are kept beside them.

The black-box flight recorder (flightrecorder.py) mirrors the same events
into its own ring (kill switch ``KGCT_FLIGHT=0``) and is NOT touched by
``/debug/trace?clear=1`` — a scoped capture must never erase the crash
evidence.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional

import numpy as np

from .flightrecorder import FlightRecorder
from .phases import (FRAME_STAGES, PHASES, STEP_KINDS, WORKER_STATES,
                     FrameClock, StepClock, StepPhaseStats)
from .prometheus import (BATCH_BUCKETS, FRAME_STAGE_BUCKETS_S,
                         LATENCY_BUCKETS_S, STEP_BUCKETS_S, Histogram, fmt,
                         render_gauge)
from .trace import EVENT_KINDS, RequestTracer, merge_perfetto

__all__ = ["Observability", "Histogram", "RequestTracer", "StepPhaseStats",
           "StepClock", "FrameClock", "FlightRecorder", "SLOTracker",
           "merge_perfetto", "EVENT_KINDS", "PHASES", "LATENCY_BUCKETS_S",
           "BATCH_BUCKETS", "render_gauge", "fmt"]

# The attainment bar when no admission-control budget is configured: the
# north-star "p50 TTFT <= 1 s" target. An operator budget
# (ResilienceConfig.default_ttft_budget_ms, wired by the API server)
# overrides it so the SLO gauge and the 429 shed line agree on one number.
SLO_DEFAULT_TTFT_BUDGET_MS = 1000.0

# kgct_frame_stage_seconds' label sets, in FrameClock.stages' order.
_STAGE_LABELS = tuple((stage,) for stage in FRAME_STAGES)


class SLOTracker:
    """Rolling SLO view over recent requests — the autoscaler-facing signal
    (ROADMAP item 4(b)): what fraction of recent traffic met its TTFT
    budget, and how many tokens/s the budget-meeting requests delivered
    (goodput — raw tok/s counts tokens nobody would have waited for).

    Bounded by construction: a fixed-size TTFT window (count-based, the
    last N first tokens) and a time-pruned goodput window. All reads are
    nan-free: attainment over an empty window is 1.0 (nothing has missed
    its budget), goodput is 0.0.

    Thread model: the engine WORKER thread writes (on_first_token /
    on_finish inside the step loop) while the HTTP thread reads
    (/metrics render). Writers are single-threaded and own all mutation
    (including the goodput prune); readers take a ``list()`` snapshot of
    each deque — atomic under the GIL — and never mutate, so a scrape can
    land mid-append without a 'deque mutated during iteration' error or a
    popleft race."""

    def __init__(self, ttft_budget_ms=None, window: int = 256,
                 goodput_window_s: float = 60.0):
        self.ttft_budget_ms = ttft_budget_ms     # None -> default bar
        self.goodput_window_s = goodput_window_s
        self._ttfts: deque = deque(maxlen=window)
        self._good: deque = deque()              # (finish_ts, tokens)
        # Start of the observation span (reset by clear()): a server up
        # 10 s must divide its goodput by 10 s, not the full 60 s window.
        self._window_start = time.monotonic()

    @property
    def budget_ms(self) -> float:
        return (self.ttft_budget_ms if self.ttft_budget_ms is not None
                else SLO_DEFAULT_TTFT_BUDGET_MS)

    def on_first_token(self, ttft_s: float) -> None:
        self._ttfts.append(ttft_s)

    def on_finish(self, ttft_s: float, n_tokens: int) -> None:
        if n_tokens <= 0 or ttft_s * 1e3 > self.budget_ms:
            return
        now = time.monotonic()
        self._good.append((now, n_tokens))
        # Writer-side prune bounds the deque to ~the window's finishes;
        # only this (single) writer thread ever pops.
        cutoff = now - self.goodput_window_s
        good = self._good
        while good and good[0][0] < cutoff:
            good.popleft()

    def attainment(self) -> float:
        """Fraction of the recent TTFT window under the budget; 1.0 on an
        empty window (a fresh server has missed nothing)."""
        ttfts = list(self._ttfts)          # snapshot: reader never iterates live
        if not ttfts:
            return 1.0
        bar = self.budget_ms
        return sum(1 for t in ttfts if t * 1e3 <= bar) / len(ttfts)

    def goodput_tokens_per_sec(self) -> float:
        """Tokens/s delivered by budget-meeting requests over the rolling
        window — 0.0 when idle. The denominator is the OBSERVED span
        (capped at the window): dividing a 10 s-old server's tokens by the
        full 60 s would systematically understate goodput. Read-only: the
        window filter re-applies on the snapshot (entries the writer has
        not pruned yet but that aged out are excluded here too)."""
        now = time.monotonic()
        cutoff = now - self.goodput_window_s
        tokens = sum(n for ts, n in list(self._good) if ts >= cutoff)
        if not tokens:
            return 0.0
        span = min(self.goodput_window_s,
                   max(now - self._window_start, 1e-6))
        return tokens / span

    def clear(self) -> None:
        """Reset the rolling windows; the budget stays."""
        self._ttfts.clear()
        self._good.clear()
        self._window_start = time.monotonic()


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds * 1e3, 3)


def _outcome(seq, reason) -> str:
    """finished | aborted | preempted — the label the e2e/TTFT-facing series
    carry. A request that was ever preempted finished late through no fault
    of its own; labeling it lets QoS dashboards split the tail."""
    rv = getattr(reason, "value", reason)
    if rv == "abort":
        return "aborted"
    if rv == "migrated":
        # Live-migrated to a peer (drain): locally terminal, but the client
        # stream continues elsewhere — its tokens WERE delivered, so the
        # goodput gate keeps them; the e2e series splits them out.
        return "migrated"
    if getattr(seq, "preempt_count", 0) > 0:
        return "preempted"
    return "finished"


class Observability:
    def __init__(self, trace_capacity: int = 8192):
        # Black-box flight recorder: mirrors every trace emit into its own
        # bounded ring (plus periodic state snapshots) and dumps to a JSON
        # file on fatal transitions — independent kill switch KGCT_FLIGHT=0.
        self.flight = FlightRecorder()
        self.tracer = RequestTracer(capacity=trace_capacity,
                                    recorder=self.flight)
        # Rolling SLO layer: TTFT attainment + goodput, the autoscaler
        # signals. The API server points ttft_budget_ms at the admission
        # controller's budget so both layers grade against one bar.
        self.slo = SLOTracker()
        # Multi-tenant QoS: per-tier SLO trackers + served counters, keyed
        # by the CONFIGURED tier names only (bounded label cardinality,
        # KGCT007 — never raw user ids). Empty when QoS is off: no labeled
        # series render and the scrape is byte-identical to the tier-less
        # server. configure_qos_tiers wires them from engine config.
        self.slo_by_tier: dict[str, SLOTracker] = {}
        self.finished_by_tier: dict[str, int] = {}
        self._qos_default_tier: str = ""
        self.phases = StepPhaseStats()
        self.ttft = Histogram(
            "kgct_ttft_seconds", "time to first token", labels=("outcome",))
        self.tpot = Histogram(
            "kgct_tpot_seconds", "inter-token latency (per-request mean)")
        self.queue_wait = Histogram(
            "kgct_queue_wait_seconds", "arrival to first scheduling")
        self.prefill_latency = Histogram(
            "kgct_prefill_seconds", "scheduling to first token, minus fetch")
        self.step_duration = Histogram(
            "kgct_step_seconds", "one iteration of the step loop (dispatch "
            "of program n+1, then fetch and post-processing of program n)")
        # The program's own clock (phases.StepPhaseStats.retire): device
        # time of the programs whose both ends the host saw, by step kind.
        self.step_device = Histogram(
            "kgct_step_device_seconds", "device time of a step program, "
            "end of its predecessor (or its own dispatch) to its own end; "
            "only programs that were waited for, behind one that was",
            buckets=STEP_BUCKETS_S, labels=("kind",))
        # How long a program had been queued when the chip came to it, by
        # step kind: only behind a predecessor that was waited for (both
        # ends of the distance are then stamps the chip set).
        self.step_lead = Histogram(
            "kgct_step_lead_seconds", "end of a step program's predecessor "
            "minus its own dispatch, floored at 0: how far the host ran "
            "ahead of the chip; only behind a predecessor that was waited "
            "for", buckets=STEP_BUCKETS_S, labels=("kind",))
        # kind of the program that came late -> seconds the chip had
        # nothing queued before it (phases.retire: ``starved_s``).
        self.device_starved = {kind: 0.0 for kind in STEP_KINDS}
        # (kind, waited) -> programs retired; waited False: found ready.
        self.steps_retired: dict[tuple[str, bool], int] = {}
        # cause -> [seconds, count] of ready gaps that counted as slow.
        self.step_slow = {"host": [0.0, 0], "device": [0.0, 0]}
        # (kind, real) -> tokens the programs computed; real False: the
        # bucket's padding.
        self.step_tokens: dict[tuple[str, bool], int] = {}
        # Per frame the event loop wrote: now - t_ready of the program whose
        # tokens it carries (the HTTP layer's own share of a token's gap),
        # and the same distance cut at the frame's stamps into
        # phases.FRAME_STAGES: five observations a frame, whose sums add up
        # to the delay's.
        self.frame_delay = Histogram(
            "kgct_frame_delay_seconds", "end of a step program to the "
            "write of the frame that carries its tokens")
        self.frame_stage = Histogram(
            "kgct_frame_stage_seconds", "the frame delay by stage: retire "
            "(program ready to post-processed), post (to the row's "
            "hand-over), wake (to the event loop's callback), queue (to the "
            "consumer's resumption), render (to the write's return)",
            buckets=FRAME_STAGE_BUCKETS_S, labels=("stage",))
        # The number the program being scheduled will get: the request
        # events of a schedule() name the step that serves them.
        self.step_launching = 0
        self.batch_size = Histogram(
            "kgct_batch_size_per_step", "real sequences per engine step",
            buckets=BATCH_BUCKETS)
        self.e2e_latency = Histogram(
            "kgct_request_e2e_seconds", "arrival to finish",
            labels=("outcome",))
        # Mixed (stall-free) batching: device steps by kind plus the
        # cumulative prefill/decode token split of mixed steps — feeds the
        # kgct_mixed_step_ratio gauge.
        self.step_kind_counts = {kind: 0 for kind in STEP_KINDS}
        self.mixed_prefill_tokens = 0
        self.mixed_decode_tokens = 0
        # The one-deep device queue (engine._step): step programs
        # dispatched, by kind and by whether one was still unfetched when
        # this one was queued behind it; and the times no step could be
        # scheduled behind the one in flight, by reason.
        self.steps_dispatched: dict[tuple[str, bool], int] = {}
        self.chain_breaks: dict[str, int] = {}
        # Expert models: (token, expert) pairs sent through the expert
        # layers, by step kind; the routing balance of the last prefill,
        # chunk or mixed step (busiest expert's pairs over the mean); and,
        # of the last such step that took the grouped path, the share of the
        # rows its expert matmuls computed that were pairs (percent).
        self.moe_routed_pairs: dict[str, int] = {}
        self.moe_expert_load_max_ratio = 0.0
        # None until a step of a model that holds a SHARE of its experts
        # reports its load: the share of real pairs that reached them.
        self.moe_pairs_held_share: Optional[float] = None
        self.moe_held: Optional[tuple] = None
        self.moe_grouped_tile_fill_share = 0.0
        # Sparse attention (a model with an indexer): over its decode rows,
        # the tokens a row could see and those it attended to (its
        # ``index_topk`` at most), from the lengths the host holds.
        self.dsa_visible_tokens = 0
        self.dsa_chosen_tokens = 0
        # A block model (generation by diffusion over blocks), counted a
        # ROW: the denoising passes its rows took, those among them beside
        # which a block's commit rode (the K/V of the block before, written
        # in the same pass), the blocks committed (every commit rides a
        # pass: the two are equal, their ratio is the mechanism's
        # engagement), the tokens the passes transferred, the positions
        # they computed (two blocks a row-pass, padding rows apart), and
        # the passes a whole block took (engine/block.py).
        self.block_passes = 0
        self.block_commit_passes = 0
        self.block_commits = 0
        self.block_tokens_transferred = 0
        self.block_positions_computed = 0
        self.block_passes_per_block = Histogram(
            "kgct_block_passes_per_block",
            "denoising passes a whole block took (its commit rides the "
            "next block's first)",
            buckets=(1, 2, 3, 4, 5, 6, 8, 9, 12, 17, 33))
        # Speculative decoding: cumulative drafted vs accepted draft tokens
        # (bonus tokens excluded from both) — feeds the
        # kgct_spec_acceptance_ratio gauge and the kgct_spec_*_tokens_total
        # counters.
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        # Draft PHASE telemetry (n-gram lookups or draft-model dispatches,
        # measured at the proposer seam): tokens the proposer actually
        # produced and the wall time spent producing them — splits a spec
        # step's cost into draft vs verify. Zero-safe when spec is off.
        self.spec_draft_tokens = 0
        self.spec_draft_latency = Histogram(
            "kgct_spec_draft_seconds",
            "draft-phase wall time per spec step (proposer seam)")
        # Acceptance-adaptive k: the controller's live rung (None = spec
        # off -> the gauge is absent from /metrics, never NaN).
        self.spec_current_k = None
        # Two-tier KV cache: pages moved device<->host (preempt-by-swap +
        # prefix-spill) and the per-transfer latency split by direction —
        # feeds kgct_kv_swap_{out,in}_pages_total and kgct_kv_swap_seconds.
        self.swap_pages = {"out": 0, "in": 0}
        self.swap_latency = Histogram(
            "kgct_kv_swap_seconds", "host<->device KV page transfer latency",
            labels=("dir",))
        # Fleet-wide prefix cache (serving/fleet_cache.py): remote prefix
        # pulls by outcome — "ok" (imported into the local cache),
        # "recompute" (pull failed/timed out/peer missed: local prefill
        # serves it, byte-identical), "skipped" (the roofline gate priced
        # the pull above recompute, or the prefix was already local) — and
        # remote spills by outcome — "ok" (a peer parked the evicted
        # page), "dropped" (bounded queue displaced it / peer had no
        # room), "error" (push failed). Pre-seeded so a fresh scrape
        # renders zeros for every outcome, nan-free, fleet cache off
        # included.
        self.fleet_pulls = {"ok": 0, "recompute": 0, "skipped": 0}
        self.fleet_spills = {"ok": 0, "dropped": 0, "error": 0}
        self.fleet_bytes = {"pull": 0, "spill": 0}
        self.fleet_pull_latency = Histogram(
            "kgct_fleet_prefix_pull_seconds",
            "remote prefix pull wall latency (fetch + streamed import)")
        # KV wire integrity (serving/handoff.py): detections by wire path
        # x outcome — "corrupt" (a frame failed its own checksums) and
        # "skew" (a peer spoke the pre-integrity dialect to a receiver
        # that requires checksums). Every cell pre-seeded: a fresh scrape
        # renders zeros for the full matrix, integrity off included.
        self.wire_corruptions = {
            (path, outcome): 0
            for path in ("handoff", "prefix", "spill", "migrate", "resume")
            for outcome in ("corrupt", "skew")}
        # Peer quarantine entries by peer URL. Bounded cardinality: the
        # label set is the configured allowlists (peer pool + prefill
        # pool), seeded at server construction so idle peers render 0.
        self.peer_quarantines: dict = {}

    # -- multi-tenant QoS ----------------------------------------------------

    def configure_qos_tiers(self, tiers, default_tier: str,
                            fallback_budget_ms=None) -> None:
        """Install the per-tier SLO trackers: one per CONFIGURED tier
        (bounded cardinality), graded against the tier's own TTFT budget
        when it has one, else ``fallback_budget_ms`` — the operator's
        admission default, so a tier child and the global tracker grade
        the same request against the same bar (None keeps the north-star
        default, matching the global tracker's own fallback). Called once
        at engine construction when QoS is on."""
        self.slo_by_tier = {
            t.name: SLOTracker(ttft_budget_ms=(
                t.ttft_budget_ms if t.ttft_budget_ms is not None
                else fallback_budget_ms))
            for t in tiers}
        self.finished_by_tier = {t.name: 0 for t in tiers}
        self._qos_default_tier = default_tier

    def _tier_slo(self, seq) -> "Optional[SLOTracker]":
        if not self.slo_by_tier:
            return None
        name = getattr(getattr(seq, "params", None), "qos_tier", None)
        if name not in self.slo_by_tier:
            name = self._qos_default_tier
        return self.slo_by_tier.get(name)

    # -- request lifecycle hooks (engine + scheduler) ------------------------

    def on_arrival(self, seq) -> None:
        self.tracer.emit("arrival", seq.request_id,
                         prompt_tokens=seq.num_prompt_tokens)

    def on_queued(self, seq, depth: int = 0) -> None:
        self.tracer.emit("queued", seq.request_id, queue_depth=depth)

    def on_scheduled(self, seq, n_batch: int) -> None:
        resumed = getattr(seq, "preempt_count", 0) > 0
        if seq.scheduled_time is None:
            seq.scheduled_time = time.monotonic()
            self.queue_wait.observe(seq.scheduled_time - seq.arrival_time)
        self.tracer.emit("resume" if resumed else "scheduled",
                         seq.request_id, batch=n_batch,
                         step=self.step_launching)

    def on_prefill_chunk(self, seq, start: int, end: int, total: int) -> None:
        self.tracer.emit("prefill_chunk", seq.request_id,
                         start=start, end=end, total=total,
                         step=self.step_launching)

    def on_preempt(self, seq, kind: str = "recompute") -> None:
        seq.preempt_count += 1
        self.tracer.emit("preempt", seq.request_id, preempt_kind=kind,
                         preempt_count=seq.preempt_count)

    def on_swap(self, direction: str, pages: int, duration_s: float,
                request_id: str = "") -> None:
        """One two-tier KV transfer: ``direction`` "out" (device->host) or
        "in" (host->device), ``pages`` moved, wall latency including the
        host-side copy."""
        if direction in self.swap_pages:
            self.swap_pages[direction] += pages
        self.swap_latency.observe(duration_s, (direction,))
        self.tracer.emit("swap", request_id, dir=direction, pages=pages)

    def on_fleet_pull(self, outcome: str, n_bytes: int = 0,
                      duration_s=None) -> None:
        """One fleet-cache pull decision/attempt (bounded outcome set —
        unknown spellings fold into "recompute" so label cardinality can
        never grow)."""
        if outcome not in self.fleet_pulls:
            outcome = "recompute"
        self.fleet_pulls[outcome] += 1
        self.fleet_bytes["pull"] += n_bytes
        if duration_s is not None:
            self.fleet_pull_latency.observe(duration_s)

    def on_fleet_spill(self, outcome: str, n_bytes: int = 0) -> None:
        """One remote-spill attempt (sender side)."""
        if outcome not in self.fleet_spills:
            outcome = "error"
        self.fleet_spills[outcome] += 1
        self.fleet_bytes["spill"] += n_bytes

    def on_wire_corruption(self, path: str, outcome: str = "corrupt"
                           ) -> None:
        """One integrity detection on a KV wire path (bounded label
        matrix — unknown spellings fold into handoff/corrupt so
        cardinality can never grow)."""
        if (path, outcome) not in self.wire_corruptions:
            path, outcome = "handoff", "corrupt"
        self.wire_corruptions[(path, outcome)] += 1

    def seed_peers(self, peers) -> None:
        """Pre-seed the quarantine counter's label set from the
        configured allowlists — zeros for every known peer on a fresh
        scrape, and the only way labels enter (bounded cardinality)."""
        for peer in peers:
            self.peer_quarantines.setdefault(peer, 0)

    def on_peer_quarantine(self, peer: str) -> None:
        """One quarantine ENTRY for ``peer`` (window extensions do not
        re-count)."""
        self.peer_quarantines[peer] = self.peer_quarantines.get(peer, 0) + 1

    def on_spec_draft(self, n_tokens: int, duration_s: float) -> None:
        """One draft phase (the proposer-seam call of a spec round):
        tokens proposed + wall time. Called by the verifier/spec-mixed
        builders on the worker thread."""
        self.spec_draft_tokens += n_tokens
        self.spec_draft_latency.observe(duration_s)

    def on_first_token(self, seq, fetch_s: float = 0.0,
                       step: Optional[int] = None) -> None:
        ttft = seq.first_token_time - seq.arrival_time
        self.ttft.observe(ttft, (_outcome(seq, None),))
        self.slo.on_first_token(ttft)
        tier_slo = self._tier_slo(seq)
        if tier_slo is not None:
            tier_slo.on_first_token(ttft)
        if seq.scheduled_time is not None:
            queue = seq.scheduled_time - seq.arrival_time
            self.prefill_latency.observe(max(ttft - queue - fetch_s, 0.0))
        self.tracer.emit("first_token", seq.request_id,
                         ttft_ms=round(ttft * 1e3, 2), step=step)

    def on_handoff_first_token(self, seq, ttft_s: float) -> None:
        """Disaggregated import: the first token(s) arrived WITH the KV
        handoff, so step()'s first-token transition never fires here.
        ``ttft_s`` is the decode-replica-observed span (remote prefill +
        transfer + import) — the client-facing quantity; it feeds the TTFT
        histogram and the SLO window, and is stashed on the sequence so
        on_finish's goodput gate judges the real latency, not the ~0 of
        first_token_time - arrival_time."""
        seq.handoff_ttft_s = ttft_s
        self.ttft.observe(ttft_s, (_outcome(seq, None),))
        self.slo.on_first_token(ttft_s)
        tier_slo = self._tier_slo(seq)
        if tier_slo is not None:
            tier_slo.on_first_token(ttft_s)
        self.tracer.emit("first_token", seq.request_id,
                         ttft_ms=round(ttft_s * 1e3, 2), handoff=True)

    def on_finish(self, seq, reason) -> None:
        """Terminal accounting — idempotent (several engine paths can reach a
        finished sequence: defer/drain, abort-in-flight, capacity kill)."""
        if seq.finish_time is not None:
            return
        seq.finish_time = time.monotonic()
        outcome = _outcome(seq, reason)
        self.e2e_latency.observe(seq.finish_time - seq.arrival_time,
                                 (outcome,))
        n = seq.num_output_tokens
        # Goodput counts DELIVERED work only: an aborted request's tokens
        # were generated but nobody received them (client disconnect /
        # group-abort), and counting them would overstate the autoscaler's
        # throughput signal under client churn.
        if seq.first_token_time is not None and outcome != "aborted":
            ttft = (seq.handoff_ttft_s
                    if getattr(seq, "handoff_ttft_s", None) is not None
                    else seq.first_token_time - seq.arrival_time)
            self.slo.on_finish(ttft, n)
            tier_slo = self._tier_slo(seq)
            if tier_slo is not None:
                tier_slo.on_finish(ttft, n)
        if self.finished_by_tier and outcome != "aborted":
            name = getattr(getattr(seq, "params", None), "qos_tier", None)
            if name not in self.finished_by_tier:
                name = self._qos_default_tier
            if name in self.finished_by_tier:
                self.finished_by_tier[name] += 1
        if seq.first_token_time is not None and n >= 2:
            self.tpot.observe(
                (seq.finish_time - seq.first_token_time) / (n - 1))
        self.tracer.emit("abort" if outcome == "aborted" else "finish",
                         seq.request_id, outcome=outcome, output_tokens=n)

    def on_expert_load(self, load, grouped: bool = False) -> None:
        """``load``: () or one [layers, E] device array, the real pairs each
        expert of each layer was sent in the step whose tokens were just
        fetched (the device is done: this read waits for nothing).
        ``grouped``: the step's experts ran by grouped dispatch, one
        ``grouped_matmul`` group an expert; its visit rule says how full
        the row tiles it computed were. Where this process holds a share of
        the experts (``moe_held``: (first, count), the engine's word) the
        balance and the tiles are THEIRS, and the share of the step's real
        pairs that reached them is kept (a quarter at 64 of 256 under
        uniform routing: the routing's skew as one share sees it)."""
        held = self.moe_held
        for layers in load:
            layers = np.asarray(layers)
            if held is not None:
                sent = layers.sum()
                layers = layers[:, held[0]:held[0] + held[1]]
                if sent > 0:
                    self.moe_pairs_held_share = float(
                        100.0 * layers.sum() / sent)
            pairs = layers.sum(axis=0)
            if pairs.sum() > 0:
                self.moe_expert_load_max_ratio = float(
                    pairs.max() / pairs.mean())
                if grouped:
                    from ..ops.pallas.grouped_matmul import tile_fill_share
                    self.moe_grouped_tile_fill_share = (
                        100.0 * tile_fill_share(layers))

    # -- step accounting (engine.step) ---------------------------------------

    def on_step_dispatched(self, kind: str, behind: bool) -> None:
        key = (kind, behind)
        self.steps_dispatched[key] = self.steps_dispatched.get(key, 0) + 1

    def on_chain_break(self, reason: str) -> None:
        self.chain_breaks[reason] = self.chain_breaks.get(reason, 0) + 1

    def on_step(self, rec: dict) -> None:
        """``rec``: the record of the program just retired (the module
        docstring names its keys; ``t_iter`` is where the iteration that
        retired it began, ``new_tokens`` what it committed, and a kind's
        extras ride along: ``mode``, ``prefill_tokens``, ``decode_tokens``,
        ``drafted_tokens``, ``accepted_tokens``, ``draft_s``,
        ``routed_pairs``). Its phases, whichever iteration ran them, go to
        the trace under ITS number."""
        # Flight-recorder state snapshot, at most once per interval: one
        # monotonic read per step when nothing is due.
        self.flight.maybe_snapshot()
        self.phases.retire(rec)
        kind, rows = rec["kind"], rec["rows"]
        # One iteration of the loop: it dispatched this program's successor
        # and then fetched this one.
        duration_s = rec["t_retired"] - rec["t_iter"]
        self.step_duration.observe(duration_s)
        self.batch_size.observe(rows)
        if rec["exact"]:
            self.step_device.observe(rec["device_s"], (kind,))
        if rec["lead_exact"]:
            self.step_lead.observe(max(rec["lead_s"], 0.0), (kind,))
        if rec["starved_s"]:
            self.device_starved[kind] += rec["starved_s"]
        key = (kind, not rec["found_ready"])
        self.steps_retired[key] = self.steps_retired.get(key, 0) + 1
        if rec["slow"] is not None:
            cell = self.step_slow[rec["slow"]]
            cell[0] += rec["ready_gap_s"]
            cell[1] += 1
        for real, n in ((True, rec["tokens"]),
                        (False, rec["padded_tokens"] - rec["tokens"])):
            self.step_tokens[(kind, real)] = (
                self.step_tokens.get((kind, real), 0) + n)
        timing = {"device_ms": _ms(rec["device_s"]), "exact": rec["exact"],
                  "wait_ms": _ms(rec["wait_s"]), "lead_ms": _ms(rec["lead_s"]),
                  "starved_ms": _ms(rec["starved_s"])}
        self.phases.end_step(step=rec["step"], kind=kind, batch=rows,
                             duration_s=duration_s, phases=rec["phases"],
                             **timing)
        if kind in self.step_kind_counts:
            self.step_kind_counts[kind] += 1
        routed_pairs = rec.get("routed_pairs", 0)
        if routed_pairs:
            self.moe_routed_pairs[kind] = (
                self.moe_routed_pairs.get(kind, 0) + routed_pairs)
        new_tokens = rec.get("new_tokens", 0)
        mode = rec.get("mode") or "greedy"
        prefill_tokens = rec.get("prefill_tokens", 0)
        decode_tokens = rec.get("decode_tokens", 0)
        drafted_tokens = rec.get("drafted_tokens", 0)
        accepted_tokens = rec.get("accepted_tokens", 0)
        # The step event (trace ring and flight recorder): the program's
        # own number, kind, rows and clock, then what its kind adds.
        event = {"step": rec["step"], "batch": rows, **timing}
        if kind == "decode":
            event.update(tokens=new_tokens, mode=mode)
        if kind in ("mixed", "spec_mixed"):
            # The stall-free batching signal: how this step's token budget
            # split between the prefill chunk and the decode rows (a
            # spec_mixed step IS a stall-free step and counts both ways).
            self.mixed_prefill_tokens += prefill_tokens
            self.mixed_decode_tokens += decode_tokens
            event.update(prefill_tokens=prefill_tokens)
            if kind == "mixed":
                event.update(decode_tokens=decode_tokens)
        if kind in ("spec", "spec_mixed"):
            # The speculative-decoding signal: of the drafts this step
            # verified, how many committed (emitted tokens = accepted +
            # one bonus per row; new_tokens carries the realized total).
            # draft/verify phase attribution: the draft half is the
            # proposer-seam wall time, the verify half is the rest of the
            # step (dispatch + fetch of the one verify program).
            draft_s = rec.get("draft_s", 0.0)
            self.spec_drafted_tokens += drafted_tokens
            self.spec_accepted_tokens += accepted_tokens
            event.update(tokens=new_tokens, drafted=drafted_tokens,
                         accepted=accepted_tokens, mode=mode,
                         draft_ms=round(draft_s * 1e3, 3),
                         verify_ms=round(
                             max(duration_s - draft_s, 0.0) * 1e3, 3))
        self.tracer.emit(kind, "", **event)

    def on_frame(self, clock: Optional[FrameClock]) -> None:
        """The event loop wrote a frame, just now, whose tokens the program
        of ``clock`` produced (None: no program did, nothing to hold it
        against): the delay behind that program's end, whole and by
        stage."""
        if clock is None:
            return
        now = time.monotonic()
        observe = self.frame_stage.observe
        for labels, seconds in zip(_STAGE_LABELS, clock.stages(now)):
            observe(seconds, labels)
        self.frame_delay.observe(now - clock.program.t_ready)

    def mixed_step_ratio(self):
        """Fraction of device steps that carried a prefill chunk alongside
        decode work — plain mixed AND spec×mixed steps both count (a
        spec_mixed step is a stall-free step whose decode half happens to
        be verify slices), or None before any step ran. Near-zero under
        mixing-off or idle-prefill regimes; rises with sustained load when
        stall-free batching is doing its job."""
        total = sum(self.step_kind_counts.values())
        if total <= 0:
            return None
        return (self.step_kind_counts["mixed"]
                + self.step_kind_counts["spec_mixed"]) / total

    def spec_acceptance_ratio(self):
        """accepted/drafted draft tokens over all spec steps, or None
        before any spec step ran. The capacity signal for n-gram drafting:
        near-0 means the workload has no lookup structure (spec steps are
        pure overhead — disable or switch proposers)."""
        if self.spec_drafted_tokens <= 0:
            return None
        return self.spec_accepted_tokens / self.spec_drafted_tokens

    # -- rendering / export --------------------------------------------------

    def render_prometheus(self) -> list[str]:
        lines: list[str] = []
        for hist in (self.ttft, self.tpot, self.queue_wait,
                     self.prefill_latency, self.step_duration,
                     self.batch_size, self.e2e_latency):
            lines.extend(hist.render())
        lines.append("# TYPE kgct_step_phase_seconds_total counter")
        for p in PHASES:
            lines.append(
                "kgct_step_phase_seconds_total{phase=\"%s\"} %s"
                % (p, fmt(round(self.phases.totals.get(p, 0.0), 6))))
        # Per-phase mean step time, promoted from the tracer's breakdown so
        # dashboards read "where a step's wall time goes" without computing
        # rate ratios; zeros before any step — a fresh scrape is nan-free.
        lines.append("# TYPE kgct_step_phase_mean_seconds gauge")
        for p in PHASES:
            n = self.phases.counts.get(p, 0)
            mean = self.phases.totals.get(p, 0.0) / n if n else 0.0
            lines.append(
                "kgct_step_phase_mean_seconds{phase=\"%s\"} %s"
                % (p, fmt(round(mean, 9))))
        # Rolling SLO layer (autoscaler signals, ROADMAP 4(b)): attainment
        # of the admission-control TTFT budget over recent requests, the
        # budget itself, and budget-meeting goodput. 1.0 / 0.0 when fresh.
        # Multi-tenant QoS: the attainment/goodput families gain a
        # bounded-cardinality ``tier`` label (values = configured tier
        # names only), rendered inside each family's TYPE block. Absent
        # entirely when QoS is off; zeros/1.0-safe on a fresh scrape (an
        # empty window has missed nothing).
        tier_names = sorted(self.slo_by_tier)
        lines += [
            "# TYPE kgct_slo_ttft_budget_ms gauge",
            f"kgct_slo_ttft_budget_ms {fmt(self.slo.budget_ms)}",
            "# TYPE kgct_slo_ttft_attainment_ratio gauge",
            "kgct_slo_ttft_attainment_ratio "
            f"{fmt(round(self.slo.attainment(), 6))}",
        ]
        lines += [
            f'kgct_slo_ttft_attainment_ratio{{tier="{n}"}} '
            f"{fmt(round(self.slo_by_tier[n].attainment(), 6))}"
            for n in tier_names]
        lines += [
            "# TYPE kgct_slo_goodput_tokens_per_sec gauge",
            "kgct_slo_goodput_tokens_per_sec "
            f"{fmt(round(self.slo.goodput_tokens_per_sec(), 3))}",
        ]
        lines += [
            f'kgct_slo_goodput_tokens_per_sec{{tier="{n}"}} '
            f"{fmt(round(self.slo_by_tier[n].goodput_tokens_per_sec(), 3))}"
            for n in tier_names]
        if self.finished_by_tier:
            lines.append("# TYPE kgct_qos_requests_finished_total counter")
            for name in sorted(self.finished_by_tier):
                lines.append(
                    f'kgct_qos_requests_finished_total{{tier="{name}"}} '
                    f"{self.finished_by_tier[name]}")
        lines.extend(render_gauge("kgct_mixed_step_ratio",
                                  self.mixed_step_ratio()))
        lines.append("# HELP kgct_steps_dispatched_total step programs "
                     "dispatched, by kind; behind=1: queued while its "
                     "predecessor was still unfetched")
        lines.append("# TYPE kgct_steps_dispatched_total counter")
        for (kind, behind), n in sorted(self.steps_dispatched.items()):
            lines.append(
                'kgct_steps_dispatched_total{kind="%s",behind="%d"} %d'
                % (kind, behind, n))
        lines.append("# HELP kgct_chain_breaks_total times no step could "
                     "be scheduled behind the one in flight, by reason "
                     "(spec, stale, no_pages, penalties)")
        lines.append("# TYPE kgct_chain_breaks_total counter")
        for reason, n in sorted(self.chain_breaks.items()):
            lines.append('kgct_chain_breaks_total{reason="%s"} %d'
                         % (reason, n))
        lines.extend(self.step_device.render())
        lines.extend(self.step_lead.render())
        lines.append("# HELP kgct_device_starved_seconds_total time the "
                     "chip had nothing queued while the engine held "
                     "unfinished requests: a program's dispatch minus the "
                     "end of the program retired before it, where positive, "
                     "by the kind of the program that came late")
        lines.append("# TYPE kgct_device_starved_seconds_total counter")
        for kind in sorted(self.device_starved):
            lines.append('kgct_device_starved_seconds_total{kind="%s"} %s'
                         % (kind, fmt(round(self.device_starved[kind], 6))))
        lines.append("# HELP kgct_steps_retired_total step programs retired, "
                     "by kind; waited=0: found ready, the host came after "
                     "the chip")
        lines.append("# TYPE kgct_steps_retired_total counter")
        for (kind, waited), n in sorted(self.steps_retired.items()):
            lines.append(
                'kgct_steps_retired_total{kind="%s",waited="%d"} %d'
                % (kind, waited, n))
        lines.append("# HELP kgct_step_slow_seconds_total gap between the "
                     "ends of two step programs where it was over 0.5 s and "
                     "3x its kind's mean; cause=host: the program was found "
                     "ready or dispatched late, device: it was waited for")
        lines.append("# TYPE kgct_step_slow_seconds_total counter")
        for cause in sorted(self.step_slow):
            lines.append('kgct_step_slow_seconds_total{cause="%s"} %s'
                         % (cause, fmt(round(self.step_slow[cause][0], 6))))
        lines.append("# TYPE kgct_step_slow_total counter")
        for cause in sorted(self.step_slow):
            lines.append('kgct_step_slow_total{cause="%s"} %d'
                         % (cause, self.step_slow[cause][1]))
        lines.append("# HELP kgct_step_tokens_total tokens the step programs "
                     "computed, by kind; real=0: the bucket's padding")
        lines.append("# TYPE kgct_step_tokens_total counter")
        for (kind, real), n in sorted(self.step_tokens.items()):
            lines.append('kgct_step_tokens_total{kind="%s",real="%d"} %d'
                         % (kind, real, n))
        lines.append("# HELP kgct_worker_seconds_total the step loop "
                     "thread's wall by state: device_wait (blocked for a "
                     "program), inbox_wait (idle), host (everything else)")
        lines.append("# TYPE kgct_worker_seconds_total counter")
        worker = self.phases.worker_seconds()
        for state in WORKER_STATES:
            lines.append('kgct_worker_seconds_total{state="%s"} %s'
                         % (state, fmt(round(worker[state], 6))))
        lines.extend(self.frame_delay.render())
        lines.extend(self.frame_stage.render())
        if self.moe_routed_pairs:
            lines.append("# HELP kgct_moe_routed_pairs_total (token, expert) "
                         "pairs sent through the expert layers, by step kind")
            lines.append("# TYPE kgct_moe_routed_pairs_total counter")
            for kind, n in sorted(self.moe_routed_pairs.items()):
                lines.append(
                    'kgct_moe_routed_pairs_total{step_kind="%s"} %d'
                    % (kind, n))
            lines.append("# HELP kgct_moe_expert_load_max_ratio busiest "
                         "expert's pairs over the mean, last prefill, chunk "
                         "or mixed step")
            lines.append("# TYPE kgct_moe_expert_load_max_ratio gauge")
            lines.append("kgct_moe_expert_load_max_ratio %.4f"
                         % self.moe_expert_load_max_ratio)
            lines.append("# HELP kgct_moe_grouped_tile_fill_share percent "
                         "of the rows the grouped expert matmuls computed "
                         "that were routed pairs, last prefill, chunk or "
                         "mixed step on the grouped path")
            lines.append("# TYPE kgct_moe_grouped_tile_fill_share gauge")
            lines.append("kgct_moe_grouped_tile_fill_share %.4f"
                         % self.moe_grouped_tile_fill_share)
            if self.moe_pairs_held_share is not None:
                lines.append("# HELP kgct_moe_pairs_held_share percent of "
                             "the real routed pairs that reached an expert "
                             "this process holds, last prefill, chunk or "
                             "mixed step")
                lines.append("# TYPE kgct_moe_pairs_held_share gauge")
                lines.append("kgct_moe_pairs_held_share %.4f"
                             % self.moe_pairs_held_share)
        if self.dsa_visible_tokens:
            lines.append("# HELP kgct_dsa_chosen_tokens_total cached tokens "
                         "the decode rows attended to (index_topk a row at "
                         "most)")
            lines.append("# TYPE kgct_dsa_chosen_tokens_total counter")
            lines.append("kgct_dsa_chosen_tokens_total %d"
                         % self.dsa_chosen_tokens)
            lines.append("# HELP kgct_dsa_visible_tokens_total cached tokens "
                         "the decode rows could have attended to (their "
                         "contexts)")
            lines.append("# TYPE kgct_dsa_visible_tokens_total counter")
            lines.append("kgct_dsa_visible_tokens_total %d"
                         % self.dsa_visible_tokens)
        if self.block_passes:
            for name, help_, n in (
                    ("kgct_block_passes_total",
                     "denoising passes a block model's rows took, counted "
                     "a row", self.block_passes),
                    ("kgct_block_commit_passes_total",
                     "of them, those that carried a commit beside (the "
                     "K/V of the block before, written in the same pass)",
                     self.block_commit_passes),
                    ("kgct_block_commits_total",
                     "blocks committed (their K/V written to the pages)",
                     self.block_commits),
                    ("kgct_block_tokens_transferred_total",
                     "tokens the denoising passes transferred",
                     self.block_tokens_transferred),
                    ("kgct_block_positions_computed_total",
                     "positions the passes computed (two blocks a "
                     "row-pass, padding rows apart)",
                     self.block_positions_computed)):
                lines.append(f"# HELP {name} {help_}")
                lines.append(f"# TYPE {name} counter")
                lines.append(f"{name} {n}")
            lines.extend(self.block_passes_per_block.render())
        lines.append("# TYPE kgct_mixed_prefill_tokens_total counter")
        lines.append("kgct_mixed_prefill_tokens_total %d"
                     % self.mixed_prefill_tokens)
        lines.append("# TYPE kgct_mixed_decode_tokens_total counter")
        lines.append("kgct_mixed_decode_tokens_total %d"
                     % self.mixed_decode_tokens)
        lines.extend(render_gauge("kgct_spec_acceptance_ratio",
                                  self.spec_acceptance_ratio()))
        lines.append("# TYPE kgct_spec_drafted_tokens_total counter")
        lines.append("kgct_spec_drafted_tokens_total %d"
                     % self.spec_drafted_tokens)
        lines.append("# TYPE kgct_spec_accepted_tokens_total counter")
        lines.append("kgct_spec_accepted_tokens_total %d"
                     % self.spec_accepted_tokens)
        # Acceptance-adaptive k: the live rung. Absent when spec is off
        # (None), present from engine construction when on — a fresh
        # scrape is nan-free either way.
        lines.extend(render_gauge("kgct_spec_current_k",
                                  self.spec_current_k))
        lines.append("# TYPE kgct_spec_draft_tokens_total counter")
        lines.append("kgct_spec_draft_tokens_total %d"
                     % self.spec_draft_tokens)
        lines.extend(self.spec_draft_latency.render())
        lines.append("# TYPE kgct_kv_swap_out_pages_total counter")
        lines.append("kgct_kv_swap_out_pages_total %d"
                     % self.swap_pages["out"])
        lines.append("# TYPE kgct_kv_swap_in_pages_total counter")
        lines.append("kgct_kv_swap_in_pages_total %d" % self.swap_pages["in"])
        lines.extend(self.swap_latency.render())
        # Fleet-wide prefix cache: every outcome pre-seeded — zeros when
        # the fleet cache is off or idle, never an absent series.
        lines.append("# TYPE kgct_fleet_prefix_pulls_total counter")
        for oc in sorted(self.fleet_pulls):
            lines.append(f'kgct_fleet_prefix_pulls_total{{outcome="{oc}"}} '
                         f"{self.fleet_pulls[oc]}")
        lines.append("# TYPE kgct_fleet_prefix_spills_total counter")
        for oc in sorted(self.fleet_spills):
            lines.append(f'kgct_fleet_prefix_spills_total{{outcome="{oc}"}} '
                         f"{self.fleet_spills[oc]}")
        lines.append("# TYPE kgct_fleet_prefix_bytes_total counter")
        for d in sorted(self.fleet_bytes):
            lines.append(f'kgct_fleet_prefix_bytes_total{{dir="{d}"}} '
                         f"{self.fleet_bytes[d]}")
        lines.extend(self.fleet_pull_latency.render())
        # KV wire integrity: the full path x outcome matrix pre-seeded.
        lines.append("# TYPE kgct_kv_wire_corruptions_total counter")
        for (path, oc) in sorted(self.wire_corruptions):
            lines.append(
                f'kgct_kv_wire_corruptions_total{{path="{path}",'
                f'outcome="{oc}"}} {self.wire_corruptions[(path, oc)]}')
        # Peer quarantines: labels only from the seeded allowlists.
        lines.append("# TYPE kgct_peer_quarantines_total counter")
        for peer in sorted(self.peer_quarantines):
            lines.append(f'kgct_peer_quarantines_total{{peer="{peer}"}} '
                         f"{self.peer_quarantines[peer]}")
        return lines

    def export_perfetto(self) -> dict:
        return self.tracer.export_perfetto(
            step_records=(self.phases.step_records()
                          + self.phases.detached_records()))

    def clear_trace(self) -> None:
        """Empty every trace ring (lifecycle events, step-phase records,
        detached slices) for a scoped capture; histogram/total state — the
        /metrics contract — is untouched."""
        self.tracer.clear()
        self.phases.clear_records()
