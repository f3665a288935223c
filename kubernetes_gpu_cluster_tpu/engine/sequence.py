"""Sequence state tracked by the continuous-batching scheduler."""

from __future__ import annotations

import enum
import time
from typing import Optional

from .sampling_params import SamplingParams


class SequenceStatus(enum.Enum):
    WAITING = "waiting"        # queued, no KV pages yet
    RUNNING = "running"        # resident in the batch
    PREEMPTED = "preempted"    # evicted under memory pressure; resumes by
                               # swap-in (host KV tier) or recompute
    FINISHED = "finished"


class FinishReason(enum.Enum):
    STOP = "stop"              # hit EOS / stop token
    LENGTH = "length"          # hit max_tokens or max_model_len
    ABORT = "abort"            # client cancelled
    MIGRATE = "migrated"       # live-migrated to a peer replica (drain):
                               # the stream continues elsewhere; locally the
                               # sequence is terminal without a client-facing
                               # finish


class Sequence:
    """One request's generation state. Pages are owned by the scheduler's
    PageAllocator; this object just records which pages back it."""

    def __init__(self, request_id: str, prompt_token_ids: list[int],
                 params: SamplingParams, eos_token_id: Optional[int] = None,
                 block_length: int = 1):
        self.request_id = request_id
        # The model's ``block_length`` B. A sequence is its committed
        # prefix (K/V in the pages) and an OPEN BLOCK of B positions
        # beyond it, each an id or masked: an autoregressive model is
        # B = 1, whose open block is its one next position. For B > 1 the
        # host holds the block AS OF THE LAST PROGRAM IT FETCHED (the
        # program in flight carries it on, on the device: engine/block.py):
        # ``num_committed`` positions are final (a multiple of B),
        # ``block_ids`` / ``block_masked`` are the open block at
        # [num_committed, num_committed + B), ``block_marks`` what the pass
        # that transferred a position said of it (log-probability, top
        # alternatives) until the position leaves in position order, and
        # ``block_passes`` the passes the open block has taken so far.
        # A block whose last masked position was transferred is final at
        # once (``num_committed`` advances, the next block opens), but its
        # K/V reach the pages one pass later, written beside the next
        # block's first denoising pass: until then it is the PENDING block,
        # ``block_pending``, at [num_committed - B, num_committed), its ids
        # the sequence's own tokens there. A request that ends with a
        # block pending never writes it; a preemption forgets the flag
        # with the pages (the tokens are re-prefilled like any other).
        self.block_length = block_length
        self.num_committed = 0
        self.block_ids: list[int] = []
        self.block_masked: list[bool] = []
        self.block_marks: list = []
        self.block_passes = 0
        self.block_pending = False
        self.prompt_token_ids = list(prompt_token_ids)
        self.output_token_ids: list[int] = []
        self.output_logprobs: list[float] = []
        self.output_top_logprobs: list[list] = []   # [(token_id, lp) x N]
        self.params = params
        self.eos_token_id = eos_token_id
        self.status = SequenceStatus.WAITING
        self.finish_reason: Optional[FinishReason] = None
        self.pages: list[int] = []
        # A state model's second kind of memory: the slot of recurrent
        # state the sequence holds from admission to finish or preemption
        # (engine/kv_cache.PageAllocator); None before, after, and always
        # for a model without state layers.
        self.state_slot: Optional[int] = None
        # Two-tier KV cache: host-pool page ids holding this sequence's
        # committed KV while it is preempted-by-swap (engine/kv_cache).
        self.host_pages: list[int] = []
        self.arrival_time = time.monotonic()
        self.first_token_time: Optional[float] = None  # for TTFT metrics
        # Disaggregated import: the decode-replica-observed TTFT (remote
        # prefill + KV transfer + import). step() never sees the first-token
        # transition for an imported sequence — append_token stamps
        # first_token_time at import — so TTFT-based accounting (histogram,
        # SLO attainment/goodput gate) must use this span, not
        # first_token_time - arrival_time (which would read ~0).
        self.handoff_ttft_s: Optional[float] = None
        # Lifecycle timestamps/counters for the observability layer: first
        # scheduling (queue-wait), terminal time (e2e latency; also the
        # idempotence guard for Observability.on_finish), preemption count
        # (outcome labeling + preempt/resume trace events).
        self.scheduled_time: Optional[float] = None
        self.finish_time: Optional[float] = None
        self.preempt_count = 0
        # Chunked prefill progress: tokens whose KV is already committed to
        # the pool by earlier chunks. Reset on preemption (pages are freed,
        # the prompt recomputes from scratch).
        self.num_prefilled = 0
        # Prefix-cache lookup done (one per (re)admission — a blocked head is
        # rescheduled many times and must not re-hash/re-fork per call).
        self.prefix_checked = False
        # Disaggregated prefill/decode: a prefill-replica request whose
        # committed KV must survive its finish so the export seam can ship
        # it to a decode replica (scheduler.finish parks it in
        # ``scheduler.held`` instead of releasing; aborts still release).
        self.hold_kv = False
        # The device queue: tokens this sequence was sampled by the step
        # program in flight (dispatched, not fetched: W of a decode window,
        # 1 of a mixed or prefill step), and the row of that program's
        # last-token output that holds the newest. The engine sets both
        # when the step's predecessor retires and clears them when the
        # step itself does; 0 and -1 whenever nothing is in flight. A block
        # model's row is told the PASSES in flight instead (a pass yields 0
        # to B tokens: which, the host learns at the fetch), and the row of
        # that program's final state that holds its open block.
        self.inflight_tokens = 0
        self.inflight_passes = 0
        self.inflight_row = -1

    @property
    def all_token_ids(self) -> list[int]:
        """Prompt + generated tokens — everything whose KV must be resident.
        This is what a recompute-prefill replays after preemption."""
        return self.prompt_token_ids + self.output_token_ids

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_output_tokens(self) -> int:
        return len(self.output_token_ids)

    @property
    def num_tokens(self) -> int:
        return self.num_prompt_tokens + self.num_output_tokens

    @property
    def prefill_len(self) -> int:
        """The tokens a prefill computes and writes: whole blocks of what
        the sequence holds (all of it at B = 1); the other
        ``num_tokens % B`` open the first block."""
        return self.num_tokens - self.num_tokens % self.block_length

    def admit_tokens(self, end: Optional[int] = None) -> int:
        """The positions a prefill that ends at ``end`` (default: the whole
        of it) must hold pages for. A block model's LAST chunk also holds
        them for the block beyond it, which the first pass beside it may
        make whole (``window_last_pos(1)``): a sequence admitted with less
        would be preempted by its own first pass, for ever."""
        whole, B = self.prefill_len, self.block_length
        end = whole if end is None else end
        return end + B if B > 1 and end >= whole else end

    def open_block(self) -> None:
        """The open block at ``num_committed``: what the sequence already
        holds there (a prompt's tail, or tokens that left before a
        preemption), the rest masked."""
        held = self.all_token_ids[self.num_committed:]
        n, B = len(held), self.block_length
        self.block_ids = held + [0] * (B - n)
        self.block_masked = [False] * n + [True] * (B - n)
        self.block_marks = [None] * B
        self.block_passes = 0

    def window_last_pos(self, passes: int, max_len: int) -> int:
        """Highest position a step program of ``passes`` passes needs a
        page for: ``last_window_pos`` for one token a pass; for a block
        model the end of the last block the passes can make whole (every
        pass may complete the open block, and the pass after it writes
        it: the block pending at the program's end is written by the
        next program's first pass, out of pages held already), the passes
        in flight counted beside the program's own since ``num_committed``
        is as of the last fetch, capped by the model's length and the last
        block this request can reach."""
        B = self.block_length
        if B == 1:
            return self.last_window_pos(self.sched_tokens - 1, passes,
                                        max_len)
        passes += self.inflight_passes
        end = self.num_committed + B * passes
        cap = -(-(self.num_prompt_tokens + self.params.max_tokens) // B) * B
        return min(end, max_len, cap) - 1

    @property
    def sched_tokens(self) -> int:
        """``num_tokens`` as it will read once the step in flight is
        fetched (a finish inside it aside): what a successor dispatched
        behind that step takes its positions, context lengths, slots and
        page growth from."""
        return self.num_tokens + self.inflight_tokens

    def finishes_in_flight(self, max_len: int) -> bool:
        """The step in flight reaches this request's ``max_tokens`` or the
        model's length: it is known to finish before its tokens are
        fetched and rides no further step."""
        return self.inflight_tokens > 0 and (
            self.num_output_tokens + self.inflight_tokens
            >= self.params.max_tokens
            or self.sched_tokens >= max_len)

    def last_window_pos(self, next_input_pos: int, window: int,
                        max_len: int) -> int:
        """Highest position a decode window starting its inputs at
        ``next_input_pos`` can touch, clamped to the model cap AND this
        request's own max_tokens budget. Window-tail tokens past either
        bound route to the scrap page, so page growth sized by this bound
        makes EXACTLY-sized pools safe (no pages a request can never use).
        The single source of truth for every decode-row builder of the
        scheduler, whether a step is in flight or not."""
        return min(next_input_pos + window - 1, max_len - 1,
                   self.num_prompt_tokens + self.params.max_tokens - 1)

    @property
    def is_finished(self) -> bool:
        return self.status == SequenceStatus.FINISHED

    def append_token(self, token_id: int,
                     logprob: Optional[float] = None,
                     top: Optional[list] = None) -> None:
        if self.first_token_time is None:
            self.first_token_time = time.monotonic()
        self.output_token_ids.append(token_id)
        if logprob is not None:
            self.output_logprobs.append(logprob)
        if top is not None:
            self.output_top_logprobs.append(top)

    def check_stop(self, max_model_len: int) -> Optional[FinishReason]:
        """Token-level stop conditions (string-level stops are handled by the
        server layer which owns the tokenizer)."""
        if not self.output_token_ids:
            return None
        last = self.output_token_ids[-1]
        if not self.params.ignore_eos and self.eos_token_id is not None \
                and last == self.eos_token_id:
            return FinishReason.STOP
        if last in self.params.stop_token_ids:
            return FinishReason.STOP
        if self.num_output_tokens >= self.params.max_tokens:
            return FinishReason.LENGTH
        if self.num_tokens >= max_model_len:
            return FinishReason.LENGTH
        return None

    def __repr__(self):
        return (f"Sequence({self.request_id}, status={self.status.value}, "
                f"prompt={self.num_prompt_tokens}, out={self.num_output_tokens})")
