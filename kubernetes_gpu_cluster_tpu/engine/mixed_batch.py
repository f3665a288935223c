"""Mixed prefill/decode batch assembly (stall-free TTFT scheduling).

The legacy scheduler policy is prefill-ELSE-decode: a scheduled prefill
window stalls every running decode for the whole step, and a busy decode
stream starves waiting prefills until its window drains — exactly the
trade-off VERDICT r5 measured as 3.1-3.4 s p50 TTFT at 70% decode
capacity (ROADMAP item #1 targets <= 1 s). Sarathi-Serve (Agrawal et al.,
OSDI'24) removes it by coalescing chunked-prefill tokens into the same
device step as decode tokens on top of Orca-style continuous batching
(Yu et al., OSDI'22): "stall-free batching".

This module assembles that step. One token-budget-bounded batch carries:

- **decode rows**: every running sequence's next decode token (decode has
  token-budget priority — it is never dropped from a mixed step), and
- **a prefill chunk**: a budgeted slice of the queue-head prompt, riding
  the existing chunked-prefill machinery (the chunk attends to the head's
  own committed pool history).

Unified ragged layout over one padded token axis ``[Tp_bucket | R_pad]``:

    tokens        [T_pad]   chunk tokens, then decode tokens, then padding
    seg_ids       [T_pad]   0 for chunk tokens, -1 elsewhere (the decode
                            slice is addressed positionally, not by segment)
    positions     [T_pad]   global position of every token (RoPE input)
    slot_mapping  [T_pad]   KV write slot per token (padding -> scrap page)
    page_tables   [R_pad, pages_bucket]  decode rows' page tables
    context_lens  [R_pad]   decode rows' valid token counts
    chunk_page_table [1, W] the head sequence's pages (history attention)
    logits_indices [R_pad]  sampled rows: decode row i at Tp_bucket + i,
                            the chunk's last token at chunk_len - 1

Sampling rows include the chunk row (R = D + 1, bucketed by the decode
buckets) so the compiled shape depends only on (Tp_bucket, R_pad, hist
width) — bounded like every other jit shape in the engine. A partial
chunk's sampled token is discarded by the engine (same contract as the
solo chunked-prefill path); a final chunk's sampled token is the
sequence's first generated token. The one step without a chunk row: a
partial chunk beside a server whose every seat decodes, where that row
would be the only one past the seats' bucket (``mixed_row_bucket``).

Invariants preserved from the legacy policy:

- A mid-chunk sequence (holding pages) only ever advances at waiting[0];
  mixing never touches sequences deeper in the queue.
- Decode page growth happens BEFORE chunk allocation and may preempt the
  youngest running sequence; chunk allocation never preempts (admitting
  waiting work must not evict running work).
- When mixing cannot produce a batch (no room in the budget, no pages for
  the chunk, batch full), the scheduler falls through to the legacy
  prefill-else-decode paths; every policy probe runs BEFORE any state
  mutation, so those bow-outs leave the scheduler untouched. The one
  post-mutation bow-out (no pages for the chunk after decode page growth)
  leaves only growth the fall-through decode step needs anyway.
  `mixed_batch_enabled=false` behavior is byte-identical.
- Bursts keep legacy packed admission: when two or more whole fresh
  prompts could ride one legacy prefill batch, mixing bows out — one
  packed step admits them all, where head-only mixing would serialize one
  prompt per step and fall behind the arrival rate. Mixing engages for
  chunk-streaming heads and the shallow-queue steady state, which is where
  decode stalls actually cost TTFT.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional

import numpy as np

from ..utils import cdiv, get_logger

if TYPE_CHECKING:  # pragma: no cover - import cycle guard (scheduler imports us)
    from ..config import SchedulerConfig
    from .scheduler import ScheduledBatch, Scheduler

logger = get_logger("mixed_batch")


def _commit_chunk_progress(sched: "Scheduler", head, end: int, n_rows: int,
                           final: bool, detail: str) -> int:
    """Chunk-progress bookkeeping shared by the mixed and spec×mixed
    builders (one definition: queue-wait stamping, chunk trace event,
    final-chunk admission + prefix registration). Returns the pre-advance
    ``hist_len``. ``detail`` labels the partial-chunk log line with the
    step shape (decode rows vs verify slices)."""
    from .sequence import SequenceStatus

    hist_len = head.num_prefilled
    head.num_prefilled = end
    if head.scheduled_time is None or (
            head.status == SequenceStatus.PREEMPTED and hist_len == 0):
        sched.obs.on_scheduled(head, n_rows + 1)
    sched.obs.on_prefill_chunk(head, hist_len, end, head.num_tokens)
    if final:
        sched.waiting.popleft()
        sched._enter_running(head)
        sched._register_prefix(head)
    else:
        logger.info("%s prefill chunk [%d:%d) of %d (%s)",
                    head.request_id, hist_len, end, head.num_tokens, detail,
                    extra={"request_id": head.request_id})
    return hist_len


def plan_chunk_tokens(remaining: int, n_decode: int, budget: Optional[int],
                      max_prefill_tokens: int) -> int:
    """Token-budget split for one mixed step: ``n_decode`` decode tokens
    claim their share of ``budget`` first, the prefill chunk gets the
    remainder (capped by the per-step prefill budget). Pure policy — unit
    tested directly."""
    total = budget if budget is not None else max_prefill_tokens
    room = min(total - n_decode, max_prefill_tokens)
    return max(0, min(remaining, room))


def mixed_row_bucket(rows: int, chunk_bucket: int,
                     sc: "SchedulerConfig") -> int:
    """The sampled-row bucket of a mixed step: the decode bucket of ``rows``,
    but never under a sixteenth of the chunk's bucket, so padding rows stay
    under 1/16 of the step's tokens, and that floor never over the bucket of
    the server's seats (``SchedulerConfig.seat_bucket``): a step is not
    built for rows that no seat can fill. Each (chunk bucket x row bucket)
    is a step program of its own to compile, to keep in the compile cache
    and to load at every start: 42 on the default grid
    (``mixed_chunk_buckets``: six chunk buckets), 12 with this floor at 64
    seats, and a server whose seats fill behind prompts of 1-2 k tokens
    meets one program a chunk bucket where it met seven.

    A padding row is cheap, not free. Dense and latent attention skip it
    (its context is 0: it meets no page) and it costs its share of the
    projections, the head and the sampler. A sparse-attention model's
    choice has no such skip: the indexer scores, sorts and looks up
    ``index_topk`` positions for every row of the bucket, and every layer
    gathers and attends over that many latent rows a row, padding or not
    (glm-5.2 on 16 seats beside a 2048-token chunk: 64 rows where 16 exist
    were an eighth of the step, PERF.md section 6, PR 48). Hence the seats'
    bucket and not the ladder's top. (One row more than the seats' bucket
    would exist where every seat decodes and a waiting head's chunk is not
    its last: ``build_mixed_batch`` leaves that chunk's row out, it samples
    nothing that counts.)"""
    from .scheduler import _bucket

    floor = min(chunk_bucket // 16, sc.seat_bucket)
    return _bucket(max(rows, floor), sc.decode_buckets)


def mixed_steps_of_prompt(sched: "Scheduler", n: int) -> list:
    """The (chunk rung, history-table width) of each mixed step a prompt of
    ``n`` tokens passes through beside full seats, as ``build_mixed_batch``
    plans its chunks: a step program each (``LLMEngine.warm_mixed_steps``)."""
    from .scheduler import _bucket

    sc, steps, done = sched.config.scheduler, [], 0
    B = sched.block_length
    n = min(n, sched.config.effective_max_len - 1)
    n = max(n - n % B, B)       # a prefill computes whole blocks
    while done < n:
        chunk = plan_chunk_tokens(n - done, sc.max_num_seqs - 1,
                                  sc.decode_priority_token_budget,
                                  sc.max_prefill_tokens)
        chunk -= chunk % B
        if chunk <= 0:
            break
        done += chunk
        steps.append((_bucket(chunk, sc.mixed_chunk_buckets),
                      sched.chunk_table_width(cdiv(done, sched.page_size))))
    return steps


def build_mixed_batch(sched: "Scheduler", behind: bool = False
                      ) -> Optional["ScheduledBatch"]:
    """Assemble one mixed step from the scheduler's live state, or return
    None when mixing is not possible this step (caller falls through to the
    legacy prefill-else-decode policy).

    Mutates scheduler state exactly like the pure paths do: decode page
    growth (with youngest-first preemption), chunk page allocation, chunk
    progress on the queue head, and running-set admission on a final chunk.
    """
    from .scheduler import ScheduledBatch, _bucket

    sc = sched.config.scheduler
    head = sched.waiting[0]
    # The model's block length: a chunk ends on a multiple of it, a row is
    # that many positions (1: a token a row, as ever).
    B = sched.block_length
    sched._try_prefix_reuse(head)

    # -- policy probes (no state mutation until all pass) -------------------
    # QoS chunk-gate (mirror of the solo-chunk path's): a mid-chunk
    # lower-priority head bows the mixed step out so the legacy admission
    # pass can schedule the owed higher-priority waiter — decode stalls
    # one step, exactly the legacy prefill-else-decode cost.
    if (sched.qos is not None
            and (head.num_prefilled > 0
                 or head.prefill_len > sc.max_prefill_tokens)
            and sched._qos_defer_chunk(head)):
        return None
    # Sampled-row count D+1 must stay inside the configured decode-bucket
    # grid: falling through to next_power_of_2 would compile an unwarmed
    # out-of-grid shape mid-serving (and dodge the compile-guard's bound).
    # D can only shrink between this probe and assembly (preemption), and a
    # smaller D still buckets inside the grid.
    if len(sched.running) + 1 > sc.decode_buckets[-1]:
        return None
    # Packing beats serial mixing under bursts: one legacy prefill step
    # admits MANY whole fresh prompts (decode stalls once), while head-only
    # mixing serializes one prompt per step and falls behind burst
    # arrivals. Mix only when the head is mid-chunk, too big to pack, or
    # effectively alone among the packable — the sustained-load steady
    # state, where stall-free steps are pure win. Deep queues keep the
    # legacy packed admission, so stability under overload is unchanged.
    # The scan mirrors legacy lookahead depth: a chunkable prompt at
    # waiting[1] must not mask packable small prompts behind it.
    if (head.num_prefilled == 0
            and head.prefill_len <= sc.max_prefill_tokens
            and len(sched.running) + 2 <= sched.max_num_seqs):
        packable, total = 0, 0
        for i in range(min(len(sched.waiting), sched.PREFILL_LOOKAHEAD + 1)):
            seq = sched.waiting[i]
            if (seq.num_prefilled == 0
                    and total + seq.prefill_len <= sc.max_prefill_tokens):
                packable += 1
                total += seq.prefill_len
                if packable >= 2:
                    return None
    remaining = head.prefill_len - head.num_prefilled

    def plan(rows: int) -> int:
        # A row claims ONE token of the step's budget whatever its width
        # (a block model's row is B positions of the same weight stream):
        # beside full seats a chunk is 2048 - rows tokens floored to B, so
        # a prompt of the admitted traffic (<= 1920) stays one mixed step.
        chunk = plan_chunk_tokens(remaining, rows,
                                  sc.decode_priority_token_budget,
                                  sc.max_prefill_tokens)
        return chunk - chunk % B

    chunk = plan(len(sched.running))
    if chunk <= 0:
        return None
    if (head.num_prefilled + chunk >= head.prefill_len
            and len(sched.running) >= sched.max_num_seqs):
        # No seat for the head once its prompt completes: let the pure
        # decode path run until a running sequence finishes.
        return None

    # -- state mutation starts here -----------------------------------------
    # Decode first: grow every running sequence's pages for ONE decode
    # position (mixed steps advance decode by a single token — the chunk in
    # the same program runs once, so there is no multi-step window to scan).
    # May preempt the youngest (tier-aware under QoS — _preempt_victim);
    # recompute victims already slot behind a mid-chunk head at
    # waiting[0]. If the chunk cannot get pages
    # after this, the growth is not wasted: the fall-through decode step
    # needs exactly these pages.
    decode_seqs = sched._grow_decode_pages(window=1, behind=behind)
    if not decode_seqs or not sched.waiting or sched.waiting[0] is not head:
        # Preemption displaced the (fresh, pageless) head — let the legacy
        # path deal with the victim-headed queue this step.
        return None
    # Recompute the chunk with the post-growth decode-row count (preemption
    # can only shrink D, which only widens the chunk's budget room; it also
    # frees a running seat, so a now-final chunk still has one).
    chunk = plan(len(decode_seqs))
    if chunk <= 0:
        return None
    end = head.num_prefilled + chunk
    final = end >= head.prefill_len
    need = cdiv(head.admit_tokens(end), sched.page_size) - len(head.pages)
    if sched.needs_slot(head) and not sched.allocator.num_free_slots:
        return None     # a state model's head waits for a slot as for pages
    if need > 0:
        if not sched.allocator.can_allocate(need):
            # Never preempt running decodes to feed a prefill chunk; the
            # legacy path owns the blocked-head handling (lookahead
            # admission, capacity termination when the pool drains).
            return None
        head.pages.extend(sched.allocator.allocate(need))
    if sched.needs_slot(head):
        head.state_slot = sched.allocator.allocate_slot()

    D = len(decode_seqs)
    Tp = _bucket(chunk, sc.mixed_chunk_buckets)
    # A chunk that is not its prompt's last samples nothing that counts
    # (the engine discards it). Beside a server whose every seat decodes,
    # its row would be the one row past the seats' bucket: a step program
    # of its own, twice the rows, that no warm-up meets. It is left out.
    # A block model's chunk never has one: its prompt's tail opens the
    # first block, no token is sampled behind a prefill.
    chunk_row = (final or D < sc.seat_bucket) and B == 1
    R_pad = mixed_row_bucket(D + chunk_row, Tp, sc)
    T_pad = Tp + R_pad * sched.row_width

    tokens = np.zeros(T_pad, np.int32)
    seg_ids = np.full(T_pad, -1, np.int32)
    positions = np.zeros(T_pad, np.int32)
    slot_mapping = np.zeros(T_pad, np.int32)     # scrap-page slots for padding

    # -- prefill chunk slice [0:Tp) -----------------------------------------
    tokens[:chunk] = head.all_token_ids[head.num_prefilled:end]
    seg_ids[:chunk] = 0
    tok_pos = np.arange(head.num_prefilled, end)
    positions[:chunk] = tok_pos
    head_pages = np.asarray(head.pages, np.int64)
    slot_mapping[:chunk] = (head_pages[tok_pos // sched.page_size] *
                            sched.page_size + tok_pos % sched.page_size)
    chunk_page_table = sched._chunk_page_table(head,
                                               end if B > 1 else None)

    # -- decode slice [Tp:Tp+R_pad) -----------------------------------------
    # Static table width: never recompiles as contexts grow (same rationale
    # as the pure decode path).
    pages_bucket = cdiv(sched.config.effective_max_len, sched.page_size)
    page_tables = np.zeros((R_pad, pages_bucket), np.int32)
    context_lens = np.zeros(R_pad, np.int32)
    tok_src = np.full(R_pad, -1, np.int32)
    rows = dict(page_tables=page_tables, context_lens=context_lens)
    if B > 1:       # the rows' open blocks, laid out on the device
        rows = sched.fill_block_rows(decode_seqs, R_pad)
    else:
        for s, seq in enumerate(decode_seqs):
            sched._fill_decode_row(seq, s, Tp, tokens, positions,
                                   slot_mapping, page_tables, context_lens,
                                   tok_src)

    # -- sampled rows -------------------------------------------------------
    logits_indices = np.zeros(R_pad, np.int32)
    logits_indices[:D] = Tp + np.arange(D)
    if chunk_row:
        logits_indices[D] = chunk - 1      # the chunk's last token's hidden

    # -- chunk progress bookkeeping (mirrors Scheduler._schedule_chunk) -----
    hist_len = _commit_chunk_progress(sched, head, end, D, final,
                                      f"mixed, +{D} decode rows")

    seqs = decode_seqs + [head]
    return ScheduledBatch(
        kind="mixed", seqs=seqs, tokens=tokens, positions=positions,
        slot_mapping=slot_mapping, seg_ids=seg_ids,
        logits_indices=logits_indices, tok_src=tok_src, **rows,
        chunk_page_table=chunk_page_table,
        hist_len=hist_len, partial=not final, prefill_token_count=chunk,
        seg_slots=sched._state_slots([head], R_pad),
        row_slots=sched._state_slots(decode_seqs, R_pad),
        **sched._sampling_arrays(seqs if chunk_row else decode_seqs, R_pad))


def padding_mixed_batch(sched: "Scheduler", Tp: int, R_pad: int,
                        hist_width: int = 1) -> "ScheduledBatch":
    """A mixed step's batch at the chunk bucket ``Tp`` beside ``R_pad`` rows
    with no sequence at all (what ``LLMEngine.warm_mixed_steps``
    dispatches): a chunk of one token with no history whose page table is
    ``hist_width`` pages wide (one, as a prompt inside a page has it),
    written like every padding token and row to the scrap page and the
    scrap slot."""
    from .scheduler import ScheduledBatch

    B = sched.block_length
    T_pad = Tp + R_pad * sched.row_width
    seg_ids = np.full(T_pad, -1, np.int32)
    seg_ids[0] = 0
    pages_bucket = cdiv(sched.config.effective_max_len, sched.page_size)
    rows = (sched.fill_block_rows([], R_pad) if B > 1 else dict(
        page_tables=np.zeros((R_pad, pages_bucket), np.int32),
        context_lens=np.zeros(R_pad, np.int32)))
    return ScheduledBatch(
        kind="mixed", seqs=[], tokens=np.zeros(T_pad, np.int32),
        positions=np.zeros(T_pad, np.int32),
        slot_mapping=np.zeros(T_pad, np.int32), seg_ids=seg_ids,
        logits_indices=np.zeros(R_pad, np.int32), **rows,
        tok_src=np.full(R_pad, -1, np.int32),
        chunk_page_table=np.zeros((1, hist_width), np.int32), hist_len=0,
        seg_slots=sched._state_slots([], R_pad),
        row_slots=sched._state_slots([], R_pad),
        **sched._sampling_arrays([], R_pad))


def build_spec_mixed_batch(sched: "Scheduler") -> Optional["ScheduledBatch"]:
    """Spec×mixed composition: one device step carrying every running row's
    ``[last, d_1..d_k]`` VERIFY SLICE plus the budgeted chunk of the
    queue-head prompt — so enabling speculative decoding no longer forfeits
    the mixed-batching TTFT win (before this, spec rows and a prefill chunk
    could not share a dispatched program, and the scheduler had to pick).

    Token-axis layout ``[Tp_bucket | R_pad * S]`` (S = k+1):

        [0:Tp)        the prefill chunk, exactly the mixed layout
                      (seg 0 on chunk tokens, history attention against
                      chunk_page_table);
        [Tp + s*S, Tp + (s+1)*S)
                      running row s's verify slice, exactly the spec
                      layout (paged history + S x S causal block); seg_ids
                      carry the row id (the sanitizer's slot map), the
                      device derives the split statically from S.

    Sampling rows are the R_pad spec rows plus ONE chunk row that rides
    device row R_pad (``chunk_device_row``); logits are computed for every
    verify slot plus the chunk's last token. The compiled family is
    (prefill bucket x row bucket x history width) per ladder rung S — one
    more bounded grid, pinned by tests/test_compile_guard.py.

    Policy probes mirror build_mixed_batch (QoS chunk-gate, burst packing,
    budget split — decode rows claim S tokens EACH, the true forward cost
    of a verify slice) plus the spec bow-outs (k throttled to 0, rows
    outside the bucket grid, nothing proposed). Every bow-out returns None
    and the caller falls through to the PLAIN mixed step, so spec×mixed
    never costs a composition the engine already had. The device queue is
    not in play at this seam: spec steps are synchronous by construction
    (the next step's drafts depend on this one's accepted tokens), unlike
    plain mixed steps, whose successor depends on chunk progress alone,
    which the host knows.
    """
    from .scheduler import ScheduledBatch, _bucket
    from .spec.verifier import collect_proposals, resolve_spec_k

    sc = sched.config.scheduler
    k = resolve_spec_k(sched)
    if k < 1:
        return None               # adaptive floor: plain mixed serves TTFT
    S = k + 1
    head = sched.waiting[0]
    sched._try_prefix_reuse(head)

    # -- policy probes (no state mutation until all pass) -------------------
    if (sched.qos is not None
            and (head.num_prefilled > 0
                 or head.prefill_len > sc.max_prefill_tokens)
            and sched._qos_defer_chunk(head)):
        return None
    # Spec rows bucket like the pure spec step; the chunk rides one row
    # PAST the bucket, so only the row count itself must stay in the grid.
    if len(sched.running) > sc.decode_buckets[-1]:
        return None
    # Burst packing beats serial mixing — the same probe as the mixed path.
    if (head.num_prefilled == 0
            and head.num_tokens <= sc.max_prefill_tokens
            and len(sched.running) + 2 <= sched.max_num_seqs):
        packable, total = 0, 0
        for i in range(min(len(sched.waiting), sched.PREFILL_LOOKAHEAD + 1)):
            seq = sched.waiting[i]
            if (seq.num_prefilled == 0
                    and total + seq.num_tokens <= sc.max_prefill_tokens):
                packable += 1
                total += seq.num_tokens
                if packable >= 2:
                    return None
    remaining = head.num_tokens - head.num_prefilled
    chunk = plan_chunk_tokens(remaining, len(sched.running) * S,
                              sc.decode_priority_token_budget,
                              sc.max_prefill_tokens)
    if chunk <= 0:
        return None
    if (head.num_prefilled + chunk >= head.num_tokens
            and len(sched.running) >= sched.max_num_seqs):
        return None

    # -- state mutation starts here -----------------------------------------
    # Verify slices write S KV entries per row before the host sees a
    # token — the spec growth window, not the mixed path's single token.
    decode_seqs = sched._grow_decode_pages(window=S)
    if not decode_seqs or not sched.waiting or sched.waiting[0] is not head:
        return None
    proposals, draft_s = collect_proposals(sched, decode_seqs, k)
    if not any(proposals):
        return None               # nothing draftable: plain mixed is cheaper
    chunk = plan_chunk_tokens(remaining, len(decode_seqs) * S,
                              sc.decode_priority_token_budget,
                              sc.max_prefill_tokens)
    if chunk <= 0:
        return None
    end = head.num_prefilled + chunk
    final = end >= head.num_tokens
    need = cdiv(end, sched.page_size) - len(head.pages)
    if need > 0:
        if not sched.allocator.can_allocate(need):
            return None
        head.pages.extend(sched.allocator.allocate(need))

    D = len(decode_seqs)
    ps = sched.page_size
    max_len = sched.config.effective_max_len
    Tp = _bucket(chunk, sc.mixed_chunk_buckets)
    R_pad = _bucket(D, sc.decode_buckets)
    T_pad = Tp + R_pad * S
    pages_bucket = cdiv(max_len, ps)

    tokens = np.zeros(T_pad, np.int32)
    seg_ids = np.full(T_pad, -1, np.int32)
    positions = np.zeros(T_pad, np.int32)
    slot_mapping = np.zeros(T_pad, np.int32)   # scrap-page slots for padding

    # -- prefill chunk slice [0:Tp) -----------------------------------------
    tokens[:chunk] = head.all_token_ids[head.num_prefilled:end]
    seg_ids[:chunk] = 0
    tok_pos = np.arange(head.num_prefilled, end)
    positions[:chunk] = tok_pos
    head_pages = np.asarray(head.pages, np.int64)
    slot_mapping[:chunk] = (head_pages[tok_pos // ps] * ps + tok_pos % ps)
    chunk_page_table = sched._chunk_page_table(head)

    # -- verify slices [Tp : Tp + R_pad*S) ----------------------------------
    # Exactly the spec verifier's per-row layout, offset by Tp (ONE shared
    # fill — fill_verify_slices — so the slot/scrap contract cannot drift);
    # padding slices keep scrap-page slots and seg -1.
    from .spec.verifier import fill_verify_slices
    slot_mapping[Tp:] = np.arange(R_pad * S, dtype=np.int32) % ps
    page_tables = np.zeros((R_pad, pages_bucket), np.int32)
    context_lens = np.zeros(R_pad, np.int32)
    draft_lens = np.zeros(R_pad, np.int32)
    fill_verify_slices(decode_seqs, proposals, k, ps, max_len, tokens,
                       seg_ids, positions, slot_mapping, page_tables,
                       context_lens, draft_lens, base=Tp)

    # -- sampled rows -------------------------------------------------------
    # Logits for EVERY verify slot (acceptance needs all draft positions)
    # plus the chunk's last token, which samples on device row R_pad.
    logits_indices = np.zeros(R_pad * S + 1, np.int32)
    logits_indices[:R_pad * S] = Tp + np.arange(R_pad * S)
    logits_indices[R_pad * S] = chunk - 1

    # -- chunk progress bookkeeping (shared with build_mixed_batch) ---------
    hist_len = _commit_chunk_progress(
        sched, head, end, D, final,
        f"spec-mixed, +{D} verify slices, k={k}")

    seqs = decode_seqs + [head]
    rows = list(range(D)) + [R_pad]
    return ScheduledBatch(
        kind="spec_mixed", seqs=seqs, tokens=tokens, positions=positions,
        slot_mapping=slot_mapping, seg_ids=seg_ids,
        logits_indices=logits_indices, page_tables=page_tables,
        context_lens=context_lens, chunk_page_table=chunk_page_table,
        hist_len=hist_len, partial=not final, prefill_token_count=chunk,
        draft_lens=draft_lens, spec_S=S, draft_time_s=draft_s,
        chunk_device_row=R_pad,
        **sched._sampling_arrays(seqs, R_pad + 1, rows=rows))
