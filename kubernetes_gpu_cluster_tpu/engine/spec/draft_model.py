"""Draft-MODEL proposer: two-model speculative decoding (Leviathan et al.).

The n-gram prompt-lookup proposer (proposer.py) is free but only drafts
where the sequence's own history repeats. A small DRAFT MODEL (e.g.
tinyllama drafting for llama-3-8b — the engine already serves both) drafts
everywhere the two models agree, which for a well-matched pair is most
tokens, at a per-token cost of the small model's decode step.

:class:`DraftModelRunner` runs that second model inside the SAME engine
process, as a :class:`~.proposer.DraftProposer`:

- **Own paged KV pool.** The draft model keeps its own ``KVCache`` + page
  allocator (same page size as the target pool, pages sized for
  max_num_seqs full sequences). Nothing outside this module touches it —
  engine/scheduler code reaches draft state only through the proposer seam
  (``propose_batch`` / ``retain``), the KGCT017 draft-state-boundary lint
  rule polices the import graph, and the KGCT_SANITIZE shadow extends to
  the draft pool (:class:`_DraftShadow`).

- **k batched decode dispatches per spec round.** One greedy single-token
  decode program, bucketed over the target's decode-bucket grid, runs k
  times per round with every spec row riding the same dispatch; drafted
  tokens feed back host-side between dispatches. Greedy drafting keeps the
  proposal distribution q ONE-HOT, which is exactly the case the verifier's
  lossless accept/resample rule is written for — draft quality affects
  acceptance rate, never correctness.

- **Rollback-consistent draft KV.** The draft pool follows the same
  append-only contract as the target pool: per row we track ``valid`` (the
  leading positions whose KV matches the target's COMMITTED tokens) and
  ``tail`` (draft tokens fed past it). At the next round the tail is
  absorbed by prefix-matching it against what the verifier actually
  committed — accepted drafts' KV is thereby kept, and every
  rejected-draft slot sits at a position >= the next feed point, so it is
  overwritten before any dispatch can read it (reads are bounded by
  ``context_lens``). No draft KV is ever copied or rolled back.

- **Catch-up and reset.** Tokens committed by paths the draft never saw
  (prompt prefill, legacy decode windows, resampled/bonus tokens) leave a
  gap ``g = num_tokens - valid``. Small gaps (g <= k) are absorbed by the
  round's own dispatches — the first g feeds replay committed tokens
  (their outputs are discarded: the committed continuation is already
  known) and the remaining k-g+1 outputs are drafts. Larger gaps re-ingest
  the whole history through a chunked prefill-with-history program (one
  row per dispatch — resets are rare: first sight of a sequence, or
  recovery after speculation was off).

Mesh regimes: spec decode is single-mesh/GSPMD-tp only (the engine gates
pp/sp off); the draft model's programs carry no shard_map wrappers and run
replicated under a tp mesh — the draft is small by construction, so
replicating it costs far less than sharding machinery would save.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...analysis.sanitize import SanitizerError, sanitize_enabled
from ...config import CacheConfig, EngineConfig, ModelConfig, get_model_config
from ...models import llama as model_lib
from ...models.llama import StepMeta
from ...utils import cdiv, get_logger
from ...utils.math import next_power_of_2
from ..kv_cache import (PageAllocator, allocate_kv_cache,
                        kv_cache_bytes_per_page)
from .proposer import DraftProposer

logger = get_logger("spec.draft_model")


class _Row:
    """Per-request draft-pool state. ``owner`` guards request-id recycling
    (same discipline as the sanitizer's shadow): state must die with its
    Sequence object, not haunt a new request wearing the same id."""

    __slots__ = ("owner", "pages", "valid", "tail")

    def __init__(self, owner):
        self.owner = owner
        self.pages: list[int] = []
        self.valid = 0            # positions [0, valid) hold committed-matching KV
        self.tail: list[int] = []  # tokens fed at positions valid, valid+1, ...


def _common_prefix(a: list[int], b: list[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


class _DraftShadow:
    """KGCT_SANITIZE extension to the DRAFT pool: the PR-4 KV-slot shadow's
    invariants, restated for the draft side — (a) no feed rewrites a
    position below the round's validated history with a different token
    (accepted-draft KV must never be stomped), (b) every write slot is the
    one the row's own page table derives (a mis-aimed slot would corrupt
    another row's draft context), (c) committed-token replays carry the
    committed token."""

    def check_feed(self, seq, row: _Row, valid_start: int, pos: int,
                   tok: int, slot: int, page_size: int, max_len: int) -> None:
        if pos < valid_start:
            committed = seq.all_token_ids
            if pos >= len(committed) or committed[pos] != tok:
                raise SanitizerError(
                    f"draft KV shadow: feed of {seq.request_id} rewrites "
                    f"validated draft position {pos} (< {valid_start}) with "
                    f"token {tok} — accepted-draft KV stomped")
        if pos < max_len:
            want = row.pages[pos // page_size] * page_size + pos % page_size
        else:
            want = pos % page_size          # scrap-page routing
        if slot != want:
            raise SanitizerError(
                f"draft KV shadow: feed of {seq.request_id} at position "
                f"{pos} targets slot {slot}, page table derives {want}")


class DraftModelRunner(DraftProposer):
    """See module docstring. Construct via :func:`build_draft_runner`."""

    def __init__(self, config: EngineConfig, draft_config: ModelConfig,
                 params=None, seed: Optional[int] = None,
                 jit_enabled: bool = True):
        target = config.model
        if draft_config.vocab_size != target.vocab_size:
            raise ValueError(
                f"draft model {draft_config.name!r} vocab "
                f"{draft_config.vocab_size} != target {target.name!r} vocab "
                f"{target.vocab_size} — drafts are target token ids")
        sc = config.scheduler
        super().__init__(sc.effective_spec_k_max)
        self.config = config
        self.draft_config = draft_config
        self.page_size = config.cache.page_size
        # Positions past the draft's own context window would extrapolate
        # its RoPE table; clamp the draft horizon to the shorter of the two
        # (feeds beyond it route to the scrap page — lossless, the verify
        # step just sees low-quality drafts near the cap).
        self.max_len = min(config.effective_max_len,
                           draft_config.max_model_len)
        self.pages_bucket = cdiv(self.max_len, self.page_size)
        # Reset-prefill chunk ladder: the runner's OWN pow-2 buckets, NOT
        # the target scheduler's prefill grid — bench/serving grids can be
        # as coarse as (4096,), and padding a 60-token catch-up to 4096
        # forward tokens would make every reset cost two orders of
        # magnitude more than the history it ingests. Bounded family:
        # log2(512/16)+1 = 6 chunk widths.
        self.chunk_buckets = tuple(
            b for b in (16, 32, 64, 128, 256, 512)
            if b <= max(next_power_of_2(self.max_len), 16))
        if params is None:
            # Random init in the draft's own dtype — the bench/test path,
            # like the target engine. Real checkpoints arrive via
            # --spec-draft-weights (engine/weights.load_weights).
            init_seed = config.seed if seed is None else seed
            params = model_lib.init_params(draft_config,
                                           jax.random.key(init_seed))
        self.params = jax.block_until_ready(params)
        draft_cache = CacheConfig(page_size=self.page_size)
        # Draft pool sizing: full coverage (max_num_seqs full-horizon
        # sequences) CAPPED by what actually fits the device — the runner
        # is built AFTER the target pool claimed its hbm_utilization share
        # of free HBM and after the draft weights above are resident, so at
        # most half of what is STILL free goes to draft KV. On
        # a production pairing (tinyllama drafting for 8B at
        # max_num_seqs=128 x 8k context) full coverage would be tens of
        # GB; the cap keeps construction alive and rows the pool cannot
        # hold simply sit spec rounds out (propose [] — lossless).
        num_pages = sc.max_num_seqs * self.pages_bucket + 1
        # Lazy: engine/engine.py imports this module lazily at runtime;
        # a top-level import back into it would cycle during package init.
        from ..engine import _device_free_memory
        hbm_free = _device_free_memory()
        if hbm_free is not None:
            fit = (hbm_free // 2) // kv_cache_bytes_per_page(draft_config,
                                                             draft_cache)
            if fit < num_pages:
                logger.warning(
                    "draft KV pool capped by free HBM: %d pages (full "
                    "coverage wants %d); rows beyond the cap skip drafting",
                    fit, num_pages)
            num_pages = max(min(num_pages, fit), 2)
        self.kv_cache = allocate_kv_cache(draft_config, draft_cache,
                                          num_pages)
        self.allocator = PageAllocator(num_pages, self.page_size)
        self._jit = jit_enabled
        self._decode_fn = self._build_decode_fn()
        self._prefill_fn = self._build_prefill_fn()
        self._rows: dict[str, _Row] = {}
        self._shadow = _DraftShadow() if sanitize_enabled() else None
        # Observability (read through the proposer seam by the verifier):
        # cumulative draft-model dispatches and reset prefills.
        self.num_dispatches = 0
        self.num_reset_prefills = 0
        logger.info("draft model %s: %d pages x %d tokens (draft KV pool)",
                    draft_config.name, num_pages, self.page_size)

    # -- jitted draft programs ----------------------------------------------

    def _maybe_jit(self, fn, donate_argnums=()):
        if not self._jit:
            return fn
        return jax.jit(fn, donate_argnums=donate_argnums)

    def _build_decode_fn(self):
        """One greedy decode dispatch: every spec row's next draft token in
        a single program against the draft pool. Compiles per decode-bucket
        row count (the target's grid) — the per-k family the adaptive
        controller reuses is ``k`` CALLS of this one program, not k
        programs."""
        cfg = self.draft_config

        def draft_decode(params, kv, tokens, int_b, context_lens):
            # int_b: [B, 2 + pages_bucket] = (position, slot, page_table...)
            meta = StepMeta(positions=int_b[:, 0], slot_mapping=int_b[:, 1],
                            page_tables=int_b[:, 2:],
                            context_lens=context_lens)
            # The draft programs run the XLA references (NO_KERNELS, the
            # forward's default): no chip has run a draft model, so whether
            # they may take the target's kernels is open (ROADMAP D5g).
            hidden, kv, _ = model_lib.forward(params, cfg, tokens, meta, kv)
            logits = model_lib.compute_logits(params, cfg, hidden)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv

        return self._maybe_jit(draft_decode, donate_argnums=(1,))

    def _build_prefill_fn(self):
        """Reset/catch-up ingestion: one row's token chunk attending to its
        committed draft-pool history (the chunked-prefill shape). Logits
        are never computed — the round's decode dispatches produce the
        drafts — so XLA dead-code-eliminates the head matmul. Compiles per
        (chunk bucket, history-table width)."""
        cfg = self.draft_config

        def draft_prefill(params, kv, int_t, page_table, hist_len):
            meta = StepMeta(seg_ids=int_t[1], positions=int_t[2],
                            slot_mapping=int_t[3],
                            logits_indices=jnp.zeros((1,), jnp.int32),
                            chunk_page_table=page_table[0],
                            hist_len=hist_len)
            _, kv, _ = model_lib.forward(params, cfg, int_t[0], meta, kv)
            return kv

        return self._maybe_jit(draft_prefill, donate_argnums=(1,))

    def compiled_variants(self) -> int:
        """Draft-program jit-cache entries — folded into the engine's
        compiled_step_variants so the compile guard and the
        kgct_jit_compiles_total gauge cover the draft family too."""
        return sum(fn._cache_size() for fn in
                   (self._decode_fn, self._prefill_fn)
                   if hasattr(fn, "_cache_size"))

    # -- proposer seam -------------------------------------------------------

    def retain(self, live_request_ids) -> None:
        """Drop draft state (and free its pages) for requests no longer
        running. Preempted/swapped sequences are dropped too — they may be
        gone for many rounds, and holding max_num_seqs-scale page sets for
        absentees could starve the live rows; their return pays one reset
        prefill."""
        live = set(live_request_ids)
        for rid in [r for r in self._rows if r not in live]:
            row = self._rows.pop(rid)
            if row.pages:
                self.allocator.free(row.pages)

    def propose(self, token_ids: list[int]) -> list[int]:
        raise NotImplementedError(
            "DraftModelRunner drafts per batch (propose_batch) — per-row "
            "propose has no request identity to keep the draft pool in sync")

    def propose_batch(self, seqs, k: int) -> list[list[int]]:
        """Drafts for one spec round: sync each row's draft KV with the
        target's committed history, then run k batched greedy decode
        dispatches. See the module docstring for the catch-up/absorb
        bookkeeping; everything here is host numpy + the two jitted draft
        programs."""
        from ..scheduler import _bucket

        k = min(int(k), self.k)
        if k < 1 or not seqs:
            return [[] for _ in seqs]
        sc = self.config.scheduler
        ps = self.page_size
        max_len = self.max_len

        # -- absorb + plan ---------------------------------------------------
        rows: list[Optional[_Row]] = []
        queues: list[list[int]] = []
        valid_starts: list[int] = []
        for seq in seqs:
            row = self._rows.get(seq.request_id)
            if row is None or row.owner is not seq:
                if row is not None and row.pages:   # recycled request id
                    self.allocator.free(row.pages)
                row = _Row(seq)
                self._rows[seq.request_id] = row
            ids = seq.all_token_ids
            n = seq.num_tokens
            if row.tail:
                row.valid += _common_prefix(row.tail, ids[row.valid:])
                row.tail = []
            row.valid = min(row.valid, n - 1)
            inert = False
            if n - row.valid > k:
                # Gap too wide for the round's own dispatches to absorb:
                # re-ingest through the chunked draft prefill. Failure (draft
                # pool exhausted), or a sequence past the draft model's
                # context horizon, sits the round out — no drafts, the
                # verifier pads with lossless filler.
                inert = (not self._reset_row(seq, row)
                         or n - row.valid > k)
            if not inert:
                inert = not self._grow(row, min(row.valid + k, max_len))
            if inert:
                rows.append(None)
                queues.append([])
                valid_starts.append(row.valid)
                continue
            rows.append(row)
            queues.append(list(ids[row.valid:n]))
            valid_starts.append(row.valid)

        active = [i for i, r in enumerate(rows) if r is not None]
        if not active:
            return [[] for _ in seqs]

        # -- k batched decode dispatches ------------------------------------
        B = len(active)
        B_pad = _bucket(B, sc.decode_buckets)
        drafts: list[list[int]] = [[] for _ in seqs]
        fed_pos = {i: rows[i].valid for i in active}
        last_out: dict[int, int] = {}
        draft_flag: dict[int, bool] = {}
        tokens = np.zeros(B_pad, np.int32)
        int_b = np.zeros((B_pad, 2 + self.pages_bucket), np.int32)
        context_lens = np.zeros(B_pad, np.int32)
        # Page tables are fixed for the whole round (pages grew above):
        # fill the slab once — per-dispatch work below touches only the
        # token/position/slot columns, keeping the latency-critical draft
        # phase O(B) per dispatch instead of O(B * pages_bucket).
        for b, i in enumerate(active):
            pages = rows[i].pages
            int_b[b, 2:2 + len(pages)] = pages
        for _ in range(k):
            for b, i in enumerate(active):
                row, seq = rows[i], seqs[i]
                if queues[i]:
                    # Catch-up feed: a committed token the draft never
                    # consumed. Its output predicts a position whose token
                    # is already known — a draft only once the queue drains
                    # (i.e. the fed token was the LAST committed one).
                    tok = queues[i].pop(0)
                    draft_flag[i] = not queues[i]
                else:
                    tok = last_out[i]
                    draft_flag[i] = True
                pos = fed_pos[i]
                pos_c = min(pos, max_len - 1)
                slot = (row.pages[pos_c // ps] * ps + pos_c % ps
                        if pos < max_len else pos % ps)
                if self._shadow is not None:
                    self._shadow.check_feed(seq, row, valid_starts[i], pos,
                                            tok, slot, ps, max_len)
                tokens[b] = tok
                int_b[b, 0] = pos_c
                int_b[b, 1] = slot
                context_lens[b] = pos_c + 1
                fed_pos[i] = pos + 1
            out, self.kv_cache = self._decode_fn(
                self.params, self.kv_cache, jnp.asarray(tokens),
                jnp.asarray(int_b), jnp.asarray(context_lens))
            self.num_dispatches += 1
            out_np = np.asarray(out)
            for b, i in enumerate(active):
                last_out[i] = int(out_np[b])
                if draft_flag[i]:
                    drafts[i].append(int(out_np[b]))

        for i in active:
            row = rows[i]
            n = seqs[i].num_tokens
            # Feeds covered positions [old_valid, old_valid + k): the queue
            # part (g committed tokens, ending at position n-1) re-validated
            # its span; the k-g draft feeds past it form the new tail the
            # next round's absorb verifies against what actually committed.
            n_draft_feeds = fed_pos[i] - n
            row.tail = (drafts[i][:n_draft_feeds] if n_draft_feeds > 0
                        else [])
            row.valid = n
        return drafts

    # -- internals -----------------------------------------------------------

    def _grow(self, row: _Row, end_tokens: int) -> bool:
        """Pages covering positions [0, min(end_tokens, max_len))."""
        need = cdiv(min(end_tokens, self.max_len), self.page_size) \
            - len(row.pages)
        if need <= 0:
            return True
        if not self.allocator.can_allocate(need):
            return False
        row.pages.extend(self.allocator.allocate(need))
        return True

    def _reset_row(self, seq, row: _Row) -> bool:
        """Re-ingest tokens [0, num_tokens-1) through the chunked draft
        prefill (history attention against the row's own draft pages), in
        prefill-bucket-sized chunks. After this the row is one catch-up
        feed away from drafting. False when the pool cannot hold the
        history (caller marks the row inert this round)."""
        from ..scheduler import _bucket

        ids = seq.all_token_ids
        n_hist = min(seq.num_tokens - 1, self.max_len)
        if n_hist <= row.valid:
            return True
        if not self._grow(row, n_hist):
            return False
        ps = self.page_size
        chunk_budget = self.chunk_buckets[-1]
        start = row.valid
        while start < n_hist:
            end = min(start + chunk_budget, n_hist)
            chunk = end - start
            T = _bucket(chunk, self.chunk_buckets)
            int_t = np.zeros((4, T), np.int32)
            int_t[1] = -1
            int_t[0, :chunk] = ids[start:end]
            int_t[1, :chunk] = 0
            pos = np.arange(start, end)
            int_t[2, :chunk] = pos
            pages = np.asarray(row.pages, np.int64)
            int_t[3, :chunk] = pages[pos // ps] * ps + pos % ps
            width = min(next_power_of_2(max(len(row.pages), 1)),
                        self.pages_bucket)
            table = np.zeros((1, width), np.int32)
            table[0, :len(row.pages)] = row.pages
            self.kv_cache = self._prefill_fn(
                self.params, self.kv_cache, jnp.asarray(int_t),
                jnp.asarray(table), jnp.int32(start))
            self.num_reset_prefills += 1
            start = end
        row.valid = n_hist
        row.tail = []
        return True


def build_draft_runner(config: EngineConfig, draft_model: str,
                       params=None, seed: Optional[int] = None,
                       jit_enabled: bool = True) -> DraftModelRunner:
    """The engine's construction seam (mirrors ``build_proposer``):
    resolve the draft preset and build the runner. ``params`` injects
    pre-loaded draft weights (serving: --spec-draft-weights through the
    streamed loader; tests: shared module params)."""
    draft_cfg = get_model_config(draft_model)
    if draft_cfg.dtype != config.model.dtype:
        # Keep the draft in the target's serving dtype: its argmax is all
        # that escapes, and a mixed-dtype pool complicates nothing for
        # gain.
        draft_cfg = dataclasses.replace(draft_cfg, dtype=config.model.dtype)
    return DraftModelRunner(config, draft_cfg, params=params, seed=seed,
                            jit_enabled=jit_enabled)
