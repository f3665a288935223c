"""A block model's step programs and what the host makes of them.

Generation by diffusion over blocks (``ModelConfig.block_length`` B > 1,
sdar_moe): a running sequence is its committed prefix in the pages, at most
one PENDING block behind it (whole and final, its K/V in no page yet) and
an OPEN BLOCK of B positions the host holds (``Sequence.block_ids`` /
``block_masked`` / ``block_pending``). A PASS runs 2 B positions a row over
the row's pages (``Kernels.block_attention``), [the pending block | the open
block] from ``start - B``, or [the open block | padding] from ``start``
where nothing is pending, masked positions carrying ``mask_token_id``;
block-causal attention inside the row (the first block never sees the
second) makes both the published passes at once:

- the open block DENOISES: logits AT each of its positions (the head and
  the sampler see the open block's B positions a row, never the 2 B), per
  masked position the sampled id and its confidence (the probability the
  sampler reports for it), then the transfer rule
  (``ops.sampling.block_transfer``); its K/V go to the scrap page (they are
  of masked inputs);
- the pending block COMMITS beside it: the pass ran its final ids over the
  same prefix a commit pass of its own would have, its K/V go to the row's
  pages, and the open block behind it saw them in the pass itself.

A block whose last masked position a pass transfers is whole: it becomes
the pending block of the pass's new state, ``start`` advances by B and the
next block opens all masked. So no pass exists only to write K/V (as
published the commit of block n is a pass of its own before the first
denoising pass of block n+1: the same mathematics, a fifth more passes at
the sampler's floor), and a request that ends with a block pending never
writes it.

``block_window``: W passes in one device program, a ``lax.scan`` over the
rows' state: one upload, one download. ``block_mixed``: a budgeted chunk of
the queue head's prompt and ONE pass of the rows in one program, as two
forwards: the chunk's (its K/V are all it is for: a block model's prefill
samples nothing), then the rows' pass exactly as a window runs it. (Until
PR 53 the two shared a forward, the chunk's tokens beside the rows'; at 2 B
positions a row that program rounded a row's positions otherwise than a
window's pass does, 0.08 nat at one position a pass, which at random
weights is enough to cross a near tie in the order of a block's transfers:
PERF.md section 6, PR 53. As two forwards the rows read what a window
reads, digit for digit. A whole window with the chunk beside its first
pass was built and measured, PERF.md section 6, PR 50: 4 % off ``tpot`` and
a 3 s capture that may hold no pure window for ``decode_step_ms`` to read.)
Both are named so that a profile's ``decode_window`` / ``mixed_step``
readers find them: a pass over all rows is this model's decode step.

A block program is dispatched BEHIND the one in flight, as every other
step program (``LLMEngine._step``): each hands on its rows' final state
(``state_width`` columns a row: ``hand_on``, one shape whatever the row
bucket), and a row of the next names the row of it that holds its blocks
(``Scheduler.fill_block_rows``'s source column), as ``_chained_tokens``
carries one token a row: a block that turns pending in a program's last
pass is written by the first pass of the next. The host replays a program
one program late (``replay``): a row it finds finished rides the program
already dispatched as a zombie, its pages held until that one is fetched;
pages are grown for every block the passes in flight may make whole
(``Sequence.window_last_pos``). The chain breaks for what breaks it for
every model: an import since the last schedule, and a batch that needs a
preemption (the victim's blocks are on the chip: fetch first; its pending
block's ids are tokens that have left, re-prefilled like any other).
"""

from __future__ import annotations

import time
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from ..models import llama as model_lib
from ..models.llama import StepMeta
from ..ops.sampling import (TOP_LOGPROBS, block_transfer, row_sample_keys,
                            sample_and_logprobs)
from .kv_cache import KVCache

if TYPE_CHECKING:  # pragma: no cover
    from .engine import LLMEngine


def state_width(block_length: int) -> int:
    """Columns of a row's state on the device: the ids of the block
    awaiting its commit, the open block's ids and masked flags, its start,
    the passes it has taken, whether a block awaits its commit."""
    return 3 * block_length + 3


def build_block_fns(engine: "LLMEngine"):
    """(block_window, block_mixed): the two jitted programs of a block
    model, one a decode row bucket and one a (chunk bucket, row bucket,
    history width)."""
    cfg = engine.model_config
    kernels = engine.kernels
    W = engine.config.scheduler.decode_window
    ps = engine.config.cache.page_size
    max_len = engine.config.effective_max_len
    B = cfg.block_length
    n_min = B // cfg.denoising_steps
    threshold = cfg.confidence_threshold
    reports_load = engine._reports_expert_load

    S = state_width(B)
    C = S + 2           # columns of a row's block in the packed buffer
    last_width = engine._last_width

    def unpack(int_b, prev):
        """int_b [R, 3B+5 | 3 | pages]: the row's block (its state as the
        host last fetched it, the positions its pages cover (0: a padding
        row), the row of ``prev`` that holds the state instead or -1),
        (top_k, seed, top_n), its page table: ONE upload, as the
        autoregressive programs pack theirs. ``prev``: the final state of
        the program dispatched before this one (``hand_on``), still on the
        device."""
        src = int_b[:, S + 1]
        st = jnp.where((src >= 0)[:, None],
                       jnp.take(prev, jnp.maximum(src, 0), axis=0),
                       int_b[:, :S])
        state = (st[:, :B], st[:, B:2 * B], st[:, 2 * B:3 * B] > 0,
                 st[:, 3 * B], int_b[:, S], st[:, 3 * B + 1],
                 st[:, 3 * B + 2] > 0)
        return state, int_b[:, C + 3:], int_b[:, C:C + 3]

    def hand_on(state):
        """A program's final state a row as ``[last_width, 3B+3]`` int32,
        whatever its row bucket: what ``unpack`` of the next program reads,
        one shape for every predecessor, so that a step kind stays one
        program (``_last_tokens``, for blocks)."""
        pend, ids, masked, start, _, passes, pending = state
        rows = jnp.concatenate(
            [pend, ids, masked.astype(jnp.int32), start[:, None],
             passes[:, None], pending[:, None].astype(jnp.int32)], axis=1)
        return jnp.zeros((last_width, S), jnp.int32).at[
            :rows.shape[0]].set(rows)

    def pack(out):
        """A pass's outputs a row as ONE int32 array [R, 2B+1] (transferred
        id or -1, the bits of its log-probability, whether the pass wrote
        a block to the row's pages): one download a program; the top
        alternatives stay behind unless a request asked."""
        toks, lps, tids, tlps, wrote = out
        return (jnp.concatenate(
            [toks, jax.lax.bitcast_convert_type(lps, jnp.int32),
             wrote[:, None].astype(jnp.int32)], axis=1), tids, tlps)

    def block_pass(params, kv, state, page_tables, int_b, float_b, key):
        """One pass over every row's two blocks. Returns the new cache, the
        new state and what the pass did: (transferred id or -1 [R, B], its
        log-probability, the top alternatives at its position, whether the
        row's pages were written [R])."""
        pend, ids, masked, start, limit, passes, pending = state
        R = ids.shape[0]
        live = limit > 0
        wide = pending & live
        # [the block awaiting its commit | the open block] from start - B,
        # or [the open block | padding] from start.
        base = jnp.where(pending, start - B, start)
        pos = base[:, None] + jnp.arange(2 * B, dtype=jnp.int32)[None, :]
        pos_c = jnp.minimum(pos, max_len - 1)
        opened = jnp.where(masked, jnp.int32(cfg.mask_token_id), ids)
        tokens = jnp.where(pending[:, None],
                           jnp.concatenate([pend, opened], axis=1),
                           jnp.concatenate([opened, jnp.zeros_like(ids)],
                                           axis=1))
        # Only the K/V of a block that awaited its commit reach the row's
        # pages, and only where its pages reach: everything else (an open
        # block's are of masked inputs) goes to the scrap page.
        page = jnp.take_along_axis(page_tables, pos_c // ps, axis=1)
        lands = wide[:, None] & (jnp.arange(2 * B) < B)[None, :] \
            & (pos < limit[:, None]) & (pos < max_len)
        slot = jnp.where(lands, page * ps + pos_c % ps, pos_c % ps)
        # Logits, the sampler and the transfer are the OPEN block's.
        at = (2 * B * jnp.arange(R, dtype=jnp.int32)
              + jnp.where(pending, B, 0))[:, None] \
            + jnp.arange(B, dtype=jnp.int32)[None, :]
        meta = StepMeta(positions=pos_c.reshape(-1),
                        slot_mapping=slot.reshape(-1),
                        page_tables=page_tables,
                        context_lens=jnp.where(live, base + 1, 0),
                        logits_indices=at.reshape(-1), row_wide=wide)
        hidden, kv, _ = model_lib.forward(params, cfg, tokens.reshape(-1),
                                          meta, kv, kernels,
                                          row_width=2 * B)
        logits = model_lib.compute_logits(params, cfg, hidden, kernels)
        with jax.named_scope("kgct.block.transfer"):
            rep = lambda a: jnp.repeat(a, B, axis=0)    # noqa: E731
            # A position is drawn afresh each pass of its block.
            open_pos = start[:, None] + jnp.arange(B, dtype=jnp.int32)[None]
            keys = row_sample_keys(
                key, rep(int_b[:, 1]),
                open_pos.reshape(-1) + max_len * rep(passes))
            cand, lps, tids, tlps = sample_and_logprobs(
                logits, keys, rep(float_b[:, 0]), rep(int_b[:, 0]),
                rep(float_b[:, 1]), row_keys=True,
                with_top=jnp.any(int_b[:, 2] > 0))
            cand, lps = cand.reshape(R, B), lps.reshape(R, B)
            xfer = block_transfer(jnp.exp(lps), masked, n_min, threshold) \
                & masked
        with jax.named_scope("kgct.block.commit"):
            # A block whose last masked position was transferred awaits
            # its commit from here on, and the next opens all masked.
            ids = jnp.where(xfer, cand, ids)
            masked = masked & ~xfer
            done = ~jnp.any(masked, axis=1)
            new_state = (jnp.where(done[:, None], ids, 0),
                         jnp.where(done[:, None], 0, ids),
                         done[:, None] | masked,
                         jnp.where(done, start + B, start), limit,
                         jnp.where(done, 0, passes + 1), done)
        out = (jnp.where(xfer, cand, -1), lps,
               tids.reshape(R, B, TOP_LOGPROBS),
               tlps.reshape(R, B, TOP_LOGPROBS), wide)
        return kv, new_state, out

    def decode_window_block(params, kv: KVCache, prev, int_b, float_b, key):
        state, page_tables, samp = unpack(int_b, prev)

        def one(carry, w):
            kv, state = carry
            kv, state, out = block_pass(
                params, kv, state, page_tables, samp, float_b,
                jax.random.fold_in(key, w))
            return (kv, state), pack(out)

        (kv, state), outs = jax.lax.scan(one, (kv, state), jnp.arange(W))
        return (*outs, hand_on(state), kv)          # outs: each [W, R, ...]

    def mixed_step_block(params, kv: KVCache, prev, int_t, chunk_page_table,
                         hist_len, int_b, float_b, key):
        # int_t: [4, Tp] the chunk alone (tokens, seg_ids, positions, slots)
        state, page_tables, samp = unpack(int_b, prev)
        load = [] if reports_load else None
        # The chunk first, a forward of its own (a block model's prefill
        # samples nothing: its K/V are what it is for), then the rows' pass
        # as a window runs it.
        _, kv, _ = model_lib.forward(
            params, cfg, int_t[0], StepMeta(
                seg_ids=int_t[1], positions=int_t[2], slot_mapping=int_t[3],
                logits_indices=jnp.zeros((1,), jnp.int32),
                chunk_page_table=chunk_page_table[0], hist_len=hist_len),
            kv, kernels, moe_load=load)
        kv, state, out = block_pass(
            params, kv, state, page_tables, samp, float_b, key)
        return (*(o[None] for o in pack(out)), hand_on(state), kv,
                *(load or ()))

    return (engine._maybe_jit(decode_window_block, donate_argnums=(1,)),
            engine._maybe_jit(mixed_step_block, donate_argnums=(1,)))


def refuse_request(params) -> None:
    """What a block model's sampler does not carry: refused by name."""
    if params.presence_penalty or params.frequency_penalty \
            or params.logit_bias:
        raise ValueError(
            "presence_penalty, frequency_penalty and logit_bias with a "
            "block model: a pass scores positions that later passes fill "
            "in any order; no histogram of 'tokens so far' exists for it")


def dispatch(engine: "LLMEngine", rec: dict, prev, float_b,
             step_key) -> None:
    """Dispatch a block window or a block mixed step, its chained rows'
    blocks read from ``prev`` (the final state of the program dispatched
    before it); what its fetch needs goes into ``rec``, and its own final
    state as ``last``."""
    ph = engine.obs.phases.phase
    batch = rec["batch"]
    mixed = batch.kind == "mixed"
    with ph("host_prep"):
        rows = len(batch.temperature)
        int_b = jnp.asarray(np.concatenate(
            [batch.block, np.stack([batch.top_k, batch.seed, batch.top_n],
                                   axis=1), batch.page_tables], axis=1))
        if mixed:
            Tp = len(batch.tokens) - rows * engine.model_config.row_width
            int_t = jnp.asarray(np.stack(
                [batch.tokens[:Tp], batch.seg_ids[:Tp], batch.positions[:Tp],
                 batch.slot_mapping[:Tp]]))
            chunk_pt = jnp.asarray(batch.chunk_page_table)
    with ph("device_dispatch", rec):
        if mixed:
            engine.stats.prefill_tokens += batch.prefill_token_count
            (toks, tids, tlps, last, engine.kv_cache,
             *load) = engine._block_mixed_fn(
                engine.params, engine.kv_cache, prev, int_t, chunk_pt,
                jnp.int32(batch.hist_len), int_b, float_b, step_key)
        else:
            (toks, tids, tlps, last,
             engine.kv_cache) = engine._block_window_fn(
                engine.params, engine.kv_cache, prev, int_b, float_b,
                step_key)
            load = ()
    rec.update(t_dispatched=time.monotonic(), toks=toks, tids=tids,
               tlps=tlps, load=load, counts=None, last=last, zombies=set())


def fetch(engine: "LLMEngine", step: dict) -> tuple:
    """Copy what a block program's replay needs off the device (inside
    ``LLMEngine._fetching``): every pass's transferred ids,
    log-probabilities and whether it wrote a block to the row's pages, and
    the top alternatives if a request asked."""
    batch = step["batch"]
    B = engine.model_config.block_length
    packed = np.asarray(step["toks"])        # [W, R, 2B+1]: ``pack``
    toks = packed[:, :, :B].tolist()
    lps = packed[:, :, B:2 * B].view(np.float32).tolist()
    commit = packed[:, :, 2 * B].tolist()
    top_i = top_l = None
    if any(s.params.top_logprobs for s in batch.seqs):
        top_i = np.asarray(step["tids"])
        top_l = np.asarray(step["tlps"])
    if batch.kind == "mixed":
        # (the load is the chunk's forward's: its tokens alone decide the
        # dispatch it ran)
        chunk = len(batch.tokens) \
            - len(batch.temperature) * engine.model_config.row_width
        engine.obs.on_expert_load(
            step["load"], model_lib.grouped_dispatch(
                chunk, engine.model_config, engine.kernels))
    return toks, lps, commit, top_i, top_l


def replay(engine: "LLMEngine", step: dict, fetched: tuple,
           carried: frozenset) -> tuple[list, dict]:
    """Replay a fetched block program's passes over the host's copy of its
    rows' blocks and hand on the tokens that became final in position
    order; returns the outputs and what the step's record adds. A block
    that a pass makes whole is pending from there on (``num_committed``
    advances, the next opens); the pass behind it writes it. The
    program's successor may be running already: a row that finishes here
    and has a row there (``carried``) keeps its pages until that one is
    fetched (``LLMEngine._finish_row``), and rides it as a zombie, whose
    passes and tokens are counted nowhere."""
    from .engine import RequestOutput

    toks, lps, commit, top_i, top_l = fetched
    batch = step["batch"]
    B = engine.model_config.block_length
    W = len(toks)
    max_len = engine.config.effective_max_len
    outputs, passes, commits, moved = [], 0, 0, 0
    for r, seq in batch.device_seq_rows():
        if seq.request_id in step["zombies"] or seq.is_finished:
            continue
        had_first = seq.first_token_time is not None
        want_lps = seq.params.logprobs
        want_top = seq.params.top_logprobs if top_i is not None else 0
        new_tokens, new_lps, new_tops = [], [], []
        for w in range(W):
            passes += 1
            if commit[w][r]:
                # The block that awaited its commit is in the pages now,
                # written beside this pass of the open block.
                commits += 1
                seq.block_pending = False
            seq.block_passes += 1
            for i, tok in enumerate(toks[w][r]):
                if tok < 0:
                    continue
                moved += 1
                seq.block_ids[i] = tok
                seq.block_masked[i] = False
                top = None
                if want_top:
                    top = [(int(t), float(v)) for t, v in
                           zip(top_i[w, r, i, :want_top],
                               top_l[w, r, i, :want_top])]
                    if tok not in (t for t, _ in top):
                        top.append((tok, lps[w][r][i]))
                seq.block_marks[i] = (lps[w][r][i], top)
            # What is final in position order leaves now.
            at = seq.num_tokens - seq.num_committed
            while at < B and not seq.block_masked[at] \
                    and not seq.is_finished:
                lp, top = seq.block_marks[at]
                seq.append_token(seq.block_ids[at],
                                 lp if want_lps else None, top)
                new_tokens.append(seq.block_ids[at])
                if want_lps:
                    new_lps.append(lp)
                if want_top:
                    new_tops.append(top)
                reason = seq.check_stop(max_len)
                if reason is not None:
                    engine._finish_row(seq, reason, carried)
                at += 1
            if seq.is_finished:
                break       # (a block it leaves awaiting is never written)
            if not any(seq.block_masked):
                # The block is whole: it awaits its commit, which rides
                # the next pass, and the next block opens all masked.
                engine.obs.block_passes_per_block.observe(seq.block_passes)
                seq.num_committed += B
                seq.block_pending = True
                seq.open_block()
        engine.stats.tokens_generated += len(new_tokens)
        if not had_first and seq.first_token_time is not None:
            engine.obs.on_first_token(seq, fetch_s=step["transfer_s"],
                                      step=step["step"])
        if seq.is_finished:
            engine.stats.requests_finished += 1
        outputs.append(RequestOutput(
            request_id=seq.request_id,
            prompt_token_ids=seq.prompt_token_ids,
            output_token_ids=list(seq.output_token_ids),
            finished=seq.is_finished,
            finish_reason=(seq.finish_reason.value
                           if seq.finish_reason else None),
            new_token_ids=new_tokens,
            new_logprobs=new_lps if want_lps else None,
            output_logprobs=(list(seq.output_logprobs)
                             if want_lps else None),
            new_top_logprobs=new_tops if want_top else None,
            output_top_logprobs=(list(seq.output_top_logprobs)
                                 if seq.params.top_logprobs else None),
            clock=step["clock"]))
    obs = engine.obs
    obs.block_passes += passes
    obs.block_commit_passes += commits
    obs.block_commits += commits
    obs.block_tokens_transferred += moved
    obs.block_positions_computed += passes * 2 * B
    # The record counts what the program did for what it is: tokens
    # TRANSFERRED, positions computed (two blocks a row-pass, the second
    # padding where nothing awaited its commit; padding rows' included in
    # ``padded_tokens``), and its passes. The experts saw the open blocks
    # and the blocks written.
    extra = dict(engine._routed((passes + commits) * B), passes=passes,
                 commit_passes=commits, positions=passes * 2 * B)
    step["tokens"] = moved
    if batch.kind == "mixed":
        extra.update(prefill_tokens=batch.prefill_token_count,
                     decode_tokens=moved)
        step["tokens"] += batch.prefill_token_count
    else:
        extra["mode"] = "block"
    return outputs, extra
