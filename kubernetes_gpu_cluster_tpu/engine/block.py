"""A block model's step programs and what the host makes of them.

Generation by diffusion over blocks (``ModelConfig.block_length`` B > 1,
sdar_moe): a running sequence is its committed prefix in the pages and an
OPEN BLOCK of B positions the host holds (``Sequence.block_ids`` /
``block_masked``). A PASS runs every row's open block, masked positions
carrying ``mask_token_id``, over the row's pages (``Kernels.block_attention``)
and gives logits AT each position. Rows of one program are in different
phases of their blocks:

- a row with a masked position DENOISES: per masked position the sampled id
  and its confidence (the probability the sampler reports for it), then the
  transfer rule (``ops.sampling.block_transfer``); nothing reaches the pages
  (the pass's K/V go to the scrap page: they are of masked inputs);
- a row with none COMMITS: the pass ran the block's final ids, its K/V go
  to the row's pages, and the next block opens all masked.

The commit of block n and the first denoising pass of block n+1 are two
passes here, as published; fusing them (2 B positions a row) is the same
mathematics and is not built (PERF.md section 7).

``block_window``: W passes in one device program, a ``lax.scan`` over the
rows' blocks (ids, masked flags, start, passes taken): one upload, one
download. ``block_mixed``: ONE pass beside a budgeted chunk of the queue
head's prompt. (A whole window with the chunk beside its first pass was
built and measured, PERF.md section 6, PR 50: 4 % off ``tpot`` and a
3 s capture that may hold no pure window for ``decode_step_ms`` to read.) Both are named so that a profile's ``decode_window`` /
``mixed_step`` readers find them: a pass over all rows is this model's
decode step.

A block program is dispatched BEHIND the one in flight, as every other
step program (``LLMEngine._step``): each hands on its rows' final state
(ids, masked flags, start, passes: ``hand_on``, one shape whatever the row
bucket), and a row of the next names the row of it that holds its open
block (``Scheduler.fill_block_rows``'s source column), as
``_chained_tokens`` carries one token a row. The host replays a program
one program late (``replay``): a row it finds finished rides the program
already dispatched as a zombie, its pages held until that one is fetched;
pages are grown for what the passes in flight may commit
(``Sequence.window_last_pos``). The chain breaks for what breaks it for
every model: an import since the last schedule, and a batch that needs a
preemption (the victim's open block is on the chip: fetch first).
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

    S = 2 * B + 2       # a row's state: ids, masked flags, start, passes
    C = S + 2           # columns of a row's block in the packed buffer
    last_width = engine._last_width

    def unpack(int_b, prev):
        """int_b [R, 2B+4 | 3 | pages]: the row's block (its state as the
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
        state = (st[:, :B], st[:, B:2 * B] > 0, st[:, 2 * B], int_b[:, S],
                 st[:, 2 * B + 1])
        return state, int_b[:, C + 3:], int_b[:, C:C + 3]

    def hand_on(state):
        """A program's final state a row as ``[last_width, 2B+2]`` int32,
        whatever its row bucket: what ``unpack`` of the next program reads,
        one shape for every predecessor, so that a step kind stays one
        program (``_last_tokens``, for blocks)."""
        ids, masked, start, _, passes = state
        rows = jnp.concatenate(
            [ids, masked.astype(jnp.int32), start[:, None],
             passes[:, None]], axis=1)
        return jnp.zeros((last_width, S), jnp.int32).at[
            :rows.shape[0]].set(rows)

    def pack(out):
        """A pass's outputs a row as ONE int32 array [R, 2B+1] (transferred
        id or -1, the bits of its log-probability, whether the row
        committed): one download a program; the top alternatives stay
        behind unless a request asked."""
        toks, lps, tids, tlps, full = out
        return (jnp.concatenate(
            [toks, jax.lax.bitcast_convert_type(lps, jnp.int32),
             full[:, None].astype(jnp.int32)], axis=1), tids, tlps)

    def block_pass(params, kv, state, page_tables, int_b, float_b, key,
                   chunk=None, load=None):
        """One pass over every row's open block (and ``chunk``: the queue
        head's prompt tokens beside them). Returns the new cache, the new
        state and what the pass did: (transferred id or -1 [R, B], its
        log-probability, the top alternatives at its position, whether the
        row committed [R])."""
        ids, masked, start, limit, passes = state
        R = ids.shape[0]
        live = limit > 0
        full = ~jnp.any(masked, axis=1)
        pos = start[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
        pos_c = jnp.minimum(pos, max_len - 1)
        tokens = jnp.where(masked, jnp.int32(cfg.mask_token_id), ids)
        # Only a committing row's K/V reach its pages, and only where its
        # pages reach: everything else goes to the scrap page.
        page = jnp.take_along_axis(page_tables, pos_c // ps, axis=1)
        lands = (full & live)[:, None] & (pos < limit[:, None]) \
            & (pos < max_len)
        slot = jnp.where(lands, page * ps + pos_c % ps, pos_c % ps)
        meta = dict(positions=pos_c.reshape(-1), slot_mapping=slot.reshape(-1))
        toks = tokens.reshape(-1)
        if chunk is not None:
            int_t, chunk_page_table, hist_len = chunk
            n_seg = int_t.shape[1]
            toks = jnp.concatenate([int_t[0], toks])
            meta = dict(
                seg_ids=jnp.concatenate(
                    [int_t[1], jnp.full((R * B,), -1, jnp.int32)]),
                positions=jnp.concatenate([int_t[2], meta["positions"]]),
                slot_mapping=jnp.concatenate(
                    [int_t[3], meta["slot_mapping"]]),
                logits_indices=n_seg + jnp.arange(R * B, dtype=jnp.int32),
                chunk_page_table=chunk_page_table[0], hist_len=hist_len)
        meta = StepMeta(page_tables=page_tables,
                        context_lens=jnp.where(live, start + 1, 0), **meta)
        hidden, kv, _ = model_lib.forward(params, cfg, toks, meta, kv,
                                          kernels, row_width=B,
                                          moe_load=load)
        logits = model_lib.compute_logits(params, cfg, hidden, kernels)
        with jax.named_scope("kgct.block.transfer"):
            rep = lambda a: jnp.repeat(a, B, axis=0)    # noqa: E731
            # A position is drawn afresh each pass of its block.
            keys = row_sample_keys(
                key, rep(int_b[:, 1]),
                pos.reshape(-1) + max_len * rep(passes))
            cand, lps, tids, tlps = sample_and_logprobs(
                logits, keys, rep(float_b[:, 0]), rep(int_b[:, 0]),
                rep(float_b[:, 1]), row_keys=True,
                with_top=jnp.any(int_b[:, 2] > 0))
            cand, lps = cand.reshape(R, B), lps.reshape(R, B)
            xfer = block_transfer(jnp.exp(lps), masked, n_min, threshold) \
                & masked
        with jax.named_scope("kgct.block.commit"):
            # A committed row's next block opens all masked.
            new_ids = jnp.where(full[:, None], 0, jnp.where(xfer, cand, ids))
            new_masked = full[:, None] | (masked & ~xfer)
            new_start = jnp.where(full, start + B, start)
            new_passes = jnp.where(full, 0, passes + 1)
        out = (jnp.where(xfer, cand, -1), lps,
               tids.reshape(R, B, TOP_LOGPROBS),
               tlps.reshape(R, B, TOP_LOGPROBS), full)
        return kv, (new_ids, new_masked, new_start, limit, new_passes), out

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
        kv, state, out = block_pass(
            params, kv, state, page_tables, samp, float_b, key,
            chunk=(int_t, chunk_page_table, hist_len), load=load)
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
            Tp = len(batch.tokens) - rows * engine.model_config.block_length
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
    log-probabilities and commit flags, and the top alternatives if a
    request asked."""
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
        engine.obs.on_expert_load(
            step["load"], model_lib.grouped_dispatch(
                len(batch.tokens), engine.model_config, engine.kernels))
    return toks, lps, commit, top_i, top_l


def replay(engine: "LLMEngine", step: dict, fetched: tuple,
           carried: frozenset) -> tuple[list, dict]:
    """Replay a fetched block program's passes over the host's copy of its
    rows' open blocks and hand on the tokens that became final in position
    order; returns the outputs and what the step's record adds. The
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
                commits += 1
                engine.obs.block_passes_per_block.observe(
                    seq.block_passes + 1)
                seq.num_committed += B
                seq.open_block()
                continue
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
                break
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
    obs.block_tokens_transferred += moved
    obs.block_positions_computed += passes * B
    # The record counts what the program did for what it is: tokens
    # TRANSFERRED, positions computed (padding rows' included in
    # ``padded_tokens``), and its passes.
    extra = dict(engine._routed(passes * B), passes=passes,
                 commit_passes=commits, positions=passes * B)
    step["tokens"] = moved
    if batch.kind == "mixed":
        extra.update(prefill_tokens=batch.prefill_token_count,
                     decode_tokens=moved)
        step["tokens"] += batch.prefill_token_count
    else:
        extra["mode"] = "block"
    return outputs, extra
