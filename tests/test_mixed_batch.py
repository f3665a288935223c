"""Mixed prefill/decode batching (stall-free TTFT scheduler).

The bar for the mixed path is the same as chunked prefill's: IDENTICAL
output to the legacy prefill-else-decode policy (greedy, and seeded
sampled — per-request seeds derive from (seed, position) so they reproduce
across engines), with decode never stalled behind a prefill window. Plus
the policy/layout contracts: decode rows claim the token budget first, the
unified ragged layout addresses both halves correctly, and the legacy
invariants (mid-chunk sequence only at waiting[0]; preemption never admits
waiting work) survive the mixing path.
"""

import numpy as np
import pytest

from kubernetes_gpu_cluster_tpu.config import (CacheConfig, EngineConfig,
                                               SchedulerConfig,
                                               get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
from kubernetes_gpu_cluster_tpu.engine.mixed_batch import (build_mixed_batch,
                                                           mixed_row_bucket,
                                                           plan_chunk_tokens)
from kubernetes_gpu_cluster_tpu.engine.scheduler import Scheduler
from kubernetes_gpu_cluster_tpu.engine.sequence import (Sequence,
                                                        SequenceStatus)


def _cfg(mixed=True, num_pages=65, page_size=4, max_num_seqs=4,
         max_prefill_tokens=16, budget=None, decode_window=2,
         decode_buckets=(1, 2, 4)):
    return EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=page_size, num_pages=num_pages),
        scheduler=SchedulerConfig(
            max_num_seqs=max_num_seqs, max_prefill_tokens=max_prefill_tokens,
            decode_buckets=decode_buckets, prefill_buckets=(16, 32, 64),
            decode_window=decode_window, mixed_batch_enabled=mixed,
            decode_priority_token_budget=budget))


def _seq(rid, n_prompt, max_tokens=64):
    return Sequence(rid, list(range(1, n_prompt + 1)),
                    SamplingParams(max_tokens=max_tokens))


class TestPolicy:
    def test_decode_rows_claim_budget_first(self):
        # budget 16, 3 decode rows -> at most 13 chunk tokens
        assert plan_chunk_tokens(100, 3, None, 16) == 13
        assert plan_chunk_tokens(5, 3, None, 16) == 5      # remaining caps
        assert plan_chunk_tokens(100, 16, None, 16) == 0   # no room left
        # explicit mixed budget larger than the prefill budget: the chunk is
        # still capped by max_prefill_tokens
        assert plan_chunk_tokens(100, 4, 64, 16) == 16
        # explicit smaller budget wins
        assert plan_chunk_tokens(100, 1, 8, 16) == 7

    @pytest.mark.parametrize("rows,chunk_bucket,seats,want", [
        (1, 2048, 64, 64),    # a long prompt's padding rows: 3 % of the step
        (33, 1024, 64, 64),
        (2, 512, 64, 32),
        (40, 512, 64, 64),    # more rows than the floor: their own bucket
        (2, 128, 64, 8),
        (9, 128, 64, 16),
        (2, 16, 64, 2),       # a sixteenth of the chunk under every row count
        # 16 seats: the floor stops at the seats' bucket, whatever the chunk
        *[(rows, chunk, 16, 16) for chunk in (2048, 1536, 1024, 512)
          for rows in (1, 15, 16)],
        (1, 128, 16, 8),      # under the seats' bucket the rule is the old one
        (15, 128, 16, 16),
        (16, 128, 16, 16),
        (17, 2048, 16, 32),   # every seat decoding beside a chunk not the last
        # 24 seats sit in the bucket of 32
        (1, 2048, 24, 32),
        (24, 1536, 24, 32),
        (2, 512, 24, 32),
        (2, 128, 24, 8),
        # more seats than the ladder's top: the top, as before
        (1, 2048, 128, 64),
        (33, 1024, 128, 64),
        (2, 512, 128, 32),
        (9, 128, 128, 16),
    ])
    def test_row_bucket_floor_is_a_sixteenth_of_the_chunk(self, rows,
                                                          chunk_bucket, seats,
                                                          want):
        """... and never over the bucket of the server's seats: a step is
        not built for rows that no seat can fill."""
        sc = SchedulerConfig(max_num_seqs=seats)
        assert sc.decode_buckets == (1, 2, 4, 8, 16, 32, 64)
        assert mixed_row_bucket(rows, chunk_bucket, sc) == want

    @pytest.mark.parametrize("seats,top,grid,want", [
        (64, 64, None, 64), (16, 64, None, 16), (24, 64, None, 32),
        (1, 64, None, 1), (128, 64, None, 64), (65, 64, None, 64),
        (4, 4, (1, 2, 4), 4), (3, 4, (1, 2, 4), 4), (6, 4, (1, 2, 4), 4),
    ])
    def test_seat_bucket_is_the_seats_rung_of_the_ladder(self, seats, top,
                                                         grid, want):
        kw = {} if grid is None else {"decode_buckets": grid}
        sc = SchedulerConfig(max_num_seqs=seats, **kw)
        assert sc.decode_buckets[-1] == top
        assert sc.seat_bucket == want

    @pytest.mark.parametrize("seats,programs", [
        (64, {(128, 8), (128, 16), (128, 32), (128, 64),
              (256, 16), (256, 32), (256, 64), (512, 32), (512, 64),
              (1024, 64), (1536, 64), (2048, 64)}),
        (16, {(128, 8), (128, 16), (256, 16), (512, 16),
              (1024, 16), (1536, 16), (2048, 16)}),
    ])
    def test_row_bucket_floor_bounds_the_program_family(self, seats,
                                                        programs):
        """(chunk bucket x row bucket) on the default grid, the rung at 1536
        tokens among the chunk's: 12 step programs at 64 seats where every
        row bucket beside every chunk bucket made 42; at 16 seats seven,
        none of them for more rows than the seats' bucket."""
        sc = SchedulerConfig(max_num_seqs=seats)
        assert len(sc.mixed_chunk_buckets) * len(sc.decode_buckets) == 42
        met = {(t, mixed_row_bucket(r, t, sc))
               for t in sc.mixed_chunk_buckets
               for r in range(1, seats + 1)}
        assert met == programs
        assert (1536, sc.seat_bucket) in met
        assert all(16 * rows >= min(t, 16 * sc.seat_bucket)
                   and rows <= sc.seat_bucket for t, rows in met)

    def test_mixed_only_when_decode_and_prefill_coexist(self):
        sched = Scheduler(_cfg(), 65)
        sched.add(_seq("a", 8))
        assert sched.schedule().kind == "prefill"   # nothing running yet
        sched.waiting.append(_seq("b", 40))
        # nothing appended to "a" yet — it still decodes from its prompt
        batch = sched.schedule()
        assert batch.kind == "mixed"

    def test_disabled_keeps_legacy_kinds(self):
        sched = Scheduler(_cfg(mixed=False), 65)
        sched.add(_seq("a", 8))
        assert sched.schedule().kind == "prefill"
        sched.add(_seq("b", 40))
        kinds = {sched.schedule().kind for _ in range(6)}
        assert "mixed" not in kinds

    def test_burst_of_packable_prompts_keeps_legacy_packed_prefill(self):
        """Two+ whole fresh prompts that fit one legacy prefill batch must
        NOT be serialized through head-only mixed steps — one packed step
        admits them all (burst stability); mixing engages once the queue is
        down to a single prompt."""
        sched = Scheduler(_cfg(max_num_seqs=8), 65)
        a = _seq("a", 8)
        sched.add(a)
        assert sched.schedule().kind == "prefill"
        a.append_token(9)
        sched.add(_seq("p1", 6))
        sched.add(_seq("p2", 6))
        sched.add(_seq("p3", 6))
        batch = sched.schedule()
        assert batch.kind == "prefill"         # packed, not mixed
        # budget 16 fits two 6-token prompts per packed step
        assert {s.request_id for s in batch.seqs} == {"p1", "p2"}
        # one fresh prompt left waiting -> stall-free mixing engages
        assert sched.schedule().kind == "mixed"

    def test_chunk_streaming_head_mixes_even_under_burst(self):
        """An oversized head streams through mixed chunks regardless of
        queue depth — long prompts are where prefill stalls hurt most."""
        sched = Scheduler(_cfg(), 65)
        a = _seq("a", 8)
        sched.add(a)
        sched.schedule()
        a.append_token(9)
        sched.add(_seq("long", 40))            # > 16-token budget: chunks
        sched.add(_seq("p1", 6))
        sched.add(_seq("p2", 6))
        assert sched.schedule().kind == "mixed"

    def test_full_occupancy_partial_chunk_stays_in_bucket_grid(self):
        """With every max_num_seqs seat running, D+1 sampled rows would
        escape the decode-bucket grid (next_power_of_2 fallback = an
        unwarmed compile shape mid-serving). Mixing must bow out — even for
        a PARTIAL chunk, which needs no seat — and leave the step to the
        legacy policy."""
        sched = Scheduler(_cfg(max_num_seqs=4), 65)   # buckets (1,2,4)
        seqs = [_seq(f"r{i}", 4) for i in range(4)]
        for s in seqs:
            sched.add(s)
        assert sched.schedule().kind == "prefill"
        for s in seqs:
            s.append_token(9)
        sched.add(_seq("long", 40))                   # chunkable head
        batch = sched.schedule()
        assert batch.kind != "mixed"

    def test_partial_chunk_beside_full_seats_gives_up_its_row(self):
        """Fewer seats than the ladder's top, every one decoding, a long
        prompt waiting: its chunks that are not the last ride mixed steps in
        the SEATS' bucket, without a sampled row of their own (what they
        would sample is discarded anyway; with it the step would be the one
        program past the seats' bucket, which no warm-up meets). The last
        chunk needs a seat and waits for one."""
        sched = Scheduler(_cfg(max_num_seqs=4, decode_buckets=(1, 2, 4, 8)),
                          65)
        assert sched.config.scheduler.seat_bucket == 4
        seqs = [_seq(f"r{i}", 4) for i in range(4)]
        for s in seqs:
            sched.add(s)
        assert sched.schedule().kind == "prefill"
        long = _seq("long", 30)
        sched.add(long)
        for done in (12, 24):                  # budget 16 - 4 decode rows
            for s in seqs:
                s.append_token(9)
            batch = sched.schedule()
            assert batch.kind == "mixed" and batch.partial
            assert batch.seqs == seqs + [long] and long.num_prefilled == done
            assert batch.tokens.shape == (16 + 4,)
            np.testing.assert_array_equal(batch.logits_indices,
                                          16 + np.arange(4))
            assert len(batch.temperature) == len(batch.context_lens) == 4
            assert [seq for _, seq in batch.device_seq_rows()] == seqs
        for s in seqs:
            s.append_token(9)
        assert sched.schedule().kind == "decode"    # no seat for the head
        sched.finish(seqs[0], "stop")
        batch = sched.schedule()                    # 3 rows + the last chunk
        assert batch.kind == "mixed" and not batch.partial
        assert batch.tokens.shape == (16 + 4,)
        assert batch.logits_indices[3] == 30 - 24 - 1

    def test_budget_full_of_decodes_falls_back_to_pure_decode(self):
        cfg = _cfg(budget=1)   # 1 decode row already exhausts the budget
        sched = Scheduler(cfg, 65)
        sched.add(_seq("a", 8))
        sched.schedule()
        sched.add(_seq("b", 12))
        batch = sched.schedule()
        # mixing had no room for a chunk; the head won a pure prefill batch
        # (legacy policy) rather than being starved forever
        assert batch.kind == "prefill"


class TestConfigValidation:
    def test_engine_rejects_unusable_mixed_budget(self):
        """A decode-priority budget that can never fit a decode row plus a
        chunk token must fail loudly at engine init, not leave mixing
        silently inert (kgct_mixed_step_ratio reading 0 forever)."""
        with pytest.raises(ValueError, match="decode_priority_token_budget"):
            LLMEngine(_cfg(budget=1))


class TestLayout:
    def _mixed_state(self):
        sched = Scheduler(_cfg(), 65)
        a = _seq("a", 8)
        sched.add(a)
        assert sched.schedule().kind == "prefill"
        a.append_token(9)                      # one decode output committed
        long = _seq("long", 40)
        sched.add(long)
        return sched, a, long

    def test_unified_ragged_layout(self):
        sched, a, long = self._mixed_state()
        batch = sched.schedule()
        assert batch.kind == "mixed"
        assert batch.seqs == [a, long]         # decode rows, then the chunk
        # budget 16 - 1 decode row = 15 chunk tokens
        assert batch.prefill_token_count == 15
        assert batch.partial and batch.hist_len == 0
        assert long.num_prefilled == 15
        Tp = 16                                # _bucket(15, prefill_buckets)
        assert batch.tokens.shape == (Tp + 2,)  # R_pad = _bucket(2, decode)
        np.testing.assert_array_equal(batch.tokens[:15],
                                      long.prompt_token_ids[:15])
        np.testing.assert_array_equal(batch.seg_ids[:15], 0)
        assert batch.seg_ids[15] == -1 and set(batch.seg_ids[Tp:]) == {-1}
        np.testing.assert_array_equal(batch.positions[:15], np.arange(15))
        # decode row: a's last output token at position num_tokens-1
        assert batch.tokens[Tp] == 9
        assert batch.positions[Tp] == a.num_tokens - 1
        assert batch.context_lens[0] == a.num_tokens
        np.testing.assert_array_equal(batch.page_tables[0, :len(a.pages)],
                                      a.pages)
        np.testing.assert_array_equal(
            batch.chunk_page_table[0, :len(long.pages)], long.pages)
        # sampled rows: decode row first, the chunk's last token second
        np.testing.assert_array_equal(batch.logits_indices, [Tp, 14])
        # KV write slots: chunk tokens into long's pages, decode row into a's
        ps = sched.page_size
        pos = a.num_tokens - 1
        assert batch.slot_mapping[Tp] == (a.pages[pos // ps] * ps + pos % ps)
        np.testing.assert_array_equal(
            batch.slot_mapping[:15],
            [long.pages[p // ps] * ps + p % ps for p in range(15)])

    def test_chunk_streams_to_final_and_joins_running(self):
        sched, a, long = self._mixed_state()
        hist = []
        while long.status != SequenceStatus.RUNNING:
            batch = sched.schedule()
            assert batch.kind == "mixed"
            hist.append((batch.hist_len, long.num_prefilled, batch.partial))
        # 40 tokens at 15/step: [0:15) [15:30) [30:40) — final joins running
        assert hist == [(0, 15, True), (15, 30, True), (30, 40, False)]
        assert long in sched.running and long not in sched.waiting
        assert sched.schedule().kind == "decode"   # queue drained


class TestInvariants:
    def test_preempt_victim_slots_behind_mid_chunk_head(self):
        """The legacy invariant — a mid-chunk sequence (holding pages) is
        only ever at waiting[0] — must survive preemption triggered from
        the MIXED path's decode page growth: the victim slots in BEHIND the
        mid-chunk head, never displacing it."""
        cfg = _cfg(num_pages=13, page_size=4, max_num_seqs=4,
                   max_prefill_tokens=16)      # 12 usable pages
        sched = Scheduler(cfg, 13)
        a, b = _seq("a", 8), _seq("b", 8)      # 2 pages each
        sched.add(a)
        sched.add(b)
        assert sched.schedule().kind == "prefill"
        a.append_token(9)
        b.append_token(9)
        long = _seq("long", 40)                # will chunk across many steps
        sched.add(long)
        batch = sched.schedule()               # mixed: chunk takes pages
        assert batch.kind == "mixed" and batch.partial
        assert sched.waiting[0] is long and long.num_prefilled > 0
        assert long.pages                      # mid-chunk head holding pages
        # Exhaust the pool so the next decode growth must preempt: grow a/b
        # to their page boundaries and drain free pages.
        free = sched.allocator.num_free
        if free:
            hold = sched.allocator.allocate(free)
        for s in (a, b):
            while s.num_tokens % 4 != 0:       # fill the current page
                s.append_token(7)
            s.append_token(7)                  # first token of a NEW page
        batch = sched.schedule()
        # b (youngest running) was preempted; the mid-chunk head kept
        # waiting[0] and the victim slotted in at waiting[1].
        assert sched.num_preemptions >= 1
        assert sched.waiting[0] is long
        assert sched.waiting[1] is b
        assert b.status == SequenceStatus.PREEMPTED and not b.pages

    def test_abort_mid_chunk_head_releases_pages(self):
        """Aborting the mid-chunk head (pages held, prompt incomplete)
        under the mixed path frees its pages and unblocks the queue."""
        eng = LLMEngine(_cfg())
        eng.add_request("a", list(range(1, 9)),
                        SamplingParams(max_tokens=8, temperature=0.0))
        eng.step()                             # prefill a
        free0 = eng.scheduler.allocator.num_free
        eng.add_request("long", list(range(1, 61)),
                        SamplingParams(max_tokens=8, temperature=0.0))
        eng.step()                             # mixed: chunk holds pages
        head = eng.scheduler.waiting[0]
        assert head.request_id == "long" and head.num_prefilled > 0
        free_mid = eng.scheduler.allocator.num_free
        held = len(head.pages)
        assert held > 0 and free_mid < free0
        assert eng.abort_request("long")
        assert all(s.request_id != "long" for s in eng.scheduler.waiting)
        # The next mixed step, with the next chunk, is already queued on
        # the device and writes the chunk's pages: exactly those come back
        # once it has been fetched (the survivor's legitimate decode page
        # growth stays).
        grown = len(eng.scheduler.running[0].pages)
        eng.step()
        assert not head.pages
        grown = len(eng.scheduler.running[0].pages) - grown
        assert eng.scheduler.allocator.num_free == free_mid + held - grown
        # engine still serves the survivor to completion
        while eng.has_unfinished_requests():
            outs = eng.step()
        assert not eng.scheduler.has_work()

    def test_mixed_never_preempts_to_admit_prefill(self):
        """Chunk page allocation must never evict running decodes: with no
        free pages for the chunk, mixing bows out and decode proceeds."""
        cfg = _cfg(num_pages=5, page_size=4, max_num_seqs=4)  # 4 usable
        sched = Scheduler(cfg, 5)
        a = _seq("a", 7, max_tokens=1)         # 2 pages (8 slots)
        b = _seq("b", 7, max_tokens=1)         # 2 pages -> pool full
        sched.add(a)
        sched.add(b)
        assert sched.schedule().kind == "prefill"
        a.append_token(9)                      # slot 7: no page growth needed
        b.append_token(9)
        sched.add(_seq("waiting", 8))
        batch = sched.schedule()
        assert batch.kind == "decode"          # no pages for a chunk
        assert sched.num_preemptions == 0
        assert len(batch.seqs) == 2


class TestEngineParity:
    @staticmethod
    def _workload(eng, tag, temperature=0.0, seed=None):
        rng = np.random.default_rng(0)
        prompts = {"a": rng.integers(1, 500, 20).tolist(),
                   "long": rng.integers(1, 500, 70).tolist(),
                   "b": rng.integers(1, 500, 12).tolist()}
        params = SamplingParams(max_tokens=8, temperature=temperature,
                                top_k=40 if temperature else 0, seed=seed)
        outs, kinds = {}, []
        on_step = eng.obs.on_step
        eng.obs.on_step = lambda rec: (kinds.append(rec["kind"]),
                                       on_step(rec))[1]
        eng.add_request(f"{tag}-a", prompts["a"], params)
        for _ in range(2):                      # a prefills, starts decoding
            for o in eng.step():
                if o.finished:
                    outs[o.request_id] = o.output_token_ids
        eng.add_request(f"{tag}-long", prompts["long"], params)
        eng.add_request(f"{tag}-b", prompts["b"], params)
        while eng.has_unfinished_requests():
            for o in eng.step():
                if o.finished:
                    outs[o.request_id] = o.output_token_ids
        eng.obs.on_step = on_step
        return {k.split("-", 1)[1]: v for k, v in outs.items()}, kinds

    def test_outputs_identical_to_legacy(self):
        """Greedy AND seeded-sampled outputs must be byte-identical to the
        legacy policy (per-request seeds derive from (seed, position), so
        they reproduce across engines). One engine pair serves both
        workloads — mid-decode arrivals exercise the mixed path, whose
        steps the legacy engine must never take."""
        legacy = LLMEngine(_cfg(mixed=False, max_prefill_tokens=32))
        mixed = LLMEngine(_cfg(mixed=True, max_prefill_tokens=32))
        ref, kinds_off = self._workload(legacy, "g")
        got, kinds_on = self._workload(mixed, "g")
        assert "mixed" in kinds_on and "mixed" not in kinds_off
        assert got == ref
        # the long prompt streamed through mixed steps instead of stalling
        # the running decodes behind pure prefill windows
        assert mixed.obs.mixed_prefill_tokens > 0
        assert mixed.obs.mixed_decode_tokens > 0
        # seeded sampled workload on the same engines
        ref, _ = self._workload(legacy, "s", temperature=1.0, seed=7)
        got, kinds = self._workload(mixed, "s", temperature=1.0, seed=7)
        assert "mixed" in kinds
        assert got == ref
        # observability rode along: ratio gauge, token counters, and
        # per-step trace events with the prefill/decode split
        ratio = mixed.obs.mixed_step_ratio()
        assert ratio is not None and 0.0 < ratio < 1.0
        assert mixed.obs.step_kind_counts["mixed"] >= 1
        assert legacy.obs.mixed_step_ratio() == 0.0
        text = "\n".join(mixed.obs.render_prometheus())
        assert "kgct_mixed_step_ratio" in text
        assert "kgct_mixed_prefill_tokens_total" in text
        assert "kgct_mixed_decode_tokens_total" in text
        mixed_events = [e for e in mixed.obs.tracer.events()
                        if e.kind == "mixed"]
        assert mixed_events
        assert all(e.args["prefill_tokens"] > 0
                   and e.args["decode_tokens"] > 0 for e in mixed_events)


    def test_full_seats_on_a_lower_rung_serve_as_the_legacy_policy(self):
        """Two seats under a ladder up to 4, both decoding when a prompt of
        several chunks arrives: its partial chunks ride mixed steps of TWO
        rows (no row of their own), and every request, a penalised and a
        biased one among them, gets what the legacy policy gives it."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 500, n).tolist() for n in (9, 11, 70, 8)]
        params = [
            SamplingParams(max_tokens=24, temperature=0.0,
                           presence_penalty=0.5),
            SamplingParams(max_tokens=24, temperature=1.0, top_k=40, seed=5,
                           logit_bias={7: 2.0}),
            SamplingParams(max_tokens=6, temperature=0.0, logprobs=1),
            SamplingParams(max_tokens=6, temperature=0.0)]

        def serve(mixed):
            eng = LLMEngine(_cfg(mixed=mixed, max_num_seqs=2,
                                 max_prefill_tokens=32))
            assert eng.config.scheduler.seat_bucket == 2
            widths, schedule = [], eng.scheduler.schedule

            def spy(*a, **kw):
                batch = schedule(*a, **kw)
                if batch is not None and batch.kind == "mixed":
                    widths.append((len(batch.context_lens), batch.partial,
                                   len(batch.seqs)))
                return batch
            eng.scheduler.schedule = spy
            outs = {}
            for i in (0, 1):
                eng.add_request(f"r{i}", prompts[i], params[i])
            for _ in range(2):
                eng.step()
            for i in (2, 3):
                eng.add_request(f"r{i}", prompts[i], params[i])
            while eng.has_unfinished_requests():
                for o in eng.step():
                    if o.finished:
                        outs[o.request_id] = (o.output_token_ids,
                                              o.output_logprobs)
            return outs, widths

        ref, none = serve(False)
        got, widths = serve(True)
        assert not none
        assert (2, True, 3) in widths          # two rows and a rowless chunk
        assert all(rows == 2 for rows, _, _ in widths)
        assert {k: v[0] for k, v in got.items()} == {
            k: v[0] for k, v in ref.items()}
        np.testing.assert_allclose(got["r2"][1], ref["r2"][1], atol=2e-5)


class TestObservability:
    def test_fresh_engine_ratio_is_none_and_renders_clean(self):
        from kubernetes_gpu_cluster_tpu.observability import Observability
        obs = Observability()
        assert obs.mixed_step_ratio() is None
        text = "\n".join(obs.render_prometheus())
        assert "nan" not in text.lower()
        # gauge absent (None renders nothing); counters present at 0
        assert "kgct_mixed_step_ratio " not in text
        assert "kgct_mixed_prefill_tokens_total 0" in text
