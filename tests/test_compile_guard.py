"""Compile-count guard: bucketed shapes keep the XLA jit cache bounded.

The engine's whole recompilation-storm defense is shape bucketing: every
step program compiles once per (kind, padded bucket) and is reused for the
serving lifetime. The mixed prefill/decode path adds a new shape family —
(prefill bucket, sampled-row bucket, history-table width) — so this guard
simulates a mixed load (staggered arrivals, varied prompt lengths, chunked
long prompts, mixing on) and asserts:

1. the total number of compiled step-program variants stays under a fixed
   bound derived from the bucket grid (a per-context-length or per-batch
   recompile would blow through it immediately), and
2. a second identical load wave compiles NOTHING new — steady state means
   zero compiles, which is the property sustained serving depends on.

Tier-1 (not slow): a shape-bucket regression must fail fast.
"""

import numpy as np

from kubernetes_gpu_cluster_tpu.config import (CacheConfig, EngineConfig,
                                               SchedulerConfig,
                                               get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams

PREFILL_BUCKETS = (16, 32)
DECODE_BUCKETS = (1, 2, 4)


def _engine():
    cfg = EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=8, num_pages=129),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32,
            decode_buckets=DECODE_BUCKETS, prefill_buckets=PREFILL_BUCKETS,
            decode_window=2, mixed_batch_enabled=True))
    return LLMEngine(cfg)


def _compiled_variants(eng) -> int:
    """Total jit-cache entries across every step program — the number of
    distinct XLA compilations the load has triggered. Includes the two-tier
    KV cache's swap gather/scatter programs when the host tier is on. The
    ONE definition lives on the engine (it also feeds the
    ``kgct_jit_compiles_total`` gauge), so the guard and the metric cannot
    drift — but the guard pins it is actually counting something by
    cross-checking one raw jit cache."""
    total = eng.compiled_step_variants()
    if hasattr(eng._prefill_fn, "_cache_size"):
        assert total >= eng._prefill_fn._cache_size()
    return total


def _run_wave(eng, tag: str) -> None:
    """Staggered mixed load: varied prompt lengths (sub-bucket, bucket-edge,
    chunked-long), arrivals interleaved with steps so prefills land while
    decodes run (the mixed path) and also while idle (the pure path)."""
    rng = np.random.default_rng(0)
    lengths = [5, 16, 33, 60, 90, 12]
    params = SamplingParams(max_tokens=4, temperature=0.0)
    pending = [(f"{tag}-{i}", rng.integers(1, 500, n).tolist())
               for i, n in enumerate(lengths)]
    while pending or eng.has_unfinished_requests():
        if pending:
            rid, prompt = pending.pop(0)
            eng.add_request(rid, prompt, params)
        for _ in range(2):
            if eng.has_unfinished_requests():
                eng.step()
    while eng.has_unfinished_requests():
        eng.step()


def test_mixed_load_compile_count_bounded():
    eng = _engine()
    _run_wave(eng, "w1")
    first = _compiled_variants(eng)
    assert eng.obs.step_kind_counts["mixed"] > 0, \
        "simulation never exercised the mixed path"
    # Bound from the bucket grid: prefill (Tp x rows), mixed (Tp x rows x
    # history widths — pages for <=90-token prompts at ps=8 span 3 pow-2
    # widths), solo-chunk (Tp x widths), decode (batch buckets x 2 modes).
    n_tp, n_rows = len(PREFILL_BUCKETS), len(DECODE_BUCKETS)
    bound = (n_tp * n_rows          # pure prefill
             + n_tp * n_rows * 3    # mixed
             + n_tp * 3             # solo chunk
             + n_rows * 2)          # decode greedy/sampled
    assert 0 < first <= bound, (first, bound)

    # Steady state: an identical second wave must reuse every compiled
    # variant — one new shape here means some step input scales with
    # context/batch instead of a bucket.
    _run_wave(eng, "w2")
    assert _compiled_variants(eng) == first, \
        "second identical load wave triggered new XLA compilations"


def _spec_engine(k: int = 3):
    cfg = EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=8, num_pages=129),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32,
            decode_buckets=DECODE_BUCKETS, prefill_buckets=PREFILL_BUCKETS,
            decode_window=2, mixed_batch_enabled=True,
            spec_decode_enabled=True, num_speculative_tokens=k))
    return LLMEngine(cfg)


def _run_spec_wave(eng, tag: str) -> None:
    """Mixed spec load: repetitive prompts (n-gram drafts hit, spec steps
    fire at several row buckets) plus structureless ones (spec bows out to
    legacy decode), staggered so prefill/mixed/spec/decode all occur."""
    rng = np.random.default_rng(1)
    pattern = rng.integers(1, 500, 4).tolist()
    prompts = [pattern * 4, rng.integers(1, 500, 12).tolist(),
               pattern * 7, pattern * 2, rng.integers(1, 500, 30).tolist()]
    params = SamplingParams(max_tokens=8, temperature=0.0)
    pending = [(f"{tag}-{i}", list(p)) for i, p in enumerate(prompts)]
    while pending or eng.has_unfinished_requests():
        if pending:
            rid, prompt = pending.pop(0)
            eng.add_request(rid, prompt, params)
        for _ in range(3):
            if eng.has_unfinished_requests():
                eng.step()
    while eng.has_unfinished_requests():
        eng.step()


def test_spec_load_compile_count_bounded():
    """Spec-decode steps stay inside the bucket-grid compile bound: the
    verify program's token width is R_pad * (k+1) with k STATIC config, so
    it adds at most one variant per decode bucket — and a second identical
    spec wave compiles NOTHING new."""
    eng = _spec_engine()
    _run_spec_wave(eng, "w1")
    assert eng.obs.step_kind_counts["spec"] > 0, \
        "simulation never exercised a spec-verify step"
    first = _compiled_variants(eng)
    n_tp, n_rows = len(PREFILL_BUCKETS), len(DECODE_BUCKETS)
    bound = (n_tp * n_rows          # pure prefill
             + n_tp * n_rows * 3    # mixed
             + n_tp * 3             # solo chunk
             + n_rows * 2           # decode greedy/sampled
             + n_rows)              # spec verify: one per row bucket
    assert 0 < first <= bound, (first, bound)

    _run_spec_wave(eng, "w2")
    assert _compiled_variants(eng) == first, \
        "second identical spec wave triggered new XLA compilations"


def _spec_draft_engine(k: int = 3):
    """Draft-model + adaptive-k + mixed: the full composition — the
    spec×mixed program family, the draft model's own decode/prefill
    families, and the adaptive ladder's per-k variants all ride one
    engine."""
    from kubernetes_gpu_cluster_tpu.models import llama as model_lib
    import jax

    cfg = EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=8, num_pages=129),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32,
            decode_buckets=DECODE_BUCKETS, prefill_buckets=PREFILL_BUCKETS,
            decode_window=2, mixed_batch_enabled=True,
            spec_decode_enabled=True, num_speculative_tokens=k,
            spec_draft_model="debug-tiny"))
    params = model_lib.init_params(cfg.model, jax.random.key(0))
    # Oracle draft (same params): every draft accepts, so spec and
    # spec_mixed steps fire deterministically at several row buckets.
    return LLMEngine(cfg, params=params, draft_params=params)


def _run_spec_mixed_wave(eng, tag: str) -> None:
    """Composition wave: a long-lived repetitive session keeps verify
    slices live (the oracle draft always proposes) while a
    longer-than-budget prompt chunks and short prompts arrive — chunk +
    verify slices must share dispatched steps, at more than one row
    bucket."""
    rng = np.random.default_rng(2)
    pattern = rng.integers(1, 500, 4).tolist()
    sess = SamplingParams(max_tokens=30, temperature=0.0)
    short = SamplingParams(max_tokens=6, temperature=0.0)
    eng.add_request(f"{tag}-s0", pattern * 5, sess)
    for _ in range(3):
        eng.step()
    eng.add_request(f"{tag}-s1", pattern * 3, sess)
    for _ in range(2):
        eng.step()
    eng.add_request(f"{tag}-long", pattern * 12, short)   # 48 > 32: chunks
    eng.add_request(f"{tag}-p", rng.integers(1, 500, 12).tolist(), short)
    while eng.has_unfinished_requests():
        eng.step()


def test_spec_mixed_draft_load_compile_count_bounded():
    """The composition's compile families stay bounded and steady-state:
    spec×mixed adds (prefill bucket x row bucket x history width) per
    ladder rung, the draft model adds its decode-per-row-bucket and
    chunked-prefill families — and a second identical wave compiles
    NOTHING new (the zero-new-compiles bar sustained serving depends on),
    counted through the same engine seam the kgct_jit_compiles_total
    gauge reads, draft programs included."""
    eng = _spec_draft_engine()
    _run_spec_mixed_wave(eng, "w1")
    assert eng.obs.step_kind_counts["spec"] > 0
    assert eng.obs.step_kind_counts["spec_mixed"] > 0, \
        "simulation never composed a chunk with verify slices"
    first = _compiled_variants(eng)
    n_tp, n_rows = len(PREFILL_BUCKETS), len(DECODE_BUCKETS)
    bound = (n_tp * n_rows          # pure prefill
             + n_tp * n_rows * 3    # mixed
             + n_tp * 3             # solo chunk
             + n_rows * 2           # decode greedy/sampled
             + n_rows               # spec verify: one per row bucket
             + n_tp * n_rows * 3    # spec_mixed: (Tp x rows x widths)
             + n_rows               # draft decode: one per row bucket
             + 12)                  # draft chunked prefill (T x width grid)
    assert 0 < first <= bound, (first, bound)

    _run_spec_mixed_wave(eng, "w2")
    assert _compiled_variants(eng) == first, \
        "second identical spec×mixed/draft wave triggered new compilations"


def _swap_engine():
    """Page-starved pool + host tier: decode growth must preempt-by-swap
    (and restore) during the wave, exercising the gather/scatter programs."""
    # Mixing off: the swap path preempts inside _grow_decode_pages either
    # way, and skipping the mixed program's compiles keeps this guard cheap
    # (the mixed family's bound is test_mixed_load_compile_count_bounded).
    cfg = EngineConfig(
        model=get_model_config("debug-tiny"),
        cache=CacheConfig(page_size=8, num_pages=13, swap_space_gb=0.01),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32,
            decode_buckets=DECODE_BUCKETS, prefill_buckets=PREFILL_BUCKETS,
            decode_window=2, mixed_batch_enabled=False))
    return LLMEngine(cfg)


def _run_swap_wave(eng, tag: str) -> None:
    rng = np.random.default_rng(3)
    lengths = [12, 16, 10, 14]
    params = SamplingParams(max_tokens=12, temperature=0.0)
    for i, n in enumerate(lengths):
        eng.add_request(f"{tag}-{i}", rng.integers(1, 500, n).tolist(),
                        params)
    while eng.has_unfinished_requests():
        eng.step()


def test_swap_load_compile_count_bounded():
    """Swap gather/scatter add a BOUNDED compile family: page-count inputs
    pad to powers of two, so each direction compiles at most
    log2(max pages/seq)+1 variants — and a second identical swap wave
    compiles NOTHING new (steady-state serving never recompiles for swap)."""
    from kubernetes_gpu_cluster_tpu.utils.math import next_power_of_2

    eng = _swap_engine()
    _run_swap_wave(eng, "w1")
    assert eng.scheduler.num_preemptions_by_kind["swap"] > 0, \
        "simulation never exercised a swap preemption"
    assert eng.obs.swap_pages["in"] > 0, "no swapped sequence was restored"
    first = _compiled_variants(eng)
    n_tp, n_rows = len(PREFILL_BUCKETS), len(DECODE_BUCKETS)
    max_pages = eng.config.effective_max_len // 8
    n_swap_sizes = int(np.log2(next_power_of_2(max_pages))) + 1
    bound = (n_tp * n_rows          # pure prefill
             + n_tp * n_rows * 3    # mixed
             + n_tp * 3             # solo chunk
             + n_rows * 2           # decode greedy/sampled
             + 2 * n_swap_sizes)    # swap gather + scatter, pow-2 sizes
    assert 0 < first <= bound, (first, bound)

    _run_swap_wave(eng, "w2")
    assert _compiled_variants(eng) == first, \
        "second identical swap wave triggered new XLA compilations"


# -- a block model: W passes over the rows' open blocks ----------------------

def _block_engine():
    cfg = EngineConfig(
        model=get_model_config("debug-block-moe"),
        cache=CacheConfig(page_size=8, num_pages=129),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=32,
            decode_buckets=DECODE_BUCKETS, prefill_buckets=PREFILL_BUCKETS,
            decode_window=3, mixed_batch_enabled=True))
    return LLMEngine(cfg)


def test_block_load_compile_count_bounded():
    """A block model's family: ONE window program a decode row bucket
    (whatever phases its rows' blocks are in, however many passes their
    blocks have taken), one mixed program a (chunk bucket, row bucket,
    history width), and the prefill programs of every model; the
    autoregressive window and mixed step are never traced."""
    eng = _block_engine()
    _run_wave(eng, "w1")
    first = _compiled_variants(eng)
    assert eng.obs.step_kind_counts["mixed"] > 0
    assert eng.obs.block_commit_passes > 0
    n_tp, n_rows = len(PREFILL_BUCKETS), len(DECODE_BUCKETS)
    assert 0 < eng._block_window_fn._cache_size() <= n_rows
    assert 0 < eng._block_mixed_fn._cache_size() <= n_tp * n_rows * 3
    for fn in (eng._decode_fn, eng._decode_fn_greedy, eng._mixed_fn):
        assert fn._cache_size() == 0
    bound = (n_tp * n_rows          # pure prefill
             + n_tp * n_rows * 3    # block mixed
             + n_tp * 3             # solo chunk
             + n_rows)              # the block window: one mode
    assert first <= bound, (first, bound)
    _run_wave(eng, "w2")
    assert _compiled_variants(eng) == first, \
        "second identical load wave triggered new XLA compilations"
