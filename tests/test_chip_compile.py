"""Kernels of the main path compiled at their real widths for a chip that is
DESCRIBED, not attached (the TPU's compiler is installed here): what Mosaic
refuses (a slice off the HBM tiling, more VMEM than a kernel may take) is
found without chip time. Nothing runs; no time or value is checked.

The topology is described inside a fixture, never at import (every xdist
worker imports every test file). Describing takes no chip, so the fixture
lets this process load the TPU's library beside another that has it (a
worker that got another of these cases, a process that holds a chip): the
cases may land on any worker. Only a machine without the TPU's compiler
skips; any other failure to describe the chip fails."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as env:
        env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except RuntimeError as e:
            if "TPU support not installed" not in str(e):
                raise
            pytest.skip(f"no compiler for a TPU here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("pairs,groups,experts,k,n", [
    # kimi-vl-a3b's mixed step: 8 expert layers' groups, up and down
    ((2048 + 64) * 6, 8 * 64, 64, 2048, 1408),
    ((2048 + 64) * 6, 8 * 64, 64, 1408, 2048),
    # mixtral-8x7b's widths: N (and K) do not fit VMEM whole
    ((2048 + 64) * 2, 8, 8, 4096, 14336),
    ((2048 + 64) * 2, 8, 8, 14336, 4096)])
def test_grouped_matmul_compiles_for_a_v5e(one_chip, no_compile_cache, pairs,
                                           groups, experts, k, n):
    from kubernetes_gpu_cluster_tpu.ops.pallas import grouped_matmul as gm

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(gm.grouped_matmul).lower(
        arr((gm.padded_rows(pairs, experts), k), jnp.bfloat16),
        arr((groups, k, n), jnp.bfloat16), arr((groups,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the rows and the weights are read where they lie: nothing is copied
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_ssm_update_compiles_for_a_v5e_in_place(one_chip, no_compile_cache):
    """granite-4.0-h-micro's decode step: 64 rows against 65 slots of
    [128, 4096] float32 in 36 state layers (4.9 GB). The pool is aliased,
    nothing of it is copied."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.ssm_update import ssm_update

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = arr((36, 65, 128, 4096))
    compiled = jax.jit(ssm_update, donate_argnums=0).lower(
        pool, arr((), jnp.int32), arr((64,), jnp.int32), arr((64, 4096)),
        arr((64, 4096)), arr((64, 128)), arr((64, 128))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**24
    assert mem.alias_size_in_bytes >= 36 * 65 * 128 * 4096 * 4


def test_kda_update_compiles_for_a_v5e_in_place(one_chip, no_compile_cache):
    """kimi-linear's decode step at the served cut: 64 rows against 65 slots
    of [32 x 128, 128] float32 in 7 KDA layers (0.95 GB). The pool is
    aliased, nothing of it is copied."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.kda_update import kda_update

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = arr((7, 65, 4096, 128))
    compiled = jax.jit(kda_update, donate_argnums=0).lower(
        pool, arr((), jnp.int32), arr((64,), jnp.int32), arr((64, 32, 128)),
        arr((64, 32)), arr((64, 32, 128)), arr((64, 32, 128)),
        arr((64, 32, 128))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # ... beside the columns' and rows' small rearrangements
    assert compiled.memory_analysis().temp_size_in_bytes < 2**23
    assert compiled.memory_analysis().alias_size_in_bytes >= 7 * 65 * 2**21


def test_kda_chunk_compiles_for_a_v5e_without_copies(one_chip,
                                                     no_compile_cache):
    """kimi-linear's chunked delta-rule form over a full prefill bucket: 2048
    tokens of 32 heads of 128 in chunks of 64, four segments. q, k, v and g
    arrive as the conv stage and the gate's projection leave them, ``[T, H
    x d]`` with the heads on lanes, named ``[T, H, d]`` for the call, and
    are read as they are: beside the kernel's own results (o, u and the
    states handed to each chunk) nothing of their size is written, so no
    operand was copied to another layout on its way in or out."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.kda_chunk import kda_chunk

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    T, H, d = 2048, 32, 128
    compiled = jax.jit(lambda *a: kda_chunk(
        *(x.reshape(T, H, d) for x in a[:4]), *a[4:], 0, 64)).lower(
        *(arr((T, H * d)),) * 4, arr((T, H)), arr((T,), jnp.int32),
        arr((4,), jnp.int32), arr((H * d, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    operand = T * H * d * 4
    # u [T, H, d] and the 32 chunks' [H x d, d] states, plus small change
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5 * operand


def test_ssm_chunk_compiles_for_a_v5e_without_copies(one_chip,
                                                     no_compile_cache):
    """granite-4.0-h-micro's chunked scan over the segment part of the
    widest mixed step (2112 tokens: 17 of the kernel's chunks, the last
    partial), 64 heads of 64, N = 128, x bfloat16 as the conv leaves it,
    ``[T, d_inner]``. x is read and y written where they lie: beside y and
    the states kept for the segment's final nothing of their size is
    written."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.ssm_chunk import ssm_chunk

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    T, H, P, N = 2112, 64, 64, 128
    bf16 = jnp.bfloat16
    compiled = jax.jit(
        lambda x, *a: ssm_chunk(x.reshape(T, H, P), *a, 0, 256)[0]
        .reshape(T, H * P)).lower(
        arr((T, H * P), bf16), arr((T, H)), arr((T, H)), arr((T, N), bf16),
        arr((T, N), bf16), arr((T,), jnp.int32), arr((1,), jnp.int32),
        arr((N, H * P))).compile()
    assert "%ssm_chunk" in compiled.as_text()
    # the padded tail's copies of x and y (T is not whole chunks here; in
    # the step program the segment part is) and two states [N, d_inner]
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= (T + 128) * H * P * (2 + 4) + 3 * N * H * P * 4)


@pytest.mark.parametrize("T", [1536, 2048])
@pytest.mark.parametrize("preset", ["kimi-linear-48b-a3b",
                                    "granite-4.0-h-micro"])
def test_conv_segments_compiles_for_a_v5e_without_copies(
        one_chip, no_compile_cache, preset, T):
    """Both state models' conv stage over the segment part of a mixed step
    beside full seats (the projection's ``[T + 64, C]`` bfloat16 handed over
    whole): kimi-linear's q | k | v as float32 ``[T, 32, 128]`` heads,
    granite's [x | B | C] in bfloat16. xbc is read and every piece written
    where it lies: nothing of their size is written beside the pieces."""
    import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
    from kubernetes_gpu_cluster_tpu.config import get_model_config
    from kubernetes_gpu_cluster_tpu.models.llama import state_conv_split
    from kubernetes_gpu_cluster_tpu.ops.pallas.conv_segments import (
        conv_segments)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    cfg = get_model_config(preset)
    split, (K1, C) = state_conv_split(cfg), cfg.state_conv_shape
    compiled = jax.jit(lambda *a: conv_segments(*a, split)).lower(
        arr((T + 64, C)), arr((T,), jnp.int32), arr((K1, C)),
        arr((K1 + 1, C)), arr((C,))).compile()
    assert "%conv_segments" in compiled.as_text()
    # the taps, the bias and the slot's rows as float32, the taps' bits
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("T", [64, 1088, 1600, 2112])
def test_stream_mixers_compile_for_a_v5e_in_place(one_chip, no_compile_cache,
                                                  T):
    """xing4.0's ``hc_pre`` / ``hc_post`` at the published widths (4 streams
    of 3584, bf16) over a full decode bucket and the three mixed steps of
    the ``batch-decode-2k`` cell (1024, 1536 and 2048 tokens beside 64
    rows: the last block of each is partial). The streams are read where
    they lie, and ``hc_post`` writes over them."""
    from kubernetes_gpu_cluster_tpu.ops import hyper_conn
    from kubernetes_gpu_cluster_tpu.ops.pallas import hc_mix

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n, d, f32 = 4, 3584, jnp.float32
    hc = hyper_conn.HCSettings(n, 20, 1e-6, (-30.0, 30.0), 1e-6)
    pre = jax.jit(lambda x, p, a, b: hc_mix.hc_pre(x, p, a, b, hc)).lower(
        arr((T, n * d)), arr((n * d, hyper_conn.COLS)), arr((3,), f32),
        arr((hyper_conn.COLS,), f32)).compile()
    assert "%hc_pre" in pre.as_text()
    assert pre.memory_analysis().temp_size_in_bytes < 2**20
    post = jax.jit(hc_mix.hc_post, donate_argnums=0).lower(
        arr((T, n * d)), arr((T, d)), arr((T, hyper_conn.COLS), f32)).compile()
    assert "%hc_post" in post.as_text()
    assert post.memory_analysis().temp_size_in_bytes < 2**20
    assert post.memory_analysis().alias_size_in_bytes == T * n * d * 2


@pytest.mark.parametrize("nh,nkv,hd,T,pps", [
    (32, 8, 64, 2048, 32),     # granite-4.0-h-micro: four lane blocks of two
    (32, 8, 128, 2048, 32),    # qwen3-4b: eight lane blocks of one head
    (32, 8, 128, 128, 32),     # ... a short prompt's chunk: one short tile
    (4, 1, 128, 2048, 64),     # a tp=8 shard of it: kd 128, one block
    (32, 4, 64, 2048, 16)],    # tinyllama: 16 q heads a lane block
    ids=["granite", "qwen", "qwen-128", "tp-shard", "tinyllama"])
def test_flash_prefill_hist_compiles_for_a_v5e(one_chip, no_compile_cache,
                                               nh, nkv, hd, T, pps):
    """The chunk kernel at served geometries, bf16, a stacked pool under a
    dynamic layer index: Mosaic takes the 128-lane pieces of a page (a
    dynamic lane offset in the kernel's own DMA), the chunk's K/V whole in
    VMEM and a tile of 512 keys against block_q x heads rows. Beside q and
    the output, laid out by lane block, nothing of their size is written."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
        flash_prefill_history)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = jnp.int32
    pool = arr((2, 2, 128, nkv * hd))
    compiled = jax.jit(
        lambda q, k, v, seg, pos, kp, vp, pt, hl, lyr: flash_prefill_history(
            q, k, v, seg, pos, kp, vp, pt, hl, hd ** -0.5, layer=lyr)).lower(
        arr((T, nh, hd)), arr((T, nkv, hd)), arr((T, nkv, hd)),
        arr((T,), i32), arr((T,), i32), pool, pool, arr((pps,), i32),
        arr((), i32), arr((), i32)).compile()
    assert "%flash_prefill_hist" in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= 2.5 * T * nh * hd * 2)


@pytest.mark.parametrize("nh,nkv,hd,hv,T", [
    (32, 32, 192, 128, 2048),  # xing, kimi-linear: as many kv heads as heads,
    (16, 16, 192, 128, 2048),  # kimi-vl:           a narrower v
    (32, 32, 192, 128, 64),    # ... the smallest bucket: one short tile
    (32, 8, 128, 128, 2048),   # qwen3-4b: two kv heads a step, 512 rows each
    (32, 8, 64, 64, 2048),     # granite-4.0-h-micro
    (4, 1, 128, 128, 2048)],   # a tp=8 shard of qwen: one kv head
    ids=["xing", "kimi-vl", "xing-64", "qwen", "granite", "tp-shard"])
def test_flash_prefill_compiles_for_a_v5e(one_chip, no_compile_cache,
                                          nh, nkv, hd, hv, T):
    """The fresh-segment kernel at served geometries, bf16: Mosaic takes a
    block of kv heads' K/V whole in VMEM, the q heads of a kv head merged
    into rows, and tiles of 512 keys against them. Beside the head-major
    copies of q, k, v and the output nothing of their size is written."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import (
        flash_ragged_prefill)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32 = jnp.int32
    compiled = jax.jit(
        lambda q, k, v, seg, pos: flash_ragged_prefill(
            q, k, v, seg, pos, hd ** -0.5)).lower(
        arr((T, nh, hd)), arr((T, nkv, hd)), arr((T, nkv, hv)),
        arr((T,), i32), arr((T,), i32)).compile()
    assert "%flash_prefill" in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= T * (nh * (hd + hv) + nkv * (hd + hv)) * 2 + 2**20)


def _compiled_mixed_step(one_chip, cfg, pages, prompt_len, steps=1):
    """The mixed step program the ``steps``-th chunk of a prompt of
    ``prompt_len`` tokens rides beside full seats under ``cfg``, compiled
    for the described chip with every kernel on: the scheduler's own batch
    gives the shapes, no weight is drawn. Returns (compiled, batch)."""
    import numpy as np

    from kubernetes_gpu_cluster_tpu.engine import SamplingParams
    from kubernetes_gpu_cluster_tpu.engine.engine import (
        LLMEngine, _pack_float_b, _pack_int_b)
    from kubernetes_gpu_cluster_tpu.engine.kv_cache import (
        allocate_kv_cache, default_state_slots)
    from kubernetes_gpu_cluster_tpu.engine.sampling_params import (
        LOGIT_BIAS_CAP)
    from kubernetes_gpu_cluster_tpu.engine.scheduler import Scheduler
    from kubernetes_gpu_cluster_tpu.engine.sequence import Sequence
    from kubernetes_gpu_cluster_tpu.models import llama as model_lib
    from kubernetes_gpu_cluster_tpu.ops.attention import Kernels

    model, seats = cfg.model, cfg.scheduler.max_num_seqs
    slots = default_state_slots(model, seats)
    sched = Scheduler(cfg, pages, num_state_slots=slots)
    rows = [Sequence(f"r{i}", [1, 2, 3, 4], SamplingParams(max_tokens=64))
            for i in range(seats - 1)]
    for seq in rows:
        sched.add(seq)
    assert sched.schedule().kind == "prefill"
    sched.add(Sequence("head", list(range(1, prompt_len + 1)),
                       SamplingParams(max_tokens=64)))
    for _ in range(steps):
        for seq in rows:
            seq.append_token(7)
        batch = sched.schedule()
        assert batch.kind == "mixed"

    # an engine's step program without an engine's weights
    shell = object.__new__(LLMEngine)
    shell.config, shell.model_config, shell.mesh = cfg, model, None
    shell.kernels = Kernels(use_pallas=True, use_pallas_hist=True,
                            grouped_experts=model.is_moe)
    shell._last_width = cfg.scheduler.decode_buckets[-1]
    B = len(batch.temperature)
    args = (jax.eval_shape(
                lambda: model_lib.init_params(model, jax.random.key(0))),
            jax.eval_shape(
                lambda: allocate_kv_cache(model, cfg.cache, pages, None,
                                          slots)),
            np.zeros(shell._last_width, np.int32),
            np.stack([batch.tokens, batch.seg_ids, batch.positions,
                      batch.slot_mapping]),
            _pack_int_b(batch), _pack_float_b(batch), batch.chunk_page_table,
            np.int32(batch.hist_len), batch.page_tables, batch.context_lens,
            np.full((B, cfg.effective_max_len), -1, np.int32),
            np.full((B, LOGIT_BIAS_CAP), -1, np.int32),
            np.zeros((B, LOGIT_BIAS_CAP), np.float32),
            jax.eval_shape(lambda: jax.random.key(0)))
    compiled = shell._build_mixed_fn().lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)).compile()
    return compiled, batch


def _cut(preset, overrides, seats, max_model_len, pages):
    from kubernetes_gpu_cluster_tpu.config import (
        CacheConfig, EngineConfig, SchedulerConfig, apply_hf_overrides,
        get_model_config)
    model = apply_hf_overrides(get_model_config(preset),
                               overrides).replace(dtype="bfloat16")
    return EngineConfig(model=model, max_model_len=max_model_len,
                        cache=CacheConfig(page_size=128, num_pages=pages),
                        scheduler=SchedulerConfig(max_num_seqs=seats))


GLM_CUT = {"num_hidden_layers": 6, "layers_from": 2,
           "experts_held": 16, "vocab_size": 19360}


@pytest.mark.parametrize("preset,overrides,kernels", [
    ("granite-4.0-h-micro", {},
     ("flash_prefill_hist", "conv_segments", "ssm_chunk", "ssm_update",
      "paged_decode", "kv_write")),
    ("kimi-vl-a3b", {"num_hidden_layers": 9},
     ("flash_prefill", "latent_prefill_hist", "grouped_matmul",
      "latent_paged_decode", "kv_write")),
    ("kimi-linear-48b-a3b",
     {"num_hidden_layers": 9, "experts_held": 64, "vocab_size": 40960},
     ("conv_segments", "kda_chunk", "kda_update", "flash_prefill",
      "latent_prefill_hist", "grouped_matmul", "latent_paged_decode",
      "kv_write")),
    ("xing4.0-29b-a4b", {"num_hidden_layers": 8},
     ("hc_pre", "hc_post", "flash_prefill", "latent_prefill_hist",
      "grouped_matmul", "latent_paged_decode", "kv_write")),
    # the indexers and the chosen rows' attention are XLA's (ops/dsa.py):
    # of the kernels the step holds the experts' and the two page writes
    ("glm-5.2", GLM_CUT, ("grouped_matmul", "kv_write"))])
def test_mixed_step_at_the_chunk_rung_compiles_for_a_v5e(
        one_chip, no_compile_cache, preset, overrides, kernels):
    """The WHOLE mixed step program a prompt of 1025-1536 tokens rides beside
    full seats, (1536, 64), at the cut and the pool the ``batch-decode-2k``
    cells serve, with every kernel on: the scheduler's own batch gives the
    shapes, no weight is drawn. Its scratch stays inside what the engine
    sets aside for a step (``step_workspace_bytes``, sized at 2048 + 64)."""
    from kubernetes_gpu_cluster_tpu.engine.engine import step_workspace_bytes

    cfg = _cut(preset, overrides, 64, 4096, 2049)
    model = cfg.model
    compiled, batch = _compiled_mixed_step(one_chip, cfg, 2049, 1100)
    assert len(batch.tokens) == 1536 + 64
    text = compiled.as_text()
    for name in kernels:
        assert f"%{name}." in text, name
    assert (compiled.memory_analysis().temp_size_in_bytes
            < step_workspace_bytes(cfg))
    # The conv of a segment part is the kernel's: XLA has no convolution
    # left to compute twice (until PR 47 granite's (1536, 64) step held
    # ``%convolution_convert_fusion.N.remat`` twins).
    assert not re.search(r"%convolution[\w.\-]*\.remat", text)
    if model.index_topk:
        # What the benchmark's readers of the indexers' device time tell
        # an indexer by: ONE conditional of the step's several returns the
        # rows' choice (the scanned expert layers' body; the one dense
        # layer's indexer is inlined, its predicate a constant).
        from perfbench.readers.dsa_index_roofline import (
            is_indexer_conditional)
        conds = [line.strip() for line in text.splitlines()
                 if " conditional(" in line]
        assert sum(is_indexer_conditional(c, model.index_topk)
                   for c in conds) == 1 < len(conds)


def test_glm_chunk_step_is_built_for_the_servers_16_seats(one_chip,
                                                          no_compile_cache):
    """The chunk step of the ``glm-5.2-bf16.batch-long-8k`` cell's server
    (16 seats under the default ladder, 12288 positions, 1537 pages): a full
    chunk with history beside 15 rows is (2048, 16). The rows' part chooses,
    gathers and attends for the 16 rows the seats can fill,
    ``[16, 2048, 640]`` (what ``dsa_chosen_attend_hbm_share`` reads), and
    holds nothing of 64 rows: until PR 48 the row floor stood at the
    ladder's top and every chunk step paid for 48 rows that no seat had."""
    cfg = _cut("glm-5.2", GLM_CUT, 16, 12288, 1537)
    model = cfg.model
    compiled, batch = _compiled_mixed_step(one_chip, cfg, 1537, 8064, steps=3)
    assert len(batch.tokens) == 2048 + 16 and batch.hist_len == 2 * 2033
    assert batch.chunk_page_table.shape == (1, 64)
    text = compiled.as_text()
    k, w = model.index_topk, 640
    assert (k, model.kv_row_padded) == (2048, w)
    assert f"bf16[16,{k},{w}]" in text
    for rows in (32, 64):
        for shape in (f"[{rows},{k},{w}]", f"[{rows * k},{w}]",
                      f"[{rows},12289]", f"[{rows},{k}]"):
            assert shape not in text, shape


@pytest.mark.parametrize("S", [4, 8])
def test_block_attend_compiles_for_a_v5e_at_the_cell_s_shapes(
        one_chip, no_compile_cache, S):
    """sdar-30b-a3b-chat's pass at the served cut: 64 rows of 8 positions
    (two blocks of 4: the block awaiting its commit and the open block; 4:
    one block, what the kernel check times beside it), 32 q / 4 kv heads of
    128, over a 6-layer bf16 pool of 2049 pages under a dynamic layer
    index, tables 32 pages wide. The pool is read where it lies: nothing
    of its size is copied."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.block_attend import (
        block_attend)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    R, nh, nkv, hd = 64, 32, 4, 128
    pool = arr((6, 2049, 128, nkv * hd))
    compiled = jax.jit(lambda q, k, v, kp, vp, tb, ctx, lyr, wide:
                       block_attend(q, k, v, kp, vp, tb, ctx, hd ** -0.5,
                                    layer=lyr, block=4, wide=wide)).lower(
        arr((R * S, nh, hd)), arr((R * S, nkv, hd)), arr((R * S, nkv, hd)),
        pool, pool, arr((R, 32), jnp.int32), arr((R,), jnp.int32),
        arr((1,), jnp.int32), arr((R,), jnp.int32)).compile()
    assert "%block_attend" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**22


def test_block_window_compiles_for_a_v5e_at_the_cell_s_cut(one_chip,
                                                          no_compile_cache):
    """The WHOLE window program of ``sdar-30b-a3b-chat-bf16.batch-decode-2k``
    (6 layers of 128 experts, 64 seats, 4096 positions, 2049 pages, 8
    passes), every kernel on: a pass is 64 rows of TWO blocks (512
    positions through the layers), and the head and the sampler see the
    open block's 256 alone. Its scratch stays inside what the engine sets
    aside for a step."""
    import numpy as np

    from kubernetes_gpu_cluster_tpu.engine import block as block_steps
    from kubernetes_gpu_cluster_tpu.engine.engine import (
        LLMEngine, _pack_float_b, step_workspace_bytes)
    from kubernetes_gpu_cluster_tpu.engine.kv_cache import allocate_kv_cache
    from kubernetes_gpu_cluster_tpu.engine.scheduler import Scheduler
    from kubernetes_gpu_cluster_tpu.models import llama as model_lib
    from kubernetes_gpu_cluster_tpu.ops.attention import Kernels

    cfg = _cut("sdar-30b-a3b-chat", {"num_hidden_layers": 6}, 64, 4096, 2049)
    model = cfg.model
    batch = Scheduler(cfg, 2049).decode_batch([], 64)
    assert batch.block.shape == (64, block_steps.state_width(4) + 2)
    shell = object.__new__(LLMEngine)
    shell.config, shell.model_config, shell.mesh = cfg, model, None
    shell.kernels = Kernels(use_pallas=True, use_pallas_hist=True,
                            grouped_experts=True, block=model.block_length)
    shell._last_width = 64
    window, _ = block_steps.build_block_fns(shell)
    args = (jax.eval_shape(
                lambda: model_lib.init_params(model, jax.random.key(0))),
            jax.eval_shape(
                lambda: allocate_kv_cache(model, cfg.cache, 2049, None, 0)),
            np.zeros((64, block_steps.state_width(4)), np.int32),
            np.concatenate([batch.block, np.stack(
                [batch.top_k, batch.seed, batch.top_n], axis=1),
                batch.page_tables], axis=1),
            _pack_float_b(batch), jax.eval_shape(lambda: jax.random.key(0)))
    compiled = window.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)).compile()
    text = compiled.as_text()
    for name in ("block_attend", "grouped_matmul", "kv_write"):
        assert f"%{name}." in text, name
    assert "[512,2048]" in text and "[256,151936]" in text
    assert "[512,151936]" not in text
    assert (compiled.memory_analysis().temp_size_in_bytes
            < step_workspace_bytes(cfg))


@pytest.mark.parametrize("kernel", ["flash_prefill", "flash_prefill_hist"])
def test_block_causal_flash_kernels_compile_for_a_v5e(one_chip,
                                                      no_compile_cache,
                                                      kernel):
    """The same cut's prompt: a 2048-token bucket under the block-causal
    mask (block 4), fresh and as a chunk with history."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import (
        flash_ragged_prefill)
    from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
        flash_prefill_history)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    T, nh, nkv, hd, i32 = 2048, 32, 4, 128, jnp.int32
    qkv = (arr((T, nh, hd)), arr((T, nkv, hd)), arr((T, nkv, hd)),
           arr((T,), i32), arr((T,), i32))
    if kernel == "flash_prefill":
        compiled = jax.jit(lambda *a: flash_ragged_prefill(
            *a, hd ** -0.5, block=4)).lower(*qkv).compile()
    else:
        pool = arr((6, 2049, 128, nkv * hd))
        compiled = jax.jit(lambda *a: flash_prefill_history(
            *a[:-1], hd ** -0.5, layer=a[-1], block=4)).lower(
            *qkv, pool, pool, arr((32,), i32), arr((), i32),
            arr((), i32)).compile()
    assert f"%{kernel}" in compiled.as_text()
