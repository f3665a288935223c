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
    tokens of 32 heads of 128 in chunks of 64, four segments. The operands
    are read as they are: beside the kernel's own results (o, u and the
    states handed to each chunk) nothing of their size is written, so no
    operand was copied to another layout on its way in or out."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.kda_chunk import kda_chunk

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    T, H, d = 2048, 32, 128
    compiled = jax.jit(lambda *a: kda_chunk(*a, 0, 64)).lower(
        *(arr((T, H, d)),) * 4, arr((T, H)), arr((T,), jnp.int32),
        arr((4,), jnp.int32), arr((H * d, d))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    operand = T * H * d * 4
    # u [T, H, d] and the 32 chunks' [H x d, d] states, plus small change
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5 * operand
