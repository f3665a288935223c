"""Bring-up contracts: nothing on the serving path may hide the device or the
kernels, the pool is sized after the weights, the compile cache can be placed
from outside, and chip_smoke.py's parent stays off JAX.

Cheap by construction (tier-1 has no room): one debug-tiny engine for the
module, no server. The end-to-end CPU rehearsal of chip_smoke.py is ``slow``.
"""

import ast
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from kubernetes_gpu_cluster_tpu.config import (
    CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
from kubernetes_gpu_cluster_tpu.engine import LLMEngine
from kubernetes_gpu_cluster_tpu.engine import engine as engine_mod
from kubernetes_gpu_cluster_tpu.ops import attention
from kubernetes_gpu_cluster_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


# -- compile cache ------------------------------------------------------------

class TestCompileCache:
    def test_env_directory_is_left_to_jax(self, monkeypatch, tmp_path):
        placed = tmp_path / "placed"
        monkeypatch.setenv(compile_cache.ENV_VAR, str(placed))

        def refuse(*a, **k):
            raise AssertionError("the cache dir must not be set in code "
                                 "when the environment places it")
        monkeypatch.setattr(jax.config, "update", refuse)
        assert compile_cache.configure_compile_cache() == str(placed)
        assert not placed.exists()     # JAX creates it on first write

    def test_default_is_one_fixed_in_checkout_directory(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            first = compile_cache.configure_compile_cache()
            second = compile_cache.configure_compile_cache()
            assert first == second == str(REPO / ".jax_compile_cache")
            assert jax.config.jax_compilation_cache_dir == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_compile_cache/" in ignored


# -- chip_smoke.py's parent ---------------------------------------------------

class TestChipSmokeParent:
    def test_imports_are_stdlib_only(self):
        """The parent never imports jax nor the package (whose config
        already imports jax): a process that touched JAX holds the chip and
        the server child could not have it."""
        tree = ast.parse(SMOKE.read_text())
        roots = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                roots |= {a.name.split(".")[0] for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                assert node.level == 0, "relative import in chip_smoke.py"
                roots.add(node.module.split(".")[0])
        assert roots and roots <= sys.stdlib_module_names, (
            roots - sys.stdlib_module_names)

    def test_restates_the_cache_helpers_rule(self):
        """It may not import the helper, so it repeats the default path; the
        two must be the same directory (the server's log line is checked
        against it at run time too)."""
        ns: dict = {"__file__": str(SMOKE), "__name__": "chip_smoke"}
        exec(compile(SMOKE.read_text(), str(SMOKE), "exec"), ns)
        assert ns["DEFAULT_CACHE_DIR"] == compile_cache.DEFAULT_DIR
        assert ns["CACHE_ENV"] == compile_cache.ENV_VAR

    def test_without_a_tpu_it_fails_at_once_and_prints_no_result(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, str(SMOKE)], env=env,
                           capture_output=True, text=True, timeout=30)
        assert r.returncode != 0
        assert r.stdout == ""
        assert "needs" in r.stderr and "TPU" in r.stderr

    @pytest.mark.slow
    def test_cpu_rehearsal_end_to_end(self):
        r = subprocess.run([sys.executable, str(SMOKE), "--cpu-rehearsal"],
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        report, verdict = map(json.loads, r.stdout.strip().splitlines()[-2:])
        # The last line is the chip check's contract: these keys, no others.
        assert verdict.keys() == {"ok", "device"} and verdict["ok"] is True
        assert verdict["device"].keys() == {"platform", "kind", "count"}
        assert verdict["device"]["platform"] == "cpu"  # never a chip result
        assert isinstance(verdict["device"]["kind"], str)
        assert type(verdict["device"]["count"]) is int
        out = report
        assert out["model"] == "debug-tiny"
        assert set(out["requests"].values()) == {200}
        assert out["mixed_step_ratio"] > 0
        assert out["server_exit_code"] == 0


# -- the engine: sizing order, no hidden fallback -----------------------------

def _config():
    # kd = 4 kv heads x 32 = 128: lane-aligned, so kernel eligibility on a
    # (faked) TPU reaches the compile probe.
    return EngineConfig(
        model=get_model_config("debug-tiny", num_kv_heads=4),
        cache=CacheConfig(page_size=16),
        scheduler=SchedulerConfig(max_num_seqs=2, max_prefill_tokens=64,
                                  decode_buckets=(1, 2),
                                  prefill_buckets=(32, 64)))


@pytest.fixture(scope="module")
def built():
    """(engine, construction events): ONE engine for the module, built with
    the weight init and the free-memory read instrumented."""
    events = []
    real_init, real_free = (engine_mod.model_lib.init_params,
                            engine_mod._device_free_memory)

    def init(*a, **k):
        events.append("weights")
        return real_init(*a, **k)

    def free(**k):
        events.append("free_memory")
        return real_free(**k)

    mp = pytest.MonkeyPatch()
    mp.setattr(engine_mod.model_lib, "init_params", init)
    mp.setattr(engine_mod, "_device_free_memory", free)
    try:
        eng = LLMEngine(_config())
    finally:
        mp.undo()
    return eng, events


class TestEngineBringUp:
    def test_pool_is_sized_after_the_weights_exist(self, built):
        _, events = built
        assert events == ["weights", "free_memory"]

    def test_cpu_engine_reports_xla_attention(self, built):
        eng, _ = built
        info = eng.runtime_info()
        assert info["platform"] == "cpu" and info["use_pallas"] is False
        assert info["use_pallas_hist"] is False
        assert info["num_pages"] == eng.scheduler.allocator.num_pages

    def test_probe_failure_on_tpu_raises(self, built, monkeypatch):
        """On a backend that says ``tpu`` an eligible kernel that does not
        compile fails construction — it never degrades to XLA attention.
        (Mosaic cannot compile for the CPU that really sits underneath.)"""
        eng, _ = built
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="paged_decode failed to "
                                               "compile at the served"):
            eng._resolve_use_pallas(None)

    def test_ineligible_geometry_is_an_explicit_recorded_decision(
            self, built, monkeypatch):
        eng, _ = built
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(eng, "model_config",
                            get_model_config("debug-tiny"))   # kd = 64
        assert eng._resolve_use_pallas(None).use_pallas is False
        assert "not 128-aligned" in eng.pallas_disabled_reason
        assert "pallas_disabled_reason" in eng.runtime_info()

    def test_missing_memory_stats_is_an_error_off_the_cpu(self, monkeypatch):
        assert engine_mod._device_free_memory() is None        # real CPU
        fake = SimpleNamespace(platform="tpu", device_kind="fake v0",
                               memory_stats=lambda: None)
        monkeypatch.setattr(jax, "local_devices", lambda: [fake])
        with pytest.raises(RuntimeError, match="no memory_stats"):
            engine_mod._device_free_memory()
        fake.memory_stats = lambda: {"bytes_limit": 100, "bytes_in_use": 40}
        assert engine_mod._device_free_memory() == 60

    def test_free_memory_waits_for_what_the_init_dropped(self, monkeypatch):
        """The bytes in use fall to the resident weights a moment after the
        last weight is ready (a dropped float32 draw): the read settles
        there; bytes that stay (another model on the device) are waited for
        no longer than the timeout, and with nothing said to be resident
        there is one read."""
        n = [0]

        def stats():
            n[0] += 1
            return {"bytes_limit": 16 << 30, "bytes_in_use": next(reads)}

        fake = SimpleNamespace(platform="tpu", device_kind="fake v0",
                               memory_stats=stats)
        monkeypatch.setattr(jax, "local_devices", lambda: [fake])
        monkeypatch.setattr(jax, "device_put",
                            lambda x, d: SimpleNamespace(
                                block_until_ready=lambda: None))
        monkeypatch.setattr(engine_mod, "SETTLE_PERIOD_S", 0.0)
        reads = iter([5 << 30, 5 << 30, (3 << 30) + (20 << 20)])
        assert engine_mod._device_free_memory(resident=3 << 30) == \
            (13 << 30) - (20 << 20)
        monkeypatch.setattr(engine_mod, "SETTLE_TIMEOUT_S", 0.05)
        reads = iter(lambda: 5 << 30, None)
        assert engine_mod._device_free_memory(resident=3 << 30) == 11 << 30
        n[0] = 0
        assert engine_mod._device_free_memory() == 11 << 30
        assert n[0] == 1


def test_pool_sizing_sets_a_step_workspace_aside():
    """0.90 of everything the weights leave free starves the first full
    prefill bucket of its own workspace (PR 21, v5e: compile-time OOM). The
    estimate is arithmetic over the config: a fraction of a GB for a dense
    4B model at the default buckets, more where MoE dense dispatch runs
    every expert, and it grows with the prefill budget."""
    def ws(name, **sched):
        return engine_mod.step_workspace_bytes(EngineConfig(
            model=get_model_config(name),
            scheduler=SchedulerConfig(**sched)))
    GiB = 2 ** 30
    assert 0.5 * GiB < ws("qwen3-4b") < 1.5 * GiB
    assert ws("mixtral-8x7b") > 2 * ws("llama-3-8b")
    assert ws("qwen3-4b", prefill_buckets=(128, 4096),
              max_prefill_tokens=4096) > 1.5 * ws("qwen3-4b")


@pytest.mark.parametrize("T", [3, 300])
def test_kv_write_is_one_formulation_for_every_flush_size(T):
    """The same per-token in-place loop below and above the old 256-token
    switch to a batched scatter (which copies the pool on TPU), against a
    NumPy row assignment."""
    import jax.numpy as jnp
    import numpy as np
    L, P, ps, kd = 2, 33, 16, 8
    rng = np.random.default_rng(0)
    k_rows, v_rows = rng.standard_normal((2, L, T, kd)).astype(np.float32)
    slots = (ps + rng.permutation((P - 1) * ps)[:T]).astype(np.int32)
    want_k = np.zeros((L, P * ps, kd), np.float32)
    want_v = np.zeros_like(want_k)
    want_k[:, slots], want_v[:, slots] = k_rows, v_rows
    pool = jnp.zeros((L, P, ps, kd), jnp.float32)
    k, v = jax.jit(attention.write_kv_pages_all_xla, donate_argnums=(0, 1))(
        pool, pool + 0, jnp.asarray(k_rows), jnp.asarray(v_rows),
        jnp.asarray(slots))
    np.testing.assert_array_equal(np.asarray(k).reshape(want_k.shape), want_k)
    np.testing.assert_array_equal(np.asarray(v).reshape(want_v.shape), want_v)


_X = object()   # an operand the operation only passes on


@pytest.mark.parametrize("operation, kernel_module, kernel, args", [
    ("decode_attention", "paged_decode", "pallas_paged_decode", [_X] * 8),
    ("prefill_attention", "flash_prefill", "flash_ragged_prefill", [_X] * 6),
    ("chunk_attention", "flash_prefill_hist", "flash_prefill_history",
     [_X] * 10),
    ("write_pages", "kv_write", "kv_write", [_X] * 5),
    # One pool of shared rows (latent attention: no V pool, no V rows): the
    # kernels of their own, by the same rule.
    ("decode_attention", "latent_decode", "latent_paged_decode",
     [_X, _X, None, _X, _X, _X, None, _X]),
    ("chunk_attention", "flash_prefill_hist", "flash_prefill_history_shared",
     [_X, _X, None, _X, _X, _X, None, _X, _X, _X]),
    ("write_pages", "kv_write", "kv_write", [_X, None, _X, None, _X]),
])
def test_use_pallas_true_means_the_kernel_or_its_exception(
        monkeypatch, operation, kernel_module, kernel, args):
    import importlib
    mod = importlib.import_module(
        f"kubernetes_gpu_cluster_tpu.ops.pallas.{kernel_module}")

    def boom(*a, **k):
        raise NameError("name 'NBUF' is not defined")
    monkeypatch.setattr(mod, kernel, boom)
    kernels = attention.Kernels(use_pallas=True, use_pallas_hist=True)
    with pytest.raises(NameError, match="NBUF"):
        getattr(kernels, operation)(*args)


# What each of the five operations reaches, K|V pool / latent pool, for
# (kernels on, history kernel eligible, tp mesh) as the engine combines them
# (LLMEngine._resolve_use_pallas). "xla": the reference; "kernel": the Pallas
# kernel called directly; "tp": its shard_map wrapper; "shared"/"latent": the
# one-pool kernels, which no wrapper shards (a latent row is one shared head).
# Verify attention has no kernel. Fresh prompt tokens are met in the
# materialised form, so their operation never sees a latent pool.
_ALL_XLA = dict(prefill="xla", chunk="xla xla", decode="xla xla",
                verify="xla", write="xla xla")
_SELECTION = {
    (True, True, False): dict(prefill="kernel", chunk="kernel shared",
                              decode="kernel latent", verify="xla",
                              write="kernel kernel"),
    (True, False, False): dict(prefill="kernel", chunk="xla xla",
                               decode="kernel latent", verify="xla",
                               write="kernel kernel"),
    (True, True, True): dict(prefill="tp", chunk="tp shared",
                             decode="tp latent", verify="xla",
                             write="tp kernel"),
    (True, False, True): dict(prefill="tp", chunk="xla xla",
                              decode="tp latent", verify="xla",
                              write="tp kernel"),
}


@pytest.mark.parametrize("latent", [False, True], ids=["k|v", "latent"])
@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "tp-mesh"])
@pytest.mark.parametrize("eligible", [True, False],
                         ids=["hist-eligible", "hist-ineligible"])
@pytest.mark.parametrize("on", [True, False], ids=["kernels", "xla"])
def test_kernel_selection_table(monkeypatch, on, eligible, mesh, latent):
    """The ONE place that chooses reference, kernel or per-shard kernel
    (ops.attention.Kernels), with every implementation replaced by a
    sentinel: nothing compiles. With the kernels off every operation is the
    reference whatever else holds."""
    import importlib
    for name, tag in (("ragged_prefill_attention_xla", "xla"),
                      ("prefill_history_attention_xla", "xla"),
                      ("paged_decode_attention_xla", "xla"),
                      ("spec_verify_attention_xla", "xla"),
                      ("write_kv_pages_all_xla", "xla"),
                      ("ragged_prefill_attention_tp", "tp"),
                      ("prefill_history_attention_tp", "tp"),
                      ("paged_decode_attention_tp", "tp"),
                      ("write_kv_pages_all_tp", "tp")):
        monkeypatch.setattr(attention, name, lambda *a, _t=tag, **k: _t)
    for module, name, tag in (
            ("flash_prefill", "flash_ragged_prefill", "kernel"),
            ("flash_prefill_hist", "flash_prefill_history", "kernel"),
            ("flash_prefill_hist", "flash_prefill_history_shared", "shared"),
            ("paged_decode", "pallas_paged_decode", "kernel"),
            ("latent_decode", "latent_paged_decode", "latent"),
            ("kv_write", "kv_write", "kernel")):
        monkeypatch.setattr(
            importlib.import_module(
                f"kubernetes_gpu_cluster_tpu.ops.pallas.{module}"),
            name, lambda *a, _t=tag, **k: _t)
    kernels = attention.Kernels(
        use_pallas=on, use_pallas_hist=on and eligible,
        tp_mesh="the mesh" if on and mesh else None)
    v = None if latent else _X      # the V pool, the V rows
    got = dict(
        prefill=kernels.prefill_attention(_X, _X, _X, _X, _X, _X),
        chunk=kernels.chunk_attention(_X, _X, v, _X, _X, _X, v, _X, _X, _X),
        decode=kernels.decode_attention(_X, _X, v, _X, _X, _X, v, _X),
        verify=kernels.verify_attention(_X, _X, _X, _X, _X, _X, _X, _X),
        write=kernels.write_pages(_X, v, _X, v, _X))
    want = _SELECTION[(on, eligible, mesh)] if on else _ALL_XLA
    assert got == {op: impl.split()[-1 if latent else 0]
                   for op, impl in want.items()}
    assert kernels.int4_pallas is (None if on else False)
    # sp: ring attention replaces the fresh-prompt operation alone.
    ring = attention.Kernels(use_pallas=on, ring_prefill=lambda *a: "ring")
    assert ring.prefill_attention(_X, _X, _X, _X, _X, _X) == "ring"
    assert ring.xla_only() == attention.Kernels(
        ring_prefill=ring.ring_prefill)
