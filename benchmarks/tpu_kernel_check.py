"""Compile + numerics check for the Pallas kernels ON THE REAL TPU CHIP.

Interpret-mode tests cannot catch Mosaic compile errors (VMEM budgets, the
(8, 128) tiling) — this script is the quick on-chip gate for ONE kernel at a
time, at the geometry the server really dispatches: the model's per-shard
heads, the TPU page size, the top decode bucket and the top prefill bucket.
The engine's construction-time probe (LLMEngine._probe_pallas_compile)
compiles the same shapes; this adds the numerics against the XLA references
and the in-place check and cost of the KV pool write.

    python benchmarks/tpu_kernel_check.py [--model qwen3-4b] [--tp 1]
                                          [--kernels decode,prefill,...]

``--tp N`` checks the per-shard geometry a tp=N mesh hands each chip (heads
divided by N) on ONE chip, before chip time is spent on N. ``--kernels``
picks checks by name; the default is what the default server runs (the
opt-in int4 matmul kernel is ``int4``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_gpu_cluster_tpu.config import SchedulerConfig, get_model_config
from kubernetes_gpu_cluster_tpu.ops.attention import (
    paged_decode_attention_xla, prefill_history_attention_xla,
    ragged_prefill_attention_xla, write_kv_pages_all)
from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill import flash_ragged_prefill
from kubernetes_gpu_cluster_tpu.ops.pallas.flash_prefill_hist import (
    flash_prefill_history)
from kubernetes_gpu_cluster_tpu.ops.pallas.paged_decode import pallas_paged_decode
from kubernetes_gpu_cluster_tpu.utils import cdiv
from kubernetes_gpu_cluster_tpu.utils.compile_cache import (
    configure_compile_cache)

PS = 128            # the engine's TPU page size
TOL = 0.06          # bf16 outputs of O(1) magnitude


def _err(out, ref, mask=None) -> float:
    d = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
    return float(jnp.max(d[mask] if mask is not None else d))


def check_decode(nh, n_kv, hd, pps, B) -> None:
    P = 1 + B * 6
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.bfloat16)
    k_pool = jnp.asarray(rng.standard_normal((P, PS, n_kv * hd)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((P, PS, n_kv * hd)), jnp.bfloat16)
    # Distinct pages per sequence, padding entries -> scrap page 0.
    tables = np.zeros((B, pps), np.int32)
    ctx = rng.integers(2, 6 * PS, B).astype(np.int32)
    ctx[0] = 1  # empty-pool path: n_chunks == 0, no DMA ever starts
    next_page = 1
    for b in range(B):
        for j in range(cdiv(int(ctx[b]) - 1, PS)):
            tables[b, j] = next_page
            next_page += 1
    assert next_page <= P, f"pool too small: need {next_page} pages"
    tables, ctx = jnp.asarray(tables), jnp.asarray(ctx)
    k_cur = jnp.asarray(rng.standard_normal((B, n_kv, hd)), jnp.bfloat16)
    v_cur = jnp.asarray(rng.standard_normal((B, n_kv, hd)), jnp.bfloat16)
    scale = hd ** -0.5

    ref = paged_decode_attention_xla(q, k_pool, v_pool, tables, ctx,
                                     k_cur, v_cur, scale)
    out = jax.jit(lambda *a: pallas_paged_decode(*a, scale))(
        q, k_pool, v_pool, tables, ctx, k_cur, v_cur)
    err = _err(out, ref)
    print(f"paged_decode B={B} pps={pps}: max|pallas-xla| = {err:.4f}")
    assert err < TOL, err


def check_prefill(nh, n_kv, hd, T) -> None:
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((T, n_kv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((T, n_kv, hd)), jnp.bfloat16)
    # Three ragged segments + trailing padding.
    lens = [T * 3 // 8, T * 3 // 8, T * 3 // 16]
    pad = T - sum(lens)
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens)]
                         + [np.full(pad, -1)]).astype(np.int32)
    pos = np.concatenate([np.arange(n) for n in lens]
                         + [np.zeros(pad)]).astype(np.int32)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    scale = hd ** -0.5
    ref = ragged_prefill_attention_xla(q, k, v, seg, pos, scale)
    out = jax.jit(lambda *a: flash_ragged_prefill(*a, scale))(q, k, v, seg, pos)
    err = _err(out, ref, np.asarray(seg) >= 0)
    print(f"flash_prefill T={T}: max|pallas-xla| = {err:.4f}")
    assert err < TOL, err


def check_prefill_history(nh, n_kv, hd, pps, T) -> None:
    # A full chunk over 3.5 pages of history, stacked pool + layer index.
    L = 2
    hist_len = 3 * PS + 70
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((T, n_kv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((T, n_kv, hd)), jnp.bfloat16)
    pad = 32
    seg = jnp.asarray(np.where(np.arange(T) < T - pad, 0, -1), jnp.int32)
    pos = jnp.asarray(np.where(np.arange(T) < T - pad,
                               hist_len + np.arange(T), 0), jnp.int32)
    pool_k = jnp.asarray(rng.standard_normal((L, 1 + pps, PS, n_kv * hd)),
                         jnp.bfloat16)
    pool_v = jnp.asarray(rng.standard_normal((L, 1 + pps, PS, n_kv * hd)),
                         jnp.bfloat16)
    pt = jnp.asarray(1 + np.arange(pps), jnp.int32)
    hl = jnp.asarray(hist_len, jnp.int32)
    scale = hd ** -0.5
    layer = jnp.asarray(1, jnp.int32)

    ref = prefill_history_attention_xla(q, k, v, seg, pos, pool_k, pool_v,
                                        pt, hl, scale, layer=layer)
    out = jax.jit(lambda *a: flash_prefill_history(*a, scale, layer=layer))(
        q, k, v, seg, pos, pool_k, pool_v, pt, hl)
    err = _err(out, ref, np.asarray(seg) >= 0)
    print(f"flash_prefill_hist T={T} pps={pps}: max|pallas-xla| = {err:.4f}")
    assert err < TOL, err


def check_kv_write(L, n_kv, hd, T) -> None:
    """The post-scan KV write on a donated pool, XLA loop and Pallas kernel
    side by side: neither may copy the pool (a served pool takes ~0.9 of
    free HBM, so one pool-sized temporary is an OOM at the first prefill:
    XLA's temp bytes must stay far under the pool's), the kernel must leave
    bitwise the loop's pool (sentinel rows included: the ref bitcast behind
    its 32-bit view of a 16-bit pool exists only on the chip), and what one
    T-token flush costs (host clock around block_until_ready, steady
    state). Slots as the scheduler lays them out: T <= 64 is a decode step
    (one token a page, offsets odd and even), above that packed prompts of
    200 tokens, their pages scattered."""
    P = 65 if T <= 64 else 2 + (T // 200 + 1) * 2
    kd = n_kv * hd
    rng = np.random.default_rng(5)
    pages = 1 + rng.permutation(P - 1)
    if T <= 64:
        slots = pages[:T] * PS + rng.integers(0, PS, T)
    else:
        off = 17 + np.arange(200)          # a prompt starts mid-page
        slots = np.concatenate([
            np.where(off < PS, pages[2 * s] * PS + off,
                     pages[2 * s + 1] * PS + off - PS)
            for s in range(T // 200 + 1)])[:T]
    assert len(set(slots.tolist())) == T
    slots = jnp.asarray(slots, jnp.int32)
    rows_k = jnp.asarray(rng.standard_normal((L, T, kd)), jnp.bfloat16)
    rows_v = jnp.asarray(rng.standard_normal((L, T, kd)), jnp.bfloat16)
    pool_bytes = 2 * L * P * PS * kd * 2
    pools = {}
    n = 20      # flushes chained in ONE program: a sub-millisecond program's
                # host-clock time is its dispatch, not its device time
    for name, use_pallas in (("loop", False), ("kernel", True)):
        def write(kk, vv, ka, va, sl, up=use_pallas):
            return write_kv_pages_all(kk, vv, ka, va, sl, use_pallas=up)

        def chain(kk, vv, ka, va, sl):
            return jax.lax.fori_loop(
                0, n, lambda _, kv: write(*kv, ka, va, sl), (kk, vv))
        fn = jax.jit(write, donate_argnums=(0, 1))
        fn_n = jax.jit(chain, donate_argnums=(0, 1))
        k_pool = jnp.full((L, P, PS, kd), 7.0, jnp.bfloat16)   # sentinel
        v_pool = jnp.full((L, P, PS, kd), -3.0, jnp.bfloat16)
        args = (rows_k, rows_v, slots)
        for f in (fn, fn_n):    # neither alone nor as a loop's carry
            temp = f.lower(k_pool, v_pool, *args
                           ).compile().memory_analysis().temp_size_in_bytes
            assert temp < pool_bytes // 8, (
                f"KV write ({name}) keeps {temp} temp bytes against a "
                f"{pool_bytes}-byte pool: it copies the pool")
        k_pool, v_pool = jax.block_until_ready(fn(k_pool, v_pool, *args))
        pools[name] = (np.asarray(k_pool).view(np.uint16),
                       np.asarray(v_pool).view(np.uint16))
        k_pool, v_pool = jax.block_until_ready(fn_n(k_pool, v_pool, *args))
        t0 = time.perf_counter()
        for _ in range(3):
            k_pool, v_pool = fn_n(k_pool, v_pool, *args)
        jax.block_until_ready((k_pool, v_pool))
        print(f"kv_write {name} T={T} L={L} kd={kd}: "
              f"{(time.perf_counter() - t0) / (3 * n) * 1e3:.3f} ms per flush, "
              f"temp {temp / 2**20:.1f} MiB "
              f"(k+v pool {pool_bytes / 2**20:.1f} MiB)")
    got = pools["loop"][0].reshape(L, P * PS, kd)[:, np.asarray(slots)]
    assert (got == np.asarray(rows_k).view(np.uint16)).all(), (
        "loop: rows did not land in their slots")
    for loop, kernel, which in zip(pools["loop"], pools["kernel"], "kv"):
        assert (loop == kernel).all(), (
            f"kv_write kernel's {which} pool differs from the loop's in "
            f"{int((loop != kernel).sum())} elements")
    print(f"kv_write T={T}: kernel pool == loop pool, bitwise")


def check_int4_matmul() -> None:
    """W4A16 dequant-fused matmul (ops/pallas/int4_matmul.py): packed tiles
    dequantized in VMEM vs the XLA fusion path, at an 8B-decode-like shape
    (B=64 rows, hidden 4096 -> ff 14336 column block)."""
    from kubernetes_gpu_cluster_tpu.ops.pallas.int4_matmul import (
        pallas_int4_matmul)
    from kubernetes_gpu_cluster_tpu.ops.quant import (int4_matmul_xla,
                                                      quantize_tensor_int4)

    T, K, N, gs = 64, 4096, 1024, 128
    rng = np.random.default_rng(3)
    w = rng.standard_normal((K, N)).astype(np.float32) * K ** -0.5
    x = jnp.asarray(rng.standard_normal((T, K)), jnp.bfloat16)
    packed, sc = quantize_tensor_int4(w, gs)
    packed, sc = jnp.asarray(packed), jnp.asarray(sc)
    ref = int4_matmul_xla(x, packed, sc)
    out = jax.jit(pallas_int4_matmul)(x, packed, sc)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"int4_matmul: max|pallas-xla| = {err:.4f}")
    assert err < TOL, err


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="qwen3-4b")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--kernels", default="decode,prefill,hist,kvwrite")
    args = ap.parse_args()
    configure_compile_cache()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={jax.device_count()}")
    if dev.platform != "tpu":
        sys.exit("tpu_kernel_check needs a TPU: Mosaic does not compile here")

    cfg = get_model_config(args.model)
    sc = SchedulerConfig()
    nh, n_kv, hd = cfg.num_heads // args.tp, cfg.num_kv_heads // args.tp, cfg.head_dim
    pps = cdiv(cfg.max_model_len, PS)
    B, T = sc.decode_buckets[-1], sc.prefill_buckets[-1]
    print(f"{cfg.name} tp={args.tp}: {nh}q/{n_kv}kv x {hd}, kd={n_kv * hd}, "
          f"page {PS}, pages/seq {pps}, B={B}, T={T}")
    checks = {
        "decode": lambda: check_decode(nh, n_kv, hd, pps, B),
        "prefill": lambda: check_prefill(nh, n_kv, hd, T),
        "hist": lambda: check_prefill_history(nh, n_kv, hd, pps, T),
        "kvwrite": lambda: [check_kv_write(cfg.num_layers, n_kv, hd, n)
                            for n in (B, T)],
        "int4": check_int4_matmul,
    }
    for name in args.kernels.split(","):
        checks[name]()
    print("OK")


if __name__ == "__main__":
    main()
