"""Decode-step component profile on the current backend (run on the real chip).

Methodology: a host<->device round trip is not free, so each measurement
CHAINS the op N times device-side (python-level feedback of on-device
buffers, async dispatch) and fetches ONE scalar at the end; per-iteration
time = (total - measured sync latency) / N.

Components timed at the serving bench shape (TinyLlama-1.1B, B=64):
  1. one decode substep (forward + logits), XLA vs Pallas attention
  2. weights-only pass (attention stubbed) - the HBM weight-streaming floor
  3. the attention op alone (both paths), one layer x L
  4. the KV write (write_kv_pages_all) alone: XLA loop and Pallas kernel
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_gpu_cluster_tpu.config import CacheConfig, get_model_config
from kubernetes_gpu_cluster_tpu.engine.kv_cache import allocate_kv_cache
from kubernetes_gpu_cluster_tpu.models import llama as model_lib
from kubernetes_gpu_cluster_tpu.ops import attention as attn
from kubernetes_gpu_cluster_tpu.utils.compile_cache import (
    configure_compile_cache)

B = 64
CTX = 320            # mid-stream context (prompt 128 + ~192 decoded)
PS = 16
MODEL = "tinyllama-1.1b" if jax.default_backend() == "tpu" else "debug-tiny"
CHAIN = 30


def sync(x):
    leaf = jax.tree.leaves(x)[0]
    return np.asarray(leaf.ravel()[0])


def timed_chain(fn, state, chain=CHAIN):
    """fn(state) -> state (device buffers; fn may donate its input). Chains
    ``chain`` calls, one scalar fetch at the end. Returns per-call ms with the
    host round-trip latency subtracted."""
    s = fn(state)                 # warmup / compile (may donate `state`)
    sync(s)
    t0 = time.perf_counter()
    sync(s)
    latency = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(chain):
        s = fn(s)
    sync(s)
    total = time.perf_counter() - t0
    return max(total - latency, 0.0) / chain * 1e3


def main():
    configure_compile_cache()
    cfg = get_model_config(MODEL)
    nkv, hd, nh, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads, cfg.num_layers
    pages_per_seq = cfg.max_model_len // PS
    num_pages = B * (CTX // PS + 2) + 1
    cache_cfg = CacheConfig(page_size=PS, num_pages=num_pages)

    def mk_kv():
        # Fresh pool per measurement: the substep chains DONATE the pool, so
        # a shared one would be invalidated after the first measurement.
        return allocate_kv_cache(cfg, cache_cfg, num_pages)

    kv = mk_kv()
    params = model_lib.init_params(cfg, jax.random.key(0))

    rng = np.random.default_rng(0)
    pt = np.zeros((B, pages_per_seq), np.int32)
    used = CTX // PS + 1
    perm = rng.permutation(np.arange(1, num_pages))[: B * used].reshape(B, used)
    pt[:, :used] = perm
    page_tables = jnp.asarray(pt)
    positions = jnp.full((B,), CTX - 1, jnp.int32)
    context_lens = jnp.full((B,), CTX, jnp.int32)
    slot_mapping = jnp.asarray(perm[:, (CTX - 1) // PS] * PS + (CTX - 1) % PS)
    tokens0 = jnp.asarray(rng.integers(1, cfg.vocab_size, B).astype(np.int32))
    meta = model_lib.DecodeMeta(positions=positions, slot_mapping=slot_mapping,
                                page_tables=page_tables, context_lens=context_lens)

    kv_bytes = 2 * kv.k.size * kv.k.dtype.itemsize
    par_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"model={MODEL} L={L} nh={nh} nkv={nkv} hd={hd} B={B} ctx={CTX} "
          f"pages/seq={pages_per_seq}")
    print(f"params={par_bytes/1e9:.2f} GB, kv pool={kv_bytes/1e9:.2f} GB, "
          f"backend={jax.default_backend()}")

    # --- 1+2: decode substep (greedy-sample feedback keeps it on device) ----
    # params flow through state as a jit ARGUMENT: closing over them would
    # bake 2.2 GB of weights into the program as constants — every compile
    # would then carry the model (minutes per measurement).
    def substep(use_pallas, stub=False):
        @functools.partial(jax.jit, donate_argnums=1)
        def f(prms, kvc, tokens):
            real = attn.paged_decode_attention
            if stub:   # trace-time stub; restored right after tracing
                attn.paged_decode_attention = lambda q, *a, **k: q
            try:
                hidden, kvc, _ = model_lib.forward_decode(
                    prms, cfg, tokens, meta, kvc, use_pallas=use_pallas)
            finally:
                attn.paged_decode_attention = real
            logits = model_lib.compute_logits(prms, cfg, hidden)
            return kvc, jnp.argmax(logits, -1).astype(jnp.int32)

        return lambda state: f(params, *state)   # params: argument, not donated

    print(f"substep XLA attn:      {timed_chain(substep(False), (mk_kv(), tokens0)):8.3f} ms")
    if jax.default_backend() == "tpu":
        print(f"substep Pallas attn:   {timed_chain(substep(True), (mk_kv(), tokens0)):8.3f} ms")
    print(f"substep attn-stub:     {timed_chain(substep(False, stub=True), (mk_kv(), tokens0)):8.3f} ms")

    # --- 3: attention alone, scanned over L layers --------------------------
    q1 = jnp.asarray(rng.standard_normal((B, nh, hd)), cfg.jnp_dtype)
    kc = jnp.asarray(rng.standard_normal((B, nkv, hd)), cfg.jnp_dtype)
    vc = jnp.asarray(rng.standard_normal((B, nkv, hd)), cfg.jnp_dtype)

    def attn_loop(use_pallas):
        @jax.jit
        def f(q1, k_pool, v_pool):
            def body(acc, xs):
                kp, vp = xs
                o = attn.paged_decode_attention(
                    q1, kp, vp, page_tables, context_lens, kc, vc,
                    hd ** -0.5, use_pallas=use_pallas)
                return acc + o.astype(jnp.float32), None
            acc, _ = jax.lax.scan(body, jnp.zeros((B, nh, hd), jnp.float32),
                                  (k_pool, v_pool))
            return acc.astype(cfg.jnp_dtype)
        # pool passed as argument (a closed-over pool would be baked into the
        # program as 0.5 GB of constants and re-uploaded at compile)
        def step(state):
            out = f(state[0], kv.k, kv.v)
            return (out, None)
        return step

    print(f"attn x{L} XLA:          {timed_chain(attn_loop(False), (q1, None)):8.3f} ms")
    if jax.default_backend() == "tpu":
        print(f"attn x{L} Pallas:       {timed_chain(attn_loop(True), (q1, None)):8.3f} ms")

    # --- 4: KV write alone: the XLA loop, and on the chip the DMA kernel ---
    k_all = jnp.asarray(rng.standard_normal((L, B, nkv * hd)), cfg.jnp_dtype)

    def kv_write(use_pallas):
        @functools.partial(jax.jit, donate_argnums=0)
        def write(pools):
            return attn.write_kv_pages_all(*pools, k_all, k_all, slot_mapping,
                                           use_pallas=use_pallas)
        return write

    for name, use_pallas in (("loop", False), ("kernel", True)):
        if use_pallas and jax.default_backend() != "tpu":
            continue
        kv_s = mk_kv()
        ms = timed_chain(kv_write(use_pallas), (kv_s.k, kv_s.v))
        print(f"kv write ({name}):{' ' * (13 - len(name))}{ms:8.3f} ms")


if __name__ == "__main__":
    main()
