#!/usr/bin/env python3
"""Hold a refactor of the step programs to the parent commit: dump the jaxpr
of every step program an engine builds, as a staged load really calls it, and
compare two dumps. CPU only; nothing here measures time.

    git archive <parent> | tar -x -C /root/scratch/parent
    PYTHONHASHSEED=0 PYTHONPATH=/root/scratch/parent \\
        python scripts/step_program_jaxprs.py dump /root/scratch/parent.json
    PYTHONHASHSEED=0 PYTHONPATH=$PWD \\
        python scripts/step_program_jaxprs.py dump /root/scratch/change.json
    python scripts/step_program_jaxprs.py compare parent.json change.json

``dump`` builds the tiny presets (dense, int4, latent + experts, the two
state kinds; meshless, tp=2, pp=2, sp=2, tp x ep; three of them again on a
server's top prefill buckets, 1024 and 2048) with the XLA references as the
CPU engine resolves them, runs a staged load (packed and chunked prompts,
mixed steps, greedy and sampled windows, speculation) and records each
program at each shape it met.
Then it traces the same shapes through an engine told ``use_pallas=True`` at a
width whose kernels trace (what a chip would trace; never run). It also
records every request's tokens and logprobs.

``compare`` sorts each program into: text-equal; graph-equal (the same
dataflow graph: only the order in which independent equations were traced,
or dead code, differs); or neither, and then prints the equations (names
dropped) that one side has and the other has not.
"""
import collections
import hashlib
import json
import os
import re
import sys

_ADDR = re.compile(r" at 0x[0-9a-f]+")
PROGRAMS = {"_prefill_fn": (), "_prefill_hist_fn": (), "_mixed_fn": (),
            "_decode_fn": (), "_decode_fn_greedy": (), "_spec_verify_fn": (),
            "_spec_mixed_fn": (2,)}     # attribute -> static argnums
CASES = [
    ("tiny", dict(model="debug-tiny")),
    ("tiny-spec", dict(model="debug-tiny", spec=True)),
    ("tiny-nomix", dict(model="debug-tiny", mixed=False)),
    ("tiny-int4", dict(model="debug-tiny", quant="int4")),
    ("tiny-int4-nomix", dict(model="debug-tiny", quant="int4", mixed=False)),
    ("mla-moe", dict(model="debug-mla-moe")),
    ("mla-moe-nomix", dict(model="debug-mla-moe", mixed=False)),
    ("tiny-tp2", dict(model="debug-tiny", mesh=dict(tp=2), spec=True)),
    ("tiny-pp2", dict(model="debug-tiny", mesh=dict(pp=2))),
    ("tiny-sp2", dict(model="debug-tiny", mesh=dict(sp=2))),
    ("moe-tp2ep2", dict(model="debug-moe", mesh=dict(tp=2, ep=2))),
    ("ssm-hybrid", dict(model="debug-ssm-hybrid")),
    ("kda-hybrid", dict(model="debug-kda-hybrid")),
    # a server's top buckets: prompts of 1100 and 1700 tokens ride mixed steps
    ("mla-moe-2k", dict(model="debug-mla-moe", long=True)),
    ("ssm-hybrid-2k", dict(model="debug-ssm-hybrid", long=True)),
    ("kda-hybrid-2k", dict(model="debug-kda-hybrid", long=True)),
]


# -- dump -------------------------------------------------------------------

def _h(*parts):
    return hashlib.sha1("\x1f".join(map(str, parts)).encode()).hexdigest()[:16]


def _param(v):
    """An equation's parameter; sub-jaxprs by their own graph hash."""
    from jax._src import core
    if isinstance(v, core.ClosedJaxpr):
        return "closed:" + graph_hash(v.jaxpr, [
            _h("const", getattr(c, "shape", None), getattr(c, "dtype", None))
            for c in v.consts])
    if isinstance(v, core.Jaxpr):
        return "jaxpr:" + graph_hash(
            v, [_h("constvar", i) for i in range(len(v.constvars))])
    if isinstance(v, (set, frozenset)):
        return "{" + ",".join(sorted(_param(x) for x in v)) + "}"
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_param(x) for x in v) + ")"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{_param(x)}" for k, x in sorted(
            v.items(), key=lambda kv: str(kv[0]))) + "}"
    return _ADDR.sub("", repr(v))


def graph_hash(jaxpr, const_hashes):
    """A hash of the jaxpr as a DATAFLOW GRAPH: a value is named by the
    primitive that made it, that primitive's parameters and its operands'
    names, so jaxprs that differ only in the order independent equations were
    traced in hash alike. Equations no output depends on do not enter."""
    from jax._src import core
    env = dict(zip(jaxpr.constvars, const_hashes))
    for i, v in enumerate(jaxpr.invars):
        env[v] = _h("in", i, v.aval)

    def read(a):
        return (_h("lit", a.val, a.aval) if isinstance(a, core.Literal)
                else env[a])
    for eqn in jaxpr.eqns:
        ins = ",".join(read(x) for x in eqn.invars)
        params = _param({k: v for k, v in eqn.params.items()
                         if k not in ("name", "debug_info")})
        for i, o in enumerate(eqn.outvars):
            env[o] = _h(eqn.primitive.name, params, ins, i, o.aval)
    return _h(*[read(o) for o in jaxpr.outvars])


def text_of(fn, static, args):
    import jax
    jaxpr = jax.make_jaxpr(fn, static_argnums=static)(*args)
    gh = graph_hash(jaxpr.jaxpr, [
        _h("const", getattr(c, "shape", None), getattr(c, "dtype", None))
        for c in jaxpr.consts])
    return "GRAPH " + gh + "\n" + _ADDR.sub("", str(jaxpr))


def _struct(x):
    import jax
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def _engine(model, mesh=None, spec=False, use_pallas=None, mixed=True,
            wide=False, quant=None, long=False):
    import dataclasses

    from kubernetes_gpu_cluster_tpu.config import (
        CacheConfig, EngineConfig, SchedulerConfig, get_model_config)
    from kubernetes_gpu_cluster_tpu.engine import LLMEngine
    from kubernetes_gpu_cluster_tpu.parallel import make_mesh
    m = get_model_config(model)
    if wide and m.is_mla:       # grouped_matmul wants whole 128-lane tiles
        m = dataclasses.replace(m, moe_intermediate_size=128)
    elif wide:                  # kd = 256: 128 lanes a shard under tp=2
        m = dataclasses.replace(m, num_heads=8, num_kv_heads=4, head_dim=64)
        if m.mamba_n_heads:     # ssm_update, ssm_chunk: granite's state
            m = dataclasses.replace(m, mamba_n_heads=64, mamba_d_head=64,
                                    mamba_d_state=128, mamba_chunk_size=256)
    if quant:
        m = dataclasses.replace(m, quantization=quant)
    budget, pages = (2048, 513) if long else (32, 129)
    cfg = EngineConfig(
        model=m, max_model_len=2048 if long else None,
        cache=CacheConfig(page_size=8, num_pages=pages),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens=budget,
            decode_buckets=(1, 2, 4), prefill_buckets=(budget // 2, budget),
            decode_window=2, mixed_batch_enabled=mixed,
            spec_decode_enabled=spec, num_speculative_tokens=3))
    return LLMEngine(cfg, mesh=make_mesh(**mesh) if mesh else None,
                     use_pallas=use_pallas)


def _run_wave(eng, tag, long=False):
    """Staggered arrivals: sub-bucket, bucket-edge and chunked prompts,
    repetitive ones (n-gram drafts hit), greedy and seeded sampled rows.
    ``long``: prompts of 1100 and 1700 tokens beside decoding rows."""
    import numpy as np

    from kubernetes_gpu_cluster_tpu.engine import SamplingParams
    rng = np.random.default_rng(1)
    pattern = rng.integers(1, 200, 4).tolist()
    prompts = [pattern * 4, rng.integers(1, 200, 12).tolist(), pattern * 7,
               rng.integers(1, 200, 90).tolist(), pattern * 2,
               (pattern * 20)[:70], rng.integers(1, 200, 30).tolist()]
    if long:
        prompts = [prompts[1], rng.integers(1, 200, 1100).tolist(),
                   prompts[6], rng.integers(1, 200, 1700).tolist()]
    pending = [(f"{tag}-{i}", list(p),
                SamplingParams(max_tokens=8, temperature=0.0) if i % 3 != 1
                else SamplingParams(max_tokens=8, temperature=0.8, top_k=5,
                                    seed=3, presence_penalty=0.1))
               for i, p in enumerate(prompts)]
    final = {}
    while pending or eng.has_unfinished_requests():
        if pending:
            eng.add_request(*pending.pop(0))
        for _ in range(2):
            if eng.has_unfinished_requests():
                for o in eng.step():
                    final[o.request_id] = (
                        list(o.output_token_ids),
                        [float(x).hex() for x in (o.output_logprobs or [])])
    return final


def dump(out_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    import jax

    import kubernetes_gpu_cluster_tpu
    print("tree:", kubernetes_gpu_cluster_tpu.__file__)
    out = {}
    for tag, kw in CASES:
        eng, shapes = _engine(**kw), {}
        for attr, static in PROGRAMS.items():
            fn = getattr(eng, attr, None)
            if fn is None:
                continue

            def wrapper(*args, _fn=fn, _attr=attr, _static=static):
                sargs = tuple(a if i in _static else jax.tree.map(_struct, a)
                              for i, a in enumerate(args))
                sig = ",".join(
                    f"{l.dtype}{list(l.shape)}" if hasattr(l, "shape")
                    else repr(l) for l in jax.tree.leaves(sargs[2:])[-12:])
                key = f"{tag}:{_attr}:{sig}"
                if key not in out:
                    out[key] = text_of(_fn, _static, sargs)
                    shapes[key] = (_attr, _static, sargs)
                return _fn(*args)
            if hasattr(fn, "_cache_size"):
                wrapper._cache_size = fn._cache_size
            setattr(eng, attr, wrapper)
        out[f"{tag}:OUTPUTS"] = json.dumps(
            _run_wave(eng, tag, kw.get("long", False)), sort_keys=True)
        print(tag, dict(eng.obs.step_kind_counts))
        eng_k = _engine(use_pallas=True, wide=True, **kw)
        for key, (attr, static, sargs) in shapes.items():
            sargs = (jax.tree.map(_struct, eng_k.params),
                     jax.tree.map(_struct, eng_k.kv_cache)) + tuple(sargs[2:])
            # What the chip would trace: a tree that asks the backend
            # anywhere below the engine is told "tpu".
            real, jax.default_backend = jax.default_backend, lambda: "tpu"
            try:
                text = text_of(getattr(eng_k, attr), static, sargs)
            except Exception as e:      # noqa: BLE001
                text = f"TRACE ERROR {type(e).__name__}: {str(e)[:300]}"
            finally:
                jax.default_backend = real
            out[key.replace(tag + ":", tag + "-kernels:", 1)] = text
    with open(out_path, "w") as f:
        json.dump(out, f, indent=0, sort_keys=True)
    print(len(out), "entries written to", out_path)


# -- compare ----------------------------------------------------------------

_DEF = re.compile(r"\b[a-z]{1,4}:(?=[a-z]+\d*\[|key<|Ref|MemRef)")
_BARE = re.compile(r"(?<![\w=\[\.'\"])\b[a-z]{1,4}\b(?![\w=\[\(:'\"])")
_ANY = re.compile(r"\b[a-z]{1,4}\b")


def _anon(line):
    """One printed line of a jaxpr with its variable names dropped."""
    line = _DEF.sub("_:", line.strip())
    if "dma_" in line or " -> " in line or "semaphore_" in line:
        return re.sub(r"\b(?!dma|p\d)[a-z]{1,4}\b", "_", line)
    if " <- " in line:          # a kernel body's ref load or store
        return _ANY.sub("_", line)
    if " = " in line:
        lhs, rhs = line.split(" = ", 1)
        m = re.match(r"([\w\.]+)(.*)", rhs, re.S)
        if m:
            return lhs + " = " + m.group(1) + _BARE.sub("_", m.group(2))
    return _BARE.sub("_", line)


def _bag(text):
    return collections.Counter(
        _anon(l) for l in text.split("\n")[1:] if l.strip())


def _pair_renamed(a, b):
    """A program whose ARGUMENTS changed is recorded under another key (the
    key holds its last arguments' shapes). Pair what only one side has, by
    case and program, each with the entry of the other side whose equations
    are nearest to its own."""
    groups = collections.defaultdict(lambda: ([], []))
    for side, (mine, other) in enumerate(((a, b), (b, a))):
        for k in sorted(set(mine) - set(other)):
            groups[tuple(k.split(":")[:2])][side].append(k)
    pairs, lone = [], []
    for (case, prog), (ka, kb) in sorted(groups.items()):
        bags = {k: _bag(b[k]) for k in kb}
        for k in ka:
            if not bags:
                lone.append(k)
                continue
            mine = _bag(a[k])
            near = min(bags, key=lambda o: sum(
                ((mine - bags[o]) + (bags[o] - mine)).values()))
            del bags[near]
            pairs.append((k, near))
        lone += sorted(bags)
    return pairs, lone


def compare(path_a, path_b):
    a, b = json.load(open(path_a)), json.load(open(path_b))
    renamed, lone = _pair_renamed(a, b)
    print("entries:", len(a), len(b), "under another key (arguments "
          "changed):", len(renamed), "only in one:", lone)
    tiers = collections.defaultdict(collections.Counter)
    shown = {}
    for k, kb in [(k, k) for k in sorted(set(a) & set(b))] + renamed:
        case, prog = k.split(":")[:2]
        ta, tb = a[k], b[kb]
        if ta == tb:
            tiers[case][prog, "text-equal"] += 1
        elif ta.startswith("GRAPH") and ta.split("\n")[0] == tb.split("\n")[0]:
            tiers[case][prog, "graph-equal"] += 1
        else:
            tiers[case][prog, "DIFFERS"] += 1
            ba, bb = _bag(ta), _bag(tb)
            lines = ([f"     - x{n} {l[:230]}" for l, n in sorted((ba - bb).items())]
                     + [f"     + x{n} {l[:230]}" for l, n in sorted((bb - ba).items())])
            sig = "\n".join(lines)
            print(f"DIFFERS {k[:100]}"
                  + (f"\n   ~ {kb[:100]}" if kb != k else ""))
            print(f"     (the lines of {shown[sig]})" if sig in shown else sig)
            shown.setdefault(sig, k[:60])
    for case in sorted(tiers):
        print(case, "|", ", ".join(f"{p} {t} x{n}" for (p, t), n
                                   in sorted(tiers[case].items())))


if __name__ == "__main__":
    {"dump": dump, "compare": compare}[sys.argv[1]](*sys.argv[2:])
