"""Goldens from the plain reference, not from the server.

    python3 -m perfbench.reference.write_golden write --config <config> [--degrade int8] [--out FILE]
    python3 -m perfbench.reference.write_golden gap --workload <cell> --golden FILE
    python3 -m perfbench.reference.write_golden diff --golden A --other B

``write`` builds the configuration's seed-0 weights exactly as the engine
does (``init_params(cfg, key(0))``; on the chip the draw is bitwise the
server's, on the host, where the committed golden was made in true float32,
the two goldens agreed to 0.002 nat up to the first near tie: PERF.md
section 2), runs the float32 reference
(``kimi_vl_a3b_lm.py`` beside this file) over the configuration's probe
prompts and its own 8 greedy continuations, one whole forward pass a
position, at the published widths, and writes ids, log-probabilities and
top-5 in the format ``correctness.compare`` reads. The prompts are
``correctness.default_prompts`` or, with ``--prompt-seeds A B``, random
prompts of the same lengths drawn from ``random.Random(A)`` and
``random.Random(B)``: a golden names its seeds and says why they were
chosen (PERF.md section 2: top-6-of-64 routers over random weights are
near ties almost everywhere, and a probe is only a yardstick where the
rows it compares are not). ``--degrade int8`` rounds every matmul weight
to int8 per output channel first: the reference "in the nearest precision
below", the reading a tolerance has to refuse. ``--degrade bf16`` leaves the
weights and runs the reference's matmuls at the device's default precision
(bf16 passes on a TPU) instead of "highest": the noise floor of the
precision the configuration itself states.

``gap`` starts the cell's server and prints the largest log-probability gap
of its probes to a golden file: served alone (twice) and beside 24 greedy
streams. ``diff`` prints the same gap between two golden files.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import correctness  # noqa: E402
from perfbench.spec import Benchmark  # noqa: E402

TOP_N = correctness.TOP_N


def model_config(config: dict):
    """The ModelConfig the server builds from this configuration file: its
    preset under its ``--hf-overrides``."""
    from kubernetes_gpu_cluster_tpu.config import (apply_hf_overrides,
                                                   get_model_config)
    cfg = get_model_config(config["preset"])
    flags = config["server_flags"]
    if "--hf-overrides" in flags:
        cfg = apply_hf_overrides(
            cfg, json.loads(flags[flags.index("--hf-overrides") + 1]))
    return cfg


def degrade_int8(params):
    """Every matmul weight rounded to int8 per output channel and back, a
    layer at a time into the donated tensor (the whole model in float32
    would not fit beside itself)."""
    import jax
    import jax.numpy as jnp

    def fake_quant(w):
        wf = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    put = jax.jit(lambda buf, l: jax.lax.dynamic_update_index_in_dim(
        buf, fake_quant(jax.lax.dynamic_index_in_dim(buf, l, 0, False)),
        l, 0), donate_argnums=0)

    def leaf(path, a):
        name = path[-1].key
        if a.ndim < 2 or name in ("router_bias", "embed") \
                or name.endswith("norm"):
            return a
        if name == "lm_head":
            return jax.jit(fake_quant, donate_argnums=0)(a)
        for l in range(a.shape[0]):
            a = put(a, l)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


def probe_prompts(vocab: int, max_len: int, seeds=None) -> list:
    """The probes' prompts: ``correctness.default_prompts``, or one prompt
    of each of its lengths drawn from ``random.Random(seed)``."""
    if not seeds:
        return correctness.default_prompts(vocab, max_len)
    import random
    if len(seeds) != len(correctness.PROBE_LENGTHS):
        raise SystemExit(f"--prompt-seeds takes one seed a probe length "
                         f"{correctness.PROBE_LENGTHS}")
    out = []
    for seed, n in zip(seeds, correctness.PROBE_LENGTHS):
        r = random.Random(seed)
        out.append([r.randrange(3, vocab) for _ in range(min(n, max_len - 16))])
    return out


def positions_compared(golden_probe: dict, served: dict) -> int:
    """How many of a probe's positions ``correctness.compare`` looks at: up
    to and including the first one whose served top-1 leaves the golden."""
    n = 0
    for g_id, s_id in zip(golden_probe["tokens"], served["tokens"]):
        n += 1
        if s_id != g_id:
            break
    return n


def reference_probe(ref, params, cfg, prompt: list,
                    precision: str = "highest") -> dict:
    import jax
    import jax.numpy as jnp
    tokens, out = list(prompt), {"tokens": [], "logprobs": [], "top": []}
    for _ in range(correctness.PROBE_TOKENS):
        logits = ref.forward(params, cfg, tokens, precision)[-1]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        vals, ids = jax.lax.top_k(lp, TOP_N)
        ids, vals = [int(i) for i in ids], [float(v) for v in vals]
        out["tokens"].append(ids[0])
        out["logprobs"].append(vals[0])
        out["top"].append({str(i): v for i, v in zip(ids, vals)})
        tokens.append(ids[0])
    return out


def cmd_write(args) -> int:
    import jax

    import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
    from kubernetes_gpu_cluster_tpu.models.llama import init_params
    from perfbench.reference import kimi_vl_a3b_lm as ref
    bench = Benchmark(Path(args.root)) if args.root else Benchmark()
    config = bench.config(args.config)
    cfg = model_config(config)
    dev = jax.devices()[0]
    t0 = time.monotonic()
    params = jax.block_until_ready(init_params(cfg, jax.random.key(0)))
    if args.degrade == "int8":
        params = jax.block_until_ready(degrade_int8(params))
    print(f"[golden] weights on {dev.platform} after "
          f"{time.monotonic() - t0:.0f}s", file=sys.stderr, flush=True)
    prompts = probe_prompts(config["vocab_size"],
                            int(config["max_position_embeddings"]),
                            args.prompt_seeds)
    probes = []
    for p in prompts:
        probes.append({"prompt": p, **reference_probe(
            ref, params, cfg, p,
            "default" if args.degrade == "bf16" else "highest")})
        print(f"[golden] probe of {len(p)} tokens done at "
              f"{time.monotonic() - t0:.0f}s: {probes[-1]['tokens']}",
              file=sys.stderr, flush=True)
    golden = {
        "about": "greedy, top-5, first 8 positions of two probe prompts: "
                 "the float32 reference (perfbench/reference/"
                 "kimi_vl_a3b_lm.py, matmul precision highest) over the "
                 "engine's seed-0 weights at the published widths, one "
                 "whole forward pass a position; NOT captured from the "
                 "server" + (f"; weights degraded to {args.degrade}"
                             if args.degrade != "none" else ""),
        "prompt_seeds": args.prompt_seeds,
        "captured_on": {"platform": dev.platform,
                        "device_kind": dev.device_kind,
                        "device_count": jax.device_count()},
        "tolerance_logprob": args.tolerance,
        "tolerance_reason": args.reason,
        "probes": probes}
    for out in ([Path(args.out)] if args.out else
                [Path("chiprun_out") / f"{args.config}.golden.json"]):
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(golden, indent=1))
        print(f"[golden] wrote {out}", file=sys.stderr, flush=True)
    return 0


def _gaps(golden: dict, served: list) -> list:
    return [correctness.max_logprob_gap(g, s)
            for g, s in zip(golden["probes"], served)]


def cmd_diff(args) -> int:
    a = json.loads(Path(args.golden).read_text())
    b = json.loads(Path(args.other).read_text())
    print(json.dumps({
        "max_logprob_gap": _gaps(a, b["probes"]),
        "positions_compared": [positions_compared(x, y) for x, y in
                               zip(a["probes"], b["probes"])],
        "problems_if_other_were_served": correctness.compare(
            a, [dict(p, usage_ok=True) for p in b["probes"]])}))
    return 0


def cmd_gap(args) -> int:
    import random

    from perfbench import cli, harness
    cell = Benchmark().cell(args.workload)
    golden = json.loads(Path(args.golden).read_text())
    args.cpu_rehearsal = False
    server, health, _ = cli._start(cell, args, f"gap-{cell.config_name}")

    async def go():
        vocab = cell.config["vocab_size"]
        async with harness.LoadClient(server.base, health["model"]) as c:
            a = await correctness.run_probes(c, golden)
            b = await correctness.run_probes(c, golden)
            load = [harness._ladder_request(
                random.Random(i), vocab, 200, 400, {"temperature": 0.0}, i)
                for i in range(24)]
            tasks = [c.spawn(c.stream(q, "ladder", time.perf_counter()))
                     for q in load]
            await harness._until_decoding(c, tasks, 16)
            busy = await correctness.run_probes(c, golden)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            return a, b, busy
    try:
        a, b, busy = asyncio.run(go())
        server.stop()
    finally:
        server.kill()
    print(json.dumps({
        "served_alone_gap": _gaps(golden, a),
        "served_alone_again_gap": _gaps(golden, b),
        "served_beside_24_streams_gap": _gaps(golden, busy),
        "positions_compared_alone": [
            positions_compared(g, s) for g, s in zip(golden["probes"], a)],
        "positions_compared_beside": [
            positions_compared(g, s) for g, s in zip(golden["probes"], busy)],
        "problems": correctness.compare(golden, a),
        "weight_bytes": health.get("weight_bytes"),
        "num_pages": health.get("num_pages")}))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="perfbench.reference.write_golden")
    sub = p.add_subparsers(dest="cmd", required=True)
    w = sub.add_parser("write")
    w.add_argument("--config", required=True)
    w.add_argument("--degrade", choices=("none", "int8", "bf16"),
                   default="none")
    w.add_argument("--out", default=None)
    w.add_argument("--root", default=None,
                   help="directory holding another BENCHMARK.json (tests)")
    w.add_argument("--prompt-seeds", nargs="+", default=None)
    w.add_argument("--tolerance", type=float, default=0.1)
    w.add_argument("--reason", default="see PERF.md section 2 (correct)")
    g = sub.add_parser("gap")
    g.add_argument("--workload", required=True)
    g.add_argument("--golden", required=True)
    d = sub.add_parser("diff")
    d.add_argument("--golden", required=True)
    d.add_argument("--other", required=True)
    args = p.parse_args(argv)
    return {"write": cmd_write, "gap": cmd_gap, "diff": cmd_diff}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
