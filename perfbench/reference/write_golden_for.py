"""Goldens from a configuration's plain reference, whichever module holds it.

    python3 -m perfbench.reference.write_golden_for write --config <config> --reference <module> [--degrade int8|bf16|state-bf16] [--out FILE]
    python3 -m perfbench.reference.write_golden_for gap --workload <cell> --golden FILE
    python3 -m perfbench.reference.write_golden_for diff --golden A --other B

``write_golden.py`` beside this file names kimi-vl-a3b's reference in its
``write`` command; this one takes the module's name under
``perfbench.reference`` (``granite_4_0_h``) and is otherwise the same
writer: the probes, one reference pass a position, the int8 degradation and
the ``gap``/``diff`` commands are that file's own, imported, not copied.
The configuration's seed-0 weights are built exactly as the engine builds
them (``init_params(cfg, key(0))``); the prompts are
``correctness.default_prompts`` unless ``--prompt-seeds`` says otherwise.

``--degrade int8`` rounds every matmul weight to int8 per output channel
(vectors, the conv and the embedding stay); ``--degrade bf16`` runs the
reference's matmuls at the device's default precision; ``--degrade
state-bf16`` (a reference that takes ``state_dtype``) rounds the recurrent
state to bfloat16 after every token: the readings a tolerance is set
against, never goldens of their own.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.reference.write_golden import (  # noqa: E402
    cmd_diff, cmd_gap, degrade_int8, model_config, probe_prompts,
    reference_probe)
from perfbench.spec import Benchmark  # noqa: E402


def degrade_matmuls_int8(params):
    """``degrade_int8`` over the matmul weights alone: it takes a stacked
    tree whose every tensor of two dimensions and more is a matrix a layer,
    so the vectors a layer and the conv are held out and put back."""
    import jax

    def is_matmul(path, a):
        return a.ndim >= 3 and path[-1].key.startswith("w")

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    held = {i for i, (path, a) in enumerate(flat) if not is_matmul(path, a)}
    degraded = jax.tree.leaves(degrade_int8(jax.tree_util.tree_unflatten(
        treedef, [a if i not in held else a.reshape(-1)[:1]
                  for i, (_, a) in enumerate(flat)])))
    return jax.tree_util.tree_unflatten(
        treedef, [a if i in held else degraded[i]
                  for i, (_, a) in enumerate(flat)])


def cmd_write(args) -> int:
    import jax
    import jax.numpy as jnp

    import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
    from kubernetes_gpu_cluster_tpu.models.llama import init_params
    ref = importlib.import_module(f"perfbench.reference.{args.reference}")
    bench = Benchmark(Path(args.root)) if args.root else Benchmark()
    config = bench.config(args.config)
    cfg = model_config(config)
    dev = jax.devices()[0]
    t0 = time.monotonic()
    params = jax.block_until_ready(init_params(cfg, jax.random.key(0)))
    if args.degrade == "int8":
        params = jax.block_until_ready(degrade_matmuls_int8(params))
    if args.degrade == "state-bf16":
        whole = ref.forward
        ref = SimpleNamespace(forward=lambda p, c, t, prec: whole(
            p, c, t, prec, state_dtype=jnp.bfloat16))
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
                 f"the float32 reference (perfbench/reference/"
                 f"{args.reference}.py, matmul precision highest) over the "
                 "engine's seed-0 weights at the published widths, one "
                 "whole forward pass a position; NOT captured from the "
                 "server" + (f"; degraded: {args.degrade}"
                             if args.degrade != "none" else ""),
        "prompt_seeds": args.prompt_seeds,
        "captured_on": {"platform": dev.platform,
                        "device_kind": dev.device_kind,
                        "device_count": jax.device_count()},
        "tolerance_logprob": args.tolerance,
        "tolerance_reason": args.reason,
        "probes": probes}
    out = Path(args.out) if args.out else (
        Path("chiprun_out") / f"{args.config}.golden.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(golden, indent=1))
    print(f"[golden] wrote {out}", file=sys.stderr, flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="perfbench.reference.write_golden_for")
    sub = p.add_subparsers(dest="cmd", required=True)
    w = sub.add_parser("write")
    w.add_argument("--config", required=True)
    w.add_argument("--reference", required=True,
                   help="module under perfbench.reference with forward()")
    w.add_argument("--degrade", default="none",
                   choices=("none", "int8", "bf16", "state-bf16"))
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
