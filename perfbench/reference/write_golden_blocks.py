"""Goldens of a model that generates by diffusion over blocks.

    python3 -m perfbench.reference.write_golden_blocks screen --config <config> --reference sdar_moe --length 600 --prompt-seeds A B C ...
    python3 -m perfbench.reference.write_golden_blocks write --config <config> --reference sdar_moe --prompt-seeds A B [--lengths 24 600] [--degrade int8|bf16] [--out FILE]
    python3 -m perfbench.reference.write_golden_blocks gap --workload <cell> --golden FILE
    python3 -m perfbench.reference.write_golden_blocks diff --golden A --other B

The three writers beside this file loop ONE next token a position
(``reference_probe``); a block model's eight probe tokens are two blocks,
each filled in whatever order the confidences give, so this writer runs the
reference's own generation (``<reference>.generate``: the published sampler
as a plain loop, every pass a whole forward of the sequence) and writes, for
each output token, the log-probability and the top-5 of the pass that
TRANSFERRED it, at its position: what the server's ``logprobs: 5`` gives.
Everything else is those writers' own, imported, not copied: the seed-0
weights as the engine builds them, the int8 degradation, ``gap`` and
``diff``.

What takes the place of the router margin here: over random weights every
confidence is ~1e-4 and the ORDER of the masked positions' confidences is a
near tie that bfloat16 crosses; after a crossing another position is
transferred and every later pass differs. ``screen`` reads, a probe, the
smallest margin over its compared passes of (a) log confidence of the most
over the second most confident masked position, (b) the transferred
position's top-1 over top-2 log-probability, (c) the routers' 8th over 9th
score at the block's positions; the probes written are chosen by it.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import correctness  # noqa: E402
from perfbench.reference.write_golden import (  # noqa: E402
    cmd_diff, cmd_gap, model_config)
from perfbench.reference.write_golden_for import (  # noqa: E402
    degrade_matmuls_int8)
from perfbench.spec import Benchmark  # noqa: E402


def prompt_of(seed: str, vocab: int, n: int) -> list:
    r = random.Random(seed)
    return [r.randrange(3, vocab) for _ in range(n)]


def margins(trace: list, out: dict) -> dict:
    """The smallest margins over a generation's denoising passes."""
    order, router = math.inf, math.inf
    for t in trace:
        conf = sorted((c for c, m in zip(t["confidence"], t["masked"]) if m),
                      reverse=True)
        if len(conf) > 1:
            order = min(order, math.log(conf[0]) - math.log(conf[1]))
        router = min(router, t["router_margin_min"])
    top2 = min(a - b for a, b, *_ in (sorted(t.values(), reverse=True)
                                      for t in out["top"]))
    return {"confidence_order_lognat": order, "top1_over_top2_lognat": top2,
            "router_8th_over_9th": router}


def _setup(args):
    import jax

    import kubernetes_gpu_cluster_tpu.engine  # noqa: F401 (before models)
    from kubernetes_gpu_cluster_tpu.models.llama import init_params
    ref = importlib.import_module(f"perfbench.reference.{args.reference}")
    bench = Benchmark(Path(args.root)) if args.root else Benchmark()
    config = bench.config(args.config)
    cfg = model_config(config)
    t0 = time.monotonic()
    params = jax.block_until_ready(init_params(cfg, jax.random.key(0)))
    if getattr(args, "degrade", "none") == "int8":
        params = jax.block_until_ready(degrade_matmuls_int8(params))
    # The reference upcasts what it is handed, a tensor at each use: handed
    # float32 it upcasts nothing (the same values; 17 GB on the host).
    params = jax.tree.map(lambda a: a.astype("float32"), params)
    print(f"[golden] weights on {jax.devices()[0].platform} after "
          f"{time.monotonic() - t0:.0f}s", file=sys.stderr, flush=True)
    return ref, config, cfg, params, t0


def cmd_screen(args) -> int:
    ref, config, cfg, params, t0 = _setup(args)
    for seed in args.prompt_seeds:
        trace = []
        out = ref.generate(
            params, cfg, prompt_of(seed, config["vocab_size"], args.length),
            correctness.PROBE_TOKENS, trace=trace)
        print(json.dumps({"seed": seed, "length": args.length,
                          **margins(trace, out), "tokens": out["tokens"],
                          "at_s": round(time.monotonic() - t0)}), flush=True)
    return 0


def cmd_write(args) -> int:
    import jax
    ref, config, cfg, params, t0 = _setup(args)
    lengths = args.lengths or list(correctness.PROBE_LENGTHS)
    if len(args.prompt_seeds) != len(lengths):
        raise SystemExit(f"--prompt-seeds takes one seed a probe length "
                         f"{lengths}")
    probes = []
    for seed, n in zip(args.prompt_seeds, lengths):
        prompt, trace = prompt_of(seed, config["vocab_size"], n), []
        out = ref.generate(
            params, cfg, prompt, correctness.PROBE_TOKENS,
            "default" if args.degrade == "bf16" else "highest", trace=trace)
        probes.append({"prompt": prompt, "tokens": out["tokens"],
                       "logprobs": out["logprobs"], "top": out["top"],
                       "passes": out["passes"],
                       "margins": margins(trace, out)})
        print(f"[golden] probe of {n} tokens done at "
              f"{time.monotonic() - t0:.0f}s: {out['tokens']} "
              f"{probes[-1]['margins']}", file=sys.stderr, flush=True)
    dev = jax.devices()[0]
    golden = {
        "about": "greedy, top-5, first 8 positions (two blocks) of two "
                 "probe prompts: the float32 reference's GENERATION "
                 f"(perfbench/reference/{args.reference}.py::generate, "
                 "matmul precision highest, every pass a whole forward) "
                 "over the engine's seed-0 weights at the published "
                 "widths; a token's log-probability and top-5 are those "
                 "of the pass that transferred it; NOT captured from the "
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
    p = argparse.ArgumentParser(prog="perfbench.reference.write_golden_blocks")
    sub = p.add_subparsers(dest="cmd", required=True)
    for name in ("screen", "write"):
        w = sub.add_parser(name)
        w.add_argument("--config", required=True)
        w.add_argument("--reference", required=True,
                       help="module under perfbench.reference with "
                            "generate()")
        w.add_argument("--prompt-seeds", nargs="+", required=True)
        w.add_argument("--root", default=None,
                       help="directory holding another BENCHMARK.json "
                            "(tests)")
    sub.choices["screen"].add_argument("--length", type=int, required=True)
    w = sub.choices["write"]
    w.add_argument("--degrade", default="none",
                   choices=("none", "int8", "bf16"))
    w.add_argument("--out", default=None)
    w.add_argument("--lengths", nargs="+", type=int, default=None,
                   help="one a seed (default: the harness's 24 and 600); "
                        "more than two probes make a file of CANDIDATES "
                        "for ``gap`` to read on the chip")
    w.add_argument("--tolerance", type=float, default=0.1)
    w.add_argument("--reason", default="see PERF.md section 2 (correct)")
    g = sub.add_parser("gap")
    g.add_argument("--workload", required=True)
    g.add_argument("--golden", required=True)
    d = sub.add_parser("diff")
    d.add_argument("--golden", required=True)
    d.add_argument("--other", required=True)
    args = p.parse_args(argv)
    return {"screen": cmd_screen, "write": cmd_write, "gap": cmd_gap,
            "diff": cmd_diff}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
