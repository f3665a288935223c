"""Goldens whose probes have lengths of the configuration's own choosing.

    python3 -m perfbench.reference.write_golden_long write --config <config> --reference <module> --lengths 4416 24 --prompt-seeds A B [--degrade int8|bf16|dense] [--out FILE]
    python3 -m perfbench.reference.write_golden_long gap --workload <cell> --golden FILE
    python3 -m perfbench.reference.write_golden_long diff --golden A --other B

The two writers beside this file fix the probes' lengths at 24 and 600
tokens (``correctness.PROBE_LENGTHS``). A model that chooses what it attends
to needs a probe several times its ``index_topk`` long, in chunks with
history: the harness sends whatever prompts the golden file holds, so the
lengths are this writer's arguments, FIRST the probe that is also repeated
beside the load and after the window. Everything else is those writers' own,
imported, not copied: the seed-0 weights as the engine builds them, one
reference pass a position, the int8 degradation, ``gap`` and ``diff``.

``--degrade dense`` (a reference whose ``forward`` takes ``dense``) drops
the choice: every layer attends to every earlier token. A reading a
tolerance has to refuse, never a golden of its own.
"""

from __future__ import annotations

import argparse
import importlib
import json
import random
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.reference.write_golden import (  # noqa: E402
    cmd_diff, cmd_gap, model_config, reference_probe)
from perfbench.reference.write_golden_for import (  # noqa: E402
    degrade_matmuls_int8)
from perfbench.spec import Benchmark  # noqa: E402


def probe_prompts(vocab: int, max_len: int, lengths, seeds) -> list:
    """One prompt a length, drawn from ``random.Random(seed)``."""
    if len(seeds) != len(lengths):
        raise SystemExit("--prompt-seeds takes one seed a length")
    if max(lengths) > max_len - 16:
        raise SystemExit(f"a probe of {max(lengths)} tokens and its 8 "
                         f"continuations do not fit {max_len} positions")
    out = []
    for seed, n in zip(seeds, lengths):
        r = random.Random(seed)
        out.append([r.randrange(3, vocab) for _ in range(n)])
    return out


def cmd_write(args) -> int:
    import jax

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
    if args.degrade == "dense":
        whole = ref.forward
        ref = SimpleNamespace(forward=lambda p, c, t, prec: whole(
            p, c, t, prec, dense=True))
    print(f"[golden] weights on {dev.platform} after "
          f"{time.monotonic() - t0:.0f}s", file=sys.stderr, flush=True)
    prompts = probe_prompts(config["vocab_size"],
                            int(config["max_position_embeddings"]),
                            args.lengths, args.prompt_seeds)
    probes = []
    for p in prompts:
        probes.append({"prompt": p, **reference_probe(
            ref, params, cfg, p,
            "default" if args.degrade == "bf16" else "highest")})
        print(f"[golden] probe of {len(p)} tokens done at "
              f"{time.monotonic() - t0:.0f}s: {probes[-1]['tokens']}",
              file=sys.stderr, flush=True)
    golden = {
        "about": "greedy, top-5, first 8 positions of probe prompts of "
                 f"{args.lengths} tokens: the float32 reference "
                 f"(perfbench/reference/{args.reference}.py, matmul "
                 "precision highest) over the engine's seed-0 weights at "
                 "the published widths, one whole forward pass a position; "
                 "NOT captured from the server"
                 + (f"; degraded: {args.degrade}"
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
    p = argparse.ArgumentParser(prog="perfbench.reference.write_golden_long")
    sub = p.add_subparsers(dest="cmd", required=True)
    w = sub.add_parser("write")
    w.add_argument("--config", required=True)
    w.add_argument("--reference", required=True,
                   help="module under perfbench.reference with forward()")
    w.add_argument("--lengths", nargs="+", type=int, required=True)
    w.add_argument("--prompt-seeds", nargs="+", required=True)
    w.add_argument("--degrade", default="none",
                   choices=("none", "int8", "bf16", "dense"))
    w.add_argument("--out", default=None)
    w.add_argument("--root", default=None,
                   help="directory holding another BENCHMARK.json (tests)")
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
