"""Command line of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 perfbench/run.py golden --workload <cell>

The first form is the contract's: the LAST line of stdout is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` with ``--trace 1``) and no other key; the
line before it is a JSON object of whatever else the run saw. Progress goes
to stderr. Without a TPU, or with fewer chips than the cell asks for, the exit
code is not 0 and no result line is printed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random
import shutil
import signal
import sys
import time
from pathlib import Path

from . import correctness, harness, readers, roofline, stats
from .server import Server, ServerFailure
from .spec import Benchmark, SpecError
from .tokenizer import write_tokenizer


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def _start(cell, args, tag: str):
    """Tokenizer, server, /health, device checks. Returns (server, health,
    peaks)."""
    tok = write_tokenizer(cell.config["vocab_size"])
    server = Server(cell.config, tok, tag, cpu_rehearsal=args.cpu_rehearsal)
    try:
        want = "cpu" if args.cpu_rehearsal else "tpu"
        health = server.wait_healthy(want, cell.chips)
        if health["platform"] != want or health["device_count"] < cell.chips:
            raise harness.RunFailure(f"/health names another device: "
                                     f"{health}")
        peaks = None if args.cpu_rehearsal \
            else roofline.peaks_for(health["device_kind"])
        log(f"/health 200 after {time.monotonic() - server.t_start:.1f}s: "
            f"{health['platform']} {health['device_kind']} x"
            f"{health['device_count']}, {health['num_pages']} pages")
        return server, health, peaks
    except BaseException:
        server.kill()
        shutil.rmtree(server.profile_dir, ignore_errors=True)
        raise


def _max_len(cell) -> int:
    flags = cell.config["server_flags"]
    if "--max-model-len" in flags:
        return int(flags[flags.index("--max-model-len") + 1])
    return int(cell.config["max_position_embeddings"])


def cmd_run(args, t_process_start: float) -> int:
    bench = Benchmark(Path(args.root)) if args.root else Benchmark()
    cell = bench.cell(args.workload)
    trace = bool(args.trace)
    tag = f"{cell.name}-s{args.seed}-t{int(trace)}"
    server, health, peaks = _start(cell, args, tag)
    try:
        raw = asyncio.run(harness.drive(
            cell, server.base, health["model"], args.seed,
            float(args.seconds), trace, t_process_start, _max_len(cell),
            log))
        rc = server.stop()
    finally:
        server.kill()
    if rc != 0:
        raise harness.RunFailure(f"the server exited {rc} after SIGTERM; "
                                 f"log tail:\n{server.log_tail()}")

    e2e = harness.end_to_end(raw, raw["window_s"])
    golden = correctness.load_golden(cell.golden_path)
    problems = correctness.compare(golden, raw["probes_before"])
    if golden is not None:
        problems += correctness.compare(golden, raw["probes_in_load"],
                                        " (beside the load)")
        log(f"probe 0 beside {raw['rows_at_probe']} rows of the load: "
            "largest log-probability gap to the golden "
            f"{correctness.max_logprob_gap(golden['probes'][0], raw['probes_in_load'][0]):.4f}"
            f" (tolerance {golden['tolerance_logprob']})")
    if raw["probes_after"][0]["tokens"] != raw["probes_before"][0]["tokens"]:
        problems.append("the probe repeated after the window gave other ids")
    if not e2e["_attempted"]:
        problems.append("no request finished in the window")
    if e2e["_failed"]:
        bad = [r for r in raw["records"] if r.failed][:3]
        problems.append(f"{e2e['_failed']} request(s) failed in the window "
                        f"({e2e['_hung']} of them hung at its end): "
                        + "; ".join(f"{r.status} {r.finish_reason} "
                                    f"{r.tokens}/{r.max_tokens} {r.error}"
                                    for r in bad))
    if args.cpu_rehearsal and problems[:1] == [
            "no golden file for this configuration"]:
        problems = problems[1:]     # a rehearsal has no chip goldens
    for p in problems:
        log(f"NOT CORRECT: {p}")

    device = {"platform": health["platform"], "kind": health["device_kind"],
              "count": health["device_count"],
              "memory_peak_bytes": int(raw["hbm_peak"])}
    result = {"correct": not problems, "attempted": e2e["_attempted"],
              "failed": e2e["_failed"], "metrics": {}, "device": device}
    # What the run saw beside the contract's keys goes on a line of its
    # own BEFORE the last one: every per-layer metric that needs no trace
    # (an untraced run sees them too), and in a traced run the end-to-end
    # metrics (not judged there; over the window up to the capture).
    ctx = dict(raw, trace=None, config=cell.config, peaks=peaks, values={})
    seen = {m["name"]: v for m in cell.per_layer
            if m["source"] != "device_trace"
            for v in [readers.load(m["reader"])(m, ctx)]
            if harness.nan_free(v)}
    seen["tpot_p99_ms"] = e2e["_tpot_p99_ms"]
    extras = {"observed": seen,
              "shapes_met_in_preroll": raw.get("shapes_met_in_preroll"),
              "rows_at_probe": raw["rows_at_probe"],
              "end_to_end": {k: v for k, v in e2e.items()
                             if not k.startswith("_")
                             and harness.nan_free(v)}}
    if not trace:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if harness.nan_free(v):
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from . import trace as tr       # imports jax: the server is gone
        summary = None
        if raw["profile"].get("reply"):
            path = harness.collect_trace(server.profile_dir)
            summary = tr.load(path)
            log(f"trace {path} ({path.stat().st_size >> 10} KiB): "
                f"{len(summary.devices)} device plane(s), "
                f"window {summary.window_s:.2f}s")
        ctx["trace"] = summary
        for m in cell.per_layer:
            v = readers.load(m["reader"])(m, ctx)
            if harness.nan_free(v):
                ctx["values"][m["name"]] = v
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None and summary.devices:
            device["busy_s"] = tr.mean_busy_seconds(summary)
            device["window_s"] = summary.window_s
            dev = summary.devices[0]
            ops = sorted(tr.op_seconds_by_name(dev).items(),
                         key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in tr.idle_gaps(dev, 5)]}
    shutil.rmtree(server.profile_dir, ignore_errors=True)   # ~25 MB a trace
    print(json.dumps(extras), flush=True)
    print(json.dumps(result), flush=True)
    return 0


def cmd_golden(args, t_process_start: float) -> int:
    """Capture the configuration's goldens on the chip: each probe served
    alone twice (must agree exactly), then again while other requests
    decode (the spread the tolerance is set from). Writes the golden file
    and prints what it saw."""
    bench = Benchmark()
    cell = bench.cell(args.workload)
    server, health, _ = _start(cell, args, f"golden-{cell.config_name}")

    async def go():
        vocab, max_len = cell.config["vocab_size"], _max_len(cell)
        async with harness.LoadClient(server.base, health["model"]) as c:
            a = await correctness.run_probes(c, None, vocab, max_len)
            b = await correctness.run_probes(c, None, vocab, max_len)
            # the same probes inside a batch of sampled streams
            load = [harness._ladder_request(
                random.Random(i), vocab, 200, 400,
                {"temperature": 0.7, "seed": i}, i) for i in range(24)]
            tasks = [c.spawn(c.stream(q, "ladder", time.perf_counter()))
                     for q in load]
            await harness._until_decoding(c, tasks, 16)
            busy = await correctness.run_probes(c, None, vocab, max_len)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            return a, b, busy
    try:
        a, b, busy = asyncio.run(go())
        server.stop()
    finally:
        server.kill()
    prompts = correctness.default_prompts(cell.config["vocab_size"],
                                          _max_len(cell))
    gaps = [correctness.max_logprob_gap(x, y) for x, y in zip(a, busy)]
    report = {
        "alone_repeat_identical": [x["tokens"] == y["tokens"]
                                   and x["logprobs"] == y["logprobs"]
                                   for x, y in zip(a, b)],
        "alone_vs_in_batch_max_logprob_gap": gaps,
        "alone_vs_in_batch_same_ids": [x["tokens"] == y["tokens"]
                                       for x, y in zip(a, busy)],
        "device": {k: health[k] for k in ("platform", "device_kind",
                                          "device_count")}}
    golden = {
        "about": "greedy, logprobs 5, first 8 positions of two probe "
                 "prompts served alone; captured through the served path",
        "captured_on": report["device"],
        "tolerance_logprob": args.tolerance,
        "tolerance_reason": "see PERF.md section 2 (correct)",
        "capture_report": report,
        "probes": [{"prompt": p, "tokens": x["tokens"],
                    "logprobs": x["logprobs"], "top": x["top"]}
                   for p, x in zip(prompts, a)]}
    for out in (cell.golden_path,
                Path("chiprun_out") / cell.golden_path.name):
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(golden, indent=1))
    print(json.dumps(report))
    return 0


def main(argv=None, t_process_start=None) -> int:
    t_process_start = t_process_start or time.monotonic()
    argv = list(sys.argv[1:] if argv is None else argv)
    sub = argv.pop(0) if argv and argv[0] == "golden" else "run"
    p = argparse.ArgumentParser(prog=f"perfbench {sub}")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, required=(sub == "run"))
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="debug the harness itself on the CPU; the result "
                        "names the cpu and no driver accepts it")
    if sub == "run":
        p.add_argument("--trace", type=int, choices=(0, 1), required=True)
        p.add_argument("--root", default=None,
                       help="directory holding another BENCHMARK.json "
                            "(tests)")
    else:
        p.add_argument("--tolerance", type=float, default=0.1)
    args = p.parse_args(argv)
    # Ended from outside (a time limit): leave through the ``finally``
    # blocks, so the server, which has a session of its own, goes too.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        return {"run": cmd_run, "golden": cmd_golden}[sub](
            args, t_process_start)
    except (SpecError, ServerFailure, harness.RunFailure,
            roofline.UnknownDevice) as e:
        log(f"FAILED: {type(e).__name__}: {e}")
        return 1
