#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on
the chip.

Starts the CLI server (``python -m kubernetes_gpu_cluster_tpu.serving.
api_server --model qwen3-4b``, default flags: bf16, random weights from the
seed, byte tokenizer, mixed batching, pool derived from HBM) as its ONE child
process, drives every step program of the default path through the OpenAI
HTTP API, checks what comes back, SIGTERMs the server and requires a clean
drain. Not a benchmark: no rates, no utilization.

This parent is stdlib only and never imports jax or the package: a chip
belongs to one process, and that process is the server. The device is
asserted from what the SERVER reports (/health and its first log line).

    python chip_smoke.py                                # one chip
    python chip_smoke.py --tensor-parallel-size 4 \\
        --expect-greedy-tokens 17,4,99,...              # four chips; tokens
                                                        # from the 1-chip run
    python chip_smoke.py --cpu-rehearsal                # debug THIS SCRIPT on
                                                        # the CPU (debug-tiny)

When every phase passed: exit code 0 and two JSON lines on stdout. The LAST
is the verdict, exactly ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with the device as the server's JAX reports it; the line before
it is the report (model, pool, kernels, cold times, per-request status,
compile cache). Anything else — no TPU, a failed phase, a fallback line in
the server log, a dirty drain — is a non-zero exit with the reason on stderr
and nothing on stdout. Nothing is caught and downgraded to a warning.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
# The helper's rule (kubernetes_gpu_cluster_tpu/utils/compile_cache.py),
# restated because this file may not import the package; the server's first
# log line names the directory it really uses and the two must agree.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = HERE / ".jax_compile_cache"
LOG_DIR = HERE / "chiprun_out"

# vocab bounds the random token-id prompts; long_prompt must exceed the
# server's default max_prefill_tokens (2048) for chunked prefill to run;
# gen is the background generation a short prompt is mixed into.
MODELS = {
    "qwen3-4b": dict(vocab=151936, long_prompt=3000, gen=256),
    # ``--model kimi-vl-a3b``: the language model at the benchmark's cut,
    # one pipeline stage's 9 of 27 layers (latent pages, grouped experts).
    "kimi-vl-a3b": dict(vocab=163840, long_prompt=3000, gen=256,
                        flags=["--hf-overrides", '{"num_hidden_layers": 9}']),
    # ``--model granite-4.0-h-micro``: the whole model, 36 state layers'
    # slots beside the pages of 4 attention layers.
    "granite-4.0-h-micro": dict(vocab=100352, long_prompt=3000, gen=256),
    # ``--model kimi-linear-48b-a3b``: the benchmark's cut, one chip's share
    # of stage 1: 9 of 27 layers (7 KDA state layers' slots beside 2 NoPE
    # latent layers' pages), experts 0-63 of 256, ids 0-40,959.
    "kimi-linear-48b-a3b": dict(
        vocab=40960, long_prompt=3000, gen=256,
        flags=["--hf-overrides", '{"num_hidden_layers": 9, '
               '"experts_held": 64, "vocab_size": 40960}']),
    # ``--model xing4.0-29b-a4b``: the benchmark's cut, stage 1 of 5: 2
    # dense + 6 expert layers, four residual streams around every sublayer.
    "xing4.0-29b-a4b": dict(
        vocab=131072, long_prompt=3000, gen=256,
        flags=["--hf-overrides", '{"num_hidden_layers": 8}']),
    # ``--model glm-5.2``: the benchmark's cut, published layers 2-7 (one
    # dense + five expert layers, indexers in two of them), experts 0-15 of
    # 256, ids 0-19,359; a 5000-token prompt is three chunks, the later two
    # choosing 2048 of their history.
    "glm-5.2": dict(
        vocab=19360, long_prompt=5000, gen=256,
        flags=["--hf-overrides", '{"num_hidden_layers": 6, '
               '"layers_from": 2, "experts_held": 16, '
               '"vocab_size": 19360}', "--max-model-len", "12288",
               "--max-num-seqs", "16"]),
    # Rehearsal only: max_model_len 512 cannot hold a chunking prompt.
    "debug-tiny": dict(vocab=512, long_prompt=400, gen=96),
    "debug-mla-moe": dict(vocab=512, long_prompt=400, gen=96),
    "debug-hc-mla-moe": dict(vocab=512, long_prompt=400, gen=96),
    "debug-dsa-mla-moe": dict(vocab=512, long_prompt=400, gen=96),
    "debug-ssm-hybrid": dict(vocab=512, long_prompt=400, gen=96),
    "debug-kda-hybrid": dict(vocab=512, long_prompt=400, gen=96,
                             flags=["--hf-overrides", '{"experts_held": 4}']),
}
REHEARSAL_OF = {"qwen3-4b": "debug-tiny", "kimi-vl-a3b": "debug-mla-moe",
                "granite-4.0-h-micro": "debug-ssm-hybrid",
                "kimi-linear-48b-a3b": "debug-kda-hybrid",
                "xing4.0-29b-a4b": "debug-hc-mla-moe",
                "glm-5.2": "debug-dsa-mla-moe"}
HEALTH_TIMEOUT_S = 600
REQUEST_TIMEOUT_S = 600
DRAIN_TIMEOUT_S = 150
DEADLINE_S = 1150       # the contract allows 1200, compilation included
FALLBACK_LINE = re.compile(r"falling back|unavailable", re.IGNORECASE)
DEVICE_LINE = re.compile(
    r"device: platform=(\S+) device_kind=(.+?) device_count=(\d+) "
    r"compile_cache=(\S+)")
TP_HBM_TOLERANCE = 0.10  # max spread of per-device bytes_in_use over the max


class SmokeFailure(Exception):
    pass


def check(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


class Server:
    """The one child: the CLI server, its log file, its HTTP endpoint."""

    def __init__(self, model: str, tp: int, rehearsal: bool, flags=()):
        LOG_DIR.mkdir(exist_ok=True)
        n = 0
        while (LOG_DIR / f"chip_smoke_server.{n}.log").exists():
            n += 1
        self.log_path = LOG_DIR / f"chip_smoke_server.{n}.log"
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        cmd = [sys.executable, "-m",
               "kubernetes_gpu_cluster_tpu.serving.api_server",
               "--model", model, "--host", "127.0.0.1",
               "--port", str(self.port), *flags]
        if tp > 1:
            cmd += ["--tensor-parallel-size", str(tp)]
        # The environment passes through unchanged; the rehearsal alone
        # pins the child to the CPU, and says so.
        env = dict(os.environ)
        if rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        self.t_start = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def log_tail(self, n: int = 40) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def kill(self) -> None:
        """Stop the whole process group, whatever state it is in."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()

    def wait_for(self, what: str, probe, timeout_s: float):
        """Poll ``probe()`` until it returns non-None; fail on a child that
        exited or on the timeout, with the tail of the server log."""
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"server exited with code {rc} before {what}; log tail "
                    f"({self.log_path}):\n{self.log_tail()}")
            got = probe()
            if got is not None:
                return got
            time.sleep(0.2)
        raise SmokeFailure(
            f"timed out after {timeout_s:.0f}s waiting for {what}; log tail "
            f"({self.log_path}):\n{self.log_tail()}")

    def get(self, path: str, timeout: float = 10):
        """(status, body text). Connection refused -> (None, '')."""
        try:
            with urllib.request.urlopen(self.base + path,
                                        timeout=timeout) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()
        except (urllib.error.URLError, ConnectionError, socket.timeout):
            return None, ""

    def post(self, path: str, body: dict):
        """Open a POST; returns the live response (caller reads/closes).
        An HTTP error status is a failed phase."""
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            return urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S)
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"POST {path} -> {e.code}: {e.read().decode()[:500]}")


def token_ids(logprobs: dict) -> list[int]:
    """``return_tokens_as_token_ids`` renders tokens as "token_id:<id>"."""
    return [int(t.split(":", 1)[1]) for t in logprobs["tokens"]]


def complete(server: Server, body: dict, path: str = "/v1/completions"):
    """One non-streamed request: 200, the requested token count, a finish
    reason. Returns the generated token ids when logprobs were asked."""
    with server.post(path, body) as r:
        check(r.status == 200, f"{path} -> {r.status}")
        out = json.loads(r.read())
    choice = out["choices"][0]
    check(out["usage"]["completion_tokens"] == body["max_tokens"],
          f"{path}: asked {body['max_tokens']} tokens, got "
          f"{out['usage']['completion_tokens']}")
    check(choice["finish_reason"] == "length",
          f"{path}: finish_reason {choice['finish_reason']!r}, want 'length'")
    if body.get("logprobs"):
        ids = token_ids(choice["logprobs"])
        check(len(ids) == body["max_tokens"],
              f"{path}: {len(ids)} logprob tokens for {body['max_tokens']}")
        return ids
    return None


def stream(server: Server, body: dict, on_first_chunk=None) -> list[int]:
    """One ``stream: true`` request: 200, token-bearing SSE frames, a finish
    reason on the last one, then ``data: [DONE]``. Returns the token ids.
    ``on_first_chunk`` fires when the first token-bearing frame arrives."""
    ids: list[int] = []
    finish = None
    done = False
    with server.post("/v1/completions", dict(body, stream=True)) as r:
        check(r.status == 200, f"stream -> {r.status}")
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[len("data:"):].strip()
            if payload == "[DONE]":
                done = True
                break
            frame = json.loads(payload)
            check("error" not in frame, f"stream error frame: {frame}")
            choice = frame["choices"][0]
            if choice.get("logprobs"):
                first = not ids
                ids += token_ids(choice["logprobs"])
                if first and ids and on_first_chunk is not None:
                    on_first_chunk()
            finish = choice.get("finish_reason") or finish
    check(done, "stream did not end with 'data: [DONE]'")
    check(finish == "length", f"stream finish_reason {finish!r}")
    check(len(ids) == body["max_tokens"],
          f"stream: asked {body['max_tokens']} tokens, got {len(ids)}")
    return ids


def metric(text: str, name: str) -> float:
    m = re.search(rf"^{re.escape(name)} (\S+)$", text, re.MULTILINE)
    check(m is not None, f"/metrics has no {name}")
    return float(m.group(1))


def run(args) -> tuple[dict, dict]:
    """(report, verdict) when every phase passed; SmokeFailure otherwise."""
    model = REHEARSAL_OF[args.model] if args.cpu_rehearsal else args.model
    want_platform = "cpu" if args.cpu_rehearsal else "tpu"
    geom = MODELS[model]
    tp = args.tensor_parallel_size

    placed = os.environ.get(CACHE_ENV)
    cache_dir = Path(placed) if placed else DEFAULT_CACHE_DIR
    cache_empty = not cache_dir.is_dir() or not any(cache_dir.iterdir())

    rng = random.Random(0)

    def prompt(n: int) -> list[int]:
        return [rng.randrange(3, geom["vocab"]) for _ in range(n)]

    server = Server(model, tp, args.cpu_rehearsal, geom.get("flags", ()))

    def on_deadline(signum, frame):
        raise SmokeFailure(f"chip_smoke exceeded its {DEADLINE_S}s deadline; "
                           f"server log tail:\n{server.log_tail()}")

    # The contract's clock: whatever happens, stop inside the limit.
    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        # -- the device, from the server's first log line -------------------
        m = server.wait_for(
            "the server's device line",
            lambda: DEVICE_LINE.search(server.log_text()), HEALTH_TIMEOUT_S)
        platform, srv_cache = m.group(1), m.group(4)
        check(platform == want_platform,
              f"the server found platform {platform!r}, not "
              f"{want_platform!r}: chip_smoke needs a TPU (pass "
              "--cpu-rehearsal to debug the script itself on the CPU)")
        check(Path(srv_cache) == cache_dir,
              f"server compile cache {srv_cache} != expected {cache_dir}")

        # -- healthy ---------------------------------------------------------
        def healthy():
            status, text = server.get("/health")
            return json.loads(text) if status == 200 else None
        health = server.wait_for("/health 200", healthy, HEALTH_TIMEOUT_S)
        t_healthy = time.monotonic() - server.t_start
        check(health["platform"] == want_platform,
              f"/health platform {health['platform']!r}")
        check(health["model"] == model, f"/health model {health['model']!r}")
        if not args.cpu_rehearsal:
            check(health["use_pallas"] is True and
                  health["use_pallas_hist"] is True,
                  f"Pallas kernels are not on: {health}")
            check("pallas_disabled_reason" not in health, str(health))
        check(health["num_pages"] >= 2, f"pool: {health['num_pages']} pages")
        want_mesh = {"tp": tp} if tp > 1 else None
        got_mesh = health["mesh"] and {
            k: v for k, v in health["mesh"].items() if v > 1}
        check((got_mesh or None) == want_mesh,
              f"mesh {health['mesh']} for --tensor-parallel-size {tp}")
        hbm_in_use = health["hbm_bytes_in_use"]
        if tp > 1 and not args.cpu_rehearsal:
            check(len(hbm_in_use) >= tp, f"{len(hbm_in_use)} devices report")
            spread = (max(hbm_in_use[:tp]) - min(hbm_in_use[:tp])) \
                / max(hbm_in_use[:tp])
            check(spread <= TP_HBM_TOLERANCE,
                  f"per-device bytes_in_use {hbm_in_use} spread "
                  f"{spread:.3f} > {TP_HBM_TOLERANCE}")

        # -- requests --------------------------------------------------------
        statuses: dict[str, int] = {}
        greedy = dict(prompt=prompt(12), max_tokens=16, temperature=0,
                      logprobs=1, return_tokens_as_token_ids=True)
        first_token_at = []
        # 1. streamed greedy, FIRST: its first frame is the cold
        #    start-to-first-token (prefill + first decode window compiles).
        ids_stream = stream(
            server, greedy,
            on_first_chunk=lambda: first_token_at.append(time.monotonic()))
        statuses["stream_greedy"] = 200
        t_first_token = first_token_at[0] - server.t_start
        # 2. the same prompt, not streamed, twice: identical tokens.
        ids_a = complete(server, greedy)
        ids_b = complete(server, greedy)
        statuses["greedy"] = statuses["greedy_repeat"] = 200
        check(ids_a == ids_b, f"greedy repeat differs: {ids_a} vs {ids_b}")
        check(ids_a == ids_stream,
              f"streamed greedy differs: {ids_stream} vs {ids_a}")
        if args.expect_greedy_tokens:
            want = [int(t) for t in args.expect_greedy_tokens.split(",")]
            check(ids_a[:len(want)] == want,
                  f"first {len(want)} greedy tokens {ids_a[:len(want)]} != "
                  f"expected {want}")
        # 3. seeded sampling over the full vocabulary, twice: the sampled
        #    decode program, and the seed makes it repeatable.
        sampled = dict(prompt=prompt(12), max_tokens=16, temperature=1.0,
                       top_k=50, top_p=0.9, seed=1234, logprobs=1,
                       return_tokens_as_token_ids=True)
        ids_s = complete(server, sampled)
        check(ids_s == complete(server, sampled), "seeded sampling differs")
        check(all(0 <= t < geom["vocab"] for t in ids_s), f"ids {ids_s}")
        statuses["sampled_seeded"] = 200
        # 4. chat endpoint.
        complete(server, dict(
            messages=[{"role": "user", "content": "ping"}], max_tokens=8,
            temperature=0), path="/v1/chat/completions")
        statuses["chat"] = 200
        # 5. a prompt longer than max_prefill_tokens: chunked prefill and
        #    the history-prefill kernel.
        complete(server, dict(prompt=prompt(geom["long_prompt"]),
                              max_tokens=8, temperature=0))
        statuses["long_prompt"] = 200
        # 6. a short prompt posted while a long generation is decoding: a
        #    mixed prefill/decode step.
        decoding = threading.Event()
        bg: dict = {}

        def background():
            try:
                bg["ids"] = stream(
                    server, dict(prompt=prompt(12), max_tokens=geom["gen"],
                                 temperature=0, logprobs=1,
                                 return_tokens_as_token_ids=True),
                    on_first_chunk=decoding.set)
            except Exception as e:   # re-raised on the main thread
                bg["error"] = e
            finally:
                decoding.set()

        th = threading.Thread(target=background, daemon=True)
        th.start()
        check(decoding.wait(REQUEST_TIMEOUT_S), "background stream stalled")
        complete(server, dict(prompt=prompt(12), max_tokens=8, temperature=0))
        th.join(REQUEST_TIMEOUT_S)
        check(not th.is_alive(), "background stream never finished")
        if "error" in bg:
            raise bg["error"]
        statuses["background_stream"] = statuses["mixed_short"] = 200

        if ("state_bytes" in health or "residual_streams" in health
                or "index_topk" in health):
            # 7. a state model: three short prompts at once on an idle
            #    server ride ONE packed prefill, whose segment boundaries
            #    fall inside the scan's chunks; each must start as it does
            #    alone (a slot found as another sequence left it, or a
            #    state carried over a boundary, would not). A model with
            #    residual streams: its mixers' token blocks hold several
            #    prompts' tokens. A model that chooses: no prompt's choice
            #    may reach into its neighbour's tokens.
            packed = [dict(prompt=prompt(n), max_tokens=8, temperature=0,
                           logprobs=1, return_tokens_as_token_ids=True)
                      for n in (40, 300, 17)]
            alone = [complete(server, body) for body in packed]
            together: dict = {}

            def one(i, body):
                try:
                    together[i] = complete(server, body)
                except Exception as e:   # re-raised on the main thread
                    together[i] = e
            threads = [threading.Thread(target=one, args=(i, b), daemon=True)
                       for i, b in enumerate(packed)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(REQUEST_TIMEOUT_S)
            for i, ids in enumerate(alone):
                if isinstance(together.get(i), Exception):
                    raise together[i]
                check(i in together and together[i][0] == ids[0],
                      f"prompt {i} starts {together.get(i)} beside others, "
                      f"{ids} alone")
            statuses["packed_prefill"] = 200

        status, metrics = server.get("/metrics", timeout=30)
        check(status == 200, f"/metrics -> {status}")
        mixed_ratio = metric(metrics, "kgct_mixed_step_ratio")
        check(mixed_ratio > 0, "no mixed step ran (kgct_mixed_step_ratio 0)")
        hbm_limit = metric(metrics, "kgct_hbm_bytes_limit")
        if not args.cpu_rehearsal:
            check(hbm_limit > 0, "kgct_hbm_bytes_limit is 0 on a TPU")

        # -- drain -----------------------------------------------------------
        server.proc.send_signal(signal.SIGTERM)
        try:
            rc = server.proc.wait(DRAIN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server still running {DRAIN_TIMEOUT_S}s after SIGTERM; "
                f"log tail:\n{server.log_tail()}")
        check(rc == 0, f"server exited {rc} after SIGTERM; log tail:\n"
                       f"{server.log_tail()}")
        bad = [ln for ln in server.log_text().splitlines()
               if FALLBACK_LINE.search(ln)]
        check(not bad, "server log has fallback lines:\n" + "\n".join(bad))
    finally:
        signal.alarm(0)
        server.kill()

    device = {"platform": health["platform"], "kind": health["device_kind"],
              "count": health["device_count"]}
    report = {
        "model": model, "dtype": health["dtype"],
        "tensor_parallel_size": tp,
        "pages": health["num_pages"], "page_size": health["page_size"],
        "kv_layout": health.get("kv_layout"),
        "weight_bytes": health.get("weight_bytes"),
        "state_bytes": health.get("state_bytes"),
        "residual_streams": health.get("residual_streams"),
        "kernels": {"use_pallas": health["use_pallas"],
                    "use_pallas_hist": health["use_pallas_hist"]},
        "seconds_to_healthy": round(t_healthy, 1),
        "seconds_to_first_token": round(t_first_token, 1),
        "requests": statuses,
        "greedy_tokens": ids_a,
        "mixed_step_ratio": mixed_ratio,
        "hbm_bytes_limit": int(hbm_limit),
        "hbm_bytes_in_use_at_start": hbm_in_use,
        "compile_cache": {"dir": str(cache_dir),
                          "empty_at_start": cache_empty},
        "server_exit_code": rc,
        "server_log": str(server.log_path),
    }
    return report, {"ok": True, "device": device}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tensor-parallel-size", type=int, default=1,
                    help="start the server on this many chips (tp mesh)")
    ap.add_argument("--expect-greedy-tokens", default=None,
                    help="comma-separated ids the greedy completion must "
                    "START with (the one-chip run's, for the tp run)")
    ap.add_argument("--model", default="qwen3-4b", choices=list(REHEARSAL_OF),
                    help="the preset to start (default: the chip check's)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debug this script on the CPU with the model's "
                    "debug preset; never a chip result")
    args = ap.parse_args()
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if not args.cpu_rehearsal and pinned and "tpu" not in pinned.split(","):
        print(f"chip_smoke: FAILED: JAX_PLATFORMS={pinned} holds JAX off the "
              "TPU; chip_smoke needs one (pass --cpu-rehearsal to debug the "
              "script itself on the CPU)", file=sys.stderr)
        return 1
    try:
        report, verdict = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(report))
    # The contract's line, and the last: these keys and no others.
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
