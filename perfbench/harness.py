"""One run of one cell: server up, probes, warm-up, pre-roll, the measured
window, (traced) a profile, drain, the result line.

The phases and what each is for:

1. ``probe``    the ``correct`` probes, served alone (golden comparison).
2. ``ladder``   a deterministic walk over the step-program shapes the
                cell's traffic meets rarely (packed prefills, and the mixed
                steps at the row counts the cell names), so that each is
                compiled, or loaded from the persistent cache, before
                anything is timed.
3. ``preroll``  the cell's own traffic, so the window starts in steady
                state (an open loop's from a FIXED seed; a closed loop's
                clients start in waves and keep going into the window);
                repeated while the server still compiles (a cold run). Half
                way through each roll the first probe again, beside the
                load.
4. ``window``   ``--seconds`` of the cell's traffic from ``--seed``. All
                end-to-end metrics come from here, on the client's clock.
                A traced run ends its window where the capture starts.
5. ``after``    one probe again (same ids as before the window), drain.

``setup_s`` is process start to the start of the window.
"""

from __future__ import annotations

import asyncio
import json
import math
import time
from pathlib import Path

import aiohttp

from . import correctness, stats
from .client import LoadClient
from .spec import Cell
from .traffic import Request, make_requests, sampling_body

PROFILE_SECONDS = 3.0
SAMPLE_PERIOD_S = 0.25
MAX_PREROLLS = 40          # a cold run compiles for minutes in its pre-roll
SLOW_STEP_LE = "2.5"        # a bucket edge of kgct_step_seconds
STALL_LIMIT_S = 2.5         # no token on any open stream for this long
LADDER_DEADLINE_S = 900.0
COLD_DEADLINE_S = 1060.0   # of the 1200 s a cell's first run may take
LADDER_STREAM_PROMPT = 32
DEFAULT_STALL_S = 10.0     # a traffic file may say ``stall_s``


class RunFailure(Exception):
    pass


# -- warm-up ladder -----------------------------------------------------------

def _ladder_request(r, vocab: int, n_prompt: int, max_tokens: int,
                    sampling: dict, index: int) -> Request:
    body = dict(sampling)
    if "seed" in body:
        body["seed"] = 1000 + index
    return Request(index=index, due_s=None, client=None,
                   prompt=[r.randrange(3, vocab) for _ in range(n_prompt)],
                   body=body, max_tokens=max_tokens)


async def run_ladder(client: LoadClient, cell: Cell, max_len: int,
                     held: list, deadline: float = float("inf")) -> dict:
    """Drive the step-program shapes that the cell's traffic meets RARELY
    in steady state, with the traffic's own sampling settings (greedy and
    sampled decode are different programs), so that each is compiled or
    loaded from the persistent cache before anything is timed. What the
    traffic meets every second (the mixed step of a single admission at
    the steady row count, the decode window there) the pre-roll warms.

    The shape grid is the configuration's (``warmup``: the server's prefill
    and decode buckets); which part of it this cell needs is the cell's
    (``ladder`` in ``cells/<cell>.json``): ``mixed_rows``, the decode-row
    buckets at which a mixed prefill+decode step is warmed per prefill
    bucket, and ``packed``, the prompt counts of packed prefill steps
    (warmed while ``hold_rows`` streams decode, default 1, so that the
    prompts sent together meet in one scheduling round). Only those row
    levels are visited.

    The streams held decoding are appended to ``held`` and stay: the caller
    cancels them (a closed loop does so when its clients have started, so
    that they too meet a busy engine and pack as they will in steady
    state). Past ``deadline`` (time.monotonic) the rest is skipped: a cold
    run compiles ~24 s a program and has 1200 s in all; what it skips
    compiles when first met, and is cached from then on."""
    import random
    warm = cell.config["warmup"]
    want = cell.load.get("ladder", {})
    vocab = cell.config["vocab_size"]
    sampling = sampling_body(cell.traffic, 0, 0)
    r = random.Random("ladder")
    window = warm["decode_window"]
    plen = cell.traffic["prompt_len"]
    budget = warm["max_prefill_tokens"]
    all_buckets = sorted(warm["prefill_buckets"])
    p_top = plen.get("max", plen.get("value", 0))
    p_min = plen.get("min", plen.get("value", 1))
    buckets = [t for t in all_buckets
               if t <= _bucket(min(p_top, budget), all_buckets)]
    n, skipped = [0], [0]

    async def one(n_prompt: int):
        if time.monotonic() > deadline:
            skipped[0] += 1
            return
        n[0] += 1
        req = _ladder_request(r, vocab, min(n_prompt, max_len - 1), 1,
                              sampling, n[0])
        await client.complete(dict(req.body, prompt=req.prompt, max_tokens=1))

    async def hold(level: int):
        """``level`` ladder streams decoding, topped up ONE at a time, each
        decoding before the next is sent (k prompts at once would be a
        packed prefill of k); then one pure decode window at this level."""
        for _ in range(3):
            while sum(1 for t in held if not t.done()) < level:
                n[0] += 1
                req = _ladder_request(
                    r, vocab, LADDER_STREAM_PROMPT,
                    max_len - LADDER_STREAM_PROMPT - 8, sampling, n[0])
                held.append(client.spawn(client.stream(
                    req, "ladder", time.perf_counter())))
                await _until_decoding(client, held, 0)
            if await _until_decoding(client, held, window):
                return

    hold_rows = int(want.get("hold_rows", 1))
    levels = sorted({max(d - 1, 1) for d in want.get("mixed_rows", [])}
                    | ({hold_rows} if want.get("packed") else set()))
    for level in levels:
        if time.monotonic() > deadline:
            skipped[0] += 1
            continue
        await hold(level)
        if _bucket(level + 1, warm["decode_buckets"]) in \
                want.get("mixed_rows", []):
            for t in buckets:
                await one(t - 16)
        if level == hold_rows:
            # b prompts sent together while the engine is busy with the
            # held streams are admitted as ONE packed prefill of their
            # total: every bucket that b prompts of this traffic can add up
            # to, twice over (the b requests must meet in one scheduling
            # round to pack).
            for b in want.get("packed", []):
                lo = _bucket(min(b * p_min, budget), all_buckets)
                hi = _bucket(min(b * p_top, budget), all_buckets)
                for t in [x for x in all_buckets if lo <= x <= hi] * 2:
                    await hold(level)       # a cold run outlasts a stream
                    await asyncio.gather(*(one((t - 16) // b)
                                           for _ in range(b)))
    return {"ladder_requests": n[0], "ladder_skipped": skipped[0]}


async def release(held: list) -> None:
    for task in held:
        task.cancel()
    await asyncio.gather(*held, return_exceptions=True)


def _bucket(value: int, buckets) -> int:
    return next((b for b in sorted(buckets) if value <= b), max(buckets))


async def _until_decoding(client: LoadClient, tasks: list, more_tokens: int,
                          timeout_s: float = 900.0) -> bool:
    """Wait until every live ladder stream has its first token and has then
    received ``more_tokens`` more (a pure decode window at this level has
    run). False when a stream ran out meanwhile: the caller tops up."""
    t_end = time.perf_counter() + timeout_s
    want = sum(1 for t in tasks if not t.done())
    marks: dict = {}
    while time.perf_counter() < t_end:
        dead = [rec for rec in client.records if rec.phase == "ladder"
                and rec.failed]
        if dead or client.errors:
            raise RunFailure("a ladder stream failed: " + (
                client.errors[0] if client.errors
                else f"{dead[0].status} {dead[0].error}"))
        if sum(1 for t in tasks if not t.done()) < want:
            return False
        rows = [rec for rec in client.inflight if rec.phase == "ladder"]
        if len(rows) >= want and all(rec.frames for rec in rows):
            for rec in rows:
                marks.setdefault(id(rec), rec.tokens)
            if all(rec.tokens - marks[id(rec)] >= more_tokens
                   for rec in rows):
                return True
        await asyncio.sleep(0.02)
    raise RunFailure(f"ladder: {want} streams did not start decoding")


# -- sampling while the load runs ---------------------------------------------

class Sampler:
    """4x a second: what the client holds in flight; once a second the
    server's HBM bytes in use (/health); with ``scrape`` also /metrics
    (KV pages free). Runs as a task beside the load."""

    def __init__(self, client: LoadClient, scrape: bool):
        self.client = client
        self.scrape = scrape
        self.samples = []
        self.hbm_peak = 0
        self._task = None

    async def _run(self):
        k = 0
        while True:
            now = time.perf_counter()
            rows, ctx = self.client.context_tokens_in_flight()
            s = {"t": now, "rows": rows, "context_tokens": ctx}
            try:
                if self.scrape:
                    s["scrape"] = stats.parse_prometheus(
                        await self.client.get_text("/metrics", 5))
                if k % 4 == 0:
                    h = json.loads(await self.client.get_text("/health", 5))
                    self.hbm_peak = max([self.hbm_peak]
                                        + list(h.get("hbm_bytes_in_use", [])))
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError):
                pass               # a missed sample is not a failed run
            self.samples.append(s)
            k += 1
            await asyncio.sleep(max(
                0.0, SAMPLE_PERIOD_S - (time.perf_counter() - now)))

    def start(self):
        self._task = asyncio.ensure_future(self._run())

    async def stop(self):
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)


# -- the run ------------------------------------------------------------------

async def _scrape(client: LoadClient) -> dict:
    return stats.parse_prometheus(await client.get_text("/metrics"))


def _compiles(scrape: dict) -> float:
    return stats.sample(scrape, "kgct_jit_compiles_total") or 0.0


def _slow_steps(scrape: dict) -> float:
    """Engine steps that took longer than SLOW_STEP_S so far. A program
    compiled in a step holds it for ~20 s; one loaded from the persistent
    cache for well under a second. (``kgct_jit_compiles_total`` counts
    both: it is the number of shapes met, not of compilations.)"""
    total = stats.sample(scrape, "kgct_step_seconds_count") or 0.0
    fast = stats.sample(scrape, "kgct_step_seconds_bucket",
                        {"le": SLOW_STEP_LE}) or 0.0
    return total - fast


async def _profile(client: LoadClient, at: float, seconds: float,
                   out: dict) -> None:
    """POST /debug/profile at perf_counter time ``at``; the server blocks
    for the window and answers with the directory it wrote."""
    await asyncio.sleep(max(0.0, at - time.perf_counter()))
    out["start"] = time.perf_counter()
    status, text = await client.post(f"/debug/profile?seconds={seconds}")
    out["end"] = time.perf_counter()
    if status != 200:
        raise RunFailure(f"/debug/profile -> {status}: {text[:300]}")
    out["reply"] = json.loads(text)


def collect_trace(profile_dir: Path) -> Path:
    """The ``*.xplane.pb`` of this run's one capture. ``profile_dir`` is the
    run's own (``server.Server`` emptied it before the server started and
    ``serve.py`` makes the profiler write there), so what lies in it is
    this run's; nothing outside it is read, moved or removed."""
    files = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise RunFailure(f"expected one .xplane.pb under {profile_dir}, "
                         f"found {len(files)}")
    return files[0]


async def drive(cell: Cell, base: str, model: str, seed: int, seconds: float,
                trace: bool, t_process_start: float, max_len: int,
                log) -> dict:
    """Everything between /health 200 and SIGTERM. Returns the raw
    material of the result: records, window, scrapes, samples, probes."""
    vocab = cell.config["vocab_size"]
    traffic, load = cell.traffic, cell.load
    closed = traffic["loop"] == "closed"
    preroll_s = float(traffic.get("preroll_s", 12))
    out: dict = {}
    async with LoadClient(base, model) as client:
        # 1. probes, served alone
        golden = correctness.load_golden(cell.golden_path)
        out["probes_before"] = await correctness.run_probes(
            client, golden, vocab, max_len)
        log(f"probes done at {time.monotonic() - t_process_start:.1f}s")

        # 2. ladder
        c0 = _compiles(await _scrape(client))
        t_l = time.perf_counter()
        held: list = []
        out.update(await run_ladder(
            client, cell, max_len, held, t_process_start + LADDER_DEADLINE_S))
        if not closed:
            await release(held)
            await _until_idle(client)  # every ladder stream has left the engine
        c1 = _compiles(await _scrape(client))
        log(f"ladder: {out['ladder_requests']} requests "
            f"({out['ladder_skipped']} skipped), "
            f"{time.perf_counter() - t_l:.1f}s, compiles {c0:.0f}->{c1:.0f}")

        sampler = Sampler(client, scrape=trace)
        # 3. pre-roll (+ repeats while the server compiles), 4. window
        t0 = time.perf_counter() + 0.05
        if closed:
            # The clients start in waves of ``start_wave`` (default: all at
            # once), ``start_wave_gap_s`` apart, while the ladder's streams
            # still decode: a wave then meets a busy engine and is admitted
            # as one packed prefill, a shape the ladder has warmed. The
            # ladder's streams leave just before the last wave, whose
            # clients need their seats.
            clients = int(load["clients"])
            wave = int(load.get("start_wave", clients))
            gap = float(load.get("start_wave_gap_s", 0.0))
            last = (clients - 1) // wave
            reqs = make_requests(traffic, load, vocab, seconds, seed, max_len)
            horizon = t0 + preroll_s * (MAX_PREROLLS + 1) + seconds + 5
            for i in range(clients):
                client.spawn(client.closed_client(
                    reqs[i::clients], "load", t0 + gap * (i // wave),
                    horizon))
            await asyncio.sleep(max(
                0.0, t0 + gap * (last - 0.5) - time.perf_counter()))
            await release(held)
        sampler.start()
        slow = _slow_steps(await _scrape(client))
        client.take_worst_stall()
        min_rolls = int(traffic.get("min_prerolls", 1))
        w0 = None
        for k in range(MAX_PREROLLS):
            # Half way through each roll the first probe again, now beside
            # the cell's own load (the batched programs that do the work of
            # the window): held to the same golden. The last roll's counts.
            busy = asyncio.ensure_future(_probe_at(
                client, golden, vocab, max_len, t0 + (k + 0.5) * preroll_s))
            if not closed:
                pre = make_requests(traffic, load, vocab, preroll_s,
                                    int(traffic.get("preroll_seed", 1)) + k,
                                    max_len)
                await client.open_loop(pre, "preroll", t0 + k * preroll_s)
            t_probe = time.perf_counter()
            out["probes_in_load"] = await busy
            out["rows_at_probe"] = client.context_tokens_in_flight()[0]
            late = time.perf_counter() - (t0 + (k + 1) * preroll_s)
            if late > 0:
                # the rolls stay on their grid; only the window moves
                log(f"pre-roll {k}: the probe beside the load came back "
                    f"{late:.2f}s after the roll's end (waited "
                    f"{time.perf_counter() - t_probe:.2f}s for it)")
            await asyncio.sleep(max(
                0.0, t0 + (k + 1) * preroll_s - 0.02 - time.perf_counter()))
            before = await _scrape(client)
            now_slow = _slow_steps(before)
            stall = client.take_worst_stall()
            # A step that compiles shows in the server's histogram only
            # once it has ended, and on the client, as no token on any
            # stream, while it lasts: a roll is clean when neither shows.
            clean = now_slow == slow and stall <= STALL_LIMIT_S
            if clean and k + 1 >= min_rolls:
                w0 = max(t0 + (k + 1) * preroll_s, time.perf_counter())
                break
            if not clean:
                log(f"pre-roll {k}: {now_slow - slow:.0f} step(s) over "
                    f"{SLOW_STEP_LE}s, longest stall of all streams "
                    f"{stall:.1f}s (compiling), rolling again")
            slow = now_slow
            if time.monotonic() - t_process_start > COLD_DEADLINE_S:
                # A first run may take 1200 s in all: measure now, and let
                # compiles_in_window say what the window paid.
                log("the cold deadline is near: the window starts anyway")
                w0 = max(t0 + (k + 1) * preroll_s, time.perf_counter())
                break
        if w0 is None:
            raise RunFailure(f"the server still compiled after "
                             f"{MAX_PREROLLS} pre-rolls")
        out["shapes_met_in_preroll"] = _compiles(before) - c1
        w1 = w0 + seconds
        out["setup_s"] = (time.monotonic() - t_process_start) \
            + (w0 - time.perf_counter())
        log(f"window starts; setup_s {out['setup_s']:.1f}")
        prof: dict = {}
        # A traced run ends its window where the capture starts. The
        # profiler's start and stop block the server's event loop (no frame
        # leaves, no request enters): counters, samples and client times
        # are taken up to there, the device metrics from the capture. And
        # no client sends another request once the capture's sleep is
        # over: the seats that empty while stop_trace() blocks would be
        # refilled in one burst, shapes that no steady state has and that
        # a later run would have to compile.
        w_end = max(w1 - PROFILE_SECONDS - 1.0, w0 + seconds / 2) \
            if trace else w1
        if trace:
            client.stop_at = w_end + PROFILE_SECONDS + 0.5
        if not closed:
            win = make_requests(traffic, load, vocab, seconds, seed, max_len)
            await client.open_loop(win, "window", w0)
        await asyncio.sleep(max(0.0, w_end - time.perf_counter()))
        after = await _scrape(client)
        if trace:
            await _profile(client, w_end, PROFILE_SECONDS, prof)
        await sampler.stop()
        await client.cancel_all()
        out.update(records=list(client.records), window=(w0, w_end),
                   window_s=seconds if not trace else w_end - w0,
                   stall_s=float(traffic.get("stall_s", DEFAULT_STALL_S)),
                   scrape_before=before, scrape_after=after,
                   samples=sampler.samples, hbm_peak=sampler.hbm_peak,
                   profile=prof)
        # 5. the first probe again: same ids as before the window
        await _until_idle(client)
        out["probes_after"] = await correctness.run_probes(
            client, golden, vocab, max_len, only_first=True)
        h = json.loads(await client.get_text("/health"))
        out["hbm_peak"] = max([out["hbm_peak"]]
                              + list(h.get("hbm_bytes_in_use", [])))
    return out


async def _probe_at(client: LoadClient, golden, vocab: int, max_len: int,
                    at: float) -> list:
    await asyncio.sleep(max(0.0, at - time.perf_counter()))
    return await correctness.run_probes(client, golden, vocab, max_len,
                                        only_first=True)


async def _until_idle(client: LoadClient, timeout_s: float = 120.0) -> None:
    """The aborted streams leave the engine within a step or two."""
    t_end = time.perf_counter() + timeout_s
    while time.perf_counter() < t_end:
        h = json.loads(await client.get_text("/health"))
        if h.get("running", 0) == 0 and h.get("waiting", 0) == 0:
            return
        await asyncio.sleep(0.1)
    raise RunFailure("the server did not go idle after the window")


# -- from raw material to metrics ---------------------------------------------

def finished_in_window(records, window) -> list:
    """The latency population: requests of the load (not the ladder) that
    ended inside the window and were not cut off by the harness."""
    w0, w1 = window
    return [r for r in records if r.phase != "ladder" and r.ended is not None
            and w0 <= r.ended <= w1 and not r.cancelled]


def stalled_at_end(records, window, stall_s: float) -> list:
    """Requests of the load still open when the window ends that have shown
    no sign of life (a token-bearing frame; before the first, being sent)
    for ``stall_s`` seconds: hung. They never end, so ``finished_in_window``
    cannot see them; they count as failed."""
    w0, w1 = window

    def last_life(r):
        seen = [t for t, _ in r.frames if t <= w1]
        return seen[-1] if seen else r.sent

    return [r for r in records if r.phase != "ladder" and r.sent
            and (r.ended is None or r.ended > w1)
            and last_life(r) <= w1 - stall_s]


def end_to_end(raw: dict, seconds: float) -> dict:
    """The end-to-end metrics, all on the client's clock, over the window.

    Population of the latency metrics: requests that FINISHED inside the
    window and, counted as beyond every percentile, those that failed in
    it or hang at its end. ``out_tok_s`` counts every token received
    inside the window."""
    w0, w1 = raw["window"]
    hung = stalled_at_end(raw["records"], raw["window"],
                          raw.get("stall_s", DEFAULT_STALL_S))
    done = finished_in_window(raw["records"], raw["window"]) + hung
    ok = [r for r in done if r.ok and r not in hung]
    misses = len(done) - len(ok)
    ttft = [(r.first_token - r.due) * 1e3 for r in ok]
    tpot = [(r.frames[-1][0] - r.frames[0][0]) * 1e3 / (r.tokens - 1)
            for r in ok if r.tokens > 1]
    tokens = sum(n for r in raw["records"] if r.phase != "ladder"
                 for t, n in r.frames if w0 <= t <= w1)
    return {
        "ttft_p50_ms": stats.percentile_with_misses(ttft, misses, 50),
        "ttft_p90_ms": stats.percentile_with_misses(ttft, misses, 90),
        "tpot_p50_ms": stats.percentile_with_misses(tpot, misses, 50),
        "tpot_p90_ms": stats.percentile_with_misses(tpot, misses, 90),
        "out_tok_s": tokens / seconds,
        "setup_s": raw["setup_s"],
        "_attempted": len(done), "_failed": misses, "_hung": len(hung),
        "_tpot_p99_ms": stats.percentile(tpot, 99),
    }


def nan_free(x):
    return x is not None and not (isinstance(x, float)
                                  and (math.isnan(x) or math.isinf(x)))
