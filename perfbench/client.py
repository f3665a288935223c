"""The load generator and SSE client: one asyncio loop, one thread.

Every timed request is a plain ``stream: true`` completion with a token-id
prompt and no ``logprobs``. A frame is token-bearing when its text holds at
least one word (the synthetic tokenizer renders one word per token), and
the client counts tokens by words. Times are ``time.perf_counter()``.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import time
from dataclasses import dataclass, field
from typing import Optional

import aiohttp


@dataclass(eq=False)        # identity-hashed: records live in sets
class Record:
    """What the client saw of one request."""
    index: int
    phase: str
    prompt_len: int
    max_tokens: int
    due: float                   # perf_counter time it was due
    sent: float = 0.0
    frames: list = field(default_factory=list)   # (time, tokens in frame)
    finish_reason: Optional[str] = None
    done: bool = False           # saw data: [DONE]
    status: Optional[int] = None
    error: Optional[str] = None
    ended: Optional[float] = None
    cancelled: bool = False      # the harness closed it (end of the run)

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.frames)

    @property
    def first_token(self) -> Optional[float]:
        return self.frames[0][0] if self.frames else None

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.done and self.error is None
                and self.finish_reason == "length"
                and self.tokens == self.max_tokens)

    @property
    def failed(self) -> bool:
        return not self.cancelled and self.ended is not None and not self.ok


class LoadClient:
    def __init__(self, base: str, model: str):
        self.base = base
        self.model = model
        self.records: list = []
        self.inflight: set = set()      # Records with a stream open
        self.errors: list = []          # exceptions of spawned tasks (bugs)
        self.stop_at = float("inf")     # perf_counter: nothing is sent after
        # the longest time with a stream open and no token on ANY stream
        # (a compile inside a step shows so while it lasts; the server's
        # step histogram shows it only once the step has ended)
        self._progress = 0.0
        self._worst_stall = 0.0
        self._tasks: set = set()
        self._session: Optional[aiohttp.ClientSession] = None

    async def __aenter__(self):
        self._session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=300))
        return self

    async def __aexit__(self, *exc):
        await self.cancel_all()
        await self._session.close()

    # -- one request --------------------------------------------------------

    async def stream(self, req, phase: str, due: float) -> Record:
        rec = Record(index=req.index, phase=phase, prompt_len=len(req.prompt),
                     max_tokens=req.max_tokens, due=due)
        self.records.append(rec)
        body = dict(req.body, model=self.model, prompt=req.prompt,
                    max_tokens=req.max_tokens, stream=True)
        rec.sent = time.perf_counter()
        if not self.inflight:
            self._progress = rec.sent       # idle time is no stall
        self.inflight.add(rec)
        try:
            async with self._session.post(self.base + "/v1/completions",
                                          json=body) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:300]
                    return rec
                async for raw in resp.content:
                    now = time.perf_counter()
                    line = raw.strip()
                    if not line.startswith(b"data:"):
                        continue
                    payload = line[5:].strip()
                    if payload == b"[DONE]":
                        rec.done = True
                        break
                    frame = json.loads(payload)
                    if "error" in frame:
                        rec.error = str(frame["error"])[:300]
                        continue
                    choice = frame["choices"][0]
                    n = len(choice.get("text", "").split())
                    if n:
                        rec.frames.append((now, n))
                        self._worst_stall = max(self._worst_stall,
                                                now - self._progress)
                        self._progress = now
                    if choice.get("finish_reason"):
                        rec.finish_reason = choice["finish_reason"]
        except asyncio.CancelledError:
            rec.cancelled = True
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                KeyError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            rec.ended = time.perf_counter()
            self.inflight.discard(rec)
        return rec

    def take_worst_stall(self) -> float:
        """Longest stall since the last call, the one still running
        included; resets the record."""
        worst = self._worst_stall
        if self.inflight:
            worst = max(worst, time.perf_counter() - self._progress)
        self._worst_stall = 0.0
        return worst

    def spawn(self, coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        self._tasks.add(task)
        task.add_done_callback(self._reap)
        return task

    def _reap(self, task: asyncio.Task) -> None:
        self._tasks.discard(task)
        if not task.cancelled() and task.exception() is not None:
            self.errors.append(repr(task.exception()))

    async def cancel_all(self) -> None:
        tasks = list(self._tasks)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    # -- loops --------------------------------------------------------------

    async def open_loop(self, requests, phase: str, t0: float) -> None:
        """Send each request at t0 + due_s whether or not earlier ones
        finished; returns when the last one was SENT."""
        for req in requests:
            due = t0 + req.due_s
            if due > self.stop_at:
                return
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            self.spawn(self.stream(req, phase, due))

    async def closed_client(self, requests, phase: str, start_at: float,
                            stop_at: float) -> None:
        """One closed-loop client: next request when the last finished.
        Its list starts over when it runs out (a cold run's pre-roll lasts
        minutes): the loop stays closed until ``stop_at``."""
        delay = start_at - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        for req in itertools.cycle(requests):
            now = time.perf_counter()
            if now >= min(stop_at, self.stop_at):
                return
            await self.stream(req, phase, now)

    # -- one untimed request (probes, warm-up) ------------------------------

    async def complete(self, body: dict, timeout_s: float = 900) -> dict:
        body = dict(body, model=self.model)
        async with self._session.post(
                self.base + "/v1/completions", json=body,
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            text = await resp.text()
            if resp.status != 200:
                raise RuntimeError(f"/v1/completions -> {resp.status}: "
                                   f"{text[:300]}")
            return json.loads(text)

    async def post(self, path: str, timeout_s: float = 120) -> tuple:
        """(status, body text) of a body-less POST."""
        async with self._session.post(
                self.base + path,
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            return resp.status, await resp.text()

    async def get_text(self, path: str, timeout_s: float = 30) -> str:
        async with self._session.get(
                self.base + path,
                timeout=aiohttp.ClientTimeout(total=timeout_s)) as resp:
            return await resp.text()

    def context_tokens_in_flight(self) -> tuple:
        """(rows decoding, their context tokens) as the client sees them:
        requests whose first token has arrived, prompt + tokens so far."""
        rows = [r for r in self.inflight if r.frames]
        return len(rows), sum(r.prompt_len + r.tokens for r in rows)
