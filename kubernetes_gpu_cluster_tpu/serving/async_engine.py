"""AsyncLLMEngine: asyncio front door over the blocking LLMEngine.

The engine's step() blocks on device sync, so it runs on a dedicated worker
thread; request submission and output streaming cross the thread boundary
through a thread-safe inbox and ``loop.call_soon_threadsafe`` fan-out into
per-request asyncio queues. This is the piece that turns the batch engine
into the always-on serving process behind the OpenAI API (the role vLLM's
AsyncLLMEngine played inside the images the reference deployed,
``old_README.md:1078-1176``).

The worker thread idles on a condition variable when there is no work — an
idle replica burns no CPU and wakes in O(µs) on the first request.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import threading
import time
from typing import AsyncIterator, Optional

from ..analysis.sanitize import build_interleave_sanitizer
from ..config import EngineConfig
from ..engine import LLMEngine, RequestOutput, SamplingParams
from ..observability import FrameClock
from ..utils import get_logger
from ..utils.stack import roomy_stack

logger = get_logger("serving.async_engine")


@dataclasses.dataclass
class StreamChunk:
    """One step's worth of progress for a request.

    ``clock`` is the frame's clock (observability/phases.py), all of it on
    ``time.monotonic``: the program's part (``clock.program``: its number,
    ``t_ready``, ``t_retired``, one object shared by the program's rows,
    stamped by the engine's worker in ``_fetching`` and ``_retired``), and
    this frame's own: ``t_posted`` (the worker, in ``_post``, at the row's
    hand-over to the event loop), ``t_woken`` (the loop, in ``_deliver``,
    when the row's callback runs) and ``t_resumed`` (the loop, in
    ``generate``, when the consumer comes back from ``await queue.get()``).
    The HTTP layer closes it at the write's return
    (``Observability.on_frame``: ``kgct_frame_delay_seconds`` and its five
    stages). None where no program made the chunk (abort, import,
    migration): such a frame is held against nothing."""
    request_id: str
    new_token_ids: list[int]
    output_token_ids: list[int]
    finished: bool
    finish_reason: Optional[str]
    new_logprobs: list[float] = dataclasses.field(default_factory=list)
    new_top_logprobs: list = dataclasses.field(default_factory=list)
    clock: Optional[FrameClock] = None


class AsyncLLMEngine:
    def __init__(self, config: EngineConfig, params=None,
                 eos_token_id: Optional[int] = None, mesh=None,
                 leader=None, draft_params=None):
        """``leader``: serving.multihost.DirectiveLeader when this process
        is rank 0 of a multi-process mesh — every worker-loop iteration's
        (adds, aborts) are broadcast to follower ranks BEFORE the local
        apply+step so all engines schedule in SPMD lockstep.
        ``draft_params``: pre-loaded draft-model weights
        (--spec-draft-weights); None random-inits when spec_draft_model is
        configured."""
        self.engine = LLMEngine(config, params=params,
                                eos_token_id=eos_token_id, mesh=mesh,
                                draft_params=draft_params)
        self.leader = leader
        # resilience.StepWatchdog, set by APIServer: armed around each
        # step() so a hung device dispatch flips /health instead of parking
        # requests forever behind a 200-ok server.
        self.watchdog = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._queues: dict[str, asyncio.Queue] = {}
        # Ids reserved via reserve_request_id whose generate() has not
        # started yet — lets release_reservation() free a slot the handler
        # abandoned (client died between reserve and first iteration)
        # without ever touching a live generator's queue.
        self._reserved: set = set()
        self._inbox: list = []            # (request_id, token_ids, params)
        self._aborts: list[str] = []
        # Disaggregated prefill/decode side-channels, keyed by request id so
        # the inbox tuples keep the exact shape the multihost directive
        # broadcast serializes: _handoffs holds a decoded KV-handoff state
        # an inbox entry should IMPORT instead of prefilling; _holds marks
        # entries whose finished KV the export seam will collect. Both are
        # leader-gated at generate() — handoff does not compose with SPMD
        # lockstep (followers would never see the import).
        self._handoffs: dict[str, dict] = {}
        self._holds: set = set()
        # Mid-stream failover: already-relayed output token ids to replay
        # as forced context when an entry is admitted WITHOUT (or after a
        # failed) KV import — the recompute rung of the resume ladder.
        self._resumes: dict[str, list] = {}
        # Backdated arrival stamps (time.monotonic) for requests whose
        # handoff pull FAILED before admission: the burned pull wait is
        # client-observed TTFT and must reach the histogram/SLO window.
        self._arrival_t0s: dict[str, float] = {}
        # Serving-layer hook: an ENGINE-side import failure (no batch seat,
        # no pages, state mismatch) degrades to local recompute after the
        # pull was already accounted — without this the operator's fallback
        # counter reads 100% successful imports on a replica that recomputes
        # everything. Set by APIServer; called on the worker thread.
        self.on_import_fallback = None
        # Worker-thread operations (the export seam): (fn(engine), future)
        # pairs executed between steps, where every engine/scheduler/device
        # touch is single-threaded by construction.
        self._ops: list = []
        # KGCT_SANITIZE_INTERLEAVE: deterministic seeded yields at the
        # loop/worker seam crossings (None when off — every hook is one
        # `is None` test, byte-identical to the sanitizer being absent).
        self._interleave = build_interleave_sanitizer()
        self._cv = threading.Condition()
        self._shutdown = False
        self._counter = itertools.count()
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="kgct-engine-step-loop")

    # -- lifecycle -----------------------------------------------------------

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
        # get_running_loop, not get_event_loop: the fan-out posts chunks
        # via call_soon_threadsafe, and a loop silently CREATED here (off
        # the server's thread, never run) would swallow them forever —
        # kgct-lint KGCT006 pins this.
        self._loop = loop if loop is not None else asyncio.get_running_loop()
        self._thread.start()

    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify()
        self._thread.join(timeout=30)
        if self.leader is not None:
            if self._thread.is_alive():
                # A wedged worker may still write the directive sockets;
                # closing now would interleave frames and corrupt the
                # follower's NDJSON stream. Leave the sockets to the OS.
                logger.warning("worker thread still alive after join "
                               "timeout; skipping leader close")
            else:
                self.leader.close()

    # -- request API ---------------------------------------------------------

    def next_request_id(self, prefix: str = "cmpl") -> str:
        return f"{prefix}-{next(self._counter)}"

    def reserve_request_id(self, request_id: str) -> bool:
        """Atomically claim ``request_id``'s output-queue slot (False if a
        live request already holds it). Synchronous on the event-loop
        thread — no await between check and claim — so the API layer calls
        this immediately before ``generate()`` and a concurrent duplicate
        of a client-supplied correlation id can never cross streams (an
        async-generator-side check would only run at first iteration,
        AFTER the caller's awaits — the TOCTOU this closes). Callers must
        pair it with :meth:`release_reservation` on every handler exit
        path, or an abandoned reservation would mark the id in-flight
        forever."""
        if request_id in self._queues:
            return False
        self._queues[request_id] = asyncio.Queue()
        self._reserved.add(request_id)
        return True

    def release_reservation(self, request_id: str) -> bool:
        """Free a reservation whose ``generate()`` never STARTED (the
        handler died between reserve and the generator's first iteration —
        e.g. ``resp.prepare`` raising on client disconnect). A no-op once
        the generator consumed the reservation: its own finally owns the
        queue's lifetime from then on.

        Returns True when a reservation WAS released — the engine never saw
        the request, so the caller must NOT enqueue an abort for it: a
        stale abort of a reused client-supplied id would terminate (or
        orphan) a LATER request that legitimately claims the same id."""
        if request_id in self._reserved:
            self._reserved.discard(request_id)
            self._queues.pop(request_id, None)
            return True
        return False

    async def generate(self, request_id: str, prompt_token_ids: list[int],
                       params: SamplingParams, handoff: dict = None,
                       hold_kv: bool = False,
                       arrival_t0: Optional[float] = None,
                       resume_outputs: Optional[list] = None
                       ) -> AsyncIterator[StreamChunk]:
        """Submit a request and yield StreamChunks until finished.

        Id contract: serving callers reserve the id first (see
        reserve_request_id, looped until owned); a DIRECT caller must use
        an id it knows to be unique — calling with an id that has a
        pending reservation would consume the reserver's slot (there is
        one namespace, no per-claimant tokens).

        Disaggregated prefill/decode: ``handoff`` carries a decoded
        KV-handoff state (serving/handoff.py) — the worker IMPORTS it as
        committed history and only falls back to a normal admission
        (local recompute, byte-identical) when the import fails.
        ``hold_kv`` marks a prefill-replica request whose finished KV the
        export seam collects (run_in_worker -> engine.export_held). Both
        are ignored under a multihost leader: import/hold on rank 0 alone
        would desynchronize the SPMD lockstep.

        ``resume_outputs``: mid-stream failover — output tokens a dead
        replica already relayed, replayed as forced context when the entry
        admits WITHOUT a usable ``handoff`` (none parked, or the import
        failed): the engine pre-seeds them as output history and the
        stream carries only genuinely new tokens. With ``handoff`` set,
        this is the import's fallback rung — a plain re-prefill of the
        prompt alone would re-emit every already-relayed token."""
        izer = self._interleave
        if izer is not None and izer.decide("generate.submit")[0]:
            # Equivalent to the caller being scheduled later: runs before
            # the reservation consume, so no new await-window opens
            # between a guard and its claim.
            await asyncio.sleep(0)
        if request_id in self._reserved:
            # Consume the slot reserve_request_id claimed for us.
            self._reserved.discard(request_id)
            queue: asyncio.Queue = self._queues[request_id]
        else:
            # Direct (unreserved) callers keep the pre-reservation
            # semantics: a FRESH queue, overwriting any collision — two
            # consumers must never share one queue (the old consumer
            # orphans, exactly as before the reservation seam existed).
            queue = asyncio.Queue()
            self._queues[request_id] = queue
        with self._cv:
            if self.leader is None:
                if handoff is not None:
                    self._handoffs[request_id] = handoff
                if hold_kv:
                    self._holds.add(request_id)
                if arrival_t0 is not None:
                    self._arrival_t0s[request_id] = arrival_t0
                if resume_outputs:
                    self._resumes[request_id] = list(resume_outputs)
            self._inbox.append((request_id, prompt_token_ids, params))
            self._cv.notify()
        try:
            while True:
                chunk = await queue.get()
                clock = getattr(chunk, "clock", None)   # (or an Exception)
                if clock is not None:
                    clock.t_resumed = time.monotonic()
                if izer is not None and izer.decide("generate.stream")[0]:
                    await asyncio.sleep(0)
                if isinstance(chunk, Exception):
                    raise chunk
                yield chunk
                if chunk.finished:
                    return
        finally:
            self._queues.pop(request_id, None)

    def abort(self, request_id: str) -> None:
        with self._cv:
            self._aborts.append(request_id)
            self._cv.notify()

    def post_exception(self, request_id: str, exc: Exception) -> None:
        """Fail a live stream's consumer with ``exc`` (thread-safe; no-op
        when the queue is gone). The drain-migration driver uses it to
        abort a client connection AFTER its sequence was pushed to a peer
        — the broken relay is the router's failover signal — without
        touching engine state (the export already retired the sequence)."""
        self._post_exc(request_id, exc)

    def run_in_worker(self, fn):
        """Awaitable execution of ``fn(engine)`` on the worker thread —
        the one place engine/scheduler/device state may be touched outside
        step() without racing it (the KV export seam runs here). The
        result (or exception) resolves the returned awaitable."""
        import concurrent.futures
        fut: concurrent.futures.Future = concurrent.futures.Future()
        with self._cv:
            if self._worker_dead():
                # An op enqueued after the worker's final wakeup would never
                # drain and its awaiter would hang forever.
                fut.set_exception(RuntimeError("engine shut down"))
            else:
                self._ops.append((fn, fut))
                self._cv.notify()
        return asyncio.wrap_future(fut)

    def post_to_worker(self, fn) -> None:
        """Fire-and-forget variant of :meth:`run_in_worker` (cleanup from
        handler ``finally`` blocks, where awaiting mid-cancellation is
        unsafe)."""
        with self._cv:
            if self._worker_dead():
                # Engine-side state the op would have cleaned dies with the
                # process anyway; dropping loudly beats a silent no-op.
                logger.warning("worker op dropped: engine shut down")
                return
            self._ops.append((fn, None))
            self._cv.notify()

    def _worker_dead(self) -> bool:
        """Caller holds ``_cv``. True once no future wakeup can drain
        ``_ops``: shutdown requested (the worker's final wakeup fails
        whatever it captured — anything appended later is unreachable), or
        the thread exited (step-crash path; it flags ``_shutdown`` too,
        this also covers a crash mid-unwind)."""
        return self._shutdown or (self._thread.ident is not None
                                  and not self._thread.is_alive())

    # -- worker thread -------------------------------------------------------

    # roomy_stack: no call site below sits on the edge of a frame chunk
    # (utils/stack.py: a first use of a program took 3.5 s longer there).
    @roomy_stack
    def _worker(self) -> None:
        izer = self._interleave
        # Host spans of a profiler capture (observability/phases.py): what
        # the worker does outside engine.step(). Nothing while none runs.
        span = self.engine.obs.phases.span
        # The thread's wall, split at its own turns (kgct_worker_seconds_
        # total): ``inbox_wait`` here, ``device_wait`` inside the engine's
        # fetch, ``host`` everything else (GIL waits included). This first
        # turn starts the clock.
        turn = self.engine.obs.phases.worker_turn
        turn("host")
        while True:
            with self._cv:
                while not (self._shutdown or self._inbox or self._aborts
                           or self._ops
                           or self.engine.has_unfinished_requests()):
                    with span("worker.wait"):
                        turn("inbox_wait")
                        self._cv.wait()
                        turn("host")
                inbox, self._inbox = self._inbox, []
                aborts, self._aborts = self._aborts, []
                ops, self._ops = self._ops, []
                if self._shutdown:
                    # Fail pending worker ops loudly: an awaiting export
                    # must not hang past the thread's death.
                    for _, fut in ops:
                        if fut is not None:
                            fut.set_exception(
                                RuntimeError("engine shut down"))
                    return
            if izer is not None:
                # Post-wake, OUTSIDE _cv (a sleep under a loop-contended
                # lock is the KGCT021 bug class itself): widen the window
                # between inbox capture and ops/admission/step.
                izer.worker_yield("worker.wake")
            with span("worker.admit"):
                for fn, fut in ops:
                    try:
                        result = fn(self.engine)
                    except BaseException as e:
                        if fut is not None:
                            fut.set_exception(e)
                        else:
                            logger.exception("worker op failed")
                    else:
                        if fut is not None:
                            fut.set_result(result)
                # A request whose add and abort arrived in the same wakeup must
                # not be admitted: the abort would no-op (nothing to abort yet)
                # and the request would then run orphaned to completion.
                aborted = set(aborts)
                inbox = [item for item in inbox if item[0] not in aborted]
                for rid in aborted:
                    self._handoffs.pop(rid, None)
                    self._holds.discard(rid)
                    self._arrival_t0s.pop(rid, None)
                    self._resumes.pop(rid, None)
                if self.leader is not None:
                    # Replicate this iteration's events to follower ranks
                    # BEFORE stepping: their engines apply the same events and
                    # step once, keeping the SPMD collectives in lockstep. A
                    # broadcast failure means the process group is broken (a
                    # dead follower hangs the collectives anyway): group-abort
                    # all in-flight work, fail every waiter loudly, and detach
                    # the leader — this rank stays serveable while the
                    # StatefulSet restarts the followers (restart-first
                    # recovery).
                    try:
                        self.leader.broadcast(inbox, aborts)
                    except Exception as e:
                        logger.exception("directive broadcast failed; "
                                         "group-aborting in-flight work")
                        # Waiters fail FIRST: the drain below steps an engine
                        # whose process group just broke, and on a real
                        # multi-host mesh those steps can hang on collectives —
                        # clients must not be held hostage to that.
                        err = RuntimeError(
                            f"multihost process group failed: {e}")
                        for rid in list(self._queues):
                            self._post_exc(rid, err)
                        try:
                            self.leader.close()
                        except Exception:
                            pass
                        self.leader = None
                        from .multihost import group_abort
                        # Armed watchdog: if the drain DOES hang on a dead
                        # rank's collectives, /health flips and kubelet
                        # restarts the pod (restart-first recovery) instead of
                        # leaving a healthy-looking zombie.
                        wd = self.watchdog
                        if wd is not None:
                            wd.arm()
                        try:
                            group_abort(self.engine)
                        except Exception:
                            logger.exception("group-abort drain failed")
                        finally:
                            if wd is not None:
                                wd.disarm()
                        continue
                for rid in aborts:
                    self.engine.abort_request(rid)
                    self._post(StreamChunk(rid, [], [], True, "abort"))
                for rid, ids, params in inbox:
                    handoff = self._handoffs.pop(rid, None)
                    arrival_t0 = self._arrival_t0s.pop(rid, None)
                    resume_outputs = self._resumes.pop(rid, None)
                    hold = rid in self._holds
                    self._holds.discard(rid)
                    try:
                        if handoff is not None:
                            # import_request pops the stamp; keep a copy so an
                            # ENGINE-side import failure backdates the
                            # recompute admission the same way a failed pull
                            # does.
                            if arrival_t0 is None:
                                arrival_t0 = handoff.get("_ttft_t0")
                            try:
                                for out in self.engine.import_request(
                                        rid, ids, params, handoff):
                                    self._post(_chunk_of(out))
                                continue
                            except Exception as e:
                                # Degrade to local recompute — byte-identical,
                                # just slower; the trace records the fallback.
                                logger.warning(
                                    "kv import for %s failed (%s); falling back"
                                    " to local prefill", rid, e,
                                    extra={"request_id": rid})
                                self.engine.obs.tracer.emit(
                                    "handoff", rid, side="import",
                                    outcome="import_fallback", error=str(e))
                                if self.on_import_fallback is not None:
                                    try:
                                        # rid lets the serving layer attribute
                                        # a MID-STREAM resume import (token-
                                        # replay rung) separately from a
                                        # disagg prefill re-run.
                                        self.on_import_fallback(rid)
                                    except Exception:
                                        logger.exception(
                                            "import-fallback hook failed")
                        self.engine.add_request(rid, ids, params, hold_kv=hold,
                                                arrival_t0=arrival_t0,
                                                resume_outputs=resume_outputs)
                    except ValueError as e:   # oversized prompt etc.
                        self._post_exc(rid, e)
            if self.engine.has_unfinished_requests():
                if izer is not None:
                    # Between admission and dispatch: the window a loop-
                    # side engine-state read (KGCT020) would race.
                    izer.worker_yield("worker.step")
                wd = self.watchdog
                if wd is not None:
                    wd.arm()
                try:
                    outs = self.engine.step()
                    with span("worker.post", **_posted(outs)):
                        for out in outs:
                            self._post(_chunk_of(out))
                except Exception as e:  # engine wedged: fail all waiters
                    logger.exception("engine step failed")
                    # Black-box dump: the ring holds the requests/steps that
                    # led here; the pod restarts, the evidence does not.
                    self.engine.obs.flight.dump("engine_step_failed",
                                                error=str(e))
                    if wd is not None:
                        # The loop is about to die: /health must STAY 503
                        # (a disarm here would resurrect health on a server
                        # that can never serve again; kubelet restarts it).
                        wd.mark_dead(f"engine step raised: {e}")
                    for rid in list(self._queues):
                        self._post_exc(rid, e)
                    # The loop is exiting for good: flag shutdown and fail
                    # any ops racing this unwind, so run_in_worker callers
                    # (KV export handlers) never await a drained-by-nobody
                    # future.
                    with self._cv:
                        self._shutdown = True
                        ops, self._ops = self._ops, []
                    for _, fut in ops:
                        if fut is not None:
                            fut.set_exception(
                                RuntimeError(f"engine step raised: {e}"))
                    return
                if wd is not None:
                    wd.disarm()

    def _post(self, chunk: StreamChunk) -> None:
        queue = self._queues.get(chunk.request_id)
        if queue is not None and self._loop is not None:
            if chunk.clock is not None:
                chunk.clock.t_posted = time.monotonic()
            self._loop.call_soon_threadsafe(_deliver, queue, chunk)

    def _post_exc(self, request_id: str, exc: Exception) -> None:
        queue = self._queues.get(request_id)
        if queue is not None and self._loop is not None:
            self._loop.call_soon_threadsafe(queue.put_nowait, exc)


def _deliver(queue: asyncio.Queue, chunk: StreamChunk) -> None:
    """On the event loop, the callback of one row's hand-over: the loop's
    turn came (the end of the frame's ``wake`` stage), the chunk joins its
    request's queue."""
    if chunk.clock is not None:
        chunk.clock.t_woken = time.monotonic()
    queue.put_nowait(chunk)


def _posted(outs: list) -> dict:
    """Arguments of the span ``kgct.worker.post`` over ``outs``: the rows
    it hands over and the number of the program that made them (the one
    ``engine.step()`` just retired; -1: none did)."""
    step = next((o.clock.step for o in outs if o.clock is not None), -1)
    return {"step": step, "rows": len(outs)}


def _chunk_of(out: RequestOutput) -> StreamChunk:
    return StreamChunk(
        request_id=out.request_id,
        new_token_ids=list(out.new_token_ids or []),
        output_token_ids=list(out.output_token_ids),
        finished=out.finished,
        finish_reason=out.finish_reason,
        new_logprobs=list(out.new_logprobs or []),
        new_top_logprobs=list(out.new_top_logprobs or []),
        clock=None if out.clock is None else FrameClock(out.clock))
