"""Step-phase attribution and the step loop's own clock.

``LLMEngine.step()`` decomposes into named phases — schedule (host-side
batch assembly policy), host_prep (numpy packing + host->device upload),
device_dispatch (jit call; async dispatch, near-zero unless compiling),
device_fetch (the blocking device->host sync), postproc (stop checks,
output assembly), and detokenize (recorded by the HTTP layer, which owns
the tokenizer). A TTFT or tok/s regression then decomposes into a phase
delta instead of a guess — the attribution VERDICT r5 said was impossible
("no way to tell whether the time is queue wait, chunked-prefill stalls,
device step time, or host-side detokenize").

**What an iteration is** (since the one-deep device queue, PR 33): one call
of ``step()`` dispatches program n+1 and THEN fetches program n. So an
iteration's phases belong to two programs: schedule, host_prep and
device_dispatch to the one it launched, device_fetch and postproc to the
one it retired. Each dispatched program has one record (the dict the
engine's dispatchers fill) with its own number ``step`` and, on
``time.monotonic`` (the request tracer's clock), the stamps ``t_launch``
(entering ``_launch``), ``t_dispatched`` (the jit call returned), ``t_wait``
(the worker starts to block for it), ``t_ready`` (``block_until_ready``
returned) and ``t_retired`` (post-processing done); the phases are filed
under the program they served (``start_step`` / ``file_under``).
``retire()`` derives from the stamps, once: ``wait_s``, whether the program
was found ready (the host came after the chip), its device time and whether
that is exact, the gap to its predecessor's end, the host's lead over the
chip and whether both ends of that lead are the chip's, the time the chip
had nothing queued before it (``starved_s``), and whether the gap counts as
slow and by whose fault. The worker thread's wall is split at its own turns
into three states (``worker_turn``): waiting for the device, waiting for
the inbox, and everything else; the last idle turn is kept, so that a pause
for want of requests is never read as a starved chip.

**The frame's clock** goes on from the record's last two stamps, on the same
clock. What the rows of one program share is one ``StepClock`` (``step``,
``t_ready``, ``t_retired``: the engine's ``_fetching`` and ``_retired``
stamp it, every ``RequestOutput`` of the program holds the same object,
never the record, which holds the batch and device arrays). A frame's own
stamps ride a ``FrameClock`` on its ``StreamChunk``: ``t_posted`` (the
worker, at the row's ``call_soon_threadsafe``), ``t_woken`` (the event loop
runs the row's callback), ``t_resumed`` (the consumer comes back from
``await queue.get()``); the write's end is the moment
``Observability.on_frame`` is called. ``FRAME_STAGES`` names the five
distances between the six stamps; they add up to the frame delay without
remainder.

Cost per phase is two clock reads and a list append; per program a handful
of clock reads, subtractions and dict adds — microseconds against steps of
tens of milliseconds, all under the successor's device time.

While a profiler capture runs (``StepPhaseStats.capturing``, set by
``POST /debug/profile`` and by nothing else) every phase is ALSO a
``jax.profiler.TraceAnnotation`` named ``kgct.<phase>``, and ``span()`` gives
the spans that are no phase (``kgct.step``, the worker's and the HTTP
layer's): host spans in the profiler's own trace, on the device trace's
clock, so that a device idle gap can be laid on what the host was doing.
With ``capturing`` False a phase pays one attribute read for it and no
annotation object exists.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque

PHASES = ("schedule", "host_prep", "device_dispatch", "device_fetch",
          "postproc", "detokenize")
SPAN_PREFIX = "kgct."
STEP_KINDS = ("prefill", "decode", "mixed", "spec", "spec_mixed")
WORKER_STATES = ("host", "device_wait", "inbox_wait")
# A program whose wait took less than this was FOUND READY: the chip had
# finished it before the host asked, so its t_ready is the host's arrival,
# not the program's end.
FOUND_READY_S = 100e-6
# A program is slow when the gap between its predecessor's end and its own
# is over both: an absolute floor and a multiple of its kind's running mean.
SLOW_GAP_S = 0.5
SLOW_GAP_RATIO = 3.0


# The frame's path from the end of its program on the device to the write of
# the frame, cut at the stamps of StepClock and FrameClock.
FRAME_STAGES = ("retire", "post", "wake", "queue", "render")

# What span() hands out while no capture runs: one shared, reusable object.
_NO_SPAN = contextlib.nullcontext()


class StepClock:
    """What the frames of one step program share: its number and the last
    two stamps of its record (``time.monotonic``). One object a program,
    made when it is ready; ``t_retired`` is None until its post-processing
    is done, which is before any of its outputs leaves ``step()``."""
    __slots__ = ("step", "t_ready", "t_retired")

    def __init__(self, step: int, t_ready: float):
        self.step = step
        self.t_ready = t_ready
        self.t_retired = None


class FrameClock:
    """One frame's stamps behind its program's (module docstring): who
    stamps what, and ``stages`` for the five distances."""
    __slots__ = ("program", "t_posted", "t_woken", "t_resumed")

    def __init__(self, program: StepClock):
        self.program = program
        self.t_posted = self.t_woken = self.t_resumed = None

    def stages(self, t_written: float) -> tuple:
        """Seconds by FRAME_STAGES for a frame whose write returned at
        ``t_written``; their sum is ``t_written - program.t_ready``."""
        p = self.program
        return (p.t_retired - p.t_ready, self.t_posted - p.t_retired,
                self.t_woken - self.t_posted, self.t_resumed - self.t_woken,
                t_written - self.t_resumed)


def _annotation(name: str, **args):
    # Looked up at call time, and only while a capture runs: a process that
    # never profiles (the router) never imports jax for this.
    import jax.profiler
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


class _PhaseCtx:
    """Reusable context manager: ``with stats.phase("host_prep"):``."""
    __slots__ = ("_stats", "_name", "_rec", "_t0", "_span")

    def __init__(self, stats: "StepPhaseStats", name: str, rec):
        self._stats = stats
        self._name = name
        self._rec = rec
        self._span = None

    def __enter__(self):
        if self._stats.capturing:
            rec = self._rec
            # whose dispatch or fetch it is: the program's own number, its
            # kind, its real rows
            self._span = _annotation(self._name, **(
                {} if rec is None else
                {"step": rec["step"], "kind": rec["kind"],
                 "rows": rec["rows"]}))
            self._span.__enter__()
        # time.monotonic: the request tracer's clock, so a phase's start
        # lies on the /debug/trace timeline as it is.
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        dur = time.monotonic() - self._t0
        if self._span is not None:
            self._span.__exit__(*exc)
            self._span = None
        self._stats.record(self._name, dur, start=self._t0)
        return False


class StepPhaseStats:
    def __init__(self, capacity: int = 512):
        self.totals = {p: 0.0 for p in PHASES}
        self.counts = {p: 0 for p in PHASES}
        self.steps_recorded = 0
        # Per-program records for trace export: {"step", "kind", "batch",
        # "duration_s", "args", "phases": [(name, start_monotonic, dur_s),
        # ...]}: the phases that SERVED that program, whichever iteration
        # ran them.
        self._ring: deque[dict] = deque(maxlen=capacity)
        # The list phases are filed under right now: the record's of the
        # program being launched or retired (start_step / file_under).
        self._current: list = []
        # Out-of-step slices (the HTTP layer's detokenize) recorded from a
        # thread that is NOT the engine step loop: they must never touch
        # _current (the step loop swaps it unsynchronized), so they land in
        # their own ring and merge at export time.
        self._detached: deque = deque(maxlen=256)
        # True only for the seconds of a profile capture (the handler sets
        # it before start_trace and clears it before it calls stop_trace):
        # phases and spans then also write TraceAnnotations.
        self.capturing = False
        # The last program retired: (step, t_ready, found_ready), what its
        # successor's device time is reckoned from.
        self._ready: tuple = (None, 0.0, False)
        # kind -> [sum, count] of the ready gaps that were not slow: the
        # running mean a slow gap is held against.
        self._gap_mean: dict[str, list] = {}
        # The worker thread's clock: (state, since, totals by
        # WORKER_STATES), ONE tuple swapped whole at every turn, so that a
        # scrape on another thread reads a consistent triple.
        self._worker: tuple = (None, 0.0, (0.0,) * len(WORKER_STATES))
        # When the worker last turned to ``inbox_wait``: the engine held no
        # unfinished request then, so a distance between two programs that
        # spans it is a pause, not a starved chip.
        self._t_idle = float("-inf")

    def phase(self, name: str, rec: dict = None) -> _PhaseCtx:
        """``rec``: the record of the program a dispatch or a fetch serves;
        its step, kind and rows are the span's arguments while a capture
        runs, and it is never looked at otherwise."""
        return _PhaseCtx(self, name, rec)

    def span(self, name: str, **args):
        """``with phases.span("worker.post"):`` — a host span ``kgct.<name>``
        in the profiler's trace while a capture runs, nothing otherwise
        (no timing, no totals: a span that should be counted is a phase)."""
        if self.capturing:
            return _annotation(name, **args)
        return _NO_SPAN

    def record(self, name: str, dur: float, start: float = None) -> None:
        """Record one phase occurrence. ``start=None`` marks an out-of-step
        caller (the HTTP layer's detokenize, on the event-loop thread): it
        stamps now-dur and goes to the detached ring only — ``_current``
        belongs to the engine thread, which swaps it between programs."""
        self.totals[name] = self.totals.get(name, 0.0) + dur
        self.counts[name] = self.counts.get(name, 0) + 1
        if start is None:
            self._detached.append((name, time.monotonic() - dur, dur))
            return
        self._current.append((name, start, dur))

    def start_step(self) -> list:
        """A fresh list becomes the one phases are filed under: that of the
        program about to be launched (its record keeps it; a launch that
        schedules nothing drops it: no record, but the totals keep the
        time, an empty schedule() call is still schedule time)."""
        self._current = []
        return self._current

    def file_under(self, phases: list) -> None:
        """File what follows under a program launched earlier: the one now
        being fetched and post-processed."""
        self._current = phases

    def end_step(self, step: int, kind: str, batch: int, duration_s: float,
                 phases: list = None, **args) -> None:
        """One program is done: its slices go to the trace ring. ``args``
        ride every slice of it beside step, kind and batch (device_ms,
        wait_ms, lead_ms)."""
        self.steps_recorded += 1
        self._ring.append({"step": step, "kind": kind, "batch": batch,
                           "duration_s": duration_s, "args": args,
                           "phases": (self._current if phases is None
                                      else phases)})
        self._current = []

    # -- the program's own clock ---------------------------------------------

    def retire(self, rec: dict) -> dict:
        """Derive, once, from the stamps of the program ``rec`` and of its
        predecessor (the program that was in flight when it was launched:
        ``rec["pred"]``, its number, or None), and write into ``rec``:

        - ``wait_s`` = t_ready − t_wait, and ``found_ready``: it was under
          FOUND_READY_S, the host came after the chip;
        - ``device_s`` = t_ready − max(the predecessor's t_ready,
          t_dispatched); with no predecessor in flight the program starts
          at its own dispatch. ``exact`` when neither it nor its predecessor
          was found ready: only then do both ends lie where the chip put
          them;
        - ``ready_gap_s`` = t_ready − the predecessor's t_ready (None
          without one), exact or not;
        - ``lead_s`` = the predecessor's t_ready − t_dispatched: how long
          the program had been queued when the chip came to it (negative:
          the chip waited for the host); ``lead_exact`` when that
          predecessor was waited for (one found ready has the host's
          arrival as its end, not the chip's);
        - ``starved_s`` = t_dispatched − the t_ready of the program retired
          before it, where positive: the chip had nothing queued for that
          long. A chained program dispatched after its predecessor's end
          (``lead_s`` < 0), or one launched with nothing in flight behind a
          program already retired (a chain break: the whole
          fetch-then-schedule distance). 0.0 for the first program, for a
          predecessor that never came back, and where the worker turned to
          ``inbox_wait`` in between (no request, no work to queue);
        - ``slow``: None, or why the gap counts as slow ("host": found
          ready, or dispatched after the predecessor was ready — a compile,
          the GIL, the machine standing still; "device": waited for), when
          it is over SLOW_GAP_S and over SLOW_GAP_RATIO x the running mean
          of its kind's gaps that were not slow (a kind's first gap only
          starts the mean)."""
        t_ready, t_disp = rec["t_ready"], rec["t_dispatched"]
        wait_s = t_ready - rec["t_wait"]
        found = wait_s < FOUND_READY_S
        p_step, p_ready, p_found = self._ready
        pred = rec.get("pred")
        has_pred = p_step is not None and p_step == pred
        self._ready = (rec["step"], t_ready, found)
        gap = lead = slow = None
        start, exact = t_disp, not found
        starved = 0.0
        if (p_step is not None and (has_pred or pred is None)
                and self._t_idle < p_ready):
            starved = max(t_disp - p_ready, 0.0)
        if has_pred:
            gap = t_ready - p_ready
            lead = p_ready - t_disp
            start = max(p_ready, t_disp)
            exact = exact and not p_found
            mean = self._gap_mean.setdefault(rec["kind"], [0.0, 0])
            if (gap > SLOW_GAP_S and mean[1]
                    and gap > SLOW_GAP_RATIO * mean[0] / mean[1]):
                slow = "host" if found or lead < 0 else "device"
            else:
                mean[0] += gap
                mean[1] += 1
        rec.update(wait_s=wait_s, found_ready=found,
                   device_s=t_ready - start, exact=exact, ready_gap_s=gap,
                   lead_s=lead, lead_exact=has_pred and not p_found,
                   starved_s=starved, slow=slow)
        return rec

    def worker_turn(self, state: str, now: float = None) -> None:
        """The worker thread enters ``state`` (one of WORKER_STATES) at
        ``now``; what has passed since its last turn goes to the state it
        leaves. The first turn starts the clock."""
        if now is None:
            now = time.monotonic()
        cur, since, totals = self._worker
        if cur is not None:
            i = WORKER_STATES.index(cur)
            totals = totals[:i] + (totals[i] + now - since,) + totals[i + 1:]
        self._worker = (state, now, totals)
        if state == "inbox_wait":
            self._t_idle = now

    def worker_seconds(self) -> dict:
        """Seconds by state up to now, the running state's share included:
        the three add up to the time since the first turn. Safe from any
        thread (one read of the tuple)."""
        cur, since, totals = self._worker
        out = dict(zip(WORKER_STATES, totals))
        if cur is not None:
            out[cur] += max(time.monotonic() - since, 0.0)
        return out

    def step_records(self) -> list[dict]:
        return list(self._ring)

    def detached_records(self) -> list[dict]:
        """Out-of-step slices wrapped in the step-record shape so the trace
        exporter renders them on the engine.step track like any phase."""
        slices = list(self._detached)
        if not slices:
            return []
        return [{"step": -1, "kind": "http", "batch": 0, "phases": slices}]

    def clear_records(self) -> None:
        """Drop the per-step and detached rings (a ``?clear=1`` scoped trace
        capture); cumulative totals/counts — the /metrics contract — stay."""
        self._ring.clear()
        self._detached.clear()
