"""What the host was doing in the device's idle gaps: the program's own
spans (``kgct.*`` ``TraceAnnotation`` events, written into the profiler's
trace while a capture runs) laid over the complement of the device's busy
intervals. Both are on the profiler's clock, in one ``.xplane.pb``.

The threads of a Python process are lines named alike (``python``) on the
``/host:CPU`` plane, so the step loop's thread is found by what it wrote:
the line that holds the ``kgct.step`` spans. Spans on a thread nest
(``kgct.step`` holds the phases); every instant belongs to the DEEPEST span
that covers it, and what of a parent no child covers is the parent's self
time, under the parent's name.

Reading uses ``jax.profiler.ProfileData`` only, and nothing of the program.
A trace without ``kgct.*`` events (a program that writes none) gives
``None``, not an error.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

from . import trace as tr

PREFIX = "kgct."
STEP = "kgct.step"          # the span that marks the step loop's thread
CLOCK = "kgct.clock"        # carries time.monotonic_ns() of its own start
UNATTRIBUTED = "unattributed"


@dataclass
class HostSpans:
    threads: list = field(default_factory=list)   # per host line: sorted
    #                                               [(start_ns, dur_ns, name)]
    clock: Optional[tuple] = None   # (start_ns in the trace, monotonic_ns)

    def worker(self) -> list:
        """Spans of the step loop's thread: the line with the most
        ``kgct.step`` events ([] when no line has one)."""
        best = max(self.threads, default=[],
                   key=lambda spans: sum(1 for s in spans if s[2] == STEP))
        return best if any(s[2] == STEP for s in best) else []


def find_capture(profile_root: Path) -> Path:
    """The one ``*.xplane.pb`` under the benchmark's profile root. A run
    empties its own directory first and removes it when its readers are
    done, so while they run exactly one capture lies there; anything else
    is somebody else's trace and no number may be read from it."""
    files = sorted(Path(profile_root).glob("*/plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_root}, "
                           f"found {len(files)}: {[str(f) for f in files]}")
    return files[0]


def load(path: Path) -> HostSpans:
    from jax.profiler import ProfileData
    out = HostSpans()
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                name = ev.name
                if not name.startswith(PREFIX):
                    continue
                if name == CLOCK:
                    stamp = dict(ev.stats).get("monotonic_ns")
                    if stamp is not None:
                        out.clock = (float(ev.start_ns), int(stamp))
                    continue
                spans.append((float(ev.start_ns), float(ev.duration_ns),
                              name))
            if spans:
                out.threads.append(sorted(spans, key=lambda s: (s[0], -s[1])))
    return out


def deepest(spans) -> list:
    """Nested spans of ONE thread -> sorted disjoint segments
    ``(start, end, name)``, each instant under the deepest span that covers
    it. A child that outlasts its parent (clock jitter at the edges) is cut
    at the parent's end."""
    out: list = []
    stack: list = []            # [end, name]

    def emit(a: float, b: float, name: str):
        if b > a:
            out.append((a, b, name))

    t = None                    # where the open segment starts
    for s, d, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(t, end, top)
            t = end
        if stack:
            emit(t, s, stack[-1][1])
        e = s + d
        if stack and e > stack[-1][0]:
            e = stack[-1][0]
        stack.append([e, name])
        t = s
    while stack:
        end, top = stack.pop()
        emit(t, end, top)
        t = end
    return out


def idle_intervals(dev: tr.DeviceTrace) -> list:
    """Gaps between the device's busy intervals, from its first operation to
    its last: sorted disjoint ``(start, end)``."""
    iv = tr.busy_intervals(dev.ops or dev.modules)
    return [(a[1], b[0]) for a, b in zip(iv, iv[1:]) if b[0] > a[1]]


def split(idle, segments) -> dict:
    """Nanoseconds of ``idle`` (sorted disjoint intervals) under each
    segment name; what no segment covers under ``UNATTRIBUTED``."""
    out: dict = {}
    total = covered = 0.0
    k = 0
    for a, b in idle:
        total += b - a
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        j = k
        while j < len(segments) and segments[j][0] < b:
            s, e, name = segments[j]
            part = min(e, b) - max(s, a)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            j += 1
    out[UNATTRIBUTED] = total - covered
    return out


def idle_by_span(summary: tr.TraceSummary, host: HostSpans) -> Optional[dict]:
    """Share (0..1) of the devices' idle time under each span name of the
    step loop's thread, ``UNATTRIBUTED`` for the rest; the shares sum to 1.
    ``None`` where the trace has no device plane, no idle time, or no
    ``kgct.step`` span."""
    worker = host.worker()
    if summary is None or not summary.devices or not worker:
        return None
    segments = deepest(worker)
    ns: dict = {}
    for dev in summary.devices:
        for name, v in split(idle_intervals(dev), segments).items():
            ns[name] = ns.get(name, 0.0) + v
    total = sum(ns.values())
    if total <= 0:
        return None
    return {name: v / total for name, v in ns.items()}
