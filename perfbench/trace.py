"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: busy intervals of the device, per-module and per-operation
time, idle gaps. Kept with the benchmark and checked against a small
recorded trace (``testdata/``), so every PR computes these numbers the same
way.

Reading uses ``jax.profiler.ProfileData`` only. Importing jax does not
touch a device; the harness still imports this module only after the
server, which holds the chip, has exited.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclass
class DeviceTrace:
    name: str
    ops: list = field(default_factory=list)       # (start_ns, dur_ns, name)
    modules: list = field(default_factory=list)   # (start_ns, dur_ns, name)


@dataclass
class TraceSummary:
    devices: list           # DeviceTrace per device plane
    start_ns: float         # extent of everything the session recorded
    end_ns: float

    @property
    def window_s(self) -> float:
        return max(self.end_ns - self.start_ns, 0.0) / 1e9


def load(path: Path) -> TraceSummary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, lo, hi = [], None, None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and \
            "CUSTOM" not in plane.name.upper()
        dev = DeviceTrace(plane.name) if is_device else None
        for line in plane.lines:
            keep = dev is not None and line.name in (MODULE_LINE, OPS_LINE)
            for ev in line.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                lo = s if lo is None or s < lo else lo
                hi = s + d if hi is None or s + d > hi else hi
                if keep:
                    (dev.ops if line.name == OPS_LINE
                     else dev.modules).append((s, d, ev.name))
        if dev is not None and (dev.ops or dev.modules):
            dev.ops.sort()
            dev.modules.sort()
            devices.append(dev)
    return TraceSummary(devices, lo or 0.0, hi or 0.0)


def busy_intervals(events) -> list:
    """Union of (start, dur, ...) intervals -> sorted disjoint (start, end)."""
    out = []
    for s, d, *_ in sorted(events):
        e = s + d
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(dev: DeviceTrace) -> float:
    """Seconds in which an operation ran on the device (modules where the
    plane records no operation line)."""
    return sum(e - s for s, e in busy_intervals(dev.ops or dev.modules)) / 1e9


def mean_busy_seconds(trace: TraceSummary) -> float:
    if not trace.devices:
        return 0.0
    return sum(busy_seconds(d) for d in trace.devices) / len(trace.devices)


def module_time(dev: DeviceTrace, contains: str) -> tuple:
    """(summed seconds, count) of module executions whose name contains
    ``contains``."""
    hits = [d for _, d, name in dev.modules if contains in name]
    return sum(hits) / 1e9, len(hits)


def op_seconds_by_name(dev: DeviceTrace) -> dict:
    """SELF seconds by (shortened) operation name: the operation line nests
    (a ``while`` holds the fusions of its body), so each event's time is its
    duration minus what its children cover; the values sum to the busy
    time."""
    out: dict = {}
    stack: list = []                 # [end_ns, key, self_ns]

    def close(upto: float):
        while stack and stack[-1][0] <= upto:
            _, key, self_ns = stack.pop()
            out[key] = out.get(key, 0.0) + max(self_ns, 0.0) / 1e9
    for s, d, name in sorted(dev.ops, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, short_op(name), d])
    close(float("inf"))
    return out


def leaf_op_seconds(dev: DeviceTrace, match) -> float:
    """Busy seconds covered by operations whose name ``match`` accepts
    (union of their intervals: a fused parent and its children overlap)."""
    return sum(e - s for s, e in busy_intervals(
        ev for ev in dev.ops if match(ev[2]))) / 1e9


def _module_at(dev: DeviceTrace, t_ns: float, after: bool) -> str:
    """Name of the module that ends last before t (or starts first after)."""
    best = None
    for s, d, name in dev.modules:
        if after and s >= t_ns:
            return name
        if not after and s + d <= t_ns:
            best = name
    return best or "none"


def idle_gaps(dev: DeviceTrace, top: int = 5) -> list:
    """The longest gaps between busy intervals: [(label, seconds)], the
    label naming the modules on either side of the gap."""
    iv = busy_intervals(dev.ops or dev.modules)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(iv, iv[1:])),
                  reverse=True)[:top]
    return [(f"{short(_module_at(dev, a, False))}->"
             f"{short(_module_at(dev, b, True))}", g / 1e9)
            for g, a, b in gaps]


def short(name: str) -> str:
    """``jit_decode_window_sampled(1234567)`` -> ``jit_decode_window_sampled``."""
    return name.split("(")[0].strip() or name


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target=\\?"([\w.\-]+)')


def short_op(name: str) -> str:
    """An operation's trace name is its whole HLO text; keep the result
    name, the opcode and a custom call's target:
    ``%sort.9 = (f32[64,151936]...) sort(...)`` -> ``%sort.9 sort``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    m = _OPCODE.search(" " + rhs)
    out = f"{lhs.strip()} {m.group(1)}" if m else lhs.strip()
    t = _TARGET.search(rhs)
    return (out + (f" {t.group(1)}" if t else ""))[:80]
