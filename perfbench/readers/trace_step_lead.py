"""How long a step program had been queued when the device came to it: for
each ``kgct.device_dispatch`` span of the capture (a ``TraceAnnotation`` of
the step loop's thread that carries ``step``, ``kind`` and ``rows`` of the
program it dispatched), the start of the module it launched minus the
span's end, the MEDIAN over the capture. The module it launched is the
first step module (its name holds ``decode_window``, ``mixed_step`` or
``prefill``) that starts after the span's start; it must be of the span's
``kind`` or the span is left out, and modules that touch an edge of the
capture are dropped (their start or end is the capture's, not their own).
Both lie on the profiler's clock, in one ``.xplane.pb``.

A large lead says the host is far ahead of the chip; a lead near 0 says
the chip starts a program as soon as it is dispatched: the host binds.

The capture is looked up as ``trace_idle_by_span`` does it. A program whose
dispatch spans carry no ``kind`` (the parent of the PR that brought this
reader) reads nothing.
"""

import statistics
from pathlib import Path

from .. import host_spans as hs
from .. import trace as tr
from ..server import PROFILE_ROOT

DISPATCH = "kgct.device_dispatch"
MODULE_OF_KIND = {"decode": "decode_window", "mixed": "mixed_step",
                  "prefill": "prefill"}


def dispatch_spans(path: Path) -> list:
    """[(start_ns, end_ns, kind)] of the capture's dispatch spans that name
    their program's kind, sorted."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != DISPATCH:
                    continue
                kind = dict(ev.stats).get("kind")
                if isinstance(kind, bytes):
                    kind = kind.decode()
                if kind is not None:
                    s = float(ev.start_ns)
                    out.append((s, s + float(ev.duration_ns), str(kind)))
    return sorted(out)


def _kind_of(module: str):
    return next((k for k, n in MODULE_OF_KIND.items() if n in module), None)


def leads_ns(spans: list, modules: list) -> list:
    """``spans``: [(start, end, kind)]; ``modules``: one device's
    [(start, dur, name)], sorted. The lead of every span whose module is
    whole and of its kind."""
    if not modules:
        return []
    lo = min(s for s, _, _ in modules)
    hi = max(s + d for s, d, _ in modules)
    steps = sorted((s, s + d, kind) for s, d, name in modules
                   for kind in [_kind_of(name)] if kind is not None)
    out, k = [], 0
    for start, end, kind in spans:
        while k < len(steps) and steps[k][0] < start:
            k += 1
        if k == len(steps):
            break
        m_start, m_end, m_kind = steps[k]
        if m_kind != kind or m_start <= lo or m_end >= hi:
            continue
        out.append(m_start - end)
    return out


def read(spec, ctx):
    path = ctx.get("trace_path")
    if path is None:
        if ctx.get("trace") is None and not (ctx.get("profile")
                                             or {}).get("reply"):
            return None                     # a run without a capture
        path = hs.find_capture(PROFILE_ROOT)
    path = Path(path)
    summary = ctx.get("trace") or tr.load(path)
    if not summary.devices:
        return None
    got = leads_ns(dispatch_spans(path), summary.devices[0].modules)
    if not got:
        return None
    return statistics.median(got) / 1e9 * spec.get("scale", 1.0)
