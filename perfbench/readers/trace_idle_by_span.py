"""Share of the device's idle time (between its first and last operation in
the capture) that lies under the program's host spans named in ``spans``
on the step loop's thread (a name ending in ``*`` matches by prefix), plus,
with ``self_of``, under that parent span's own time, which no child span
covers -- ``host_spans.py``; the spans are ``kgct.*`` TraceAnnotations that
the program writes while a capture runs.

``ctx`` has the parsed device planes (``trace``) but not the file, so the
capture is looked up where ``harness.collect_trace`` finds it: the one
``*.xplane.pb`` under ``server.PROFILE_ROOT`` (``ctx["trace_path"]`` wins
once a harness provides it). In a run with a capture the split is worked
out once, on the first call, also when ``trace`` is not in ``ctx`` yet
(the pass that fills the ``observed`` line). A program that writes no such
spans gives ``None`` for every metric of this reader.
"""

from pathlib import Path

from .. import host_spans as hs
from .. import trace as tr
from ..server import PROFILE_ROOT

_SPLITS: dict = {}          # capture path -> idle_by_span() of it


def split_of(ctx):
    path = ctx.get("trace_path")
    if path is None:
        if ctx.get("trace") is None and not (ctx.get("profile")
                                             or {}).get("reply"):
            return None                     # a run without a capture
        path = hs.find_capture(PROFILE_ROOT)
    path = Path(path)
    if path not in _SPLITS:
        summary = ctx.get("trace") or tr.load(path)
        _SPLITS[path] = hs.idle_by_span(summary, hs.load(path))
    return _SPLITS[path]


def _matches(name: str, pattern: str) -> bool:
    return name.startswith(pattern[:-1]) if pattern.endswith("*") \
        else name == pattern


def read(spec, ctx):
    shares = split_of(ctx)
    if shares is None:
        return None
    wanted = list(spec.get("spans", []))
    if spec.get("self_of"):
        wanted.append(spec["self_of"])
    hit = sum(v for name, v in shares.items() if name != hs.UNATTRIBUTED
              and any(_matches(name, p) for p in wanted))
    return hit * spec.get("scale", 1.0)
