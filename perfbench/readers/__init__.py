"""Readers of per-layer metrics, found by name.

``layer_metrics/<metric>.json`` names a reader and its arguments; the
reader is ``readers/<reader>.py`` with one function::

    def read(spec: dict, ctx: dict) -> float | None

``spec`` is the metric's file. ``ctx`` holds what one run produced:
``records`` (client.Record), ``window`` (w0, w1), ``scrape_before`` /
``scrape_after`` (parsed /metrics at the window's ends), ``samples`` (4/s:
time, rows and context tokens in flight, and in a traced run a parsed
/metrics scrape), ``trace`` (trace.TraceSummary or None), ``profile``
(start/end of the capture on the client's clock), ``config`` (the
configuration file), ``peaks`` (this device's row of peaks.json or None),
``values`` (metrics already computed in this run, by name).

A reader that finds nothing to read returns None and the metric is left out
of the line.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}").read
