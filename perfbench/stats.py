"""Percentiles and Prometheus-histogram arithmetic (the sound parts of
``bench.py``'s ``_hist_buckets`` / ``_hist_delta`` / ``_bucket_quantile``,
copied so that no later PR to the program can change the yardstick)."""

from __future__ import annotations

import math
import re
from typing import Optional


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def percentile_with_misses(values, misses: int, q: float) -> Optional[float]:
    """Percentile over values plus ``misses`` requests that failed or were
    refused: each counts as beyond every limit (+inf). None when the
    percentile itself falls among the misses or nothing was measured."""
    n = len(values) + misses
    if n == 0:
        return None
    xs = sorted(values) + [math.inf] * misses
    v = percentile(xs, q)
    return None if v is None or math.isinf(v) or math.isnan(v) else v


_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> dict:
    """{(name, ((label, value), ...)): float} of one /metrics scrape."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


def sample(scrape: dict, name: str, labels: Optional[dict] = None):
    """The one sample of ``name`` whose labels include ``labels``; the sum
    when several match (e.g. a counter split by kind)."""
    want = set((labels or {}).items())
    got = [v for (n, ls), v in scrape.items()
           if n == name and want <= set(ls)]
    return sum(got) if got else None


def hist_buckets(scrape: dict, name: str) -> list:
    """[(upper bound, cumulative count)] of histogram ``name``, sorted."""
    out = []
    for (n, ls), v in scrape.items():
        if n == name + "_bucket":
            le = dict(ls).get("le")
            if le is not None:
                out.append((math.inf if le == "+Inf" else float(le), v))
    return sorted(out)


def hist_delta(before: list, after: list) -> list:
    """Cumulative buckets of the observations made between two scrapes."""
    b = dict(before)
    return [(le, c - b.get(le, 0.0)) for le, c in after]


def bucket_quantile(buckets: list, q: float) -> Optional[float]:
    """Prometheus ``histogram_quantile``: linear inside the bucket that
    holds the q-quantile; the lower edge of the +Inf bucket at most."""
    if not buckets or buckets[-1][1] <= 0:
        return None
    rank = q * buckets[-1][1]
    lo_le, lo_c = 0.0, 0.0
    for le, c in buckets:
        if c >= rank:
            if math.isinf(le):
                return lo_le
            if c == lo_c:
                return le
            return lo_le + (le - lo_le) * (rank - lo_c) / (c - lo_c)
        lo_le, lo_c = le, c
    return lo_le
