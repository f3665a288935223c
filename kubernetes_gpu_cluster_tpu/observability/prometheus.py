"""Minimal Prometheus primitives for the serving stack.

The /metrics surface graduated from two-point summaries (p50/p95 computed
host-side, useless for cross-replica aggregation) to real histograms:
``_bucket``/``_sum``/``_count`` exposition lets Prometheus compute any
quantile across replicas and time windows, which the north-star metric
("p50 TTFT under continuous batching") needs once more than one replica
serves. No client library is baked into the image, so this is the text
exposition format written by hand — same approach as serving/metrics.py.

Rendering is nan-free by construction: an empty histogram renders all-zero
buckets (a freshly started server must scrape cleanly), and cumulative
bucket counts are monotone because they are accumulated that way.
"""

from __future__ import annotations

from bisect import bisect_left
from typing import Optional

# Latency buckets (seconds): µs-scale device steps up to multi-second TTFT
# under load — covers the 3.4 s p50 sustained-load regime VERDICT r5 flagged.
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

# Device time of one step program: 1-2-5 from 1 ms to 10 s (a decode window
# of 100-220 ms and a mixed step of 50-140 ms fall in different buckets; the
# mean comes from _sum/_count either way).
STEP_BUCKETS_S = (0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5,
                  1.0, 2.0, 5.0, 10.0)

# One stage of a frame's path from the chip to the socket: 1-2-5 from 100 us
# (a hand-over, a turn of the event loop) to 1 s.
FRAME_STAGE_BUCKETS_S = (0.0001, 0.0002, 0.0005, 0.001, 0.002, 0.005, 0.01,
                         0.02, 0.05, 0.1, 0.2, 0.5, 1.0)

# Batch-size-per-step buckets: powers of two matching the scheduler's padded
# decode buckets, so the histogram reads as "which compiled shape ran".
BATCH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0)


def fmt(v: float) -> str:
    """Exposition-safe number: integral floats render without the trailing
    .0 churn, everything else with enough precision to be useful."""
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class Histogram:
    """A labeled cumulative histogram in Prometheus text exposition format.

    ``labels``: optional tuple of label NAMES; each observe() then supplies
    the matching label VALUES. One (counts, sum, count) cell per labelset.
    """

    def __init__(self, name: str, help_text: str = "",
                 buckets: tuple = LATENCY_BUCKETS_S,
                 labels: tuple = ()):
        self.name = name
        self.help_text = help_text
        self.buckets = tuple(sorted(buckets))
        self.label_names = tuple(labels)
        # labelset (tuple of values) -> [per-bucket counts, sum, count]
        self._cells: dict[tuple, list] = {}
        if not self.label_names:
            self._cells[()] = [[0] * len(self.buckets), 0.0, 0]

    def observe(self, value: float, label_values: tuple = ()) -> None:
        if value != value:          # nan never enters the exposition
            return
        cell = self._cells.get(label_values)
        if cell is None:
            cell = self._cells[label_values] = [[0] * len(self.buckets),
                                                0.0, 0]
        # Count and sum BEFORE the bucket: the engine worker thread observes
        # while the HTTP thread renders, and render() snapshots buckets
        # before reading the count — this ordering guarantees every bucket
        # increment a render sees is already in its count, so the scrape's
        # cumulative buckets never exceed +Inf/_count (the monotonicity
        # strict parsers and the exposition validator enforce).
        cell[1] += value
        cell[2] += 1
        # the first bucket whose bound is >= value; none above the last
        i = bisect_left(self.buckets, value)
        if i < len(self.buckets):
            cell[0][i] += 1

    @property
    def count(self) -> int:
        return sum(cell[2] for cell in self._cells.values())

    @property
    def sum(self) -> float:
        return sum(cell[1] for cell in self._cells.values())

    def merged_counts(self) -> list:
        """Per-bucket counts summed over all labelsets, with one extra
        trailing cell for observations ABOVE the last finite bound (observe
        drops those from the bucket array; the quantile must still rank
        them). A snapshot callers can difference against a later one for
        WINDOWED quantiles (counts only grow, so deltas stay valid)."""
        merged = [0] * (len(self.buckets) + 1)
        for cell in self._cells.values():
            finite = 0
            for i, c in enumerate(cell[0]):
                merged[i] += c
                finite += c
            merged[-1] += cell[2] - finite
        return merged

    def quantile(self, q: float) -> float:
        """Histogram-quantile over all labelsets, Prometheus-style (see
        quantile_from_counts). Served live to the admission controller, so
        it reads under concurrent observe(): bucket counts are snapshotted
        by merged_counts first."""
        return quantile_from_counts(self.buckets, self.merged_counts(), q)

    def _labelstr(self, values: tuple, extra: str = "") -> str:
        pairs = [f'{k}="{v}"' for k, v in zip(self.label_names, values)]
        if extra:
            pairs.append(extra)
        return "{" + ",".join(pairs) + "}" if pairs else ""

    def render(self) -> list[str]:
        lines = [f"# TYPE {self.name} histogram"]
        if self.help_text:
            lines.insert(0, f"# HELP {self.name} {self.help_text}")
        for values, cell in sorted(self._cells.items()):
            # Snapshot buckets BEFORE reading count (see observe's ordering
            # comment): cum <= n even mid-observe on another thread.
            counts = list(cell[0])
            total, n = cell[1], cell[2]
            cum = 0
            for bound, c in zip(self.buckets, counts):
                cum += c
                le = 'le="' + fmt(bound) + '"'
                lines.append(
                    f"{self.name}_bucket{self._labelstr(values, le)} {cum}")
            inf_le = 'le="+Inf"'
            lines.append(
                f"{self.name}_bucket{self._labelstr(values, inf_le)} {n}")
            lines.append(f"{self.name}_sum{self._labelstr(values)} "
                         f"{fmt(round(total, 6))}")
            lines.append(f"{self.name}_count{self._labelstr(values)} {n}")
        return lines


def quantile_from_counts(buckets: tuple, counts: list, q: float) -> float:
    """Prometheus-style histogram quantile over per-bucket counts (counts
    may carry one extra trailing overflow cell, merged_counts-style): find
    the bucket holding the q-th observation and interpolate linearly inside
    it (lower bound 0 for the first bucket; overflow observations clamp to
    the last finite bound). 0.0 on an empty set — callers treat "no data"
    as "no wait", the right admission-control default for a fresh server."""
    n = sum(counts)
    if n <= 0:
        return 0.0
    rank = q * n
    cum = 0
    lo = 0.0
    for bound, c in zip(buckets, counts):
        if cum + c >= rank and c > 0:
            frac = (rank - cum) / c
            return lo + (bound - lo) * min(max(frac, 0.0), 1.0)
        cum += c
        lo = bound
    return buckets[-1]


def render_gauge(name: str, value: Optional[float],
                 labels: str = "") -> list[str]:
    """One gauge sample; None/nan values render NOTHING (a fresh server must
    scrape cleanly, and Prometheus treats an absent series correctly where a
    0 or nan would lie)."""
    if value is None or value != value:
        return []
    return [f"# TYPE {name} gauge", f"{name}{labels} {fmt(round(value, 6))}"]
