import math

import pytest

from perfbench import stats

TEXT = """# HELP x
kgct_q_bucket{le="0.1"} 2
kgct_q_bucket{le="0.5"} 6
kgct_q_bucket{le="+Inf"} 8
kgct_q_sum 3.5
kgct_q_count 8
kgct_pre_total{kind="recompute"} 3
kgct_pre_total{kind="swap"} 1
kgct_phase_total{phase="schedule"} 1.0
kgct_phase_total{phase="device_fetch"} 3.0
kgct_jit 7
"""


def test_percentile_matches_numpy_convention():
    xs = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    assert stats.percentile(xs, 50) == 5.5
    assert stats.percentile(xs, 90) == pytest.approx(9.1)
    assert stats.percentile([], 50) is None
    assert stats.percentile([4.0], 99) == 4.0


def test_a_failed_request_counts_as_beyond_the_percentile():
    xs = list(range(1, 10))           # 9 good ones
    assert stats.percentile_with_misses(xs, 0, 50) == 5
    # one miss of ten: the median moves up, the p90 is still finite ...
    assert stats.percentile_with_misses(xs, 1, 50) == 5.5
    # ... two misses of eleven: the p90 falls among the misses
    assert stats.percentile_with_misses(xs, 2, 95) is None
    assert stats.percentile_with_misses([], 3, 50) is None


def test_parse_and_sample():
    s = stats.parse_prometheus(TEXT)
    assert stats.sample(s, "kgct_jit") == 7
    assert stats.sample(s, "kgct_pre_total") == 4          # summed
    assert stats.sample(s, "kgct_pre_total", {"kind": "swap"}) == 1
    assert stats.sample(s, "absent") is None


def test_histogram_delta_and_quantile():
    before = stats.hist_buckets(stats.parse_prometheus(TEXT), "kgct_q")
    after_text = TEXT.replace('le="0.1"} 2', 'le="0.1"} 4') \
        .replace('le="0.5"} 6', 'le="0.5"} 12') \
        .replace('le="+Inf"} 8', 'le="+Inf"} 14')
    after = stats.hist_buckets(stats.parse_prometheus(after_text), "kgct_q")
    assert before == [(0.1, 2), (0.5, 6), (math.inf, 8)]
    delta = stats.hist_delta(before, after)
    assert delta == [(0.1, 2), (0.5, 6), (math.inf, 6)]
    # 6 observations: 2 in (0, .1], 4 in (.1, .5]; median = rank 3 ->
    # a quarter into the second bucket
    assert stats.bucket_quantile(delta, 0.5) == pytest.approx(0.2)
    assert stats.bucket_quantile(delta, 1 / 6) == pytest.approx(0.05)
    assert stats.bucket_quantile([(0.1, 0), (math.inf, 0)], 0.5) is None
    # everything in the +Inf bucket: its lower edge
    assert stats.bucket_quantile([(0.1, 0), (math.inf, 5)], 0.5) == 0.1
