"""Quantile of a /metrics histogram over the window (bucket deltas)."""

from .. import stats


def read(spec, ctx):
    delta = stats.hist_delta(
        stats.hist_buckets(ctx["scrape_before"], spec["family"]),
        stats.hist_buckets(ctx["scrape_after"], spec["family"]))
    v = stats.bucket_quantile(delta, spec["q"])
    return None if v is None else v * spec.get("scale", 1.0)
