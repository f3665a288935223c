"""Growth of a /metrics counter over the window (summed over label sets
that include ``labels``)."""

from .. import stats


def read(spec, ctx):
    labels = spec.get("labels")
    a = stats.sample(ctx["scrape_after"], spec["family"], labels)
    b = stats.sample(ctx["scrape_before"], spec["family"], labels)
    return None if a is None or b is None else a - b
