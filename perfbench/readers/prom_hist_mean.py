"""Mean of a /metrics histogram over the window: sum delta / count delta."""

from .. import stats


def read(spec, ctx):
    fam = spec["family"]

    def d(suffix):
        a = stats.sample(ctx["scrape_after"], fam + suffix)
        b = stats.sample(ctx["scrape_before"], fam + suffix)
        return None if a is None or b is None else a - b
    total, count = d("_sum"), d("_count")
    if not count:
        return None
    return total / count * spec.get("scale", 1.0)
