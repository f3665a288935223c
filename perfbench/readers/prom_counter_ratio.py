"""Growth of the counter ``num`` over the window as a share of the growth of
the counter ``den`` (two families, no labels). Nothing to
read (None) where the denominator did not grow, as on a program without the
counters."""

from .. import stats


def read(spec, ctx):
    def grown(family):
        after = stats.sample(ctx["scrape_after"], family)
        before = stats.sample(ctx["scrape_before"], family) or 0.0
        return None if after is None else after - before
    num, den = grown(spec["num"]), grown(spec["den"])
    if num is None or not den or den <= 0:
        return None
    return num / den * spec.get("scale", 1.0)
