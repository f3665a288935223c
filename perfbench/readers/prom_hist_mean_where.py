"""Mean of a labelled /metrics histogram over the window: the sum's growth
over the count's growth, over the series whose labels include ``labels``
(e.g. ``{"kind": "decode"}``), divided by ``per`` (a key of the
configuration's ``warmup`` group, e.g. the steps of a decode window) where
the file names one. A series that first appears inside the window counts
from 0. A program without the family (or with no observation in the
window) reads nothing."""

from .. import stats


def read(spec, ctx):
    fam, labels = spec["family"], spec.get("labels")

    def grown(suffix):
        after = stats.sample(ctx["scrape_after"], fam + suffix, labels)
        if after is None:
            return None
        return after - (stats.sample(ctx["scrape_before"], fam + suffix,
                                     labels) or 0.0)
    total, count = grown("_sum"), grown("_count")
    if not count:
        return None
    per = ctx["config"]["warmup"][spec["per"]] if "per" in spec else 1
    return total / (count * per) * spec.get("scale", 1.0)
