"""Share of a labelled counter's growth over the window that falls on the
label values in ``numerator`` (e.g. host phases of the step loop)."""

from .. import stats


def read(spec, ctx):
    fam, label = spec["family"], spec["label"]
    num = den = 0.0
    for (name, labels), after in ctx["scrape_after"].items():
        if name != fam:
            continue
        value = dict(labels).get(label)
        d = after - ctx["scrape_before"].get((name, labels), 0.0)
        den += d
        if value in spec["numerator"]:
            num += d
    return None if den <= 0 else num / den * spec.get("scale", 1.0)
