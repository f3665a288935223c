"""Growth of one /metrics counter over the window minus the growth of
another (``family`` minus ``minus``): e.g. programs handed to the compiler
less those the persistent cache answered."""

from .. import stats


def read(spec, ctx):
    deltas = []
    for fam in (spec["family"], spec["minus"]):
        a = stats.sample(ctx["scrape_after"], fam, spec.get("labels"))
        b = stats.sample(ctx["scrape_before"], fam, spec.get("labels"))
        if a is None or b is None:
            return None
        deltas.append(a - b)
    return deltas[0] - deltas[1]
