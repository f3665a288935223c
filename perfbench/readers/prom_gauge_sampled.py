"""A gauge pair sampled 4x a second inside the window (traced runs carry
the scrapes): ``1 - min(num) / den`` as a share, e.g. the peak share of KV
pages in use."""

from .. import stats


def read(spec, ctx):
    w0, w1 = ctx["window"]
    lows, den = [], None
    for s in ctx["samples"]:
        sc = s.get("scrape")
        if sc is None or not w0 <= s["t"] <= w1:
            continue
        n = stats.sample(sc, spec["num"])
        d = stats.sample(sc, spec["den"])
        if n is not None and d:
            lows.append(n)
            den = d
    if not lows:
        return None
    return (1.0 - min(lows) / den) * spec.get("scale", 1.0)
