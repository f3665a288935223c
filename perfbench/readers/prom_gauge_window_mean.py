"""Mean of a /metrics gauge over the scrapes taken 4x a second inside the
window (traced runs carry them): what the gauge held over the window, not
the one step before its last scrape. An untraced run keeps no scrapes and
reports the gauge at the window's end."""

from .. import stats


def read(spec, ctx):
    w0, w1 = ctx["window"]
    scrapes = [s["scrape"] for s in ctx["samples"]
               if s.get("scrape") is not None and w0 <= s["t"] <= w1]
    vals = [v for sc in scrapes or [ctx["scrape_after"]]
            for v in [stats.sample(sc, spec["family"], spec.get("labels"))]
            if v is not None]
    if not vals:
        return None
    return sum(vals) / len(vals) * spec.get("scale", 1.0)
