"""Client-clock quantities over the requests of the window."""

from .. import stats
from ..harness import finished_in_window


def read(spec, ctx):
    what, q = spec["quantity"], spec.get("percentile", 50)
    w0, w1 = ctx["window"]
    if what == "gen_late_ms":
        # how late the generator sent, over requests due in the window
        xs = [(r.sent - r.due) * 1e3 for r in ctx["records"]
              if r.phase != "ladder" and w0 <= r.due <= w1]
    elif what == "chunk_gap_ms":
        # gaps between consecutive token-bearing frames of one stream
        xs = [(b[0] - a[0]) * 1e3 for r in ctx["records"]
              if r.phase != "ladder"
              for a, b in zip(r.frames, r.frames[1:]) if w0 <= b[0] <= w1]
    elif what == "ttft_ms":
        xs = [(r.first_token - r.due) * 1e3 for r in finished_in_window(ctx["records"], ctx["window"])
              if r.ok]
    elif what == "tpot_ms":
        xs = [(r.frames[-1][0] - r.frames[0][0]) * 1e3 / (r.tokens - 1)
              for r in finished_in_window(ctx["records"], ctx["window"])
              if r.ok and r.tokens > 1]
    else:
        raise ValueError(f"client reader: unknown quantity {what!r}")
    return stats.percentile(xs, q)
