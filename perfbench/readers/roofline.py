"""Share of the HBM-bandwidth roofline one decode step reaches: the bytes
the step must move (weights once + the KV of the contexts in flight, from
shapes; ``perfbench/roofline.py``) over the published bandwidth, over the
step's device time (the metric named by ``step_metric``, already computed
from the trace). A step-level share, not a kernel's."""

from .. import roofline as rf


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks = ctx.get("peaks")
    p0, p1 = ctx["profile"].get("start"), ctx["profile"].get("end")
    if not step_ms or not peaks or p0 is None:
        return None
    inside = [s["context_tokens"] for s in ctx["samples"]
              if p0 <= s["t"] <= p1 and s["rows"] > 0]
    if not inside:
        return None
    ctx_tokens = sum(inside) / len(inside)
    least_s = rf.decode_step_bytes(ctx["config"], ctx_tokens) \
        / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
