"""Share of the HBM-bandwidth roofline one decode step of a latent-attention
expert model reaches: the weights its rows touch and the latent rows of the
contexts in flight (``perfbench/roofline_latent_moe.py``) over the published
bandwidth, over the step's device time (``step_metric``, already computed
from the trace). Rows and contexts are what the client held in flight
during the capture. A step-level share, not a kernel's."""

from .. import roofline_latent_moe as rf


def in_flight(ctx):
    """(mean rows, mean context tokens) over the capture, or None."""
    p0, p1 = ctx["profile"].get("start"), ctx["profile"].get("end")
    if p0 is None or p1 is None:
        return None
    inside = [s for s in ctx["samples"]
              if p0 <= s["t"] <= p1 and s["rows"] > 0]
    if not inside:
        return None
    return (sum(s["rows"] for s in inside) / len(inside),
            sum(s["context_tokens"] for s in inside) / len(inside))


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks, held = ctx.get("peaks"), in_flight(ctx)
    if not step_ms or not peaks or held is None \
            or "kv_lora_rank" not in ctx["config"]:
        return None
    least_s = rf.decode_step_bytes(ctx["config"], *held) \
        / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
