"""Share of the HBM-bandwidth roofline one decode step of a latent-attention
expert model WITH RESIDUAL STREAMS reaches: ``latent_moe_step_hbm_share``
with ``perfbench/roofline_hc.py``'s bytes (the low-rank query's weights,
the mixers' weights, the rows' streams through the mixers) over the
published bandwidth, over the step's device time (``step_metric``). Rows
and contexts are what the client held in flight during the capture. A
step-level share, not a kernel's."""

from .. import roofline_hc as rf
from .latent_moe_step_hbm_share import in_flight


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks, held = ctx.get("peaks"), in_flight(ctx)
    if not step_ms or not peaks or held is None \
            or "hc_mult" not in ctx["config"]:
        return None
    least_s = rf.decode_step_bytes(ctx["config"], *held) \
        / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
