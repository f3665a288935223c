"""Share of the HBM-bandwidth roofline one decode step of a delta-rule /
latent-attention decoder with a share of its experts reaches: the weights
its rows reach once (of the experts HELD, those the rows hit in expectation
under uniform routing), every row's slot read and written in every KDA
layer, and the latent rows of the contexts in flight
(``perfbench/roofline_kda.py``) over the published bandwidth, over the
step's device time (``step_metric``, already computed from the trace). Rows
and contexts are what the client held in flight during the capture. It
counts the same work whatever implements it: a step-level share, not a
kernel's. Nothing to read (None) on a configuration without KDA layers."""

from .. import roofline_kda as rf
from .hybrid_step_hbm_share import in_flight
from .kda_kernel_hbm_share import has_kda


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks, held = ctx.get("peaks"), in_flight(ctx)
    if not step_ms or not peaks or held is None \
            or not has_kda(ctx["config"]):
        return None
    least_s = rf.decode_step_bytes(ctx["config"], *held) \
        / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
