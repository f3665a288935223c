"""Share of the HBM-bandwidth roofline one decode step of a sparse-attention
decoder with a share of its experts reaches: the weights its rows reach once
(of the experts HELD, those the rows hit in expectation), the latent rows
its rows CHOSE in every layer and the index keys they could see in every
layer that holds an indexer (``perfbench/roofline_dsa.py``) over the
published bandwidth, over the step's device time (``step_metric``, already
computed from the trace). Rows and contexts are what the client held in
flight during the capture. It counts the same work whatever implements it:
a step-level share, not a kernel's. Nothing to read (None) on a
configuration without an indexer."""

from .. import roofline_dsa as rf
from .hybrid_step_hbm_share import in_flight


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks, held = ctx.get("peaks"), in_flight(ctx)
    if not step_ms or not peaks or held is None \
            or not rf.has_indexer(ctx["config"]):
        return None
    least_s = rf.decode_step_bytes(ctx["config"], *held) \
        / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
