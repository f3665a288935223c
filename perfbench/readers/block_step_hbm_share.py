"""Share of the HBM-bandwidth roofline one PASS of a block model reaches (a
pass over every row's open block is this model's decode step): the weights
outside the experts, the experts its rows x block_length positions hit in
expectation, the head, and the rows' K and V as the client holds them
(``perfbench/roofline_block.py``) over the published bandwidth, over the
pass's device time (``step_metric``, already computed from the trace). It
counts the same work whatever implements it: the share of the whole step,
not a kernel's. Nothing to read (None) on a configuration that is no block
model."""

from .. import roofline_block as rf
from .hybrid_step_hbm_share import in_flight


def is_block_model(cfg: dict) -> bool:
    return int(cfg.get("block_length", 1)) > 1


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks, held = ctx.get("peaks"), in_flight(ctx)
    if not step_ms or not peaks or held is None \
            or not is_block_model(ctx["config"]):
        return None
    least_s = rf.pass_bytes(ctx["config"], *held) / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
