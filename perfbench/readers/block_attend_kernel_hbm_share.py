"""Share of the HBM-bandwidth roofline the block-attend kernel reaches by
its OWN events: the device operations whose name contains ``contains``
(``pallas_call(name="block_attend")`` shows in the HLO instruction's name),
each call (one layer of one pass) needing every row's valid pages, whole, K
and V (``roofline_block.block_attend_kernel_bytes``) for the rows and
contexts the client held in flight during the capture, over the summed
duration of those events. Nothing to read (None) where the trace holds no
such operation, as on a program without the kernel, or the configuration is
no block model."""

from .. import roofline_block as rf
from .. import trace as tr
from .block_step_hbm_share import is_block_model
from .hybrid_step_hbm_share import in_flight


def read(spec, ctx):
    t, peaks, held = ctx.get("trace"), ctx.get("peaks"), in_flight(ctx)
    if t is None or not t.devices or not peaks or held is None \
            or not is_block_model(ctx["config"]):
        return None
    needle = spec["contains"]
    calls = seconds = 0.0
    for dev in t.devices:
        hits = [d for _, d, name in dev.ops if needle in tr.short_op(name)]
        calls += len(hits)
        seconds += sum(hits) / 1e9
    if not calls or seconds <= 0:
        return None
    least_s = calls * rf.block_attend_kernel_bytes(ctx["config"], *held) \
        / peaks["hbm_bytes_per_s"]
    return least_s / seconds * spec.get("scale", 1.0)
