"""Share of the HBM-bandwidth roofline the state-update kernel reaches by
its OWN events: the device operations whose name contains ``contains``
(``pallas_call(name=...)`` shows in the HLO instruction's name), each call
(one state layer of one step) needing every row's recurrent state read and
written once (``roofline_ssm.ssm_update_kernel_bytes``) for the rows the
client held in flight, over the summed duration of those events. Nothing to
read (None) where the trace holds no such operation, as on a program
without the kernel."""

from .. import roofline_ssm as rf
from .. import trace as tr
from .hybrid_step_hbm_share import has_state, in_flight


def read(spec, ctx):
    t, peaks, held = ctx.get("trace"), ctx.get("peaks"), in_flight(ctx)
    if t is None or not t.devices or not peaks or held is None \
            or not has_state(ctx["config"]):
        return None
    needle = spec["contains"]
    calls = seconds = 0.0
    for dev in t.devices:
        hits = [d for _, d, name in dev.ops
                if needle in tr.short_op(name)]
        calls += len(hits)
        seconds += sum(hits) / 1e9
    if not calls or seconds <= 0:
        return None
    least_s = calls * rf.ssm_update_kernel_bytes(ctx["config"], held[0]) \
        / peaks["hbm_bytes_per_s"]
    return least_s / seconds * spec.get("scale", 1.0)
