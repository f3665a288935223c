"""Share of the HBM-bandwidth roofline the delta-rule state-update kernel
reaches by its OWN events: the device operations whose name contains
``contains`` (``pallas_call(name=...)`` shows in the HLO instruction's
name), each call (one KDA layer of one step) needing every row's state read
and written once (``roofline_kda.kda_update_kernel_bytes``) for the rows
the client held in flight (taken as ``ssm_kernel_hbm_share`` takes them),
over the summed duration of those events. Nothing to read (None) where the
trace holds no such operation, as on a program without the kernel, or the
configuration has no KDA layers."""

from .. import roofline_kda as rf
from .. import trace as tr
from .hybrid_step_hbm_share import in_flight


def has_kda(cfg: dict) -> bool:
    return bool((cfg.get("linear_attn_config") or {}).get("kda_layers"))


def read(spec, ctx):
    t, peaks, held = ctx.get("trace"), ctx.get("peaks"), in_flight(ctx)
    if t is None or not t.devices or not peaks or held is None \
            or not has_kda(ctx["config"]):
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
    least_s = calls * rf.kda_update_kernel_bytes(ctx["config"], held[0]) \
        / peaks["hbm_bytes_per_s"]
    return least_s / seconds * spec.get("scale", 1.0)
