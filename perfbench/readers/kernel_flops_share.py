"""Share of the bf16 FLOP/s peak the grouped expert matmul kernel reaches by
its OWN events: the device operations whose name contains ``contains``,
each needing 2 x rows x hidden x expert-width operations
(``roofline_latent_moe.grouped_matmul_flops``) for the ``rows`` its result
holds (the first dimension of the result shape in the operation's HLO text;
bucket padding included: it is the work the kernel is handed), over the
summed duration of those events. Nothing to read (None) where the trace
holds no such operation, as on a program without the kernel."""

import re

from .. import roofline_latent_moe as rf

_RESULT_ROWS = re.compile(r"= [a-z0-9]+\[(\d+),")


def read(spec, ctx):
    t, peaks = ctx.get("trace"), ctx.get("peaks")
    if t is None or not t.devices or not peaks \
            or "moe_intermediate_size" not in ctx["config"]:
        return None
    needle = spec["contains"]
    flops = seconds = 0.0
    for dev in t.devices:
        for _, dur, name in dev.ops:
            m = _RESULT_ROWS.search(name)
            if needle in name.split(" = ", 1)[0] and m:
                flops += rf.grouped_matmul_flops(ctx["config"],
                                                 int(m.group(1)))
                seconds += dur / 1e9
    if seconds <= 0:
        return None
    return flops / peaks["bf16_flops_per_s"] / seconds \
        * spec.get("scale", 1.0)
