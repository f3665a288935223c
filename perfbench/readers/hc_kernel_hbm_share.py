"""Share of the HBM-bandwidth roofline a stream-mixer kernel reaches by its
OWN events: the device operations named ``contains`` (``hc_pre`` or
``hc_post``), each needing the bytes of its operands and results THAT LIE IN
HBM, over the published bandwidth, over the summed duration of those events.

What lies where is the compiler's to say, and the operation's HLO text (the
event's name) says it: a shape whose layout carries a memory space
(``S(1)``) is held in on-chip memory, and XLA keeps the residual streams of
a step there between a sublayer's two mixers where they fit (at 2112 tokens
``hc_post`` read and wrote 121 MB in 114 us with every operand but the
sublayer's result there; against the bytes of ``roofline_hc.hc_post_bytes``
that would read 158 %). So the bytes counted are the ones that cross the HBM
interface, the share cannot pass 100 %, and a low share says the kernel is
bound by its arithmetic on resident streams, not by its copies. Nothing to
read (None) where the trace holds no such operation, as on a program
without the kernel, or for a configuration without streams."""

import math
import re

_SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]\{([^}]*)\}")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}


def hbm_bytes(hlo: str) -> int:
    """Bytes of the results and operands of one operation that its HLO text
    places in HBM (no memory space in the layout)."""
    total = 0
    for dtype, dims, layout in _SHAPE.findall(
            hlo.split("custom_call_target", 1)[0]):
        if "S(" not in layout and dtype in _ITEM:
            total += _ITEM[dtype] * math.prod(
                int(n) for n in dims.split(",") if n)
    return total


def read(spec, ctx):
    t, peaks = ctx.get("trace"), ctx.get("peaks")
    if t is None or not t.devices or not peaks \
            or "hc_mult" not in ctx["config"]:
        return None
    needle = spec["contains"]
    least = seconds = 0.0
    for dev in t.devices:
        for _, dur, name in dev.ops:
            if needle in name.split(" = ", 1)[0]:
                least += hbm_bytes(name)
                seconds += dur / 1e9
    if seconds <= 0:
        return None
    return least / peaks["hbm_bytes_per_s"] / seconds * spec.get("scale", 1.0)
