"""Share of the device's busy time inside operations whose name contains
any of ``contains`` (e.g. the Pallas custom calls) -- device trace."""

from .. import trace as tr


def read(spec, ctx):
    t = ctx.get("trace")
    if t is None or not t.devices:
        return None
    needles = [n.lower() for n in spec["contains"]]
    busy = hit = 0.0
    for dev in t.devices:
        busy += tr.busy_seconds(dev)
        hit += tr.leaf_op_seconds(
            dev, lambda name: any(n in name.lower() for n in needles))
    return None if busy <= 0 else hit / busy * spec.get("scale", 1.0)
