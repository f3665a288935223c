"""1 - (union of the device's operation intervals / traced window),
averaged over the devices used."""

from .. import trace as tr


def read(spec, ctx):
    t = ctx.get("trace")
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return (1.0 - tr.mean_busy_seconds(t) / t.window_s) \
        * spec.get("scale", 1.0)
