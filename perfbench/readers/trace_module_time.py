"""Device time of the XLA modules whose name contains ``contains``, per
execution, divided by ``steps_per_module`` (a key of the configuration's
``warmup`` group, e.g. the decode window) -- from the device trace."""

from .. import trace as tr


def read(spec, ctx):
    t = ctx.get("trace")
    if t is None or not t.devices:
        return None
    total = count = 0.0
    for dev in t.devices:
        s, n = tr.module_time(dev, spec["contains"])
        total += s
        count += n
    if not count:
        return None
    per = ctx["config"]["warmup"][spec["steps_per_module"]] \
        if "steps_per_module" in spec else 1
    return total / (count * per) * spec.get("scale", 1.0)
