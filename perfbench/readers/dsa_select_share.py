"""Share of the device's busy time the indexers take: their projections,
index scores and the choice. What the trace shows of them is the
``conditional`` each scanned layer's indexer runs in
(``dsa_index_roofline.indexer_events``: told from a step program's other
conditionals by the choice it returns); a section of ONE layer is no loop
on the device, its predicate is a constant and its indexer's operations lie
among the step's other fusions under no name of their own. Its indexer has
the same shapes as the others: the seconds seen are scaled by all indexers
over those seen (``roofline_dsa.layers_in_conditionals``). Nothing to read
(None) on a configuration without an indexer or a program without such an
operation."""

from .. import roofline_dsa as rf
from .. import trace as tr
from .dsa_index_roofline import indexer_events


def read(spec, ctx):
    t, cfg = ctx.get("trace"), ctx["config"]
    if t is None or not t.devices or not rf.has_indexer(cfg):
        return None
    seen, _ = rf.layers_in_conditionals(cfg)
    busy = hit = 0.0
    for dev in t.devices:
        busy += tr.busy_seconds(dev)
        hit += sum(e - s for s, e in tr.busy_intervals(
            indexer_events(dev, cfg["index_topk"]))) / 1e9
    if busy <= 0 or hit <= 0 or not seen:
        return None
    return hit * rf.index_layers(cfg) / seen / busy * spec.get("scale", 1.0)
