"""Share of its roofline the indexer reaches in the decode windows: the
least time one indexer's scores of a step can take
(``roofline_dsa.index_least_seconds``: the larger of the scores' operations
at the bf16 peak and the visible index keys at the HBM's bandwidth, for the
rows and contexts the client held in flight during the capture), a call,
over the device time of the indexers' own events inside the modules whose
name contains ``module``.

An indexer's event is the ``conditional`` a layer's indexer runs in, told
from every other conditional of a step program (the top log-probabilities',
the penalties', the bias's, top-p's) by what it RETURNS: the choice of the
rows, ``s32[rows, index_topk]`` beside ``pred[rows, index_topk]``
(``indexer_events``). The same operation runs in every layer of a scanned
section, with an empty branch in a "shared" layer: the indexers' calls are
the events' share that ``roofline_dsa.layers_in_conditionals`` gives. The
events hold the indexer's projections and its top-k beside the scores, so
the share is a floor of the score fusion's own. Nothing to read (None) on a
configuration without an indexer or a program without such an operation."""

import re

from .. import roofline_dsa as rf
from .. import trace as tr
from .hybrid_step_hbm_share import in_flight


def is_indexer_conditional(name: str, topk: int) -> bool:
    """``%cond.86 = (s32[16,2048]{...}, pred[16,2048]{...}, bf16[...])
    conditional(...)``: a conditional whose result holds a choice of
    ``topk`` positions a row and which of them exist."""
    result, sep, _ = name.partition(" conditional(")
    if not sep:
        return False
    made = result.partition(" = ")[2]
    rows = set(re.findall(rf"s32\[(\d+),{topk}\]", made))
    return bool(rows & set(re.findall(rf"pred\[(\d+),{topk}\]", made)))


def indexer_events(dev, topk: int, module: str = "") -> list:
    """(start, duration) in ns of the indexers' conditionals that start
    inside a module whose name contains ``module``."""
    spans = tr.busy_intervals(ev for ev in dev.modules if module in ev[2])
    return [(s, d) for s, d, name in dev.ops
            if is_indexer_conditional(name, topk)
            and any(a <= s < b for a, b in spans)]


def read(spec, ctx):
    t, peaks, cfg = ctx.get("trace"), ctx.get("peaks"), ctx["config"]
    if t is None or not t.devices or not peaks or not rf.has_indexer(cfg):
        return None
    held = in_flight(ctx)
    if held is None:
        return None
    hits = [d for dev in t.devices
            for _, d in indexer_events(dev, cfg["index_topk"], spec["module"])]
    full, every = rf.layers_in_conditionals(cfg)
    seconds = sum(hits) / 1e9
    if not hits or not full or seconds <= 0:
        return None
    return len(hits) * full / every \
        * rf.index_least_seconds(cfg, peaks, *held) / seconds \
        * spec.get("scale", 1.0)
