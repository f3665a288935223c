"""Share of the HBM-bandwidth roofline one decode step of a state-space /
attention hybrid reaches: the weights once, every row's state slot read and
written in every state layer, and the attention layers' K and V of the
contexts in flight (``perfbench/roofline_ssm.py``) over the published
bandwidth, over the step's device time (``step_metric``, already computed
from the trace). Rows and contexts are what the client held in flight
during the capture (``in_flight``). A step-level share, not a kernel's. Nothing to read
(None) on a configuration without state layers."""

from .. import roofline_ssm as rf

# The client's POST returns only when stop_trace() has: half a minute after
# the capture in which no request is sent and the rows run out. The rows of
# the CAPTURE are the samples from the POST's start over the trace's own
# extent, and this much for the profiler to start.
START_SLACK_S = 1.0


def has_state(cfg: dict) -> bool:
    return "mamba" in (cfg.get("layer_types") or ())


def in_flight(ctx):
    """(mean rows, mean context tokens) the client held during the capture,
    or None. (``latent_moe_step_hbm_share.in_flight`` averages over the
    whole POST; where the rows' state is over half of a step's bytes that
    halves the reading: PERF.md section 7.)"""
    p0, t = ctx["profile"].get("start"), ctx.get("trace")
    if p0 is None or t is None:
        return None
    inside = [s for s in ctx["samples"]
              if p0 <= s["t"] <= p0 + t.window_s + START_SLACK_S
              and s["rows"] > 0]
    if not inside:
        return None
    return (sum(s["rows"] for s in inside) / len(inside),
            sum(s["context_tokens"] for s in inside) / len(inside))


def read(spec, ctx):
    step_ms = ctx["values"].get(spec["step_metric"])
    peaks, held = ctx.get("peaks"), in_flight(ctx)
    if not step_ms or not peaks or held is None \
            or not has_state(ctx["config"]):
        return None
    least_s = rf.decode_step_bytes(ctx["config"], *held) \
        / peaks["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * spec.get("scale", 1.0)
