"""Rotary position embeddings (half-split convention, matching HF llama/qwen).

Computed on the fly from integer positions — no precomputed cos/sin table to
keep resident or re-slice, which keeps decode steps free of dynamic-slice ops
on a side table and lets XLA fuse the rotation into the q/k projections.

Scaling: Llama-3.1/3.2 checkpoints ship ``rope_scaling`` (type "llama3") —
piecewise frequency rescaling that stretches low-frequency components by
``factor`` with a smooth ramp between the high/low wavelength cutoffs.
"linear" (positions / factor everywhere) is also supported, and "yarn" as
deepseek_v3 writes it (a ramp between the pairs that turn ``beta_fast`` and
``beta_slow`` times within the original window: those below keep their
frequency, those above are divided by ``factor``; the softmax scale takes
``yarn_attn_factor``, cos and sin ``yarn_cos_factor``). All are compile-time
transforms of ``inv_freq``; unsupported types are rejected at config load
(engine/weights.config_from_hf), never silently ignored.
"""

from __future__ import annotations

from typing import Optional

import math

import jax.numpy as jnp
import numpy as np


def _kind(scaling: Optional[dict]) -> Optional[str]:
    return (scaling.get("rope_type") or scaling.get("type")) if scaling else None


def yarn_mscale(factor: float, mscale: float) -> float:
    """deepseek_v3's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_attn_factor(scaling: Optional[dict]) -> float:
    """What YaRN multiplies the softmax scale by: m^2, m from
    ``mscale_all_dim`` (0 or absent: 1). 1 without YaRN."""
    if _kind(scaling) != "yarn" or not scaling.get("mscale_all_dim"):
        return 1.0
    return yarn_mscale(float(scaling.get("factor", 1.0)),
                       float(scaling["mscale_all_dim"])) ** 2


def yarn_cos_factor(scaling: Optional[dict]) -> float:
    """What YaRN multiplies cos and sin by: m(mscale) / m(mscale_all_dim)."""
    if _kind(scaling) != "yarn":
        return 1.0
    factor = float(scaling.get("factor", 1.0))
    return (yarn_mscale(factor, float(scaling.get("mscale", 1)))
            / yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0))))


def scaled_inv_freq(head_dim: int, theta: float,
                    scaling: Optional[dict] = None) -> np.ndarray:
    """Per-pair inverse frequencies [head_dim//2], with HF ``rope_scaling``
    applied. Pure numpy on static config — folded into the program as a
    constant."""
    half = head_dim // 2
    inv_freq = theta ** -(np.arange(half, dtype=np.float32) / half)
    if not scaling:
        return inv_freq
    kind = _kind(scaling)
    factor = float(scaling.get("factor", 1.0))
    if kind == "linear":
        return inv_freq / factor
    if kind == "llama3":
        lo_f = float(scaling.get("low_freq_factor", 1.0))
        hi_f = float(scaling.get("high_freq_factor", 4.0))
        orig = float(scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * np.pi / inv_freq
        # Wavelengths shorter than orig/hi_f keep full resolution; longer than
        # orig/lo_f are stretched by `factor`; in between, interpolate.
        ramp = (orig / wavelen - lo_f) / (hi_f - lo_f)
        smooth = np.clip(ramp, 0.0, 1.0)
        scaled = inv_freq * (smooth + (1.0 - smooth) / factor)
        return scaled.astype(np.float32)
    if kind == "yarn":
        orig = float(scaling.get("original_max_position_embeddings", 4096))

        def pair_turning(turns):    # the (real-valued) pair that turns so often
            return (head_dim * math.log(orig / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(pair_turning(float(scaling.get("beta_fast", 32)))), 0)
        high = min(math.ceil(pair_turning(float(scaling.get("beta_slow", 1)))),
                   head_dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                       / (high - low if high != low else 0.001), 0.0, 1.0)
        return (inv_freq * ((1.0 - ramp) + ramp / factor)).astype(np.float32)
    raise ValueError(f"unsupported rope_scaling type {kind!r} "
                     "(supported: llama3, linear, yarn)")


def rope_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float,
                 dtype=jnp.float32, scaling: Optional[dict] = None):
    """positions: [...] int32 -> cos/sin of shape [..., head_dim//2]."""
    inv_freq = jnp.asarray(scaled_inv_freq(head_dim, theta, scaling))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    m = yarn_cos_factor(scaling)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    return cos.astype(dtype), sin.astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [..., n_heads, head_dim]; cos/sin: [..., head_dim//2] (broadcast over
    the heads axis). Half-split rotation: (x1, x2) -> (x1*c - x2*s, x2*c + x1*s).
    """
    half = x.shape[-1] // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)
