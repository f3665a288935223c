"""Weight-only quantization ladder (W8A16 / W4A16) for the serving hot path.

Decode on TPU is weight-streaming-bound: every substep reads all matmul
weights from HBM (~2.7 ms floor for a 2.2 GB bf16 model on v5e), and the
BENCH_r05 roofline shows the 8B int8 config already at 0.70 HBM-BW
utilization — the next throughput gain must come from smaller weights. The
activation path stays bf16 on both rungs (no activation calibration):

- **int8** (per-output-channel symmetric): the scale is per OUTPUT channel,
  so it factors OUT of the dot::

      dot(x, dequant(w_q)) == dot(x, w_q) * scale[None, :]

  XLA reads int8 straight from HBM, converts inside the dot fusion, and
  applies one [out]-vector multiply on the f32 result.

- **int4** (group-wise symmetric, AWQ/GPTQ class): per-output-channel alone
  is too coarse at 4 bits, so scales are per (input-dim group, output
  channel) with ``group_size`` (default 128) input rows per group. Two
  nibbles pack into one int8 byte along the INPUT dim — byte ``i`` holds
  input rows ``2i`` (low nibble) and ``2i+1`` (high nibble) — so HBM stores
  HALF the int8 bytes plus one f32 scale per group per channel (~6%
  overhead at group 128). Group scales do NOT factor out of the dot; the
  fused matmul (:func:`int4_matmul`) contracts per group and applies the
  scale on the per-group partials, so no dequantized ``[in, out]`` weight
  copy ever exists in HBM. On TPU a Pallas kernel
  (ops/pallas/int4_matmul.py) streams packed tiles HBM->VMEM and
  dequantizes in VMEM; elsewhere the XLA path unpacks with nibble shifts
  that fuse into the dot as elementwise producers.

Both rungs are engine config (``ModelConfig.quantization = "int8"|"int4"``),
applied to any checkpoint at load time — no pre-quantized artifacts needed.
This is the quantization story the reference's engine exposed via vLLM flags
(``--kv-cache-dtype``/quantized checkpoints hinted at reference
``values-01-minimal-example8.yaml:29``).

Layouts (the discriminator :func:`is_packed_int4` keys off these):

- int8:  weight ``[..., in, out]`` int8, scale ``[..., out]`` f32
  (``scale.ndim == w.ndim - 1``)
- int4:  weight ``[..., in/2, out]`` int8 (packed), scale
  ``[..., in/group, out]`` f32 (``scale.ndim == w.ndim``)
"""

from __future__ import annotations

from typing import Any

import numpy as np

# Weight names eligible for quantization (the big streamed matmuls). Norms,
# biases, embeddings and the MoE router stay high-precision: tiny,
# quality-critical. The kgct-lint quant-surface rule (KGCT009) pins this
# tuple against the dequant-fused call sites in models/ — a quantized key
# consumed outside the fused ``_dot`` path would silently stream unpacked
# weights.
QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

QUANT_METHODS = ("int8", "int4")

# int4 group size along the input dim. 128 matches the TPU lane width (one
# scale row per MXU-aligned tile) and divides every served model's matmul
# input dims (hidden/ff/nh*hd are all multiples of 128 in
# config/model_config.py presets).
DEFAULT_INT4_GROUP = 128


def quantize_tensor(w, xp=None):
    """w: [..., in, out] -> (w_q int8 [..., in, out], scale f32 [..., out]).
    Works on numpy and jax arrays (pass the array module as ``xp``)."""
    if xp is None:
        xp = np if isinstance(w, np.ndarray) else _jnp()
    wf = w.astype(xp.float32)
    amax = xp.max(xp.abs(wf), axis=-2)
    scale = xp.maximum(amax / 127.0, 1e-8).astype(xp.float32)
    w_q = xp.clip(xp.round(wf / scale[..., None, :]), -127, 127).astype(xp.int8)
    return w_q, scale


def pack_int4(q, xp=None):
    """Nibble values ``[..., in, out]`` int8 in [-8, 7] -> packed int8
    ``[..., in/2, out]``: byte ``i`` holds input row ``2i`` in its low
    nibble and ``2i+1`` in its high nibble."""
    if xp is None:
        xp = np if isinstance(q, np.ndarray) else _jnp()
    if q.shape[-2] % 2:
        raise ValueError(f"int4 packing needs an even input dim, got "
                         f"{q.shape[-2]}")
    lo = q[..., 0::2, :] & 0xF
    hi = q[..., 1::2, :] & 0xF
    return (lo | (hi << 4)).astype(xp.int8)


def unpack_int4(packed, xp=None):
    """Packed int8 ``[..., in/2, out]`` -> nibble values ``[..., in, out]``
    int8 in [-8, 7]. Sign extension is two arithmetic shifts — elementwise
    ops XLA fuses into the consuming dot, so the unpacked copy exists only
    inside the fusion, never in HBM."""
    if xp is None:
        xp = np if isinstance(packed, np.ndarray) else _jnp()
    lo = (xp.left_shift(packed, 4)).astype(xp.int8) >> 4
    hi = packed >> 4
    out = xp.stack([lo, hi], axis=-2)            # [..., in/2, 2, out]
    return out.reshape(packed.shape[:-2] + (packed.shape[-2] * 2,)
                       + packed.shape[-1:])


def int4_group_scale(w, group_size: int = DEFAULT_INT4_GROUP, xp=None):
    """w: [..., in, out] -> f32 scales [..., in/group_size, out]. The ONE
    definition of the int4 scale formula (amax/7 with a 1e-8 floor):
    engine/weights.py's streamed scale readers must reproduce the full
    quantize's scales bit-for-bit from shard slices, so they call this
    instead of hand-copying the arithmetic."""
    if xp is None:
        xp = np if isinstance(w, np.ndarray) else _jnp()
    din = w.shape[-2]
    if din % group_size:
        raise ValueError(
            f"int4 input dim {din} not divisible by group_size {group_size}")
    wf = w.astype(xp.float32)
    grouped = wf.reshape(wf.shape[:-2] + (din // group_size, group_size)
                         + wf.shape[-1:])
    amax = xp.max(xp.abs(grouped), axis=-2)      # [..., n_groups, out]
    return xp.maximum(amax / 7.0, 1e-8).astype(xp.float32)


def quantize_tensor_int4(w, group_size: int = DEFAULT_INT4_GROUP, xp=None):
    """w: [..., in, out] -> (packed int8 [..., in/2, out],
    scale f32 [..., in/group_size, out]).

    Symmetric round-to-nearest per (group, output channel); nibbles clipped
    to [-7, 7] so the scale maps amax exactly onto the top code (the -8 code
    is unused, like -128 for int8). Requires ``in % group_size == 0`` —
    group boundaries must also align with any row-shard boundaries so a
    shard quantizing its own slice reproduces the global scales bit-for-bit
    (engine/weights.py relies on this)."""
    if xp is None:
        xp = np if isinstance(w, np.ndarray) else _jnp()
    scale = int4_group_scale(w, group_size, xp=xp)
    wf = w.astype(xp.float32)
    din = w.shape[-2]
    grouped = wf.reshape(wf.shape[:-2] + (din // group_size, group_size)
                         + wf.shape[-1:])
    q = xp.clip(xp.round(grouped / scale[..., None, :]), -7, 7)
    q = q.astype(xp.int8).reshape(wf.shape)
    return pack_int4(q, xp=xp), scale


def is_packed_int4(w, scale) -> bool:
    """Layout discriminator for the two quant rungs (see module docstring):
    group scales carry the extra group axis, per-channel scales don't."""
    return (w.dtype == np.dtype(np.int8) or str(w.dtype) == "int8") \
        and scale is not None and scale.ndim == w.ndim


def int4_matmul_xla(x, w_packed, scale):
    """Dequant-fused ``x @ dequant(w_packed)`` without materializing the
    dequantized weight: contract each input group separately (one batched
    dot over the group axis — the nibble unpack and int->float convert fuse
    in as elementwise producers), then fold the per-(group, channel) scales
    into the f32 partials. x: [T, in]; returns f32 [T, out].

    The nibbles pass through a ``jnp.int4`` intermediate so XLA sees the
    4-bit value range (TPU keeps int4 packed through such fusions)."""
    jnp = _jnp()
    din = w_packed.shape[-2] * 2
    n_groups = scale.shape[-2]
    gs = din // n_groups
    w = unpack_int4(w_packed, xp=jnp)                    # [in, out] int8
    w = w.astype(jnp.int4)
    wg = w.reshape(n_groups, gs, w.shape[-1]).astype(x.dtype)
    xg = x.reshape(x.shape[0], n_groups, gs)
    partial = jnp.einsum("tgi,gio->tgo", xg, wg,
                         preferred_element_type=jnp.float32)
    return jnp.einsum("tgo,go->to", partial, scale,
                      preferred_element_type=jnp.float32)


def int4_matmul(x, w_packed, scale, use_pallas=None):
    """Dispatched dequant-fused int4 matmul. The default is the XLA fusion
    path everywhere — it is already dequant-fused (no weight copy in HBM)
    and partitions under GSPMD like any einsum. The Pallas kernel
    (ops/pallas/int4_matmul.py: packed tiles stream HBM->VMEM and
    dequantize there) is OPT-IN via ``KGCT_INT4_PALLAS=1`` on TPU until
    the driver captures its on-chip compile + A/B (ROADMAP item 3 tail):
    it has no shard_map wrapper yet, so the opt-in is for single-device
    serving; ``use_pallas=False`` (the engine kill-switch) always forces
    XLA. The env read happens at trace time, once per compile."""
    if use_pallas is None:
        import os

        import jax
        use_pallas = (os.environ.get("KGCT_INT4_PALLAS") == "1"
                      and jax.default_backend() == "tpu")
    if use_pallas:
        from .pallas.int4_matmul import pallas_int4_matmul
        return pallas_int4_matmul(x, w_packed, scale)
    return int4_matmul_xla(x, w_packed, scale)


def quantize_params(params: dict[str, Any], method: str,
                    group_size: int = DEFAULT_INT4_GROUP) -> dict[str, Any]:
    """Quantize the big matmul weights of a models/llama params pytree
    in place (returns the same dict). ``method``: "int8" or "int4"."""
    if method not in QUANT_METHODS:
        raise ValueError(
            f"unsupported quantization {method!r} (one of {QUANT_METHODS})")

    def quant(w):
        if method == "int4":
            return quantize_tensor_int4(w, group_size)
        return quantize_tensor(w)

    layers = params["layers"]
    for key in QUANT_LAYER_KEYS:
        if key in layers:
            layers[key], layers[key + "_scale"] = quant(layers[key])
    if "lm_head" in params:
        params["lm_head"], params["lm_head_scale"] = quant(params["lm_head"])
    return params


def _jnp():
    import jax.numpy as jnp
    return jnp
