"""Bytes and operations the algorithm needs, from the configuration's
shapes alone (dense decoder, grouped-query attention; the dense arithmetic
of ``bench.py``'s ``_roofline``/``_weight_stream_bytes``, corrected: a tied
output head is still streamed once per decode step).

``cfg`` is a configuration file of this directory: HF-style shape keys plus
``dtype`` and ``quantization``.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name}; add "
            "its published peaks with their source")
    return table[device_kind]


def _dtype_bytes(cfg: dict) -> int:
    return {"bfloat16": 2, "float32": 4}[cfg.get("dtype", "bfloat16")]


def _shapes(cfg: dict):
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or h // nh
    return h, inter, nh, nkv, hd, cfg["num_hidden_layers"], cfg["vocab_size"]


def _matmuls(cfg: dict) -> list:
    """(in, out, count) of every weight matrix a decode step streams."""
    h, inter, nh, nkv, hd, L, V = _shapes(cfg)
    return [(h, nh * hd, L), (h, nkv * hd, 2 * L), (nh * hd, h, L),
            (h, inter, 2 * L), (inter, h, L), (h, V, 1)]


def _matrix_bytes(cfg: dict, din: int, dout: int) -> int:
    quant = cfg.get("quantization")
    if quant == "int8":
        return din * dout + 4 * dout        # 1 B/weight + f32 scale/column
    if quant:
        raise ValueError(f"no byte model for quantization {quant!r}")
    return din * dout * _dtype_bytes(cfg)


def streamed_weight_bytes(cfg: dict) -> int:
    """HBM bytes of the matrices one decode step reads once: every layer's
    projections and the output head (the tied embedding, when tied)."""
    return sum(_matrix_bytes(cfg, din, dout) * count
               for din, dout, count in _matmuls(cfg))


def resident_weight_bytes(cfg: dict) -> int:
    """Bytes of weights held on the device: the streamed matrices plus an
    embedding table of its own when the head is not tied (kept in the
    serving dtype: only matmul weights are quantized)."""
    h, _, _, _, _, _, V = _shapes(cfg)
    extra = 0 if cfg.get("tie_word_embeddings") else V * h * _dtype_bytes(cfg)
    return streamed_weight_bytes(cfg) + extra


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over all layers; the pool is kept in bf16."""
    _, _, _, nkv, hd, L, _ = _shapes(cfg)
    return 2 * L * nkv * hd * 2


def decode_step_bytes(cfg: dict, context_tokens: float) -> float:
    """Least HBM traffic of one decode step: the weights once, plus the KV
    of every token in the contexts of the rows in flight."""
    return streamed_weight_bytes(cfg) + kv_bytes_per_token(cfg) * context_tokens


def matmul_flops_per_token(cfg: dict) -> int:
    """2 FLOPs per multiply-add over every streamed matrix."""
    return 2 * sum(din * dout * count for din, dout, count in _matmuls(cfg))


def attention_flops_per_token(cfg: dict, context: float) -> float:
    """QK^T and PV against ``context`` cached tokens, all layers."""
    _, _, nh, _, hd, L, _ = _shapes(cfg)
    return 4 * L * nh * hd * context
