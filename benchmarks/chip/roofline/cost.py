"""Operations and bytes that scoring needs, from a cell's shapes alone.

Scoring takes ``B`` images of ``K`` detection slots (boxes f32 x4, score
f32, class i32, mask 1 byte) to one f32 estimate each, through four stages:

``topk``         confidence top-``k`` of ``K`` slots per image
``features``     per-box features of the ``k`` kept boxes and the global stats
``standardize``  ``(x - mu) / sigma`` over ``F`` features
``mlp``          ``sigmoid(gelu(x W1 + b1) w2 + b2)``, hidden width ``H``

The counts are what the algorithm needs, not what a kernel moves: every
input and weight is read once per call and every output written once; the
features between stages never leave the chip.  A call that scores ``B``
images reads the whole estimator once, which is why small calls are bound
by bandwidth.  Comparisons and selects count as one operation each, like
adds and multiplies; a ``tanh`` or ``exp`` counts as one.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))

#: bytes of one detection slot: 4 box coordinates, score, class, mask
SLOT_BYTES = 4 * 4 + 4 + 4 + 1


def feature_dim(num_classes: int, top_k: int) -> int:
    return top_k * (7 + num_classes) + 4 + num_classes


def stage_flops(B: int, K: int, k: int, C: int, H: int) -> Dict[str, float]:
    F = feature_dim(C, k)
    return {
        # a selection network of K log2 K compare-exchanges per image
        "topk": B * K * math.log2(max(K, 2)),
        # per kept box: 6 geometry ops, clip/scale of the aspect, C one-hot
        # compares, and 7 masks; then the histogram, sums, max and entropy
        "features": B * k * (6 + 3 + C + 7) + B * (k * (C + 5) + 8),
        "standardize": 2.0 * B * F,
        # two matmuls, the bias adds, GELU (8 ops) and the sigmoid (3 ops)
        "mlp": 2.0 * B * F * H + B * H * (1 + 8) + 2.0 * B * H + 4.0 * B,
    }


def call_bytes(B: int, K: int, k: int, C: int, H: int) -> float:
    """Least bytes one scoring call of ``B`` images moves: detections in,
    the estimator (W1, b1, w2, b2, mu, sigma) once, estimates out."""
    F = feature_dim(C, k)
    weights = 4.0 * (F * H + H + H + 1 + 2 * F)
    return B * K * SLOT_BYTES + weights + 4.0 * B


def call_flops(B: int, K: int, k: int, C: int, H: int) -> float:
    return sum(stage_flops(B, K, k, C, H).values())


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; have {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> Dict[str, float]:
    """The larger of compute time and memory time at the chip's peaks."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops_s": t_flops, "bytes_s": t_bytes,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
