"""Plain reference of the scoring path: weak-detector output to reward
estimate, written from the paper's description (arXiv 2410.18919, §V-A)
and imported from nothing of the program.

Features of one image: the ``top_k`` boxes by confidence (stable order,
empty slots last), each ``[score, cx, cy, w, h, area, aspect/10, onehot]``
with coordinates over the image size and the aspect clipped to [0, 10];
then ``[boxes/top_k, mean score, max score, score entropy]`` and the class
histogram of the selected boxes.  The estimator standardizes the features
and runs ``sigmoid(gelu(x W1 + b1) w2 + b2)`` with the tanh form of GELU.

``xp`` is ``numpy`` for the reference itself (float64) and ``jax.numpy``
for the control, which runs the same arithmetic in a lower precision.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def features(det: Dict, *, num_classes: int, top_k: int, image_size: float,
             xp=np, dtype=np.float64):
    boxes, scores = det["boxes"], det["scores"]
    classes, mask = det["classes"], det["mask"]
    B, K = scores.shape
    if K < top_k:
        pad = top_k - K
        boxes = xp.pad(boxes, ((0, 0), (0, pad), (0, 0)))
        scores = xp.pad(scores, ((0, 0), (0, pad)))
        classes = xp.pad(classes, ((0, 0), (0, pad)), constant_values=-1)
        mask = xp.pad(mask, ((0, 0), (0, pad)))
    keys = xp.where(mask, scores, -xp.inf)
    if xp is np:
        order = np.argsort(-keys, axis=1, kind="stable")[:, :top_k]
    else:
        order = xp.argsort(-keys, axis=1, stable=True)[:, :top_k]
    m = xp.take_along_axis(mask, order, axis=1).astype(dtype)
    s = xp.take_along_axis(scores, order, axis=1).astype(dtype) * m
    cls = xp.clip(xp.take_along_axis(classes, order, axis=1), 0, num_classes - 1)
    b = xp.take_along_axis(boxes, order[:, :, None], axis=1).astype(dtype) / dtype(image_size)
    cx = (b[..., 0] + b[..., 2]) / 2
    cy = (b[..., 1] + b[..., 3]) / 2
    w = xp.maximum(b[..., 2] - b[..., 0], 0)
    h = xp.maximum(b[..., 3] - b[..., 1], 0)
    area = w * h
    aspect = xp.clip(w / xp.maximum(h, dtype(1e-6)), 0, 10) / 10
    onehot = (cls[..., None] == xp.arange(num_classes)).astype(dtype) * m[..., None]
    per_box = xp.concatenate(
        [xp.stack([s, cx * m, cy * m, w * m, h * m, area * m, aspect * m], axis=-1), onehot],
        axis=-1,
    )
    n = m.sum(axis=1)
    nonempty = n > 0
    safe_n = xp.maximum(n, 1)
    hist = xp.where(nonempty[:, None], onehot.sum(axis=1) / safe_n[:, None], 0)
    s_sum = s.sum(axis=1)
    p = s / xp.maximum(s_sum, dtype(1e-9))[:, None]
    entropy = -(p * xp.log(xp.maximum(p, dtype(1e-12)))).sum(axis=1)
    s_max = xp.max(xp.where(m > 0, s, -xp.inf), axis=1)
    glob = xp.stack([n / top_k, s_sum / safe_n, xp.where(nonempty, s_max, 0), entropy], axis=-1)
    glob = xp.where(nonempty[:, None], glob, 0)
    return xp.concatenate([per_box.reshape(B, -1), glob, hist], axis=1).astype(dtype)


def gelu(x, xp=np):
    c = float(np.sqrt(2.0 / np.pi))
    return 0.5 * x * (1.0 + xp.tanh(c * (x + 0.044715 * x * x * x)))


def forward(x, p: Dict, xp=np, dtype=np.float64):
    """Estimates for features ``x`` under weights ``p`` (``w1``, ``b1``,
    ``w2``, ``b2``, ``mu``, ``sigma``), every array in ``dtype``."""
    q = {k: xp.asarray(v).astype(dtype) for k, v in p.items()}
    z = (xp.asarray(x).astype(dtype) - q["mu"]) / q["sigma"]
    h = gelu(z @ q["w1"] + q["b1"], xp)
    o = h @ q["w2"] + q["b2"]
    return 1 / (1 + xp.exp(-o))


def logit(p, eps: float = 1e-6):
    """Log-odds of estimates, clipped to ``[eps, 1 - eps]``."""
    p = np.clip(np.asarray(p, np.float64), eps, 1 - eps)
    return np.log(p) - np.log1p(-p)
