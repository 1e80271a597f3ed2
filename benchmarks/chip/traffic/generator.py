"""The one generator every traffic mix of the chip benchmark goes through.

A mix is a data file beside this module (``<mix>.json``) holding only
parameters: the arrival process and its rate, the client's block cap, and
the shape of the weak detector's output.  Everything is drawn from the
run's seed, so the same seed gives the same frames and the same schedule.

Detections are COCO-shaped: ``max_dets`` slots per image (the COCO
evaluation's maxDets=100), ``num_classes`` classes, boxes in pixels of an
``image_size`` square.  Per image the detector reports a log-normal number
of boxes capped at ``max_dets``; about ``true_mean`` of them are real
objects with high confidence, the rest are low-confidence clutter; classes
follow a Zipf law (COCO's are skewed, person first); box sides are
log-uniform.  ``districts`` splits the cameras into contiguous blocks, each
with its own crowding and confidence.

Arrival processes:

``poisson``  ``round(rate * seconds)`` frames at uniform order statistics
             over the window: a Poisson process conditioned on its count,
             so every seed offers the same amount of work in another order.
``ticks``    every camera one frame per tick, ticks due at ``rate`` per
             second from the window's start.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> Dict:
    path = os.path.join(HERE, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _one_shape(rng: np.random.Generator, n: int, shape: Dict, *, num_classes: int,
               max_dets: int, image_size: float) -> Dict[str, np.ndarray]:
    """``n`` images drawn from one shape of detector output."""
    counts = np.minimum(
        np.floor(rng.lognormal(np.log(shape["count_median"]), shape["count_sigma"], n)),
        max_dets,
    ).astype(np.int64)
    slots = np.arange(max_dets)[None, :]
    mask = slots < counts[:, None]
    n_true = np.minimum(rng.poisson(shape["true_mean"], n), counts)
    # real objects sit at random slots among the reported ones: the program
    # sorts by confidence itself, so its input order is not given
    rank = np.argsort(rng.random((n, max_dets)) + (~mask), axis=1)
    is_true = np.zeros((n, max_dets), bool)
    np.put_along_axis(is_true, rank, slots < n_true[:, None], axis=1)
    ta, tb = shape["true_beta"]
    fa, fb = shape["clutter_beta"]
    scores = np.where(
        is_true, rng.beta(ta, tb, (n, max_dets)), rng.beta(fa, fb, (n, max_dets))
    ) * mask
    zipf = 1.0 / np.arange(1, num_classes + 1) ** shape["class_zipf"]
    classes = rng.choice(num_classes, (n, max_dets), p=zipf / zipf.sum())
    lo, hi = shape["box_side_px"]
    wh = np.exp(rng.uniform(np.log(lo), np.log(hi), (n, max_dets, 2)))
    wh = np.minimum(wh, image_size)
    xy = rng.uniform(0.0, 1.0, (n, max_dets, 2)) * (image_size - wh)
    boxes = np.concatenate([xy, xy + wh], -1) * mask[..., None]
    return {
        "boxes": boxes.astype(np.float32),
        "scores": scores.astype(np.float32),
        "classes": np.where(mask, classes, -1).astype(np.int32),
        "mask": mask,
    }


def detections(rng: np.random.Generator, n: int, mix: Dict, *, num_classes: int,
               max_dets: int, image_size: float, cameras: int = 0) -> Dict[str, np.ndarray]:
    """``n`` images of padded weak-detector output.  With ``districts`` in
    the mix, image ``i`` belongs to camera ``i % cameras`` and the cameras
    are split into contiguous, equal district blocks."""
    det = mix["detections"]
    kw = dict(num_classes=num_classes, max_dets=max_dets, image_size=image_size)
    districts: List[Dict] = det.get("districts") or [det]
    if len(districts) == 1:
        return _one_shape(rng, n, districts[0], **kw)
    if cameras % len(districts):
        raise ValueError(f"{cameras} cameras do not split into {len(districts)} districts")
    district = (np.arange(n) % cameras) * len(districts) // cameras
    parts = [_one_shape(rng, int((district == d).sum()), s, **kw)
             for d, s in enumerate(districts)]
    out = {k: np.empty((n,) + v.shape[1:], v.dtype) for k, v in parts[0].items()}
    for d, part in enumerate(parts):
        for k, v in part.items():
            out[k][district == d] = v
    return out


def arrivals(rng: np.random.Generator, mix: Dict, seconds: float) -> np.ndarray:
    """Due times in seconds from the window's start, sorted: one per frame
    (``poisson``) or one per tick (``ticks``)."""
    kind, rate = mix["arrivals"], float(mix["rate"])
    n = int(round(rate * seconds))
    if kind == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    if kind == "ticks":
        return np.arange(n) / rate
    raise ValueError(f"unknown arrival process {kind!r}")
