"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on the real
1-device CPU view."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def make_noisy_pair(rng, n_images=60, num_classes=8, size=64):
    """Synthetic GT + weak/strong detection lists with realistic error mix."""
    from repro.detection.map_engine import Detections, GroundTruth

    def rand_dets(k):
        b = rng.uniform(0, size - 20, (k, 2))
        wh = rng.uniform(5, 20, (k, 2))
        return Detections(
            np.concatenate([b, b + wh], 1),
            rng.uniform(0.1, 1, k),
            rng.integers(0, num_classes, k),
        )

    def noisy(gt, drop, jitter, hall_p):
        keep = rng.uniform(size=len(gt)) > drop
        boxes = gt.boxes[keep] + rng.normal(0, jitter, (int(keep.sum()), 4))
        cls = gt.classes[keep].copy()
        flip = rng.uniform(size=len(cls)) < 0.1
        cls[flip] = rng.integers(0, num_classes, int(flip.sum()))
        scores = rng.uniform(0.4, 1.0, len(cls))
        extra = rand_dets(int(rng.integers(0, 3))) if rng.uniform() < hall_p else rand_dets(0)
        return Detections(
            np.concatenate([boxes, extra.boxes]),
            np.concatenate([scores, extra.scores * 0.5]),
            np.concatenate([cls, extra.classes]),
        )

    gts, weak, strong = [], [], []
    for _ in range(n_images):
        m = int(rng.integers(1, 5))
        b = rng.uniform(0, size - 25, (m, 2))
        wh = rng.uniform(8, 20, (m, 2))
        gt = GroundTruth(np.concatenate([b, b + wh], 1), rng.integers(0, num_classes, m))
        gts.append(gt)
        weak.append(noisy(gt, 0.4, 4.0, 0.5))
        strong.append(noisy(gt, 0.1, 1.0, 0.1))
    return gts, weak, strong


@pytest.fixture(scope="session")
def noisy_pair():
    return make_noisy_pair(np.random.default_rng(7))
