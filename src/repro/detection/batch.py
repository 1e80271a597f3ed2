"""Batched detection data plane: padded struct-of-arrays containers + the
device-resident COCO greedy matcher.

The fit-time loop (per-image matching §IV, weak-output features §V-A, ORIC
labels Eq. 5–6) historically ran as per-image Python over ragged numpy
``Detections``.  This module is the batched substrate everything now rides
on:

* ``DetectionsBatch`` / ``GroundTruthBatch`` — fixed ``max_boxes`` padding,
  float32 struct-of-arrays with validity masks.  ``from_list`` pads a ragged
  list; ``__getitem__``/``to_list`` round-trip back to the host dataclasses.
* ``match_batch`` — a jitted matcher that computes per-image IoU through the
  ``iou_matrix`` Pallas kernel (``iou_matrix_batch``) and reproduces COCO
  greedy matching (per class, detections by descending score, one GT per
  detection, per IoU threshold) as masked ``lax`` ops over the whole batch.
  Its tp flags are identical to per-image ``match_detections`` under the
  plane's float32 convention (float64 inputs distinguishable only below
  float32 precision — score ties, IoUs within ~1e-7 of a threshold — may
  resolve differently than a float64 host match of the originals).
* ``to_image_evals`` — converts a ``MatchResult`` back into the exact
  ``ImageEval`` structure the AP accumulator consumes, so the incremental
  mAP engine and the ORIC oracle run unchanged on top of batched matching.

Padding conventions: padded box rows are all-zero (degenerate boxes, IoU 0),
padded classes are ``-1`` (never equal to a real class id), padded scores 0;
the ``mask`` arrays are the source of truth — consumers must never rely on
sentinel values alone.  ``from_list`` produces prefix masks (valid entries
first) but the matcher and feature kernels only require the mask.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.detection.map_engine import Detections, GroundTruth, ImageEval
from repro.kernels.iou_matrix.ops import iou_matrix_batch, resolve_path
from repro.obs.jit_stats import register_jit


def _pad_dim(n: int, multiple: int = 8) -> int:
    return max(multiple, -(-n // multiple) * multiple)


def _stack_padded(
    arrays: Sequence[np.ndarray], max_n: int, trailing: Tuple[int, ...], dtype, fill
) -> np.ndarray:
    out = np.full((len(arrays), max_n) + trailing, fill, dtype=dtype)
    for i, a in enumerate(arrays):
        out[i, : len(a)] = a
    return out


@dataclass(kw_only=True)
class _BoxBatch:
    """Shared padded struct-of-arrays core: ``boxes (B, N, 4)`` float32,
    ``classes (B, N)`` int32, ``mask (B, N)`` bool.

    Keyword-only construction: the silent dtype coercion in
    ``__post_init__`` would otherwise let positionally swapped arrays pass
    shape checks and corrupt downstream matching."""

    boxes: np.ndarray
    classes: np.ndarray
    mask: np.ndarray

    def __post_init__(self) -> None:
        self.boxes = np.asarray(self.boxes, np.float32)
        self.classes = np.asarray(self.classes, np.int32)
        self.mask = np.asarray(self.mask, bool)

    @staticmethod
    def _padded_fields(items, max_boxes: Optional[int]):
        """(resolved max_boxes, common field dict) for a ragged item list —
        every item exposes ``boxes``/``classes`` and ``len``.

        ``items`` may be empty: the result is the explicit zero-length
        batch (``B == 0`` with ``max_boxes`` at the padding floor), which
        round-trips through ``to_list``/``match_batch``/``to_image_evals``
        like any other batch."""
        ns = [len(it) for it in items]
        top = max(ns, default=0)
        if max_boxes is None:
            max_boxes = _pad_dim(top)
        elif top > max_boxes:
            raise ValueError(f"image with {top} boxes exceeds max_boxes={max_boxes}")
        fields = dict(
            boxes=_stack_padded(
                [it.boxes for it in items], max_boxes, (4,), np.float32, 0.0
            ),
            classes=_stack_padded(
                [it.classes for it in items], max_boxes, (), np.int32, -1
            ),
            mask=_stack_padded(
                [np.ones(n, bool) for n in ns], max_boxes, (), bool, False
            ),
        )
        return max_boxes, fields

    #: per-field batch-axis padding fills — the same conventions ``from_list``
    #: uses for the box axis (empty images: zero boxes, class -1, mask False)
    _IMAGE_FILL = {"boxes": 0.0, "classes": -1, "scores": 0.0, "mask": False}

    def pad_images(self, n_images: int):
        """The batch extended to ``n_images`` along the *image* axis with
        empty (all-masked) images — ragged last-shard padding for the
        sharded data plane (``repro.fleet.plane``).  Padded images match and
        featurize to all-False/all-zero rows, so cropping after a sharded
        gather recovers the original results exactly."""
        B = len(self)
        if n_images < B:
            raise ValueError(f"pad_images({n_images}) below batch size {B}")
        if n_images == B:
            return self
        kwargs = {}
        for f in dataclasses.fields(self):
            a = getattr(self, f.name)
            pad = np.full(
                (n_images - B,) + a.shape[1:], self._IMAGE_FILL[f.name], a.dtype
            )
            kwargs[f.name] = np.concatenate([a, pad])
        return type(self)(**kwargs)

    def slice_images(self, start: int, stop: int):
        """Images ``[start, stop)`` as a batch of the same type (views of
        this batch's arrays, no copy)."""
        return type(self)(
            **{
                f.name: getattr(self, f.name)[start:stop]
                for f in dataclasses.fields(self)
            }
        )

    def __len__(self) -> int:
        return self.boxes.shape[0]

    @property
    def max_boxes(self) -> int:
        return self.boxes.shape[1]

    @property
    def counts(self) -> np.ndarray:
        return self.mask.sum(axis=1)

    def to_list(self) -> list:
        return [self[i] for i in range(len(self))]


@dataclass(kw_only=True)
class GroundTruthBatch(_BoxBatch):
    """Padded per-image annotations: ``boxes (B, M, 4)``, ``classes (B, M)``,
    ``mask (B, M)`` — float32/int32 struct-of-arrays."""

    @classmethod
    def from_list(
        cls, gts: Sequence[GroundTruth], max_boxes: Optional[int] = None
    ) -> "GroundTruthBatch":
        """Pad a ragged annotation list; ``[]`` yields the explicit
        zero-length batch."""
        _, fields = cls._padded_fields(gts, max_boxes)
        return cls(**fields)

    def __getitem__(self, i: int) -> GroundTruth:
        m = self.mask[i]
        return GroundTruth(self.boxes[i][m], self.classes[i][m])


@dataclass(kw_only=True)
class DetectionsBatch(_BoxBatch):
    """Padded per-image detector output: ``boxes (B, K, 4)``, ``scores
    (B, K)``, ``classes (B, K)``, ``mask (B, K)``."""

    scores: np.ndarray

    def __post_init__(self) -> None:
        super().__post_init__()
        self.scores = np.asarray(self.scores, np.float32)

    @classmethod
    def from_list(
        cls, dets: Sequence[Detections], max_boxes: Optional[int] = None
    ) -> "DetectionsBatch":
        """Pad a ragged detection list; ``[]`` yields the explicit
        zero-length batch."""
        max_boxes, fields = cls._padded_fields(dets, max_boxes)
        scores = _stack_padded(
            [d.scores for d in dets], max_boxes, (), np.float32, 0.0
        )
        return cls(scores=scores, **fields)

    def __getitem__(self, i: int) -> Detections:
        m = self.mask[i]
        return Detections(self.boxes[i][m], self.scores[i][m], self.classes[i][m])


# ---------------------------------------------------------------------------
# Batched greedy matching
# ---------------------------------------------------------------------------

@dataclass
class MatchResult:
    """Batched matching output, aligned to the original detection slots.

    ``tp[b, t, k]`` — detection slot ``k`` of image ``b`` is a true positive
    at IoU threshold ``t``; ``match_gt[b, t, k]`` — the matched GT *slot*
    (into the padded GT arrays) or -1.  Padded detection slots are never tp.
    """

    tp: np.ndarray  # (B, T, K) bool
    match_gt: np.ndarray  # (B, T, K) int32
    iou_thresholds: Tuple[float, ...] = field(default=(0.5,))


@jax.jit
def _greedy_match(
    iou: jnp.ndarray,  # (B, K, M) masked: ineligible pairs hold -1
    order: jnp.ndarray,  # (B, K) detection slots by descending score
    thresholds: jnp.ndarray,  # (T,)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, K, M = iou.shape
    T = thresholds.shape[0]
    iou_s = jnp.take_along_axis(iou, order[:, :, None], axis=1)

    def step(taken, row):  # taken (B, T, M); row (B, M)
        avail = jnp.where(taken, -1.0, row[:, None, :])  # (B, T, M)
        j = jnp.argmax(avail, axis=-1)  # (B, T) first max, as np.argmax
        best = jnp.take_along_axis(avail, j[..., None], axis=-1)[..., 0]
        hit = best >= thresholds[None, :]
        slot = lax.broadcasted_iota(jnp.int32, (B, T, M), 2)
        taken = taken | (hit[:, :, None] & (slot == j[:, :, None].astype(jnp.int32)))
        return taken, (hit, jnp.where(hit, j.astype(jnp.int32), -1))

    taken0 = jnp.zeros((B, T, M), bool)
    _, (tp_s, mj_s) = lax.scan(step, taken0, jnp.moveaxis(iou_s, 1, 0))
    tp_s = jnp.moveaxis(tp_s, 0, 2)  # (B, T, K), sorted-detection order
    mj_s = jnp.moveaxis(mj_s, 0, 2)
    # scatter back to original detection slots
    inv = jnp.argsort(order, axis=1)  # inv[b, slot] = sorted position of slot
    tp = jnp.take_along_axis(tp_s, inv[:, None, :], axis=2)
    mj = jnp.take_along_axis(mj_s, inv[:, None, :], axis=2)
    return tp, mj


register_jit("detection.greedy_match", _greedy_match)


@jax.jit
def _match_inputs(
    d_scores, d_classes, d_mask, g_classes, g_mask, iou
):
    """Eligibility masking + the global score order that reproduces the
    per-class stable sort of ``match_detections``."""
    eligible = (
        d_mask[:, :, None]
        & g_mask[:, None, :]
        & (d_classes[:, :, None] == g_classes[:, None, :])
    )
    masked = jnp.where(eligible, iou, -1.0)
    # Greedy matching is independent per class, so one global pass in
    # descending-score order with class-eligibility masking is exactly the
    # per-class loop.  Stable sort keeps the reference's tie order; invalid
    # slots sink to the end with -inf keys.
    keys = jnp.where(d_mask, d_scores, -jnp.inf)
    order = jnp.argsort(-keys, axis=1, stable=True)
    return masked, order


register_jit("detection.match_inputs", _match_inputs)


def match_batch(
    det: DetectionsBatch,
    gt: GroundTruthBatch,
    iou_thresholds: Sequence[float] = (0.5,),
    *,
    interpret: Union[None, bool, str] = None,
    tile_b: int = 8,
    tile_n: int = 128,
    tile_m: int = 128,
) -> MatchResult:
    """Batched COCO greedy matching on device; tp flags are identical to
    per-image :func:`repro.detection.map_engine.match_detections`.

    The per-image IoU runs through the ``iou_matrix`` dispatch
    (``interpret=None`` auto-selects the jitted jnp reference on CPU and
    the compiled Pallas kernel on TPU/GPU), the greedy assignment through
    one ``lax.scan`` over score-ordered slots.
    """
    if len(det) != len(gt):
        raise ValueError(f"batch size mismatch: {len(det)} dets vs {len(gt)} gts")
    thresholds = jnp.asarray(iou_thresholds, jnp.float32)
    interp = resolve_path(interpret)
    if interp == "interpret":
        # interpreter mode runs one Python step per grid cell: shrink tiles
        # to the (small) padded box axes and batch more images per step so
        # the grid stays short.  Compiled TPU keeps the 128-lane tiles.
        tile_n = min(tile_n, _pad_dim(det.max_boxes))
        tile_m = min(tile_m, _pad_dim(gt.max_boxes))
        tile_b = min(64, _pad_dim(len(det)))
    iou = iou_matrix_batch(
        jnp.asarray(det.boxes), jnp.asarray(gt.boxes),
        tile_b=tile_b, tile_n=tile_n, tile_m=tile_m, interpret=interp,
    )
    masked, order = _match_inputs(
        jnp.asarray(det.scores), jnp.asarray(det.classes), jnp.asarray(det.mask),
        jnp.asarray(gt.classes), jnp.asarray(gt.mask), iou,
    )
    tp, mj = _greedy_match(masked, order, thresholds)
    return MatchResult(
        tp=np.asarray(tp),
        match_gt=np.asarray(mj, np.int32),
        iou_thresholds=tuple(float(t) for t in iou_thresholds),
    )


def to_image_evals(
    det: DetectionsBatch, gt: GroundTruthBatch, result: MatchResult
) -> List[ImageEval]:
    """Convert a batched :class:`MatchResult` into the per-image
    ``ImageEval`` list ``APAccumulator``/``RewardOracle`` consume — the same
    structure ``match_detections`` produces (per-class scores sorted
    descending, (T, n) tp flags, per-class-local matched GT indices)."""
    out: List[ImageEval] = []
    for b in range(len(det)):
        d_slots = np.where(det.mask[b])[0]
        g_slots = np.where(gt.mask[b])[0]
        d_cls = det.classes[b][d_slots]
        g_cls = gt.classes[b][g_slots]
        scores = det.scores[b].astype(np.float64)
        ev = ImageEval()
        for c in np.unique(g_cls):
            ev.gt_counts[int(c)] = int(np.sum(g_cls == c))
        if d_slots.size or g_slots.size:
            class_ids = np.unique(np.concatenate([d_cls, g_cls]))
        else:
            class_ids = np.zeros((0,), np.int64)
        for c in class_ids:
            c = int(c)
            d_idx = d_slots[d_cls == c]
            if d_idx.size == 0:
                continue
            order = np.argsort(-scores[d_idx], kind="stable")
            d_idx = d_idx[order]
            g_idx = g_slots[g_cls == c]  # ascending slot order == per-class order
            mj = result.match_gt[b][:, d_idx]  # (T, n) global GT slots
            local = np.searchsorted(g_idx, np.where(mj < 0, 0, mj))
            ev.per_class[c] = (scores[d_idx], result.tp[b][:, d_idx])
            ev.matched_gt[c] = np.where(mj < 0, -1, local).astype(np.int64)
        out.append(ev)
    return out
