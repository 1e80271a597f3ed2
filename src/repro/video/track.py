"""Device-resident multi-object tracker over the batched detection plane.

The temporal subsystem needs two primitives the per-image stack lacks:

* **association** — which detection in frame ``t`` is the same object as a
  detection in frame ``t-1`` (greedy IoU, class-gated, score order — the
  matching idiom of ``repro.detection.batch`` turned along time), and
* **propagation** — placing a *stale* result (an edge response that took a
  few frames to come back over the netsim link) onto the current frame.

Both run on the PR 3 data plane: per-frame IoU goes through the
``iou_matrix`` Pallas kernel (``iou_matrix_batch`` — one (B, K, N) tile
block per step over all streams), and a whole clip is tracked by ONE jitted
``lax.scan`` over T — no per-frame Python.  The track state is a fixed
``max_tracks`` padded struct-of-arrays per stream: box, constant-velocity
estimate, confidence, age, class, identity, active mask.

Update rules (all float32, deterministic):

- matched track: box := detection box, velocity := EMA of per-frame box
  deltas (``vel_smooth``), confidence pulled toward the detection score
  (``conf_update``), age reset;
- unmatched track: box coasts at constant velocity, confidence decays by
  ``conf_decay``, age grows; tracks die past ``max_age`` or below
  ``min_conf`` (dead slots are zeroed so state stays exactly reproducible);
- unmatched detections above ``spawn_score`` spawn into the lowest free
  slots in score order with fresh identities.

``track_clip_ref`` is the per-frame pure-Python reference associator —
the hypothesis oracle: the jitted scan must produce identical association
(identities, active masks, matches) on any clip.

``VideoTracker`` is the streaming form (one jitted step per frame, same
function the scan uses) and carries ``propagate(edge_dets, t0, t1)``:
stale edge detections are greedy-matched onto the *current* tracks and
snapped to their constant-velocity-updated boxes, with scores decayed by
``stale_decay`` per frame of staleness — the stale-result reuse primitive
the video policies credit.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.detection.batch import DetectionsBatch, _pad_dim
from repro.detection.map_engine import Detections
from repro.kernels.iou_matrix.ops import iou_matrix_batch, resolve_path


@dataclass(frozen=True)
class TrackerConfig:
    """Tracker knobs; frozen (and hashable) so it rides jit static args."""

    max_tracks: int = 16
    max_dets: int = 16          # per-frame detection slots (streaming pad)
    iou_thresh: float = 0.3     # association gate
    vel_smooth: float = 0.5     # EMA weight on the previous velocity
    conf_update: float = 0.5    # pull toward the matched detection score
    conf_decay: float = 0.75    # per unmatched frame
    max_age: int = 3            # unmatched frames before a track dies
    min_conf: float = 0.05
    spawn_score: float = 0.1    # min detection score to open a track
    stale_decay: float = 0.9    # propagate(): score decay per stale frame
    prop_iou: float = 0.2       # propagate(): stale-det -> track gate


@dataclass(kw_only=True)
class TrackFrame:
    """One frame's track state across ``B`` streams (host arrays).

    ``det_track[b, k]`` is the track slot detection ``k`` matched (-1 for
    unmatched/padded detections); ``n_active``/``n_matched``/``n_new``/
    ``n_dead`` are per-stream counts after the update.
    """

    boxes: np.ndarray      # (B, N, 4) float32
    vel: np.ndarray        # (B, N, 4) float32
    conf: np.ndarray       # (B, N) float32
    age: np.ndarray        # (B, N) int32
    classes: np.ndarray    # (B, N) int32, -1 inactive
    ids: np.ndarray        # (B, N) int32, -1 inactive
    active: np.ndarray     # (B, N) bool
    det_track: np.ndarray  # (B, K) int32
    n_active: np.ndarray   # (B,) int32
    n_matched: np.ndarray  # (B,) int32
    n_new: np.ndarray      # (B,) int32
    n_dead: np.ndarray     # (B,) int32

    def churn(self) -> np.ndarray:
        """Per-stream track churn in [0, 1]: births + deaths over the live
        population — the scene-change signal the keyframe policy probes."""
        turn = self.n_new + self.n_dead
        return turn / np.maximum(self.n_active + self.n_dead, 1)


@dataclass(kw_only=True)
class TrackHistory:
    """Stacked per-frame track state over a clip: the :class:`TrackFrame`
    arrays with a leading time axis."""

    boxes: np.ndarray
    vel: np.ndarray
    conf: np.ndarray
    age: np.ndarray
    classes: np.ndarray
    ids: np.ndarray
    active: np.ndarray
    det_track: np.ndarray
    n_active: np.ndarray
    n_matched: np.ndarray
    n_new: np.ndarray
    n_dead: np.ndarray

    @property
    def n_frames(self) -> int:
        return self.boxes.shape[0]

    def frame(self, t: int) -> TrackFrame:
        return TrackFrame(
            **{f: getattr(self, f)[t] for f in _FRAME_FIELDS}
        )


_FRAME_FIELDS = (
    "boxes", "vel", "conf", "age", "classes", "ids", "active", "det_track",
    "n_active", "n_matched", "n_new", "n_dead",
)


# ------------------------------------------------------------ jitted step


def _init_state(n_streams: int, cfg: TrackerConfig):
    B, N = n_streams, cfg.max_tracks
    return (
        jnp.zeros((B, N, 4), jnp.float32),          # boxes
        jnp.zeros((B, N, 4), jnp.float32),          # vel
        jnp.zeros((B, N), jnp.float32),             # conf
        jnp.zeros((B, N), jnp.int32),               # age
        jnp.full((B, N), -1, jnp.int32),            # classes
        jnp.full((B, N), -1, jnp.int32),            # ids
        jnp.zeros((B, N), bool),                    # active
        jnp.zeros((B,), jnp.int32),                 # next_id
    )


def _step(state, frame, cfg: TrackerConfig, interpret: str):
    """One tracker update over all B streams — pure jnp/lax + the Pallas
    IoU kernel, scanned over T by :func:`track_clip`."""
    boxes, vel, conf, age, cls, ids, active, next_id = state
    d_boxes, d_scores, d_cls, d_mask = frame
    B, N = conf.shape
    K = d_scores.shape[1]

    # associate: IoU of detections vs constant-velocity-predicted tracks
    pred = boxes + vel
    iou = iou_matrix_batch(
        d_boxes, pred,
        tile_b=_pad_dim(B), tile_n=_pad_dim(K), tile_m=_pad_dim(N),
        interpret=interpret,
    )
    eligible = (
        d_mask[:, :, None]
        & active[:, None, :]
        & (d_cls[:, :, None] == cls[:, None, :])
    )
    miou = jnp.where(eligible, iou, -1.0)
    keys = jnp.where(d_mask, d_scores, -jnp.inf)
    order = jnp.argsort(-keys, axis=1, stable=True)            # (B, K)
    iou_s = jnp.take_along_axis(miou, order[:, :, None], axis=1)

    def assoc(taken, row):  # taken (B, N); row (B, N)
        avail = jnp.where(taken, -1.0, row)
        j = jnp.argmax(avail, axis=-1)
        best = jnp.take_along_axis(avail, j[:, None], axis=-1)[:, 0]
        hit = best >= cfg.iou_thresh
        slot = lax.broadcasted_iota(jnp.int32, (B, N), 1)
        taken = taken | (hit[:, None] & (slot == j[:, None].astype(jnp.int32)))
        return taken, (hit, jnp.where(hit, j.astype(jnp.int32), -1))

    _, (hit_s, tj_s) = lax.scan(
        assoc, jnp.zeros((B, N), bool), jnp.moveaxis(iou_s, 1, 0)
    )
    inv = jnp.argsort(order, axis=1)
    hit = jnp.take_along_axis(jnp.moveaxis(hit_s, 0, 1), inv, axis=1)  # (B, K)
    tj = jnp.take_along_axis(jnp.moveaxis(tj_s, 0, 1), inv, axis=1)

    # track-side inverse map: which detection matched each track slot
    bidx = lax.broadcasted_iota(jnp.int32, (B, K), 0)
    kidx = lax.broadcasted_iota(jnp.int32, (B, K), 1)
    det_of = (
        jnp.full((B, N), -1, jnp.int32)
        .at[bidx, jnp.where(hit, tj, N)]
        .set(kidx, mode="drop")
    )

    # update matched / coast unmatched
    matched = det_of >= 0
    sd = jnp.maximum(det_of, 0)
    dbox_t = jnp.take_along_axis(d_boxes, sd[:, :, None], axis=1)
    dscore_t = jnp.take_along_axis(d_scores, sd, axis=1)
    new_vel = cfg.vel_smooth * vel + (1.0 - cfg.vel_smooth) * (dbox_t - boxes)
    boxes = jnp.where(matched[:, :, None], dbox_t, pred)
    vel = jnp.where(matched[:, :, None], new_vel, vel)
    conf = jnp.where(
        matched,
        (1.0 - cfg.conf_update) * conf + cfg.conf_update * dscore_t,
        conf * cfg.conf_decay,
    )
    age = jnp.where(matched, 0, age + 1)
    survive = active & (matched | ((age <= cfg.max_age) & (conf >= cfg.min_conf)))
    n_dead = (active & ~survive).sum(axis=1).astype(jnp.int32)
    active = survive
    # zero dead/inactive slots so state is exactly reproducible
    boxes = jnp.where(active[:, :, None], boxes, 0.0)
    vel = jnp.where(active[:, :, None], vel, 0.0)
    conf = jnp.where(active, conf, 0.0)
    age = jnp.where(active, age, 0)
    cls = jnp.where(active, cls, -1)
    ids = jnp.where(active, ids, -1)

    # spawn unmatched detections into the lowest free slots, score order
    spawn = d_mask & ~hit & (d_scores >= cfg.spawn_score)
    spawn_s = jnp.take_along_axis(spawn, order, axis=1)
    rank = jnp.take_along_axis(
        jnp.cumsum(spawn_s.astype(jnp.int32), axis=1) - 1, inv, axis=1
    )
    slot_iota = lax.broadcasted_iota(jnp.int32, (B, N), 1)
    free_sorted = jnp.sort(jnp.where(~active, slot_iota, N), axis=1)
    free_padded = jnp.concatenate(
        [free_sorted, jnp.full((B, 1), N, jnp.int32)], axis=1
    )
    target = jnp.where(
        spawn,
        jnp.take_along_axis(free_padded, jnp.clip(rank, 0, N), axis=1),
        N,
    )
    placed = spawn & (target < N)
    n_new = placed.sum(axis=1).astype(jnp.int32)
    boxes = boxes.at[bidx, target].set(d_boxes, mode="drop")
    vel = vel.at[bidx, target].set(jnp.zeros_like(d_boxes), mode="drop")
    conf = conf.at[bidx, target].set(d_scores, mode="drop")
    age = age.at[bidx, target].set(jnp.zeros((B, K), jnp.int32), mode="drop")
    cls = cls.at[bidx, target].set(d_cls, mode="drop")
    ids = ids.at[bidx, target].set(next_id[:, None] + rank, mode="drop")
    active = active.at[bidx, target].set(jnp.ones((B, K), bool), mode="drop")
    next_id = next_id + n_new

    out = dict(
        boxes=boxes, vel=vel, conf=conf, age=age, classes=cls, ids=ids,
        active=active, det_track=jnp.where(hit, tj, -1),
        n_active=active.sum(axis=1).astype(jnp.int32),
        n_matched=(hit & d_mask).sum(axis=1).astype(jnp.int32),
        n_new=n_new, n_dead=n_dead,
    )
    return (boxes, vel, conf, age, cls, ids, active, next_id), out


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def _step_jit(state, frame, cfg, interpret):
    return _step(state, frame, cfg, interpret)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def _scan_jit(state, frames, cfg, interpret):
    return lax.scan(lambda s, fr: _step(s, fr, cfg, interpret), state, frames)


def _frame_arrays(batch: DetectionsBatch, max_dets: int):
    k = batch.max_boxes
    if k > max_dets:
        raise ValueError(
            f"frame has {k} detection slots, tracker pads to max_dets={max_dets}"
        )
    pad = ((0, 0), (0, max_dets - k))
    return (
        jnp.asarray(np.pad(batch.boxes, pad + ((0, 0),))),
        jnp.asarray(np.pad(batch.scores, pad)),
        jnp.asarray(np.pad(batch.classes, pad, constant_values=-1)),
        jnp.asarray(np.pad(batch.mask, pad)),
    )


def track_clip(
    dets: "DetectionClip",
    config: Optional[TrackerConfig] = None,
    *,
    interpret: Union[None, bool, str] = None,
) -> TrackHistory:
    """Track a whole clip as ONE jitted ``lax.scan`` over T — per-frame IoU
    on the kernel path ``interpret`` resolves to, association + state
    update as masked lax ops over all streams at once."""
    cfg = config or TrackerConfig()
    frames = (
        jnp.asarray(dets.boxes, jnp.float32),
        jnp.asarray(dets.scores, jnp.float32),
        jnp.asarray(dets.classes, jnp.int32),
        jnp.asarray(dets.mask),
    )
    _, out = _scan_jit(
        _init_state(dets.n_streams, cfg), frames, cfg, resolve_path(interpret)
    )
    return TrackHistory(**{k: np.asarray(v) for k, v in out.items()})


# -------------------------------------------------------- Python reference


def _iou_f32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The Pallas kernel's IoU arithmetic, elementwise in float32 — the
    reference associator must round identically to the device path."""
    a = np.asarray(a, np.float32).reshape(-1, 4)
    b = np.asarray(b, np.float32).reshape(-1, 4)
    lt = np.maximum(a[:, None, :2], b[None, :, :2])
    rb = np.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = np.maximum(rb - lt, np.float32(0.0))
    inter = wh[..., 0] * wh[..., 1]
    area_a = np.maximum(a[:, 2] - a[:, 0], 0) * np.maximum(a[:, 3] - a[:, 1], 0)
    area_b = np.maximum(b[:, 2] - b[:, 0], 0) * np.maximum(b[:, 3] - b[:, 1], 0)
    union = area_a[:, None] + area_b[None, :] - inter
    return np.where(
        union > 0, inter / np.maximum(union, np.float32(1e-12)), np.float32(0.0)
    ).astype(np.float32)


def greedy_match_boxes(
    boxes: np.ndarray,
    scores: np.ndarray,
    targets: np.ndarray,
    iou_thresh: float,
    eligible: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Host-side greedy IoU assignment — the association idiom shared by
    ``propagate``, its rematch baseline, and the frame-difference feature:
    boxes claim targets in descending score order (stable), one target per
    box, gated by ``iou_thresh`` (and an optional ``(n_boxes, n_targets)``
    eligibility mask).  Returns the matched target index per box, -1 for
    unmatched."""
    match = np.full(len(boxes), -1, np.int32)
    if not len(boxes) or not len(targets):
        return match
    miou = _iou_f32(boxes, targets)
    if eligible is not None:
        miou = np.where(eligible, miou, np.float32(-1.0))
    taken = np.zeros(len(targets), bool)
    for k in np.argsort(-np.asarray(scores), kind="stable"):
        avail = np.where(taken, np.float32(-1.0), miou[k])
        j = int(np.argmax(avail))
        if avail[j] >= iou_thresh:
            taken[j] = True
            match[k] = j
    return match


def track_clip_ref(
    dets: "DetectionClip", config: Optional[TrackerConfig] = None
) -> TrackHistory:
    """Per-frame pure-Python/numpy reference tracker (float32 throughout) —
    the correctness oracle for the jitted scan."""
    cfg = config or TrackerConfig()
    f32 = np.float32
    T, B, K = dets.n_frames, dets.n_streams, dets.max_boxes
    N = cfg.max_tracks
    out = {
        "boxes": np.zeros((T, B, N, 4), f32),
        "vel": np.zeros((T, B, N, 4), f32),
        "conf": np.zeros((T, B, N), f32),
        "age": np.zeros((T, B, N), np.int32),
        "classes": np.full((T, B, N), -1, np.int32),
        "ids": np.full((T, B, N), -1, np.int32),
        "active": np.zeros((T, B, N), bool),
        "det_track": np.full((T, B, K), -1, np.int32),
        "n_active": np.zeros((T, B), np.int32),
        "n_matched": np.zeros((T, B), np.int32),
        "n_new": np.zeros((T, B), np.int32),
        "n_dead": np.zeros((T, B), np.int32),
    }
    for b in range(B):
        boxes = np.zeros((N, 4), f32)
        vel = np.zeros((N, 4), f32)
        conf = np.zeros(N, f32)
        age = np.zeros(N, np.int32)
        cls = np.full(N, -1, np.int32)
        ids = np.full(N, -1, np.int32)
        active = np.zeros(N, bool)
        next_id = 0
        for t in range(T):
            d_boxes = dets.boxes[t, b].astype(f32)
            d_scores = dets.scores[t, b].astype(f32)
            d_cls = dets.classes[t, b]
            d_mask = dets.mask[t, b]
            pred = boxes + vel
            iou = _iou_f32(d_boxes, pred)
            eligible = (
                d_mask[:, None] & active[None, :] & (d_cls[:, None] == cls[None, :])
            )
            miou = np.where(eligible, iou, f32(-1.0))
            keys = np.where(d_mask, d_scores, -np.inf)
            order = np.argsort(-keys, kind="stable")
            taken = np.zeros(N, bool)
            hit = np.zeros(K, bool)
            tj = np.full(K, -1, np.int32)
            for k in order:
                avail = np.where(taken, f32(-1.0), miou[k])
                j = int(np.argmax(avail))
                if avail[j] >= cfg.iou_thresh:
                    taken[j] = True
                    hit[k] = True
                    tj[k] = j
            det_of = np.full(N, -1, np.int32)
            det_of[tj[hit]] = np.flatnonzero(hit)
            matched = det_of >= 0
            sd = np.maximum(det_of, 0)
            new_vel = f32(cfg.vel_smooth) * vel + f32(1.0 - cfg.vel_smooth) * (
                d_boxes[sd] - boxes
            )
            boxes = np.where(matched[:, None], d_boxes[sd], pred)
            vel = np.where(matched[:, None], new_vel, vel)
            conf = np.where(
                matched,
                f32(1.0 - cfg.conf_update) * conf + f32(cfg.conf_update) * d_scores[sd],
                conf * f32(cfg.conf_decay),
            ).astype(f32)
            age = np.where(matched, 0, age + 1).astype(np.int32)
            survive = active & (
                matched | ((age <= cfg.max_age) & (conf >= cfg.min_conf))
            )
            n_dead = int((active & ~survive).sum())
            active = survive
            boxes = np.where(active[:, None], boxes, f32(0.0))
            vel = np.where(active[:, None], vel, f32(0.0))
            conf = np.where(active, conf, f32(0.0)).astype(f32)
            age = np.where(active, age, 0).astype(np.int32)
            cls = np.where(active, cls, -1).astype(np.int32)
            ids = np.where(active, ids, -1).astype(np.int32)
            spawn = d_mask & ~hit & (d_scores >= cfg.spawn_score)
            free = np.flatnonzero(~active)
            n_new = 0
            for r, k in enumerate(order[spawn[order]]):
                if r >= free.size:
                    break
                slot = free[r]
                boxes[slot] = d_boxes[k]
                vel[slot] = 0.0
                conf[slot] = d_scores[k]
                age[slot] = 0
                cls[slot] = d_cls[k]
                ids[slot] = next_id + r
                active[slot] = True
                n_new += 1
            next_id += n_new
            out["boxes"][t, b] = boxes
            out["vel"][t, b] = vel
            out["conf"][t, b] = conf
            out["age"][t, b] = age
            out["classes"][t, b] = cls
            out["ids"][t, b] = ids
            out["active"][t, b] = active
            out["det_track"][t, b] = np.where(hit, tj, -1)
            out["n_active"][t, b] = int(active.sum())
            out["n_matched"][t, b] = int((hit & d_mask).sum())
            out["n_new"][t, b] = n_new
            out["n_dead"][t, b] = n_dead
    return TrackHistory(**out)


# ------------------------------------------------------------- streaming


class VideoTracker:
    """Streaming tracker over ``n_streams`` parallel streams: one jitted
    step per arriving frame (the same function :func:`track_clip` scans),
    plus the stale-result ``propagate`` primitive."""

    def __init__(
        self,
        n_streams: int = 1,
        config: Optional[TrackerConfig] = None,
        *,
        interpret: Union[None, bool, str] = None,
    ):
        self.config = config or TrackerConfig()
        self.n_streams = int(n_streams)
        self._interpret = resolve_path(interpret)
        self.reset()

    def reset(self) -> None:
        self._state = _init_state(self.n_streams, self.config)
        self.frame_index = 0
        self._last: Optional[TrackFrame] = None

    @property
    def snapshot(self) -> Optional[TrackFrame]:
        """Track state after the most recent ``update`` (None before)."""
        return self._last

    def update(self, frame: DetectionsBatch) -> TrackFrame:
        """Advance every stream by one frame of detections (``len(frame)``
        must equal ``n_streams``)."""
        if len(frame) != self.n_streams:
            raise ValueError(
                f"frame batch has {len(frame)} streams, tracker {self.n_streams}"
            )
        self._state, out = _step_jit(
            self._state,
            _frame_arrays(frame, self.config.max_dets),
            self.config,
            self._interpret,
        )
        self.frame_index += 1
        self._last = TrackFrame(**{k: np.asarray(v) for k, v in out.items()})
        return self._last

    def propagate(
        self, dets: Detections, t0: float, t1: float, *, stream: int = 0
    ) -> Detections:
        """Reuse a stale edge result: place detections observed at frame
        ``t0`` onto frame ``t1`` by snapping them to the current tracks.

        Each stale detection greedy-matches by pure IoU (>= ``prop_iou``,
        score order) against the stream's active track boxes — which the
        tracker has been coasting/correcting since ``t0`` — and takes the
        matched track's box while KEEPING its own class: the tracks supply
        up-to-date geometry, the edge result supplies the (better) labels,
        so the association is deliberately class-agnostic — the weak
        detector's misclassified objects are exactly the ones a stale edge
        result must still land on.  Unmatched detections keep their stale
        geometry.  Scores decay by ``stale_decay ** (t1 - t0)``.
        """
        dt = float(t1) - float(t0)
        if dt < 0:
            raise ValueError(f"propagate backwards in time: t0={t0} > t1={t1}")
        scores = np.asarray(dets.scores, np.float64) * (self.config.stale_decay ** dt)
        out_boxes = np.asarray(dets.boxes, np.float64).copy()
        snap = self._last
        if len(dets) and snap is not None and snap.active[stream].any():
            t_boxes = snap.boxes[stream][np.flatnonzero(snap.active[stream])]
            match = greedy_match_boxes(
                dets.boxes, scores, t_boxes, self.config.prop_iou
            )
            hit = match >= 0
            out_boxes[hit] = t_boxes[match[hit]]
        return Detections(out_boxes, scores, np.asarray(dets.classes).copy())


def propagate_rematch_ref(
    edge_dets: Detections,
    weak_frames: Sequence[Detections],
    *,
    stale_decay: float = 0.9,
    iou_thresh: float = 0.2,
) -> Detections:
    """The naive alternative to tracked propagation: carry a stale result
    forward by re-matching it against EVERY intermediate frame's weak
    detections (per-frame Python greedy matching) — the O(T · N · M)
    baseline for the tracker's ``propagate``."""
    boxes = np.asarray(edge_dets.boxes, np.float64).copy()
    classes = np.asarray(edge_dets.classes)
    scores = np.asarray(edge_dets.scores, np.float64).copy()
    for wdet in weak_frames:
        # class-agnostic like VideoTracker.propagate: geometry from the
        # weak stream, labels from the edge result
        match = greedy_match_boxes(boxes, scores, wdet.boxes, iou_thresh)
        hit = match >= 0
        boxes[hit] = np.asarray(wdet.boxes, np.float64)[match[hit]]
    scores = scores * (stale_decay ** len(weak_frames))
    return Detections(boxes, scores, classes.copy())
