#!/usr/bin/env python3
"""Drive the offload-decision main path once on a TPU and check its outputs.

    python3 chip_smoke.py                 # one chip, every phase below
    python3 chip_smoke.py --four-chips    # four chips, the sharded fleet plane only

One chip:

  fit       an engine built like ``build_engine``: box features (8 classes,
            top-25) into a one-hidden-layer (128) MLP, fitted on seeded
            synthetic detections; held-out estimates must track the reward
  decide    ``engine.decide`` on a 1024-image block through the compiled
            fused score kernel, against the ``reference`` path and a float64
            host forward pass
  simulate  ``simulate()`` over ``default_congested_fleet`` on a 2048-frame
            stream
  session   ``OffloadSession.submit_batch``: the fused fast route against
            the buffered route over host features
  packed    ``score_pipeline`` on host blocks (one packed buffer per call)
            against the same rows as device arrays, bit for bit, at the
            COCO head (80 classes, 100 slots, top-100), blocks of 1-64 rows
  tracker   the tracker's ``lax.scan`` with the compiled IoU kernel against
            the pure-Python ``track_clip_ref``
  fleet     ``simulate_fleet`` on the default 1024-stream city on a 1-device
            mesh: coordinated budget redistribution must beat the static
            split at equal spend

Four chips (``--four-chips``): ``FleetPlane.score``, ``score_detections``
and ``match`` on ``make_fleet_mesh(4)``, and ``simulate_fleet(n_shards=4)``,
each against the single-device engine in the same process.

Everything is built from ``--seed``; nothing is read from disk.  Each phase
prints one line: name, shapes, backend compile seconds, wall seconds and
its check.  The last line of stdout is ``{"ok": true, "device": {...}}``
and is printed only when every phase passed on a TPU; a run on another
backend, or any failed phase, exits non-zero.  JAX's compile cache goes to
``JAX_COMPILATION_CACHE_DIR`` when it is set, else to ``.jax_cache/`` in
the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import DetectionBoxFeatures, MLPRewardModel, OffloadEngine  # noqa: E402
from repro.core import EstimatorConfig  # noqa: E402
from repro.core.features import extract_features_batch, feature_dim  # noqa: E402
from repro.detection.batch import DetectionsBatch, GroundTruthBatch, match_batch  # noqa: E402
from repro.fleet import FleetPlane, default_city_scenario, run_city_scenario  # noqa: E402
from repro.fleet.runtime import simulate_fleet  # noqa: E402
from repro.kernels.dispatch import resolve_path  # noqa: E402
from repro.kernels.score_pipeline import ops as score_ops  # noqa: E402
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.obs import jit_stats  # noqa: E402
from repro.runtime import OffloadSession, default_congested_fleet, simulate  # noqa: E402
from repro.video import (  # noqa: E402
    WEAK_PROFILE,
    generate_clip,
    synthesize_detections,
    track_clip,
    track_clip_ref,
)
from repro.video import track as track_mod  # noqa: E402

NUM_CLASSES = 8
TOP_K = 25
MAX_BOXES = 30  # detector slots per image: more than top_k, so selection runs
IMAGE_SIZE = 64.0  # the weak detector's input size
HIDDEN = 128

#: |estimate difference| allowed between two float32 routes to the same
#: estimate.  XLA's default f32 matmul precision on a TPU rounds operands to
#: bfloat16 (relative error 2^-9); through a 387-wide and a 128-wide
#: contraction and the sigmoid's slope of at most 1/4, that moves an
#: estimate in [0, 1] by about 1e-3.  A wrong kernel (a misplaced weight
#: row, a missed mask) moves it by 1e-1 or more.
EST_TOL = 1e-2
#: the held-out estimate/reward correlation a fitted engine must reach
MIN_CORR = 0.5
#: tolerance of the tracker's box/velocity/confidence state (test_video's)
TRACK_TOL = 1e-5
#: the COCO head of the chip benchmark's camera cell: classes and detector
#: slots (top-k keeps every slot), and its image size
COCO_CLASSES, COCO_SLOTS, COCO_IMAGE = 80, 100, 640.0
#: rows of the packed phase's blocks: ragged chunk tails and a full chunk
PACKED_ROWS = (1, 2, 7, 13, 31, 38, 63, 64)


@dataclass(frozen=True)
class Paths:
    """The kernel path the phases must find resolved, and whether the
    lowered programs must hold a compiled Mosaic kernel."""

    kernel: str  # repro.kernels.dispatch.resolve_path(None)
    mosaic: bool  # lowered programs contain tpu_custom_call

    @classmethod
    def tpu(cls) -> "Paths":
        return cls(kernel="compiled", mosaic=True)


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def _has_mosaic(jitted, *args, **kwargs) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).as_text()


def _check_paths(paths: Paths) -> None:
    got = resolve_path(None)
    check(got == paths.kernel, f"kernel paths resolved to {got!r}, expected {paths.kernel!r}")


# ------------------------------------------------------------------ data


def synth_detections(
    rng: np.random.Generator, n: int, max_boxes: int = MAX_BOXES,
    num_classes: int = NUM_CLASSES, image_size: float = IMAGE_SIZE,
) -> DetectionsBatch:
    """``n`` images of padded weak-detector output: 0..max_boxes boxes
    each, uniform corners inside the image, Beta(2, 2) confidences."""
    counts = rng.integers(0, max_boxes + 1, n)
    mask = np.arange(max_boxes)[None, :] < counts[:, None]
    xy = rng.uniform(0.0, image_size - 20.0, (n, max_boxes, 2))
    wh = rng.uniform(2.0, 20.0, (n, max_boxes, 2))
    boxes = np.concatenate([xy, xy + wh], -1) * mask[..., None]
    scores = rng.beta(2.0, 2.0, (n, max_boxes)) * mask
    classes = np.where(mask, rng.integers(0, num_classes, (n, max_boxes)), -1)
    return DetectionsBatch(boxes=boxes, scores=scores, classes=classes, mask=mask)


def synth_rewards(rng: np.random.Generator, db: DetectionsBatch) -> np.ndarray:
    """Offload reward that the weak output reveals, as ORIC's does: crowded
    images with unsure boxes gain most from the strong detector."""
    n = db.mask.sum(axis=1)
    mean_score = db.scores.sum(axis=1) / np.maximum(n, 1)
    unsure = ((db.scores < 0.4) & db.mask).sum(axis=1) / MAX_BOXES
    return 0.5 * unsure + 0.3 * n / MAX_BOXES - 0.2 * mean_score + rng.normal(
        0.0, 0.05, len(db)
    )


def synth_ground_truth(rng: np.random.Generator, db: DetectionsBatch) -> GroundTruthBatch:
    """Annotations near the detections: jittered copies of the first few
    boxes of each image, so matching finds true and false positives."""
    m = np.minimum(db.mask.sum(axis=1), 6)
    mask = np.arange(8)[None, :] < m[:, None]
    boxes = db.boxes[:, :8] + rng.normal(0.0, 2.0, (len(db), 8, 4))
    return GroundTruthBatch(
        boxes=boxes * mask[..., None],
        classes=np.where(mask, db.classes[:, :8], -1),
        mask=mask,
    )


def _f64_scores(engine: OffloadEngine, feats: np.ndarray) -> np.ndarray:
    """The fitted MLP forward pass in float64 numpy on host features."""
    est = engine.reward_model.estimator
    p = {k: {n: np.asarray(a, np.float64) for n, a in v.items()}
         for k, v in est.params.items()}
    x = (np.asarray(feats, np.float64) - est._mu) / est._sigma
    h = x @ p["layer0"]["w"] + p["layer0"]["b"]
    h = 0.5 * h * (1.0 + np.tanh(np.sqrt(2.0 / np.pi) * (h + 0.044715 * h**3)))
    o = h @ p["layer1"]["w"][:, 0] + p["layer1"]["b"][0]
    return 1.0 / (1.0 + np.exp(-o))


# ---------------------------------------------------------------- phases


def phase_fit(seed: int, n_images: int = 4096, epochs: int = 40,
              n_eval: int = 1024) -> Tuple[OffloadEngine, Dict]:
    rng = np.random.default_rng(seed)
    db = synth_detections(rng, n_images)
    rewards = synth_rewards(rng, db)
    engine = OffloadEngine(
        feature_extractor=DetectionBoxFeatures(
            NUM_CLASSES, top_k=TOP_K, image_size=IMAGE_SIZE
        ),
        reward_model=MLPRewardModel(
            config=EstimatorConfig(hidden=(HIDDEN,), epochs=epochs, seed=seed)
        ),
        ratio=0.2,
    )
    engine.fit(db, rewards)
    check(engine.reward_model.fused, "fitted model is not the fused one-hidden-layer MLP")
    held = synth_detections(rng, n_eval)
    est = engine.score(held)
    check(bool(np.isfinite(est).all()), "non-finite held-out estimates")
    corr = float(np.corrcoef(est, synth_rewards(rng, held))[0, 1])
    check(corr >= MIN_CORR, f"held-out corr(estimate, reward)={corr:.3f} < {MIN_CORR}")
    return engine, {
        "shapes": f"images={n_images},boxes={MAX_BOXES},features={engine.reward_model.in_dim},hidden={HIDDEN},epochs={epochs}",
        "check": f"corr(estimate,reward)={corr:.4f}>={MIN_CORR}",
    }


def phase_decide(engine: OffloadEngine, seed: int, paths: Paths,
                 n_images: int = 1024) -> Dict:
    _check_paths(paths)
    db = synth_detections(np.random.default_rng(seed + 1), n_images)
    dec = engine.decide(db)
    fx = engine.feature_extractor
    params = engine.reward_model.pipeline_params()
    kw = dict(num_classes=fx.num_classes, top_k=fx.top_k, image_size=fx.image_size)
    if paths.mosaic:
        check(
            _has_mosaic(
                score_ops._score_pipeline_pallas,
                db.boxes, db.scores, db.classes, db.mask,
                params["w1"], params["b1"], params["w2"], params["b2"],
                params["mu"], params["sigma"], np.float32(fx.image_size),
                fx.num_classes, fx.top_k, 128, paths.kernel,
            ),
            "the fused score program holds no compiled Mosaic kernel",
        )
    lax = np.asarray(score_ops.score_pipeline(db, params, path="reference", **kw))
    ref = _f64_scores(engine, extract_features_batch(db, **kw))
    d_lax = float(np.max(np.abs(dec.estimates - lax)))
    d_ref = float(np.max(np.abs(dec.estimates - ref)))
    d_lax_ref = float(np.max(np.abs(lax - ref)))
    flips = int(np.sum(dec.offload != engine.policy.decide_batch(lax)))
    check(dec.estimates.shape == (n_images,), f"estimates shape {dec.estimates.shape}")
    check(bool(np.isfinite(dec.estimates).all()), "non-finite estimates")
    check(d_lax <= EST_TOL, f"max|{paths.kernel}-reference|={d_lax:.3g} > {EST_TOL}")
    check(d_ref <= EST_TOL, f"max|{paths.kernel}-f64|={d_ref:.3g} > {EST_TOL}")
    return {
        "shapes": f"images={n_images},boxes={MAX_BOXES},top_k={TOP_K},classes={NUM_CLASSES}",
        "check": (
            f"path={paths.kernel} max|{paths.kernel}-reference|={d_lax:.3e}"
            f" max|{paths.kernel}-f64|={d_ref:.3e} max|reference-f64|={d_lax_ref:.3e}"
            f" tol={EST_TOL:g}"
            f" decisions_differing_from_reference={flips} ratio={dec.ratio:.4f}"
        ),
    }


def phase_simulate(engine: OffloadEngine, seed: int, paths: Paths,
                   n_frames: int = 2048) -> Dict:
    _check_paths(paths)
    db = synth_detections(np.random.default_rng(seed + 2), n_frames)
    trace = simulate(
        engine, db, edges=default_congested_fleet(3, seed=seed), seed=seed
    )
    counts = trace.outcome_counts()
    est = np.array([r.estimate for r in trace.records])
    ratio = float(np.mean([r.offload for r in trace.records]))
    check(len(trace.records) == n_frames, f"{len(trace.records)} records for {n_frames} frames")
    check(sum(counts.values()) == n_frames, f"outcomes {counts} do not cover {n_frames} frames")
    check(bool(np.isfinite(est).all() and (est >= 0).all() and (est <= 1).all()),
          "estimates outside [0, 1]")
    check(abs(ratio - engine.ratio) <= 0.1, f"realized ratio {ratio:.3f} vs target {engine.ratio}")
    return {
        "shapes": f"frames={n_frames},edges=3,micro_batch=8",
        "check": f"outcomes={json.dumps(counts, sort_keys=True)} decided_ratio={ratio:.4f}",
    }


def phase_session(engine: OffloadEngine, seed: int, paths: Paths,
                  n_images: int = 1024, micro_batch: int = 64) -> Dict:
    _check_paths(paths)
    db = synth_detections(np.random.default_rng(seed + 3), n_images)
    fast = OffloadSession(engine, micro_batch=micro_batch).submit_batch(db)
    buffered = OffloadSession(engine, micro_batch=micro_batch)
    slow = buffered.submit_batch(db, flush=False) + buffered.flush()
    check(len(fast) == len(slow) == n_images, "routes decided different frame counts")
    check([d.step for d in fast] == [d.step for d in slow], "routes decided out of order")
    ef = np.array([d.estimate for d in fast])
    es = np.array([d.estimate for d in slow])
    delta = float(np.max(np.abs(ef - es)))
    differ = np.array([a.offload != b.offload for a, b in zip(fast, slow)])
    # a decision may only flip where the two estimates straddle the threshold
    thr = buffered.policy.threshold
    unexplained = int(np.sum(differ & (np.abs(es - thr) > delta)))
    check(delta <= EST_TOL, f"max|fast-buffered|={delta:.3g} > {EST_TOL}")
    check(unexplained == 0, f"{unexplained} decisions differ away from the threshold")
    return {
        "shapes": f"images={n_images},micro_batch={micro_batch}",
        "check": (
            f"max|fast-buffered|={delta:.3e} tol={EST_TOL:g}"
            f" decisions_differing={int(differ.sum())}"
        ),
    }


def phase_packed(seed: int, paths: Paths, rows: Tuple[int, ...] = PACKED_ROWS,
                 per_size: int = 8) -> Dict:
    """Host blocks cross as one packed buffer per scoring call; their
    estimates must equal those of the same rows passed as device arrays
    (the four-array route) bit for bit, on the resolved path and reference."""
    _check_paths(paths)
    rng = np.random.default_rng(seed + 6)
    F = feature_dim(COCO_CLASSES, COCO_SLOTS)
    params = {k: jnp.asarray(v, jnp.float32) for k, v in {
        "w1": rng.normal(0.0, F ** -0.5, (F, HIDDEN)),
        "b1": rng.normal(0.0, 0.1, HIDDEN),
        "w2": rng.normal(0.0, HIDDEN ** -0.5, HIDDEN),
        "b2": rng.normal(0.0, 0.1),
        "mu": rng.normal(0.0, 0.1, F),
        "sigma": rng.uniform(0.5, 2.0, F),
    }.items()}
    kw = dict(num_classes=COCO_CLASSES, top_k=COCO_SLOTS, image_size=COCO_IMAGE)
    pipeline_paths = tuple(dict.fromkeys((paths.kernel, "reference")))
    worst, unequal, blocks = 0.0, 0, 0
    calls0 = jit_stats.snapshot()["score_pipeline.packed"][1]
    for path in pipeline_paths:
        for n in rows:
            for _ in range(per_size):
                db = synth_detections(rng, n, COCO_SLOTS, COCO_CLASSES, COCO_IMAGE)
                host = np.asarray(score_ops.score_pipeline(db, params, path=path, **kw))
                on_device = tuple(jax.device_put(a) for a in (db.boxes, db.scores, db.classes, db.mask))
                dev = np.asarray(score_ops.score_pipeline(on_device, params, path=path, **kw))
                unequal += int(not np.array_equal(host, dev))
                worst = max(worst, float(np.max(np.abs(host - dev))))
                blocks += 1
    packed = jit_stats.snapshot()["score_pipeline.packed"][1] - calls0
    check(unequal == 0, f"{unequal} of {blocks} packed blocks differ, max|packed-arrays|={worst:.3g}")
    check(packed == blocks, f"{packed} packed calls for {blocks} host blocks")
    return {
        "shapes": (f"classes={COCO_CLASSES},slots={COCO_SLOTS},top_k={COCO_SLOTS},"
                   f"rows={min(rows)}-{max(rows)},blocks_per_path={blocks // len(pipeline_paths)}"),
        "check": (f"paths={','.join(pipeline_paths)} max|packed-arrays|={worst:.3e}"
                  f" bit_identical_blocks={blocks - unequal}/{blocks} packed_calls={packed}"),
    }


def phase_tracker(seed: int, paths: Paths, n_streams: int = 8,
                  n_frames: int = 64) -> Dict:
    _check_paths(paths)
    clip = generate_clip(n_streams, n_frames, seed=seed)
    weak = synthesize_detections(clip, WEAK_PROFILE, seed=seed + 1)
    if paths.mosaic:
        cfg = track_mod.TrackerConfig()
        frames = tuple(
            np.asarray(a) for a in (weak.boxes, weak.scores, weak.classes, weak.mask)
        )
        check(
            _has_mosaic(
                track_mod._scan_jit, track_mod._init_state(n_streams, cfg),
                frames, cfg, paths.kernel,
            ),
            "the tracker scan holds no compiled Mosaic IoU kernel",
        )
    got = track_clip(weak)
    ref = track_clip_ref(weak)
    exact = ("ids", "active", "classes", "age", "det_track",
             "n_active", "n_matched", "n_new", "n_dead")
    bad = [f for f in exact if not np.array_equal(getattr(got, f), getattr(ref, f))]
    check(not bad, f"tracker association differs from track_clip_ref in {bad}")
    close = max(
        float(np.max(np.abs(getattr(got, f) - getattr(ref, f))))
        for f in ("boxes", "vel", "conf")
    )
    check(close <= TRACK_TOL, f"tracker state max|Δ|={close:.3g} > {TRACK_TOL}")
    return {
        "shapes": f"streams={n_streams},frames={n_frames},max_dets={weak.max_boxes}",
        "check": (
            f"association==track_clip_ref state_max|Δ|={close:.3e} tol={TRACK_TOL:g}"
            f" tracks_opened={int(got.n_new.sum())}"
        ),
    }


def phase_fleet(seed: int, paths: Paths, n_streams: int = 1024, n_ticks: int = 48,
                calibration_frames: int = 4096) -> Dict:
    _check_paths(paths)
    scenario = default_city_scenario(
        n_streams=n_streams, n_ticks=n_ticks, seed=seed,
        calibration_frames=calibration_frames,
    )
    plane = FleetPlane(make_fleet_mesh(1))
    static = run_city_scenario(scenario, coordinated=False, plane=plane)
    coord = run_city_scenario(scenario, coordinated=True, plane=plane)
    gap = abs(coord.realized_ratio() - static.realized_ratio())
    check(gap <= 0.02, f"arms spent different budgets: |Δratio|={gap:.4f}")
    check(coord.mean_effective() > static.mean_effective(),
          "coordinated redistribution did not beat the static split")
    return {
        "shapes": f"streams={n_streams},ticks={n_ticks},districts={scenario.n_shards},mesh=1",
        "check": (
            f"outcomes={json.dumps(coord.trace.outcome_counts(), sort_keys=True)}"
            f" eff_acc coordinated={coord.mean_effective():.4f}"
            f" static={static.mean_effective():.4f}"
            f" ratio={coord.realized_ratio():.4f}/{static.realized_ratio():.4f}"
        ),
    }


def phase_four_chips(engine: OffloadEngine, seed: int, n_images: int = 1022,
                     n_streams: int = 1024, n_ticks: int = 16) -> Dict:
    """The sharded plane on a 4-device mesh against the single-device
    engine.  ``n_images`` is ragged against four shards on purpose."""
    mesh = make_fleet_mesh(4)
    check(mesh.devices.size == 4, f"fleet mesh has {mesh.devices.size} devices, need 4")
    plane = FleetPlane(mesh)
    rng = np.random.default_rng(seed + 4)
    db = synth_detections(rng, n_images)
    gb = synth_ground_truth(rng, db)
    fx = engine.feature_extractor
    feats = extract_features_batch(db, fx.num_classes, fx.top_k, fx.image_size)

    score_ref = np.asarray(engine.score(features=feats))
    score_got = plane.score(engine, feats)
    det_ref = np.asarray(engine.score_device(db))
    det_got = plane.score_detections(engine, db)
    m_ref = match_batch(db, gb, (0.5, 0.75))
    m_got = plane.match(db, gb, (0.5, 0.75))

    scenario = default_city_scenario(n_streams=n_streams, n_ticks=n_ticks, seed=seed)
    one = simulate_fleet(
        scenario.engine, scenario.features, n_shards=4,
        plane=FleetPlane(make_fleet_mesh(1)), fleet_factory=scenario.fleet_factory,
        seed=seed,
    )
    four = simulate_fleet(
        scenario.engine, scenario.features, n_shards=4, plane=plane,
        fleet_factory=scenario.fleet_factory, seed=seed,
    )
    est1 = np.stack([s.estimates for s in one.steps])
    est4 = np.stack([s.estimates for s in four.steps])
    dec_differ = int(np.sum(one.decision_mask() != four.decision_mask()))

    d_score = float(np.max(np.abs(score_got - score_ref)))
    d_det = float(np.max(np.abs(det_got - det_ref)))
    d_fleet = float(np.max(np.abs(est4 - est1)))
    match_same = bool(
        np.array_equal(m_got.tp, m_ref.tp) and np.array_equal(m_got.match_gt, m_ref.match_gt)
    )
    check(match_same, "sharded match differs from match_batch")
    for name, d in (("score", d_score), ("score_detections", d_det), ("fleet", d_fleet)):
        check(d <= EST_TOL, f"sharded {name} max|Δ|={d:.3g} > {EST_TOL}")
    bitident = {
        "score": bool(np.array_equal(score_got, score_ref)),
        "score_detections": bool(np.array_equal(det_got, det_ref)),
        "match": match_same,
        "simulate_fleet": bool(np.array_equal(est4, est1)),
    }
    return {
        "shapes": (
            f"images={n_images},mesh=4,features={feats.shape[1]},"
            f"city_streams={n_streams},ticks={n_ticks}"
        ),
        "check": (
            f"max|Δ| score={d_score:.3e} score_detections={d_det:.3e}"
            f" simulate_fleet={d_fleet:.3e} tol={EST_TOL:g}"
            f" fleet_decisions_differing={dec_differ}"
            f" bit_identical={json.dumps(bitident, sort_keys=True)}"
        ),
    }


# ------------------------------------------------------------------ main


class CompileClock:
    """Backend compile seconds, summed from JAX's compile-duration events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def run_phases(
    phases: List[Tuple[str, Callable[[], Dict]]], clock: CompileClock
) -> List[str]:
    """Run each phase, print its line, and return the names that failed.
    A failing phase is reported with its traceback on stderr; the phases
    after it still run so one call shows every fault."""
    failed = []
    for name, fn in phases:
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            out = fn()
            status = out["check"]
            shapes = out["shapes"]
        except Exception as e:  # the boundary: report and go on
            traceback.print_exc()
            failed.append(name)
            status, shapes = f"FAILED {type(e).__name__}: {e}", "-"
        print(
            f"phase={name} shapes={shapes} compile_s={clock.seconds - c0:.2f}"
            f" wall_s={time.perf_counter() - t0:.2f} {status}",
            flush=True,
        )
    return failed


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded fleet plane on four chips",
    )
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}", file=sys.stderr)
        return 2
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))

    clock = CompileClock()
    paths = Paths.tpu()
    seed = args.seed
    state: Dict[str, OffloadEngine] = {}

    def fit() -> Dict:
        state["engine"], out = phase_fit(seed)
        return out

    failed = run_phases([("fit", fit)], clock)
    if not failed:
        engine = state["engine"]
        if args.four_chips:
            phases = [("four_chips", lambda: phase_four_chips(engine, seed))]
        else:
            phases = [
                ("decide", lambda: phase_decide(engine, seed, paths)),
                ("simulate", lambda: phase_simulate(engine, seed, paths)),
                ("session", lambda: phase_session(engine, seed, paths)),
                ("packed", lambda: phase_packed(seed, paths)),
                ("tracker", lambda: phase_tracker(seed, paths)),
                ("fleet", lambda: phase_fleet(seed, paths)),
            ]
        failed = run_phases(phases, clock)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
