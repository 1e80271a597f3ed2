"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Timing benchmarks measure
the CPU host (the TPU numbers come from the dry-run roofline, see
benchmarks/roofline.py); `derived` carries the table's headline quantity
(speedup, mAP, ms/image, ...).

  bench_fig5_context_cost    ORIC computation cost vs |E| (footnote 2)
  bench_fig5_context_gain    oracle mAP vs |E| (Fig. 5, from artifacts)
  bench_table2_conservatism  reward-sign subsets (Table II, from artifacts)
  bench_fig6_errors          TIDE decomposition (Fig. 6, from artifacts)
  bench_fig9_10_policies     mAP per policy @ r=0.2 (Figs. 9/10)
  bench_table3_pipeline      per-image pipeline latency breakdown (Table III)
  bench_fig13_ratio_latency  detection time & mAP vs offloading ratio (Fig 13)
  bench_incremental_map      APAccumulator incremental vs full recompute
  bench_oric_batch           vectorized oric_batch vs per-image loop
  bench_match_batch          batched device matcher vs per-image Python
  bench_features_batch       batched feature kernel vs per-image Python
  bench_score_pipeline       fused boxes→estimates dispatch vs the composed
                             features→score route (+ per-stage breakdown)
  bench_engine_score         OffloadEngine fused-Pallas batched scoring
  bench_dispatcher_throughput  streaming OffloadRuntime end-to-end frames/s
  bench_netsim_throughput    congested GE-linked fleet frames/s + the
                             value-iteration ref loop vs jitted scan sweep
  bench_video_pipeline       video tracker-scan fps + stale-result propagate
                             vs per-frame rematch
  bench_online_update        closed-loop updates/s (incremental last-layer
                             solve vs jitted mini-refit) + NetworkEstimator
                             per-offload overhead
  bench_fleet_scale          sharded data-plane scoring streams/s on a 1-device
                             mesh vs a mesh over every visible device
  bench_mobility_handover    motion-scan rollout throughput + handover-aware
                             vs static-pin effective accuracy at equal budget
  bench_iou                  iou_matrix ref vs Pallas side by side (+ratio)
  bench_kernels              Pallas oracles (jnp path) per-call time

``--smoke`` runs only the artifact-free benches (batched data plane, engine
scoring, dispatcher/netsim/video throughput, kernels) — the CI job.
``--only a,b,...`` filters either set by bench name (comma-separated
substrings, any match; a dev iteration aid: such runs skip the artifact
writes below).  ``--list`` prints the registered benches per set and
exits; ``--check`` verifies every module-level ``bench_*`` function is
registered in the full/smoke selection — CI runs it so a new bench can't
silently drop out of the smoke allowlist.  Every full run also writes
``artifacts/BENCH_<rev>.json`` (per-bench median ms + shapes) so the perf
trajectory is tracked across commits; CI uploads it as an artifact.
JAX's persistent compile cache goes to ``JAX_COMPILATION_CACHE_DIR`` when
it is set, else to ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import time
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(ROOT, "artifacts")
ROWS: List[str] = []
BENCHES: List[Dict] = []


def emit(
    name: str,
    us: float,
    derived: str,
    shape: Optional[Dict] = None,
    stages: Optional[Dict[str, float]] = None,
) -> None:
    """Record one bench row; ``stages`` is an optional per-stage breakdown
    (median ms per stage) carried into ``BENCH_<rev>.json`` so
    ``benchmarks/compare.py`` can name the stage that regressed."""
    row = f"{name},{us:.1f},{derived}"
    ROWS.append(row)
    entry = {
        "name": name, "median_ms": round(us / 1e3, 6), "derived": derived,
        "shape": shape or {},
    }
    if stages:
        entry["stages"] = {k: round(v, 6) for k, v in stages.items()}
    BENCHES.append(entry)
    print(row)


def _timeit(fn: Callable, n: int = 5, warmup: int = 1) -> float:
    """Median per-call time in μs over ``n`` samples."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples)) * 1e6


def _load_results():
    path = os.path.join(ART, "repro_results.json")
    if not os.path.exists(path):
        from repro.experiments.detection_repro import run_all

        return run_all(quick=True)
    with open(path) as f:
        return json.load(f)


def _pipeline_state():
    from repro.experiments.detection_repro import build_pipeline

    return build_pipeline()


def bench_fig5_context_gain() -> None:
    r = _load_results()
    f5 = r["figure5"]
    for ratio_key, cur in f5["curves"].items():
        gain = cur["mean"][-1] - cur["mean"][0]
        emit(
            f"fig5_gain_{ratio_key}", 0.0,
            f"mAP(|E|=0)={cur['mean'][0]:.4f};mAP(|E|max)={cur['mean'][-1]:.4f};delta={gain:+.4f}",
        )


def bench_fig5_context_cost() -> None:
    """ORIC evaluation cost grows with |E| — the footnote-2 trade-off."""
    import numpy as _np

    from repro.core.reward import RewardOracle

    state = _pipeline_state()
    pairs = state.val_pairs[:100]
    rng = _np.random.default_rng(0)
    for E in (0, 100, 400, 800):
        oracle = RewardOracle.from_pool(state.pool_weak_evals, E, rng)
        us = _timeit(lambda: oracle.oric_batch(pairs), n=2)
        emit(f"fig5_cost_E{E}", us / len(pairs), f"us_per_image_at_context_{E}")


def bench_table2_conservatism() -> None:
    r = _load_results()
    for k, v in r["table2"].items():
        emit(
            f"table2_{k}", 0.0,
            f"pct={v['pct']:.1f};weak_map={v['weak_map']:.4f};strong_map={v['strong_map']:.4f}",
        )


def bench_fig6_errors() -> None:
    r = _load_results()
    for policy in ("weak", "strong", "ORI", "ORIC"):
        e = r["figure6"][policy]
        derived = ";".join(
            f"{c}={e[c]:.4f}" for c in ("cls", "loc", "cls_loc", "dupe", "bkg", "miss")
        )
        emit(f"fig6_{policy}", 0.0, derived)


def bench_fig9_10_policies() -> None:
    r = _load_results()
    ratios = r["figure9_10"]["ratios"]
    i = ratios.index(0.2)
    for name, cur in r["figure9_10"]["curves"].items():
        emit(f"fig10_{name}_r0.2", 0.0, f"norm_map={cur['norm'][i]:.1f}%")
    emit("fig10_dcsb", 0.0,
         f"ratio={r['figure9_10']['dcsb']['ratio']:.2f};norm_map={r['figure9_10']['dcsb']['norm']:.1f}%")


def bench_table3_pipeline() -> None:
    """Per-image latency breakdown on this host (Table III analogue); the
    decision stage is the unified OffloadEngine's batched score."""
    import jax

    from repro.api import DetectionBoxFeatures, MLPRewardModel, OffloadEngine
    from repro.core import EstimatorConfig
    from repro.data.shapes import ShapesDataset
    from repro.models.detector import STRONG, WEAK, decode_detections, detector_init

    val = ShapesDataset.generate(64, seed=5)
    pw = detector_init(jax.random.PRNGKey(0), WEAK)
    ps = detector_init(jax.random.PRNGKey(1), STRONG)
    eng = OffloadEngine(
        feature_extractor=DetectionBoxFeatures(num_classes=8, image_size=64.0),
        reward_model=MLPRewardModel(config=EstimatorConfig(hidden=(128,), epochs=1)),
    )
    eng.fit(features=np.zeros((8, 387), np.float32), rewards=np.zeros(8))

    us_weak = _timeit(lambda: decode_detections(pw, WEAK, val.images), n=2) / len(val)
    dets = decode_detections(pw, WEAK, val.images)
    feats = eng.feature_extractor(dets)
    us_est = _timeit(lambda: eng.score(features=feats), n=5) / len(val)
    us_strong = _timeit(lambda: decode_detections(ps, STRONG, val.images), n=2) / len(val)
    total_off = us_weak + us_est + us_strong
    emit("table3_weak_detector", us_weak, f"share_not_offloaded={us_weak/(us_weak+us_est)*100:.1f}%")
    emit("table3_reward_estimation", us_est, f"share_not_offloaded={us_est/(us_weak+us_est)*100:.1f}%")
    emit("table3_strong_detector", us_strong, f"share_offloaded={us_strong/total_off*100:.1f}%")


def bench_fig13_ratio_latency() -> None:
    """mAP and mean per-image time vs offloading ratio (concavity check)."""
    r = _load_results()
    state = _pipeline_state()
    # host timings for weak/strong passes
    import jax

    from repro.models.detector import STRONG, WEAK, decode_detections
    from repro.train.checkpoint import load_pytree
    from repro.models.detector import detector_init

    curves = r["figure9_10"]["curves"]["est_MORIC"]
    ratios = r["figure9_10"]["ratios"]
    pw = detector_init(jax.random.PRNGKey(0), WEAK)
    ps = detector_init(jax.random.PRNGKey(1), STRONG)
    from repro.data.shapes import ShapesDataset

    val = ShapesDataset.generate(32, seed=6)
    us_w = _timeit(lambda: decode_detections(pw, WEAK, val.images), n=2) / 32
    us_s = _timeit(lambda: decode_detections(ps, STRONG, val.images), n=2) / 32
    for ratio, m in zip(ratios, curves["map"]):
        us = us_w + ratio * us_s
        emit(f"fig13_r{ratio}", us, f"map={m:.4f}")


def bench_incremental_map() -> None:
    """Beyond-paper: incremental context evaluation vs full recompute."""
    from repro.detection.map_engine import APAccumulator, dataset_map, match_detections

    state = _pipeline_state()
    evals = state.pool_weak_evals[:800]
    acc = APAccumulator((0.5,))
    for ev in evals:
        acc.add(ev)
    acc.map()  # warm caches
    probe = state.val_pairs[0].weak
    us_inc = _timeit(lambda: acc.map_with_image(probe), n=20)

    def full():
        a2 = APAccumulator((0.5,))
        for ev in evals:
            a2.add(ev)
        a2.add(probe)
        return a2.map()

    us_full = _timeit(full, n=2)
    emit("incremental_map", us_inc, f"full_recompute_us={us_full:.0f};speedup={us_full/us_inc:.0f}x")


def bench_oric_batch() -> None:
    """Vectorized RewardOracle.oric_batch vs the per-image oric() loop."""
    from repro.core.reward import RewardOracle

    state = _pipeline_state()
    pairs = state.val_pairs[:200]
    rng = np.random.default_rng(0)
    oracle = RewardOracle.from_pool(state.pool_weak_evals, 400, rng)

    def loop():
        return np.array([oracle.oric(im) for im in pairs])

    us_loop = _timeit(loop, n=2)
    us_vec = _timeit(lambda: oracle.oric_batch(pairs), n=2)
    emit(
        "oric_batch_vectorized", us_vec,
        f"loop_us={us_loop:.0f};speedup={us_loop / max(us_vec, 1e-9):.2f}x",
    )


def _synthetic_detections(n_images: int, seed: int, num_classes: int = 8,
                          size: float = 64.0):
    """Artifact-free ragged detection/GT lists with a realistic size mix."""
    from repro.detection.map_engine import Detections, GroundTruth

    rng = np.random.default_rng(seed)
    dets, gts = [], []
    for _ in range(n_images):
        m = int(rng.integers(1, 6))
        b = rng.uniform(0, size - 25, (m, 2))
        wh = rng.uniform(5, 20, (m, 2))
        gts.append(GroundTruth(np.concatenate([b, b + wh], 1),
                               rng.integers(0, num_classes, m)))
        k = int(rng.integers(1, 12))
        b = rng.uniform(0, size - 25, (k, 2))
        wh = rng.uniform(5, 20, (k, 2))
        dets.append(Detections(np.concatenate([b, b + wh], 1),
                               rng.uniform(0.1, 1.0, k),
                               rng.integers(0, num_classes, k)))
    return dets, gts


def bench_match_batch(n_images: int = 512) -> None:
    """Batched device matcher (Pallas IoU + lax greedy scan) vs the
    per-image Python ``match_detections`` loop at pool scale."""
    from repro.detection.batch import DetectionsBatch, GroundTruthBatch, match_batch
    from repro.detection.map_engine import match_detections

    dets, gts = _synthetic_detections(n_images, seed=0)
    db = DetectionsBatch.from_list(dets)
    gb = GroundTruthBatch.from_list(gts)

    def loop():
        return [match_detections(d, g, (0.5,)) for d, g in zip(dets, gts)]

    us_batch = _timeit(lambda: match_batch(db, gb, (0.5,)), n=5)
    us_loop = _timeit(loop, n=2)
    emit(
        f"match_batch_b{n_images}", us_batch / n_images,
        f"loop_us_per_image={us_loop / n_images:.1f}"
        f";speedup={us_loop / max(us_batch, 1e-9):.1f}x",
        shape={"images": n_images, "max_det": int(db.max_boxes),
               "max_gt": int(gb.max_boxes), "thresholds": 1},
    )


def bench_features_batch(n_images: int = 512, num_classes: int = 8) -> None:
    """One jitted feature kernel over a DetectionsBatch vs the per-image
    numpy ``extract_features`` loop."""
    from repro.core.features import extract_features, extract_features_batch
    from repro.detection.batch import DetectionsBatch

    dets, _ = _synthetic_detections(n_images, seed=1)
    db = DetectionsBatch.from_list(dets)

    def loop():
        return np.stack(
            [extract_features(d, num_classes, 25, 64.0) for d in dets]
        )

    us_batch = _timeit(lambda: extract_features_batch(db, num_classes, 25, 64.0), n=5)
    us_loop = _timeit(loop, n=2)
    emit(
        f"features_batch_b{n_images}", us_batch / n_images,
        f"loop_us_per_image={us_loop / n_images:.1f}"
        f";speedup={us_loop / max(us_batch, 1e-9):.1f}x",
        shape={"images": n_images, "max_det": int(db.max_boxes),
               "top_k": 25, "num_classes": num_classes},
    )


def bench_score_pipeline(n_images: int = 512, num_classes: int = 8) -> None:
    """The fused device-resident boxes→estimates dispatch
    (``engine.score_device``) vs the composed ``extract_features_batch →
    engine.score`` route at serve-block scale, with the per-stage
    breakdown (iou / features / mlp / fused, median ms) recorded in the
    bench JSON so ``benchmarks/compare.py`` can name a regressing stage."""
    import jax.numpy as jnp

    from repro.api import DetectionBoxFeatures, OffloadEngine
    from repro.core.features import extract_features_batch
    from repro.detection.batch import DetectionsBatch
    from repro.kernels.iou_matrix import iou_matrix_batch

    dets, _ = _synthetic_detections(n_images, seed=2, num_classes=num_classes)
    db = DetectionsBatch.from_list(dets)
    fx = DetectionBoxFeatures(num_classes=num_classes, top_k=25, image_size=64.0)
    rng = np.random.default_rng(0)
    xcal = extract_features_batch(db, num_classes, 25, 64.0)
    eng = OffloadEngine(feature_extractor=fx, ratio=0.3)
    eng.fit(features=xcal, rewards=rng.uniform(0, 1, n_images))

    def composed():
        return eng.score(features=extract_features_batch(db, num_classes, 25, 64.0))

    def fused():
        return np.asarray(eng.score_device(db))

    # warm both paths and pin the bit-identity contract while we're here
    assert np.array_equal(composed(), fused()), "fused path diverged from composed"
    us_comp = _timeit(composed, n=10)
    us_fused = _timeit(fused, n=10)
    us_feat = _timeit(
        lambda: extract_features_batch(db, num_classes, 25, 64.0), n=10
    )
    us_mlp = _timeit(lambda: eng.score(features=xcal), n=10)
    boxes = jnp.asarray(db.boxes)
    iou_matrix_batch(boxes, boxes).block_until_ready()
    us_iou = _timeit(
        lambda: iou_matrix_batch(boxes, boxes).block_until_ready(), n=10
    )
    speedup = us_comp / max(us_fused, 1e-9)
    emit(
        f"score_pipeline_b{n_images}", us_fused,
        f"composed_us={us_comp:.0f};speedup={speedup:.2f}x"
        f";frames_per_s={n_images / (us_fused / 1e6):.0f}",
        shape={"images": n_images, "max_det": int(db.max_boxes),
               "top_k": 25, "num_classes": num_classes},
        stages={"iou": us_iou / 1e3, "features": us_feat / 1e3,
                "mlp": us_mlp / 1e3, "fused": us_fused / 1e3},
    )


def bench_engine_score() -> None:
    """OffloadEngine batched scoring through the fused Pallas MLP path."""
    from repro.api import MLPRewardModel, OffloadEngine
    from repro.core import EstimatorConfig

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (1024, 387)).astype(np.float32)
    r = rng.normal(0, 1, 1024)
    eng = OffloadEngine(
        reward_model=MLPRewardModel(config=EstimatorConfig(hidden=(128,), epochs=2))
    )
    eng.fit(features=x, rewards=r)
    eng.score(features=x)  # compile
    us = _timeit(lambda: eng.score(features=x), n=5)
    emit("engine_score_b1024", us / 1024, f"us_per_image;fused={eng.reward_model.fused}")


def _smoke_engine(hidden=(128,), n=1024, d=387, seed=0):
    from repro.api import MLPRewardModel, OffloadEngine
    from repro.core import EstimatorConfig

    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    r = rng.normal(0, 1, n)
    eng = OffloadEngine(
        reward_model=MLPRewardModel(config=EstimatorConfig(hidden=hidden, epochs=2))
    )
    eng.fit(features=x, rewards=r)
    return eng, x


def bench_dispatcher_throughput() -> None:
    """Streaming serve loop end to end: session micro-batched scoring through
    the fused Pallas path + multi-edge dispatch, per strategy."""
    from repro.runtime import default_edge_fleet, simulate

    eng, x = _smoke_engine()
    n = len(x)
    for strategy in ("round_robin", "least_loaded", "score_weighted"):
        def run():
            return simulate(
                eng, features=x, edges=default_edge_fleet(3, seed=0),
                strategy=strategy, ratio=0.3, micro_batch=64, seed=0,
            )

        us = _timeit(run, n=2, warmup=1)
        trace = run()
        out = trace.outcome_counts()
        fps = n / (us / 1e6)
        emit(
            f"dispatcher_{strategy}_b{n}", us / n,
            f"frames_per_s={fps:.0f};offloaded={out.get('offloaded', 0)}"
            f";degraded={out.get('degraded', 0)};fused={eng.reward_model.fused}",
        )


def bench_netsim_throughput() -> None:
    """The netsim data plane end to end: queue-aware streaming through a
    congested Gilbert–Elliott 3-edge fleet (frames/s), plus the
    value-iteration solver — per-state Python reference loop vs the jitted
    ``lax.scan`` vmapped over a whole ratio grid."""
    from repro.netsim import (
        quantile_threshold,
        value_iteration_ref,
        value_iteration_sweep,
    )
    from repro.netsim.policy import _estimate_bins
    from repro.runtime import default_congested_fleet, simulate

    eng, x = _smoke_engine(n=512)
    qa = eng.with_policy("queue_aware")
    n = len(x)

    def run():
        return simulate(
            qa, features=x, edges=default_congested_fleet(3, seed=0),
            ratio=0.3, micro_batch=1, seed=0,
        )

    us = _timeit(run, n=2, warmup=1)
    trace = run()
    d = trace.latency_decomposition() or {}
    emit(
        f"netsim_congested_fps_b{n}", us / n,
        f"frames_per_s={n / (us / 1e6):.0f}"
        f";mean_queue_delay={d.get('queue', 0.0):.2f}"
        f";offloaded={trace.outcome_counts().get('offloaded', 0)}",
        shape={"frames": n, "edges": 3},
    )

    cal = np.asarray(eng.calibration_scores)
    ratios = np.linspace(0.05, 0.95, 16)
    e_bins = _estimate_bins(cal, 32)

    def ref_loop():
        return [
            value_iteration_ref(
                e_bins, quantile_threshold(cal, r), max_queue=16, n_sweeps=64
            )
            for r in ratios
        ]

    us_ref = _timeit(ref_loop, n=2)
    kw = dict(max_queue=16, n_sweeps=64, n_bins=32)
    value_iteration_sweep(cal, ratios, **kw)  # compile
    us_jit = _timeit(lambda: value_iteration_sweep(cal, ratios, **kw), n=5)
    emit(
        "netsim_value_iteration_sweep", us_jit,
        f"ref_loop_us={us_ref:.0f};speedup={us_ref / max(us_jit, 1e-9):.1f}x",
        shape={"ratios": len(ratios), "max_queue": 16, "sweeps": 64, "bins": 32},
    )


def bench_iou(n: int = 512, m: int = 512, interpret=None) -> None:
    """iou_matrix jnp reference vs the dispatched kernel, side by side, with
    the dispatch/ref ratio — ``interpret`` threads through to the kernel
    wrapper (None = backend auto: compiled Pallas on TPU/GPU, the jitted
    jnp reference on CPU where the interpreter cannot win; see
    ``repro.kernels.dispatch``).  On the auto reference path the ratio is
    asserted ~1x — the fallback must never reintroduce the old
    pallas-slower-than-ref regression."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.iou_matrix import iou_matrix, iou_matrix_ref, resolve_path

    rng = np.random.default_rng(0)
    a = jnp.asarray(np.concatenate([rng.uniform(0, 50, (n, 2))] * 2, 1), jnp.float32)
    b = jnp.asarray(np.concatenate([rng.uniform(0, 50, (m, 2))] * 2, 1), jnp.float32)
    shape = {"n": n, "m": m}
    f = jax.jit(iou_matrix_ref)
    f(a, b).block_until_ready()
    us_ref = _timeit(lambda: f(a, b).block_until_ready(), n=20)
    emit(f"kernel_iou_ref_{n}x{m}", us_ref, "jnp_oracle", shape=shape)
    mode = resolve_path(interpret)
    iou_matrix(a, b, interpret=interpret).block_until_ready()
    us_pal = _timeit(
        lambda: iou_matrix(a, b, interpret=interpret).block_until_ready(), n=20
    )
    ratio = us_pal / max(us_ref, 1e-9)
    if mode == "reference":
        assert ratio < 1.25, (
            f"auto iou dispatch ({ratio:.2f}x) slower than the jnp reference "
            f"it resolves to — dispatch overhead regression"
        )
    emit(
        f"kernel_iou_pallas_{n}x{m}", us_pal,
        f"mode={mode};pallas_over_ref={ratio:.2f}x",
        shape=shape,
    )


def bench_video_pipeline(n_streams: int = 8, n_frames: int = 64) -> None:
    """The repro.video data plane: jitted tracker-scan throughput over a
    seeded multi-stream clip, and stale-edge-result ``propagate`` (snap to
    live tracks) vs the naive per-frame rematch baseline."""
    from repro.video import (
        STRONG_PROFILE,
        WEAK_PROFILE,
        VideoTracker,
        generate_clip,
        propagate_rematch_ref,
        synthesize_detections,
        track_clip,
    )

    clip = generate_clip(n_streams, n_frames, seed=0)
    weak = synthesize_detections(clip, WEAK_PROFILE, seed=1)
    strong = synthesize_detections(clip, STRONG_PROFILE, seed=2)
    track_clip(weak)  # compile the scan
    frames = n_streams * n_frames
    us = _timeit(lambda: track_clip(weak), n=5)
    emit(
        f"video_tracker_scan_t{n_frames}_b{n_streams}", us / frames,
        f"frames_per_s={frames / (us / 1e6):.0f}",
        shape={"frames": n_frames, "streams": n_streams,
               "max_dets": int(weak.max_boxes)},
    )

    vt = VideoTracker(n_streams)
    for t in range(n_frames):
        vt.update(weak.frame(t))
    t0, t1 = n_frames - 5, n_frames - 1
    edge = strong.det(t0, 0)
    weak_seq = [weak.det(t, 0) for t in range(t0 + 1, t1 + 1)]
    us_prop = _timeit(lambda: vt.propagate(edge, t0, t1, stream=0), n=20)
    us_ref = _timeit(lambda: propagate_rematch_ref(edge, weak_seq), n=20)
    emit(
        "video_propagate_vs_rematch", us_prop,
        f"rematch_us={us_ref:.0f};speedup={us_ref / max(us_prop, 1e-9):.1f}x",
        shape={"staleness": t1 - t0, "edge_dets": len(edge)},
    )


def bench_online_update(n: int = 512, block: int = 8) -> None:
    """The closed-loop update path: incremental last-layer solve vs the
    jitted mini-refit (full updates/s, recalibration included), plus the
    NetworkEstimator record+poll per-offload overhead."""
    from repro.online import AdaptiveEngine, NetworkEstimator, OnlineConfig

    rng = np.random.default_rng(0)
    rewards = rng.uniform(0, 1, n)

    def make(update_every: int, refit_every: int):
        eng, x = _smoke_engine(n=n)
        cfg = OnlineConfig(
            min_observations=1, update_every=update_every,
            refit_every=refit_every, refit_epochs=2,
        )
        ada = AdaptiveEngine(eng, cfg)
        est = np.asarray(eng.score(features=x))
        state = {"i": 0}

        def step():
            i = state["i"] % (n // block)
            sl = slice(i * block, (i + 1) * block)
            ada.observe(x[sl], est[sl], rewards[sl])
            ada.maybe_update()
            state["i"] += 1

        return step

    us_incr = _timeit(make(1, 10**9), n=20, warmup=4)
    emit(
        f"online_incremental_update_b{block}", us_incr,
        f"updates_per_s={1e6 / us_incr:.0f};last_layer_solve",
        shape={"block": block, "features": 387},
    )
    us_refit = _timeit(make(10**9, 1), n=3, warmup=2)
    emit(
        f"online_mini_refit_b{block}", us_refit,
        f"updates_per_s={1e6 / us_refit:.0f}"
        f";incremental_speedup={us_refit / max(us_incr, 1e-9):.1f}x",
        shape={"block": block, "buffer": n},
    )

    net = NetworkEstimator()
    tick = {"t": 0.0}

    def net_step():
        t = tick["t"]
        net.record(t, 2.5, bits=8.0)
        net.poll(t + 5.0)
        tick["t"] = t + 1.0

    us_net = _timeit(net_step, n=200, warmup=10)
    emit(
        "online_netstate_step", us_net,
        f"rtt={net.rtt():.2f};per_offload_overhead",
    )


def bench_fleet_scale(n_streams: int = 2048) -> None:
    """Sharded fleet-plane scoring throughput on a 1-device mesh and on a
    mesh over every visible device, both built in this process (one
    process holds the chips).  On a CPU host, run the whole command under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to get four
    devices; with one visible device only the 1-device row is emitted.
    Each mesh re-checks bit-identity against the single-device engine path
    (compile excluded — the median is warmed)."""
    from repro.fleet import FleetPlane
    from repro.launch.mesh import make_fleet_mesh

    eng, _ = _smoke_engine()
    feats = np.random.default_rng(0).normal(0, 1, (n_streams, 387)).astype(np.float32)
    ref = np.asarray(eng.score(features=feats))
    meshes = [make_fleet_mesh(1)]
    if make_fleet_mesh().devices.size > 1:
        meshes.append(make_fleet_mesh())
    for mesh in meshes:
        plane = FleetPlane(mesh)
        out = plane.score(eng, feats)  # also warms the sharded path
        assert np.array_equal(ref, out), "sharded scoring diverged"
        us = _timeit(lambda: plane.score(eng, feats), n=5, warmup=0)
        shards = plane.n_devices
        emit(
            f"fleet_scale_shards{shards}", us / n_streams,
            f"streams_per_s={n_streams / (us / 1e6):.0f};devices={shards}",
            shape={"streams": n_streams, "features": 387, "shards": shards},
        )


def bench_kernels() -> None:
    import jax.numpy as jnp

    from repro.kernels.estimator_mlp.ref import estimator_mlp_ref
    import jax

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (256, 384)), jnp.float32)
    w1 = jnp.asarray(rng.normal(0, 0.1, (384, 128)), jnp.float32)
    b1 = jnp.zeros(128)
    w2 = jnp.asarray(rng.normal(0, 0.1, 128), jnp.float32)
    g = jax.jit(estimator_mlp_ref)
    g(x, w1, b1, w2, 0.0).block_until_ready()
    emit("kernel_estimator_mlp_b256", _timeit(lambda: g(x, w1, b1, w2, 0.0).block_until_ready(), n=50),
         "jnp_oracle;pallas_validated_in_tests")


def bench_obs_overhead() -> None:
    """Observability must cost nothing when it is off.  The streaming
    serve workload runs three ways — ``obs=None`` (the default), a noop
    handle (every plane constructed but disabled), and a fully enabled
    ``Obs`` — interleaved best-of-N so host-speed drift cancels.  The
    disabled handle is asserted within 3% of ``obs=None``; the enabled
    cost is reported, not gated (it buys metrics + spans + profiling)."""
    from repro.obs import Obs
    from repro.runtime import default_edge_fleet, simulate

    eng, x = _smoke_engine(n=512)

    def run(obs):
        return simulate(
            eng, features=x, edges=default_edge_fleet(3, seed=0),
            ratio=0.3, micro_batch=64, seed=0, obs=obs,
        )

    arms = {
        "none": lambda: run(None),
        "noop": lambda: run(Obs.noop()),
        "enabled": lambda: run(Obs()),
    }
    for f in arms.values():
        f()  # warm every path (jit caches, allocator)
    best = {k: float("inf") for k in arms}
    for _ in range(7):
        for k, f in arms.items():
            t0 = time.perf_counter()
            f()
            best[k] = min(best[k], time.perf_counter() - t0)
    over_noop = best["noop"] / best["none"] - 1.0
    over_on = best["enabled"] / best["none"] - 1.0
    assert over_noop < 0.03, (
        f"disabled observability costs {over_noop:+.1%} over obs=None "
        f"(>3%) — a hot path lost its `is None` guard"
    )
    emit(
        f"obs_overhead_b{len(x)}", best["none"] * 1e6 / len(x),
        f"noop={over_noop:+.1%};enabled={over_on:+.1%}"
        f";frames_per_s={len(x) / best['none']:.0f}",
        shape={"frames": len(x), "edges": 3, "reps": 7},
    )


def bench_mobility_handover(n_clients: int = 4, n_steps: int = 120) -> None:
    """The repro.mobility plane: jitted motion-scan rollout throughput
    (client-steps/s vs the pure-Python reference loop), and the headline
    quantity — handover-aware dispatch vs static edge pinning, effective
    accuracy at equal realized offload budget."""
    from repro.mobility import (
        MotionConfig,
        default_mobile_scenario,
        rollout,
        rollout_ref,
        run_mobile_scenario,
    )

    cfg = MotionConfig(area=(1200.0, 600.0), speed=14.0)
    T, n = 256, 64
    rollout(cfg, n, T, seed=0)  # compile the scan
    us_scan = _timeit(lambda: rollout(cfg, n, T, seed=0), n=5)
    us_ref = _timeit(lambda: rollout_ref(cfg, n, T, seed=0), n=2)
    steps = T * n
    emit(
        f"mobility_motion_scan_t{T}_b{n}", us_scan / steps,
        f"client_steps_per_s={steps / (us_scan / 1e6):.0f}"
        f";ref_loop_us={us_ref:.0f}"
        f";speedup={us_ref / max(us_scan, 1e-9):.1f}x",
        shape={"steps": T, "clients": n, "model": cfg.model},
    )

    sc = default_mobile_scenario(n_clients=n_clients, n_steps=n_steps, seed=0)

    def serve(mode):
        return run_mobile_scenario(sc, mode)

    us_serve = _timeit(lambda: serve("handover"), n=2, warmup=1)
    handover = serve("handover")
    static = serve("static")
    frames = n_clients * n_steps
    gain = (
        handover.mean_effective_accuracy() - static.mean_effective_accuracy()
    )
    emit(
        f"mobility_handover_b{frames}", us_serve / frames,
        f"frames_per_s={frames / (us_serve / 1e6):.0f}"
        f";eff_acc_gain={gain:+.4f}"
        f";handover={handover.mean_effective_accuracy():.4f}"
        f";static={static.mean_effective_accuracy():.4f}"
        f";handovers={handover.n_handovers()}",
        shape={"clients": n_clients, "steps": n_steps,
               "stations": len(sc.coverage.stations)},
    )


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, check=True,
        ).stdout.strip()
    except Exception:
        return "dev"


def _write_bench_json(smoke: bool) -> str:
    import jax

    rev = _git_rev()
    path = os.path.join(ART, f"BENCH_{rev}.json")
    payload = {
        "rev": rev,
        "smoke": smoke,
        "backend": jax.default_backend(),
        "benches": BENCHES,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def _write_obs_artifacts() -> List[str]:
    """One observed run of the congested-fleet scenario per bench sweep:
    exports ``artifacts/metrics_<rev>.json`` (the full registry, retrace
    counters included) and ``artifacts/trace_<rev>.json`` (Chrome trace —
    open in Perfetto) so every CI bench run leaves an inspectable picture
    of the serve stack, not just medians."""
    from repro.obs import Obs
    from repro.runtime import default_congested_fleet, simulate

    eng, x = _smoke_engine(n=256)
    obs = Obs()
    simulate(
        eng, features=x, edges=default_congested_fleet(3, seed=0),
        ratio=0.3, micro_batch=16, seed=0, obs=obs,
    )
    rev = _git_rev()
    metrics_path = os.path.join(ART, f"metrics_{rev}.json")
    trace_path = os.path.join(ART, f"trace_{rev}.json")
    obs.metrics.export_json(metrics_path)
    obs.tracer.export(trace_path)
    return [metrics_path, trace_path]


def registered_benches(interpret=None):
    """The selection registry: (full-run-only, smoke/artifact-free) bench
    lists.  Every module-level ``bench_*`` function MUST appear in exactly
    one of them (``--check`` / tests enforce it) — a new bench left out
    would silently never run in CI."""
    full = [
        ("fig5_context_gain", bench_fig5_context_gain),
        ("fig5_context_cost", bench_fig5_context_cost),
        ("table2_conservatism", bench_table2_conservatism),
        ("fig6_errors", bench_fig6_errors),
        ("fig9_10_policies", bench_fig9_10_policies),
        ("table3_pipeline", bench_table3_pipeline),
        ("fig13_ratio_latency", bench_fig13_ratio_latency),
        ("incremental_map", bench_incremental_map),
        ("oric_batch", bench_oric_batch),
    ]
    smoke = [
        ("match_batch", bench_match_batch),
        ("features_batch", bench_features_batch),
        ("score_pipeline", bench_score_pipeline),
        ("engine_score", bench_engine_score),
        ("dispatcher_throughput", bench_dispatcher_throughput),
        ("netsim_throughput", bench_netsim_throughput),
        ("video_pipeline", bench_video_pipeline),
        ("online_update", bench_online_update),
        ("fleet_scale", bench_fleet_scale),
        ("mobility_handover", bench_mobility_handover),
        ("iou", lambda: bench_iou(interpret=interpret)),
        ("kernels", bench_kernels),
        ("obs_overhead", bench_obs_overhead),
    ]
    return full, smoke


def check_registry() -> List[str]:
    """Module-level ``bench_*`` functions missing from the selection
    registry (a registry name is its function name minus the prefix)."""
    full, smoke = registered_benches()
    registered = {name for name, _ in full + smoke}
    return sorted(
        name
        for name, fn in globals().items()
        if name.startswith("bench_")
        and callable(fn)
        and name[len("bench_"):] not in registered
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="artifact-free benches only (batched data plane, engine score, "
             "dispatcher, netsim, video, kernels)",
    )
    ap.add_argument(
        "--interpret", choices=("auto", "true", "false"), default="auto",
        help="Pallas execution mode for bench_iou (auto = backend default)",
    )
    ap.add_argument(
        "--only", default=None, metavar="NAMES",
        help="run only benches whose name contains any of the "
             "comma-separated substrings (applied after --smoke selection)",
    )
    ap.add_argument(
        "--list", action="store_true",
        help="print the registered benches per selection set and exit",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="fail if any bench_* function is missing from the selection "
             "registry (the CI guard)",
    )
    args = ap.parse_args(argv)
    interpret = {"auto": None, "true": True, "false": False}[args.interpret]
    full, smoke = registered_benches(interpret)
    if args.check:
        missing = check_registry()
        if missing:
            raise SystemExit(
                f"benches missing from the registry (add them to "
                f"registered_benches): {missing}"
            )
        print(f"# registry complete: {len(full)} full + {len(smoke)} smoke benches")
        return
    if args.list:
        for label, benches in (("full-only", full), ("smoke", smoke)):
            for name, _ in benches:
                print(f"{label},{name}")
        return
    selected = ([] if args.smoke else full) + smoke
    if args.only is not None:
        needles = [s for s in args.only.split(",") if s]
        selected = [
            (name, fn)
            for name, fn in selected
            if any(s in name for s in needles)
        ]
        if not selected:
            ap.error(f"--only {args.only!r} matches no bench")
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax

        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache")
        )
    print("name,us_per_call,derived")
    os.makedirs(ART, exist_ok=True)
    for _, fn in selected:
        fn()
    if args.only is not None:
        # a filtered run is a dev iteration: never overwrite the canonical
        # full-run artifacts with a subset
        print("# --only run: artifacts not written")
        return
    out = os.path.join(ART, "bench_results_smoke.csv" if args.smoke else "bench_results.csv")
    with open(out, "w") as f:
        f.write("name,us_per_call,derived\n" + "\n".join(ROWS) + "\n")
    print(f"# wrote {out}")
    print(f"# wrote {_write_bench_json(args.smoke)}")
    for p in _write_obs_artifacts():
        print(f"# wrote {p}")


if __name__ == "__main__":
    main()
