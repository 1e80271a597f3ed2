"""A camera fleet served through ``OffloadSession.submit_batch``.

Set-up: a seeded pool of COCO-shaped detections, the seeded estimator
installed through ``MLPRewardModel.from_state``, calibration scores from
scoring a seeded block through ``engine.score_device`` in ``micro_batch``
chunks (which compiles the full chunk), then every ragged chunk size
``1 .. micro_batch - 1``, and one block through the session and the
dispatcher.

Window: open loop.  Each turn of the client's loop takes every frame that
is due (at most ``max_block``), submits them as one block, and dispatches
each offload to ``MultiEdgeDispatcher`` at its own due time on the edge
clock (frames of ``1 / fps`` seconds per time unit), so edge outcomes
depend on the seed and the decisions alone.  A frame's decision has
returned when its block's decisions and dispatches have.

Check: a seeded sample of the window's frames and of the calibration block
against the plain reference (estimates as log-odds), and every sampled
decision against the threshold policy over the served estimates.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from operator import attrgetter
from typing import Callable, Dict, List, Tuple

import numpy as np

from core import weights as W
from core.spans import Spans
from reference import estimator as ref
from traffic import generator

from repro.api import DetectionBoxFeatures, MLPRewardModel, OffloadEngine
from repro.api.policies import make_policy
from repro.detection.batch import DetectionsBatch
from repro.runtime import OffloadRuntime
from repro.runtime.simulate import default_linked_fleet

#: frames of the window the check compares, and of the calibration block
SAMPLE = 4096
CAL_SAMPLE = 512
#: frames with the highest and with the lowest estimates the check adds,
#: from the window and from the calibration block
EXTREMES = 16
#: seconds past the window's close the loop keeps deciding frames due in it
DRAIN_S = 30.0
#: frames in the detection pool the window cycles through, frames in the
#: calibration block, and threads that build the ragged chunk programs
POOL_FRAMES = 8192
CALIBRATION_FRAMES = 4096
WARM_THREADS = 8


def _det_kw(cfg: Dict) -> Dict:
    return dict(num_classes=cfg["num_classes"], max_dets=cfg["max_dets"],
                image_size=float(cfg["image_size"]))


def _ref_kw(cfg: Dict) -> Dict:
    return dict(num_classes=cfg["num_classes"], top_k=cfg["top_k"],
                image_size=float(cfg["image_size"]))


def _rows(det: Dict, idx: np.ndarray) -> Dict:
    return {k: v[idx] for k, v in det.items()}


class Served:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, devices, spans: Spans,
                 profile: bool = False):
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.setup_phases: List[Tuple[str, float]] = []
        t = time.perf_counter()

        def phase(name: str) -> None:
            nonlocal t
            now = time.perf_counter()
            self.setup_phases.append((name, now - t))
            t = now

        mb = self.micro_batch = int(cfg["micro_batch"])
        self.max_block = int(mix["max_block"])
        P = self.P = POOL_FRAMES
        pool = generator.detections(np.random.default_rng([seed, 0]), P, mix, **_det_kw(cfg))
        self.pool_host = pool
        ext = {k: np.concatenate([v, v[: self.max_block]]) for k, v in pool.items()}
        self.pool = DetectionsBatch(**ext)
        n_cal = CALIBRATION_FRAMES
        cal = generator.detections(np.random.default_rng([seed, 1]), n_cal, mix, **_det_kw(cfg))
        self.cal_host = cal
        phase("pool")
        self.params = W.make(seed, _rows(cal, np.arange(min(1024, n_cal))),
                             hidden=cfg["hidden"], **_ref_kw(cfg))
        model = MLPRewardModel.from_state(*W.artifact(self.params, hidden=cfg["hidden"]))
        engine = self.engine = OffloadEngine(
            feature_extractor=DetectionBoxFeatures(
                cfg["num_classes"], top_k=cfg["top_k"], image_size=float(cfg["image_size"])),
            reward_model=model, policy=cfg["policy"], ratio=float(cfg["ratio"]),
        )
        phase("weights")
        calb = DetectionsBatch(**cal)
        parts = [engine.score_device(calb.slice_images(lo, min(lo + mb, n_cal)))
                 for lo in range(0, n_cal, mb)]
        engine.calibration_scores = np.concatenate(
            [np.asarray(p, np.float64) for p in parts])
        engine.policy = make_policy(engine.policy_name, engine.calibration_scores, engine.ratio)
        phase("calibration")
        # every ragged tail a block can leave: one program per row count,
        # traced and compiled (or read from the persistent cache) on threads
        with ThreadPoolExecutor(WARM_THREADS) as ex:
            list(ex.map(lambda n: engine.score_device(
                self.pool.slice_images(0, n)).block_until_ready(), range(1, mb)))
        phase("ragged_chunks")
        self.runtime = OffloadRuntime(
            engine, default_linked_fleet(int(cfg["edges"]), seed=seed), seed=seed)
        self.session = self.runtime.open_session(micro_batch=mb)
        self.fps = float(cfg["fps_per_camera"])
        warm = self.session.submit_batch(self.pool.slice_images(0, mb + 1))
        for d in warm:
            if d.offload:
                self.runtime.dispatcher.dispatch(0.0, -1, d.estimate)
        phase("serve_path")

    # ------------------------------------------------------------ window

    def window(self, due: np.ndarray, seconds: float,
               probe: Callable[[float], None]) -> Dict:
        n = len(due)
        rng = np.random.default_rng([self.seed, 2])
        sample = np.sort(rng.choice(n, min(SAMPLE, n), replace=False))
        est_s = np.full(len(sample), np.nan)
        off_s = np.zeros(len(sample), bool)
        lat = np.full(n, np.nan)
        sim = due * self.fps
        blocks: List[Tuple[float, float, int]] = []
        session, dispatch = self.session, self.runtime.dispatcher.dispatch
        spans, pool, P, cap = self.spans, self.pool, self.P, self.max_block
        clock = self.runtime.clock
        by_estimate = attrgetter("estimate")
        extremes: List[Tuple[float, int, bool]] = []
        k = 0
        t0 = time.perf_counter()
        deadline = seconds + DRAIN_S
        while k < n:
            now = time.perf_counter() - t0
            wait = due[k] - now
            if wait > 0:
                if wait > 0.0015:
                    time.sleep(wait - 0.001)
                continue
            if now > deadline:
                break
            probe(now)
            k1 = min(int(np.searchsorted(due, now, "right")), k + cap)
            lo = k % P
            with spans("bench.generate"):
                block = pool.slice_images(lo, lo + k1 - k)
            with spans("bench.submit"):
                decisions = session.submit_batch(block)
            with spans("bench.dispatch"):
                clock.advance(max(sim[k1 - 1] - clock.t, 0.0))
                for i, d in enumerate(decisions):
                    if d.offload:
                        dispatch(sim[k + i], k + i, d.estimate)
            t = time.perf_counter() - t0
            if len(decisions) != k1 - k:
                raise RuntimeError(f"{len(decisions)} decisions for a block of {k1 - k}")
            lat[k:k1] = t - due[k:k1]
            a, b = np.searchsorted(sample, (k, k1))
            for j in range(a, b):
                d = decisions[sample[j] - k]
                est_s[j], off_s[j] = d.estimate, d.offload
            step0 = decisions[0].step - k
            for d in (max(decisions, key=by_estimate), min(decisions, key=by_estimate)):
                extremes.append((d.estimate, d.step - step0, d.offload))
            blocks.append((now, t, k1 - k))
            k = k1
        end = time.perf_counter() - t0
        # the window's most extreme estimates join the sample: where the
        # estimate is nearest 0 or 1, a lower precision shows most
        ext = sorted(set(extremes))
        ext = ext[:EXTREMES] + ext[-EXTREMES:]
        keep = ~np.isin([i for _, i, _ in ext], sample)
        self._result = {
            "sample": np.concatenate([sample, np.array([i for _, i, _ in ext], int)[keep]]),
            "est": np.concatenate([est_s, np.array([e for e, _, _ in ext])[keep]]),
            "offload": np.concatenate([off_s, np.array([o for _, _, o in ext], bool)[keep]]),
        }
        return {"lat": lat, "end": end, "blocks": blocks, "decided": k}

    def scoring_calls(self, blocks, t_from: float, t_to: float) -> List[Tuple[int, int]]:
        """``(rows, chips)`` of each scoring call the blocks picked up in
        ``[t_from, t_to]`` made: one per ``micro_batch`` chunk."""
        mb, calls = self.micro_batch, []
        for t_pick, _, n in blocks:
            if t_from <= t_pick <= t_to:
                calls += [(mb, 1)] * (n // mb) + ([(n % mb, 1)] if n % mb else [])
        return calls

    def layer_seconds(self, spans: Dict[str, float]) -> Dict[str, float]:
        return {"serve": spans.get("bench.submit", 0.0),
                "dispatch": spans.get("bench.dispatch", 0.0)}

    # ------------------------------------------------------------- check

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        self.cal_scores = np.asarray(self.engine.calibration_scores, np.float64)
        self.host_params = W.host_copy(self.params)
        del self.session, self.runtime, self.engine, self.pool, self.params

    def check_rows(self) -> Tuple[Dict, np.ndarray, np.ndarray]:
        """The detections the check compares, and the program's estimates
        for them: the window's sample, then a sample of the calibration
        with its most extreme estimates."""
        r = self._result
        rng = np.random.default_rng([self.seed, 3])
        order = np.argsort(self.cal_scores)
        cal_idx = np.union1d(
            rng.choice(len(order), min(CAL_SAMPLE, len(order)), replace=False),
            np.concatenate([order[:EXTREMES], order[-EXTREMES:]]))
        rows = {k: np.concatenate([self.pool_host[k][r["sample"] % self.P],
                                   self.cal_host[k][cal_idx]])
                for k in self.pool_host}
        est = np.concatenate([r["est"], self.cal_scores[cal_idx]])
        return rows, est, cal_idx

    def check(self, estimates: Callable = None) -> List[Tuple[str, float, float]]:
        """The compared numbers with their limits.  ``estimates(rows,
        params, cfg)``, where given, is put in the program's place for the
        sampled rows (the control)."""
        cfg, r = self.cfg, self._result
        rows, est, _ = self.check_rows()
        if estimates is not None:
            est = estimates(rows, self.host_params, cfg)
        want = reference_forward(rows, self.host_params, cfg)
        ok = np.isfinite(est)
        gap = float(np.max(np.abs(ref.logit(est[ok]) - ref.logit(want[ok])))) if ok.any() else np.inf
        thr = float(np.quantile(np.sort(self.cal_scores), 1.0 - float(cfg["ratio"])))
        decided = np.isfinite(r["est"])
        mismatch = int(np.sum(r["offload"][decided] != (r["est"][decided] > thr)))
        self.est_gap = float(np.max(np.abs(est[ok] - want[ok]))) if ok.any() else np.inf
        return [("logit_gap", gap, float(cfg["limits"]["logit_gap"])),
                ("decision_mismatch", float(mismatch), 0.0),
                ("unscored_sampled", float(np.sum(~ok)), 0.0)]

    def check_control(self) -> List[Tuple[str, float, float]]:
        """The check, with the control put in the program's place: the
        reference in bfloat16 (``reference/control.py``) on the same rows."""
        from reference import control
        return self.check(estimates=control.estimates)


def reference_forward(rows: Dict, params: Dict, cfg: Dict, block: int = 512) -> np.ndarray:
    """Reference estimates in float64, in blocks of rows."""
    n = len(rows["scores"])
    out = np.empty(n)
    for lo in range(0, n, block):
        part = {k: v[lo: lo + block] for k, v in rows.items()}
        out[lo: lo + block] = ref.forward(ref.features(part, **_ref_kw(cfg)), params)
    return out
