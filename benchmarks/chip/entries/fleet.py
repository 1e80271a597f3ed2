"""A city of cameras served through ``FleetRuntime.step``.

Set-up: a seeded pool of ticks of COCO-shaped detections (every camera one
frame per tick, cameras split into contiguous districts), the seeded
estimator installed through ``MLPRewardModel.from_state``, calibration
scores from featurizing and scoring seeded camera-wide blocks through
``engine.features`` and ``FleetPlane.score`` (the window's own shapes),
then ``WARM_TICKS`` ticks through the runtime.

Window: tick-synchronous open loop.  Each due tick takes its pool tick,
featurizes it with ``engine.features`` and serves it with
``FleetRuntime.step``: the plane scores every camera, ``fleet_fair``
decides district by district under the shared budget, and offloads
dispatch to each district's edges.  The runtime advances its own clock by
one arrival period per tick.  Every frame of a tick returns with it.

Check: a seeded sample of the window's ticks and of the calibration against
the plain reference (estimates as log-odds), and every decision of every
tick served against the reference ``fleet_fair`` replayed over the served
estimates and the edges' admissions.
"""
from __future__ import annotations

import sys
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

from core import weights as W
from core.spans import Spans
from entries.session import reference_forward
from reference import estimator as ref
from reference import fleet_fair
from traffic import generator

from repro.api import DetectionBoxFeatures, MLPRewardModel, OffloadEngine
from repro.api.policies import make_policy
from repro.detection.batch import DetectionsBatch
from repro.fleet import FleetPlane
from repro.fleet.experiment import CityScenario
from repro.fleet.runtime import FleetRuntime
from repro.launch.mesh import make_fleet_mesh
from repro.obs import Obs
from repro.runtime.dispatch import OUTCOME_OFFLOADED

#: window ticks the estimate check compares, each with every camera
SAMPLE_TICKS = 4
#: cameras with the highest and with the lowest estimates the check adds
EXTREMES = 16
DRAIN_S = 30.0
#: ticks in the detection pool the window cycles through, camera-wide
#: calibration ticks, and ticks served before the window
POOL_TICKS = 16
CALIBRATION_TICKS = 4
WARM_TICKS = 2


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

        S = self.S = int(cfg["cameras"])
        self.chips = len(devices)
        det_kw = dict(num_classes=cfg["num_classes"], max_dets=cfg["max_dets"],
                      image_size=float(cfg["image_size"]), cameras=S)
        P = self.P = POOL_TICKS
        pool = generator.detections(np.random.default_rng([seed, 0]), P * S, mix, **det_kw)
        self.pool_host = pool
        self.ticks = [DetectionsBatch(**{k: v[t * S:(t + 1) * S] for k, v in pool.items()})
                      for t in range(P)]
        n_cal = CALIBRATION_TICKS * S
        cal = generator.detections(np.random.default_rng([seed, 1]), n_cal, mix, **det_kw)
        self.cal_host = cal
        phase("pool")
        ref_kw = dict(num_classes=cfg["num_classes"], top_k=cfg["top_k"],
                      image_size=float(cfg["image_size"]))
        self.params = W.make(seed, {k: v[:1024] for k, v in cal.items()},
                             hidden=cfg["hidden"], **ref_kw)
        model = MLPRewardModel.from_state(*W.artifact(self.params, hidden=cfg["hidden"]))
        engine = self.engine = OffloadEngine(
            feature_extractor=DetectionBoxFeatures(
                cfg["num_classes"], top_k=cfg["top_k"], image_size=float(cfg["image_size"])),
            reward_model=model, policy="threshold", ratio=float(cfg["ratio"]),
        )
        phase("weights")
        plane = FleetPlane(make_fleet_mesh(self.chips))
        parts = []
        for c in range(CALIBRATION_TICKS):
            block = DetectionsBatch(**{k: v[c * S:(c + 1) * S] for k, v in cal.items()})
            parts.append(np.asarray(plane.score(engine, engine.features(block)), np.float64))
        engine.calibration_scores = np.concatenate(parts)
        engine.policy = make_policy(engine.policy_name, engine.calibration_scores, engine.ratio)
        phase("calibration")
        districts = int(cfg["districts"])
        if len(mix["detections"]["districts"]) != districts:
            raise ValueError(f"the traffic mix describes {len(mix['detections']['districts'])} "
                             f"districts, the deployment has {districts}")
        scenario = CityScenario(
            engine=engine, features=np.zeros((0, S, 0), np.float32),
            weak_ap=np.zeros((1, S)), strong_ap=np.zeros((1, S)),
            hardness=tuple(range(districts)), seed=seed,
        )
        self.obs = Obs(metrics=False, tracing=False, profiling=True) if profile else None
        ff = cfg["fleet_fair"]
        self.runtime = FleetRuntime(
            engine, S, n_shards=districts, plane=plane, ratio=float(cfg["ratio"]),
            gain=float(ff["gain"]), congestion_weight=float(ff["congestion_weight"]),
            staleness_weight=float(ff["staleness_weight"]),
            redistribute_every=float(cfg["redistribute_every"]),
            min_share=float(cfg["min_share"]), smooth=float(cfg["smooth"]),
            fleet_factory=scenario.fleet_factory, arrival_period=float(cfg["arrival_period"]),
            seed=seed, obs=self.obs,
        )
        self.served: Dict[int, float] = {}
        for sh in self.runtime.shards:
            sh.dispatcher.dispatch = self._recording(sh.dispatcher.dispatch)
        self.est: List[np.ndarray] = []
        self.offload: List[np.ndarray] = []
        self.split: List[Tuple[float, float]] = []  # (featurize, step) seconds per tick
        for w in range(WARM_TICKS):
            self._serve(self.ticks[w % P])
        self.first_window_tick = len(self.est)
        phase("warm_ticks")

    def _recording(self, dispatch: Callable) -> Callable:
        """Wrap a district's dispatch to note each admitted offload's uplink
        sojourn, keyed by ``tick * cameras + camera``."""
        served = self.served

        def recorded(now, step, estimate, **kw):
            res = dispatch(now, step, estimate, **kw)
            if res.outcome == OUTCOME_OFFLOADED:
                bd = res.breakdown
                served[step] = (bd.queue + bd.transmit) if bd is not None and (
                    bd.queue or bd.transmit) else 0.0
            return res

        return recorded

    def _serve(self, batch: DetectionsBatch) -> None:
        spans = self.spans
        t0 = time.perf_counter()
        with spans("bench.featurize"):
            x = self.engine.features(batch)
        t1 = time.perf_counter()
        with spans("bench.fleet_step"):
            step = self.runtime.step(x)
        self.split.append((t1 - t0, time.perf_counter() - t1))
        self.est.append(step.estimates)
        self.offload.append(step.offload)

    # ------------------------------------------------------------ window

    def window(self, due: np.ndarray, seconds: float,
               probe: Callable[[float], None]) -> Dict:
        n = len(due)
        lat = np.full(n * self.S, np.nan)
        blocks: List[Tuple[float, float, int]] = []
        spans, ticks, P, S = self.spans, self.ticks, self.P, self.S
        if self.obs is not None:
            self.obs.profiler.clear()
        w0 = self.first_window_tick
        j = 0
        t0 = time.perf_counter()
        deadline = seconds + DRAIN_S
        while j < n:
            now = time.perf_counter() - t0
            wait = due[j] - now
            if wait > 0:
                if wait > 0.0015:
                    time.sleep(wait - 0.001)
                continue
            if now > deadline:
                break
            probe(now)
            with spans("bench.generate"):
                batch = ticks[(w0 + j) % P]
            self._serve(batch)
            t = time.perf_counter() - t0
            lat[j * S:(j + 1) * S] = t - due[j]
            blocks.append((now, t, S))
            j += 1
        end = time.perf_counter() - t0
        self.window_ticks = j
        worst = sorted(self.split[w0:], key=lambda p: -(p[0] + p[1]))[:3]
        print("slowest ticks (featurize_s, step_s): "
              + " ".join(f"({a:.4f},{b:.4f})" for a, b in worst), file=sys.stderr)
        return {"lat": lat, "end": end, "blocks": blocks, "decided": j * S}

    def scoring_calls(self, blocks, t_from: float, t_to: float) -> List[Tuple[int, int]]:
        """One call per tick picked up in ``[t_from, t_to]``: every camera,
        split evenly over the chips."""
        return [(n // self.chips, self.chips) for t_pick, _, n in blocks
                if t_from <= t_pick <= t_to]

    def layer_seconds(self, spans: Dict[str, float]) -> Dict[str, float]:
        out = {"serve": spans.get("bench.fleet_step", 0.0),
               "featurize": spans.get("bench.featurize", 0.0)}
        if self.obs is not None:
            out["fleet_decide"] = self.obs.profiler.totals().get("fleet.decide_dispatch", 0.0)
        return out

    # ------------------------------------------------------------- check

    def release(self) -> None:
        self.cal_scores = np.asarray(self.engine.calibration_scores, np.float64)
        self.host_params = W.host_copy(self.params)
        del self.runtime, self.engine, self.ticks, self.params

    def check_rows(self) -> Tuple[Dict, np.ndarray, np.ndarray]:
        """Sampled window ticks (every camera), one calibration tick, and
        the frames with the most extreme estimates of the window and of the
        calibration: where an estimate is nearest 0 or 1, a lower precision
        shows most."""
        S, w0 = self.S, self.first_window_tick
        rng = np.random.default_rng([self.seed, 3])
        n_win = self.window_ticks
        picks = np.sort(rng.choice(n_win, min(SAMPLE_TICKS, n_win), replace=False)) + w0
        cal_tick = int(rng.integers(len(self.cal_scores) // S))
        E = np.stack(self.est[w0:w0 + n_win])
        order = np.argsort(E, axis=None)
        ext = np.concatenate([order[:EXTREMES], order[-EXTREMES:]])
        ext_t, ext_c = ext // S + w0, ext % S
        cal_order = np.argsort(self.cal_scores)
        cal_ext = np.concatenate([cal_order[:EXTREMES], cal_order[-EXTREMES:]])
        rows = {k: np.concatenate(
            [v[(t % self.P) * S:(t % self.P + 1) * S] for t in picks]
            + [self.cal_host[k][cal_tick * S:(cal_tick + 1) * S]]
            + [v[(ext_t % self.P) * S + ext_c], self.cal_host[k][cal_ext]])
            for k, v in self.pool_host.items()}
        est = np.concatenate([self.est[t] for t in picks]
                             + [self.cal_scores[cal_tick * S:(cal_tick + 1) * S]]
                             + [E.ravel()[ext], self.cal_scores[cal_ext]])
        return rows, est, picks

    def check(self, estimates: Callable = None) -> List[Tuple[str, float, float]]:
        """The compared numbers with their limits.  ``estimates(rows,
        params, cfg)``, where given, is put in the program's place for the
        sampled rows (the control)."""
        cfg = self.cfg
        rows, est, _ = self.check_rows()
        if estimates is not None:
            est = estimates(rows, self.host_params, cfg)
        want = reference_forward(rows, self.host_params, cfg)
        gap = float(np.max(np.abs(ref.logit(est) - ref.logit(want))))
        self.est_gap = float(np.max(np.abs(est - want)))
        E, O = np.stack(self.est), np.stack(self.offload)
        served = [dict() for _ in range(len(E))]
        for step, sojourn in self.served.items():
            served[step // self.S][step % self.S] = sojourn
        replay_cfg = dict(cfg["fleet_fair"], ratio=cfg["ratio"],
                          districts=cfg["districts"],
                          arrival_period=cfg["arrival_period"],
                          redistribute_every=cfg["redistribute_every"],
                          min_share=cfg["min_share"], smooth=cfg["smooth"])
        want_off = fleet_fair.replay(E, served, self.cal_scores, replay_cfg)
        mismatch = int(np.sum(want_off != O))
        return [("logit_gap", gap, float(cfg["limits"]["logit_gap"])),
                ("decision_mismatch", float(mismatch), 0.0)]

    def check_control(self) -> List[Tuple[str, float, float]]:
        """The check, with the control put in the program's place: the
        reference in bfloat16 (``reference/control.py``) on the same rows."""
        from reference import control
        return self.check(estimates=control.estimates)
