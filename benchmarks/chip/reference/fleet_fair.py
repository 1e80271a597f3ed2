"""Plain reference of the city's decision layer: per-district ``fleet_fair``
decisions under one shared token-bucket budget, replayed over the
estimates the program served.

Semantics (a shared-rate budget in the manner of Qiu et al., arXiv
2208.00485, split over districts):

- The fleet holds ``ratio * cameras / period`` tokens per time unit, split
  into one bucket per district (depth ``depth``), each refilled at its
  share of the rate when it is asked for a token, at the tick's time.
- A district budgets ``ratio * share * districts``.  Its threshold is the
  ``1 - r`` quantile of its own last ``window`` estimates (of the
  calibration scores until ``warmup`` have been seen), where ``r`` adds
  ``gain`` times its shortfall of wanted offloads against that budget.
- A frame is wanted when its estimate exceeds the threshold, and offloaded
  when its district's bucket also gives a token.
- Every ``redistribute_every`` time units the shares move half way towards
  a floor of ``min_share / districts`` plus the rest in proportion to each
  district's EMA of served-offload estimates, scaled by
  ``(1 + staleness_weight) / (1 + congestion_weight * relative uplink
  sojourn EMA)``; bucket levels carry over.

The edge fleet is a simulated network, not a layer the check judges, so
which offloads an edge admitted and their uplink sojourns are taken as
recorded (teacher forcing, as a served model's tokens are).  So are the
estimates, which the estimate comparison checks on its own.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


class _Bucket:
    def __init__(self, rate: float, depth: float):
        self.rate, self.depth, self.level, self.t = rate, depth, depth, 0.0

    def refill(self, now: float) -> None:
        dt = max(now - self.t, 0.0)
        self.t = now
        self.level = min(self.level + self.rate * dt, self.depth)

    def take(self, now: float) -> bool:
        self.refill(now)
        if self.level >= 1.0:
            self.level -= 1.0
            return True
        return False


def _quantile_threshold(scores: np.ndarray, r: float) -> float:
    if r >= 1.0:
        return -1e9
    if r <= 0.0:
        return 1e9
    return float(np.quantile(scores, 1.0 - r))


def replay(estimates: np.ndarray, served: Sequence[Dict[int, float]],
           calibration: np.ndarray, cfg: Dict) -> np.ndarray:
    """Offload decisions ``(ticks, cameras)`` for the served ``estimates``.

    ``served[t]`` maps each camera whose offload an edge admitted at tick
    ``t`` to its uplink sojourn (queue + transmit; 0 on an idle link)."""
    T, S = estimates.shape
    D = int(cfg["districts"])
    per = S // D
    ratio, period = float(cfg["ratio"]), float(cfg["arrival_period"])
    gain, window, warmup = float(cfg["gain"]), int(cfg["window"]), int(cfg["warmup"])
    total = ratio * S / period
    depth = max(8.0, 2.0 * ratio * per)
    alpha = 1.0 - 0.5 ** (1.0 / int(cfg["reward_halflife"]))
    shares = np.full(D, 1.0 / D)
    buckets = [_Bucket(total * s, depth) for s in shares]
    cal = np.sort(np.asarray(calibration, np.float64))
    recent = np.zeros((D, window))  # ring buffers: a quantile reads values, not order
    filled = np.zeros(D, np.int64)
    decided = np.zeros(D, np.int64)
    wanted = np.zeros(D, np.int64)
    reward, reward_seen = np.zeros(D), np.zeros(D, bool)
    cong, cong_seen = np.zeros(D), np.zeros(D, bool)
    last_redistribution = None
    out = np.zeros((T, S), bool)
    for t in range(T):
        now = t * period
        for d in range(D):
            alloc = float(np.clip(ratio * shares[d] * D, 0.0, 1.0))
            ring = recent[d]
            for i in range(d * per, (d + 1) * per):
                est = float(estimates[t, i])
                n = int(filled[d])
                dist = (ring[:n] if n < window else ring) if n >= warmup else cal
                if alloc <= 0.0:
                    thr = 1e9
                elif alloc >= 1.0:
                    thr = -1e9
                else:
                    deficit = alloc * decided[d] - wanted[d]
                    thr = _quantile_threshold(dist, min(max(alloc + gain * deficit, 0.0), 1.0))
                want = est > thr
                ring[n % window] = est
                filled[d] = n + 1
                out[t, i] = want and buckets[d].take(now)
                decided[d] += 1
                wanted[d] += int(want)
            for i in range(d * per, (d + 1) * per):
                if i not in served[t]:
                    continue
                sojourn = served[t][i]
                for ema, seen, v, skip in ((reward, reward_seen, estimates[t, i], False),
                                           (cong, cong_seen, sojourn, not sojourn)):
                    if skip:
                        continue
                    if seen[d]:
                        ema[d] += alpha * (float(v) - ema[d])
                    else:
                        ema[d], seen[d] = float(v), True
        if last_redistribution is None:
            last_redistribution = now
            continue
        if now - last_redistribution < float(cfg["redistribute_every"]):
            continue
        last_redistribution = now
        score = np.where(reward_seen, np.maximum(reward, 0.0), 0.0)
        if score.sum() <= 0.0:
            continue
        mult = np.ones(D) * (1.0 + float(cfg["staleness_weight"]) * np.ones(D))
        if cong_seen.any() and float(cong[cong_seen].mean()) > 0.0:
            rel = np.where(cong_seen, cong / float(cong[cong_seen].mean()), 1.0)
        else:
            rel = np.ones(D)
        mult = mult / (1.0 + float(cfg["congestion_weight"]) * rel)
        score = score * mult
        floor = float(cfg["min_share"]) / D
        target = floor + (1.0 - float(cfg["min_share"])) * score / score.sum()
        shares = shares + float(cfg["smooth"]) * (target - shares)
        shares /= shares.sum()
        for b, s in zip(buckets, shares):
            b.refill(now)
            b.rate = total * s
    return out
