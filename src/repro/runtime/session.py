"""`OffloadSession` — the stateful per-stream serve loop over a frozen
:class:`repro.api.OffloadEngine`.

The engine is the *fitted artifact* (features → estimator → rank transform →
policy construction recipe); a session is one device's *stream* through it:

- frames arrive one at a time and are buffered into micro-batches so reward
  scoring runs the engine's batched path (the fused Pallas ``estimator_mlp``
  kernel for the deployable single-hidden-layer MLP),
- decisions are taken strictly in arrival order through a session-private
  policy instance, so stateful policies (``token_bucket``) carry their
  bucket level across the stream without cross-talk between sessions,
- rolling telemetry tracks the realized offload ratio and (optionally)
  realized rewards against the target budget,
- ``set_ratio`` re-budgets mid-stream without touching the shared engine.

Sessions never mutate the engine: N concurrent streams can serve from one
loaded artifact.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

#: initial pending-buffer capacity (rows); grows geometrically — the hot
#: loop never allocates per frame after warmup
_MIN_BUFFER_ROWS = 64

from repro.api.engine import OffloadEngine
from repro.api.policies import make_policy, policy_context_params
from repro.detection.batch import DetectionsBatch
from repro.obs.metrics import Counter, Gauge, Histogram, DEFAULT_TIME_BUCKETS


def _host_nbytes(x: Any) -> int:
    """Bytes of the host (numpy) arrays in a scoring input — a feature
    block or a padded detection batch; arrays already on the device count
    0."""
    if isinstance(x, np.ndarray):
        return x.nbytes
    if dataclasses.is_dataclass(x):
        return sum(_host_nbytes(getattr(x, f.name)) for f in dataclasses.fields(x))
    return 0


@dataclass(frozen=True)
class StepDecision:
    """One frame's serve-time decision, in arrival order."""

    step: int
    estimate: float
    offload: bool


@dataclass(frozen=True)
class SessionTelemetry:
    """Snapshot of a session's counters (cumulative + rolling window).

    The video counters (``covered_frames``/``mean_staleness``/
    ``effective_frames``/``mean_effective_accuracy``) stay zero unless the
    stream records temporal state (see ``record_staleness`` /
    ``record_effective_accuracy``); ``as_dict`` keeps them behind
    ``include_video`` so existing consumers see a byte-stable payload.
    The online counters (``mean_rtt``/``mean_bandwidth``/
    ``online_updates``) follow the same pattern behind ``include_online``:
    they stay zero unless the runtime records measured round trips
    (``record_rtt``/``record_bandwidth``) or closed-loop model updates
    (``record_update``).  The fleet counters (``budget_share``/
    ``budget_redistributions``) sit behind ``include_fleet`` the same way:
    zero unless a fleet runtime records the stream's coordinated budget
    state (``record_budget_share``/``record_redistribution``).  The
    mobility counters (``handovers``/``mean_coverage_dbm``) follow suit
    behind ``include_mobility``: zero unless a mobile runtime records edge
    migrations (``record_handover``) or received-signal-strength samples
    (``record_coverage``)."""

    processed: int
    offloaded: int
    realized_ratio: float
    rolling_ratio: float
    mean_estimate: float
    target_ratio: float
    pending: int
    reward_sum: float
    rewards_recorded: int
    covered_frames: int = 0
    mean_staleness: float = 0.0
    effective_frames: int = 0
    mean_effective_accuracy: float = 0.0
    rtt_samples: int = 0
    mean_rtt: float = 0.0
    bandwidth_samples: int = 0
    mean_bandwidth: float = 0.0
    online_updates: int = 0
    budget_share: float = 0.0
    budget_redistributions: int = 0
    handovers: int = 0
    coverage_samples: int = 0
    mean_coverage_dbm: float = 0.0

    def as_dict(
        self,
        include_video: bool = False,
        include_online: bool = False,
        include_fleet: bool = False,
        include_mobility: bool = False,
    ) -> Dict[str, Any]:
        out = {
            "processed": self.processed,
            "offloaded": self.offloaded,
            "realized_ratio": self.realized_ratio,
            "rolling_ratio": self.rolling_ratio,
            "mean_estimate": self.mean_estimate,
            "target_ratio": self.target_ratio,
            "pending": self.pending,
            "reward_sum": self.reward_sum,
            "rewards_recorded": self.rewards_recorded,
        }
        if include_video:
            out.update(
                {
                    "covered_frames": self.covered_frames,
                    "mean_staleness": self.mean_staleness,
                    "effective_frames": self.effective_frames,
                    "mean_effective_accuracy": self.mean_effective_accuracy,
                }
            )
        if include_online:
            out.update(
                {
                    "rtt_samples": self.rtt_samples,
                    "mean_rtt": self.mean_rtt,
                    "bandwidth_samples": self.bandwidth_samples,
                    "mean_bandwidth": self.mean_bandwidth,
                    "online_updates": self.online_updates,
                }
            )
        if include_fleet:
            out.update(
                {
                    "budget_share": self.budget_share,
                    "budget_redistributions": self.budget_redistributions,
                }
            )
        if include_mobility:
            out.update(
                {
                    "handovers": self.handovers,
                    "coverage_samples": self.coverage_samples,
                    "mean_coverage_dbm": self.mean_coverage_dbm,
                }
            )
        return out


class OffloadSession:
    """Stateful per-stream wrapper around a fitted ``OffloadEngine``.

    Parameters
    ----------
    engine : OffloadEngine
        Must be fitted (or loaded); the session builds its own policy
        instance from the engine's calibration scores so per-stream policy
        state is isolated.
    ratio : float or None
        Session-local target offloading ratio; defaults to the engine's.
    micro_batch : int
        Frames buffered before one batched scoring call.  1 = score every
        arrival immediately; larger values trade decision latency for
        scoring throughput through the fused Pallas path.
    telemetry_window : int
        Length of the rolling window behind ``telemetry.rolling_ratio``.
    clock : callable or None
        Injected time source forwarded to time-based policies
        (``token_bucket``); ignored by stateless policies.  Never the wall
        clock in tests/simulations — see ``repro.runtime.clock.ManualClock``.
    congestion : callable or None
        Zero-arg probe of the predicted uplink sojourn at the best edge,
        forwarded to policies that declare it (``queue_aware``); wired by
        ``OffloadRuntime.open_session`` from its link-fronted fleet.
    state_probe : callable or None
        Zero-arg probe of the observed ``(queue_depth, channel_state)``,
        forwarded to policies that declare it (``value_iteration``).
    staleness : callable or None
        Zero-arg probe of the stream's current edge-result staleness
        (frames since the newest covering result was captured, ``inf`` when
        none), forwarded to policies that declare it
        (``temporal_hysteresis``); wired by the video runtime.
    scene_change : callable or None
        Zero-arg probe of the stream's scene-change score in [0, 1],
        forwarded to policies that declare it (``keyframe``).
    coverage_ttl : callable or None
        Zero-arg probe of the stream's predicted time-to-coverage-loss
        (sim time units until the serving base station's signal drops
        below the usable floor, ``inf`` when not leaving coverage),
        forwarded to policies that declare it (``mobility_aware``); wired
        by the mobile runtime from its motion trace + coverage map.
    tracker : repro.video.track.VideoTracker or None
        Optional temporal state carried with the stream — sessions opened
        on video streams hold the tracker that ages/propagates stale edge
        results (possibly shared between sessions when the tracker is
        batched over streams).  The session itself never calls it; it rides
        here so stream state travels as one object.
    obs : repro.obs.Obs or None
        Observability handle.  The session's telemetry counters *are*
        metric instruments (``repro.obs.metrics``); with an obs handle
        whose metrics plane is on they are created through its registry —
        labeled ``{stream=<name>}`` — so Prometheus/JSON exports see the
        live values with no second accounting path.  With ``obs=None``
        (default) the instruments are standalone objects and nothing else
        changes: ``telemetry.as_dict()`` payloads are byte-identical
        either way.  The tracer plane (when on) receives one
        ``session.flush`` span per scoring drain on track ``tid``.  The
        profiler plane (when on) times the phases
        ``session.score_enqueue``, ``session.score_wait`` and
        ``session.decide`` on every route; the metrics plane also counts
        ``repro_session_transfer_bytes_total{direction="h2d"|"d2h"}``,
        the host arrays handed to the scoring calls and the estimates
        read back.
    name : str or None
        Stream label used for this session's metric series; auto-numbered
        within the registry when omitted.
    tid : int
        Trace track for this session's spans (runtimes assign one per
        stream).

    Each injected callable reaches the policy constructor only when the
    policy's ``context_params`` declares it — runtime wiring, never part of
    the engine artifact.
    """

    def __init__(
        self,
        engine: OffloadEngine,
        *,
        ratio: Optional[float] = None,
        micro_batch: int = 8,
        telemetry_window: int = 64,
        clock: Optional[Callable[[], float]] = None,
        congestion: Optional[Callable[[], float]] = None,
        state_probe: Optional[Callable[[], tuple]] = None,
        staleness: Optional[Callable[[], float]] = None,
        scene_change: Optional[Callable[[], float]] = None,
        coverage_ttl: Optional[Callable[[], float]] = None,
        tracker: Optional[Any] = None,
        obs: Optional[Any] = None,
        name: Optional[str] = None,
        tid: int = 0,
    ):
        if engine.calibration_scores is None:
            raise RuntimeError("OffloadSession over an unfitted engine")
        self.engine = engine
        self.tracker = tracker
        self.micro_batch = max(int(micro_batch), 1)
        self._ratio = float(engine.ratio if ratio is None else ratio)
        kwargs = dict(engine.policy_kwargs)
        accepted = set(policy_context_params(engine.policy_name))
        context = {
            "clock": clock,
            "congestion": congestion,
            "state_probe": state_probe,
            "staleness": staleness,
            "scene_change": scene_change,
            "coverage_ttl": coverage_ttl,
        }
        kwargs.update(
            {k: v for k, v in context.items() if v is not None and k in accepted}
        )
        # kept so `recalibrate()` can rebuild the policy (same runtime
        # wiring) against refreshed engine calibration scores
        self._policy_build_kwargs = dict(kwargs)
        self.policy = make_policy(
            engine.policy_name, engine.calibration_scores, self._ratio, **kwargs
        )
        # pending features live in one preallocated (capacity, F) buffer —
        # rows [0, _pending_rows) are queued arrivals.  The old per-frame
        # list of (1, F) blocks + np.concatenate on every drain was the
        # prime suspect in the dispatcher fps regression.
        self._buf: Optional[np.ndarray] = None
        self._pending_rows = 0
        self._next_step = 0                   # arrival index of next submit
        self._window = deque(maxlen=max(int(telemetry_window), 1))
        self._tracer = obs.tracer if obs is not None else None
        self._profiler = obs.profiler if obs is not None else None
        self._tid = int(tid)
        self._flush_t0: Optional[float] = None
        reg = obs.metrics if obs is not None else None
        self._init_instruments(reg, name)
        # host<->device bytes of the scoring calls, summed over the sessions
        # of the registry; kept only when a metrics plane is on
        self._transfer = None if reg is None else tuple(
            reg.counter(
                "repro_session_transfer_bytes_total", {"direction": d},
                help=f"bytes of the scoring calls' {d} copies",
            )
            for d in ("h2d", "d2h")
        )

    def _init_instruments(self, reg, name: Optional[str]) -> None:
        """The telemetry counters ARE metric instruments: standalone
        objects when observability is off, registry-backed (walked by the
        exporters) when an obs handle carries a metrics plane.  One write
        path either way — `telemetry` is a view, never a second ledger."""
        if reg is not None:
            opened = reg.counter(
                "repro_sessions_total", help="sessions opened on this registry"
            )
            if name is None:
                name = str(opened.value)
            opened.inc()
            labels: Optional[Dict[str, str]] = {"stream": str(name)}
            counter, gauge, histogram = reg.counter, reg.gauge, reg.histogram
        else:
            labels = None
            counter = lambda n, labels=None, help="": Counter(n)
            gauge = lambda n, labels=None, help="", fn=None: Gauge(n, fn=fn)
            histogram = (
                lambda n, buckets=DEFAULT_TIME_BUCKETS, labels=None, help="":
                Histogram(n, buckets=buckets)
            )
        self._processed = counter(
            "repro_frames_processed_total", labels, help="frames decided"
        )
        self._offloaded = counter(
            "repro_frames_offloaded_total", labels,
            help="frames the policy sent to an edge",
        )
        self._estimate_sum = counter(
            "repro_estimate_sum_total", labels, help="sum of reward estimates"
        )
        self._reward_sum = counter(
            "repro_reward_sum_total", labels, help="sum of realized rewards"
        )
        self._rewards_recorded = counter(
            "repro_rewards_recorded_total", labels, help="realized rewards seen"
        )
        self._staleness_sum = counter(
            "repro_staleness_sum_total", labels,
            help="summed age of propagated edge results (frames)",
        )
        self._covered_frames = counter(
            "repro_covered_frames_total", labels,
            help="frames served from a propagated edge result",
        )
        self._accuracy_sum = counter(
            "repro_effective_accuracy_sum_total", labels,
            help="summed per-frame effective accuracy",
        )
        self._effective_frames = counter(
            "repro_effective_frames_total", labels,
            help="frames with an effective-accuracy sample",
        )
        self._rtt = histogram(
            "repro_offload_rtt", DEFAULT_TIME_BUCKETS, labels,
            help="measured offload round-trip time (sim time units)",
        )
        self._bandwidth_sum = counter(
            "repro_bandwidth_sum_total", labels,
            help="summed measured uplink goodput",
        )
        self._bandwidth_samples = counter(
            "repro_bandwidth_samples_total", labels, help="goodput samples"
        )
        self._online_updates = counter(
            "repro_online_updates_total", labels,
            help="closed-loop model updates visible to this stream",
        )
        self._budget_share = gauge(
            "repro_budget_share", labels,
            help="stream's share of the fleet offload budget",
        )
        self._budget_redistributions = counter(
            "repro_budget_redistributions_total", labels,
            help="fleet budget redistributions applied",
        )
        self._handovers = counter(
            "repro_handovers_total", labels,
            help="mid-stream edge handovers executed",
        )
        self._coverage_sum = counter(
            "repro_coverage_dbm_sum_total", labels,
            help="summed received signal strength samples (dBm)",
        )
        self._coverage_samples = counter(
            "repro_coverage_samples_total", labels,
            help="received signal strength samples",
        )
        self._coverage_dbm = gauge(
            "repro_coverage_dbm", labels,
            help="latest received signal strength from the serving edge (dBm)",
        )
        # live views with zero hot-path cost: evaluated only at collection
        gauge(
            "repro_realized_ratio", labels,
            help="offloaded / processed",
            fn=lambda: (
                self._offloaded.value / self._processed.value
                if self._processed.value else 0.0
            ),
        )
        gauge(
            "repro_pending_frames", labels,
            help="frames buffered awaiting a scoring flush",
            fn=lambda: self._pending_rows,
        )
        gauge(
            "repro_target_ratio", labels,
            help="session target offload ratio",
            fn=lambda: self._ratio,
        )

    # ------------------------------------------------------------- streaming

    def submit(
        self, weak_output: Any = None, *, features: Optional[np.ndarray] = None
    ) -> List[StepDecision]:
        """Enqueue one frame.  Returns the decisions flushed by this arrival
        — empty until the micro-batch fills, then ``micro_batch`` decisions
        in arrival order."""
        if features is not None:
            row = np.asarray(features, np.float32)
            if row.ndim != 1:
                raise ValueError(
                    f"submit() takes one frame; features must be 1-D, got {row.shape}"
                )
            self._enqueue(row[None, :])
        else:
            if weak_output is None:
                raise ValueError("pass weak_output or features=")
            self._enqueue(np.asarray(self.engine.features([weak_output]), np.float32))
        if self._pending_rows >= self.micro_batch:
            return self.flush()
        return []

    def submit_batch(
        self,
        weak_outputs: Any = None,
        *,
        features: Optional[np.ndarray] = None,
        flush: bool = True,
    ) -> List[StepDecision]:
        """Stream a pre-batched matrix through the session in arrival order.

        Feature extraction happens once for the whole batch (adapters like
        ``detection_boxes`` consume a ``DetectionsBatch``, ``lm_logits``
        batch-shaped logits) and the rows enter the pending queue as ONE
        block — no per-item conversion or row-at-a-time Python.  Scoring
        drains in micro-batch chunks and decisions stay sequential; with
        ``flush=False`` a trailing partial micro-batch stays buffered for
        the next call.

        With ``flush=True``, nothing already pending and a padded
        ``DetectionsBatch``, the batch never touches the host feature
        queue: each micro-batch chunk of images goes through
        ``engine.score_device`` — under the detection extractor + fused
        MLP that is the one-dispatch boxes→estimates pipeline.  Both routes
        score the same rows in the same ``micro_batch`` chunks, so their
        decisions agree."""
        if (
            flush
            and self._pending_rows == 0
            and features is None
            and isinstance(weak_outputs, DetectionsBatch)
        ):
            est = self._score_chunks(
                len(weak_outputs), weak_outputs.slice_images, self.engine.score_device
            )
            if est.size == 0:
                return []
            self._next_step += est.size
            return self._decide(est)
        x = np.asarray(self.engine.features(weak_outputs, features=features), np.float32)
        self._enqueue(x)
        if flush:
            return self.flush()
        full = self._pending_rows - self._pending_rows % self.micro_batch
        return self._drain(full)

    def _enqueue(self, block: np.ndarray) -> None:
        if block.ndim != 2:
            raise ValueError(f"feature blocks must be 2-D, got {block.shape}")
        rows = block.shape[0]
        if rows:
            if self._tracer is not None and self._pending_rows == 0:
                # the flush span opens when the first frame starts waiting
                self._flush_t0 = self._tracer.clock()
            need = self._pending_rows + rows
            if self._buf is None or self._buf.shape[1] != block.shape[1]:
                cap = max(_MIN_BUFFER_ROWS, self.micro_batch, need)
                self._buf = np.empty((cap, block.shape[1]), np.float32)
            elif need > self._buf.shape[0]:
                grown = np.empty(
                    (max(need, 2 * self._buf.shape[0]), block.shape[1]),
                    np.float32,
                )
                grown[: self._pending_rows] = self._buf[: self._pending_rows]
                self._buf = grown
            self._buf[self._pending_rows : need] = block
            self._pending_rows = need
        self._next_step += rows

    def flush(self) -> List[StepDecision]:
        """Score everything pending and decide each frame in arrival order
        through the session policy."""
        return self._drain(self._pending_rows)

    def _score_chunks(
        self,
        rows: int,
        chunk: Callable[[int, int], Any],
        score: Callable[[Any], Any],
    ) -> np.ndarray:
        """Estimates for rows ``[0, rows)``: ``score(chunk(lo, hi))`` over
        consecutive ``micro_batch`` chunks — the one row partition every
        route uses, so a backend whose reductions depend on the row count
        still gives each frame the same estimate on every route.  All
        chunks are dispatched (phase ``session.score_enqueue``) before the
        first is read back (``session.score_wait``)."""
        if rows <= 0:
            return np.zeros((0,), np.float64)
        mb, prof = self.micro_batch, self._profiler
        first = self._next_step - self._pending_rows
        if prof is not None:
            t0 = prof.begin("session.score_enqueue", first, rows)
        inputs = [chunk(lo, min(lo + mb, rows)) for lo in range(0, rows, mb)]
        parts = [score(x) for x in inputs]
        if prof is not None:
            prof.add("session.score_enqueue", t0)
            t0 = prof.begin("session.score_wait", first, rows)
        est = np.concatenate([np.asarray(p, np.float64).ravel() for p in parts])
        if prof is not None:
            prof.add("session.score_wait", t0)
        if self._transfer is not None:
            h2d, d2h = self._transfer
            h2d.inc(sum(_host_nbytes(x) for x in inputs))
            d2h.inc(sum(int(p.nbytes) for p in parts))
        return est

    def _drain(self, rows: int) -> List[StepDecision]:
        """Score the first ``rows`` pending frames and decide them in
        arrival order."""
        rows = min(rows, self._pending_rows)
        if rows <= 0:
            return []
        buf, score = self._buf, self.engine.score_device
        # device scoring; one host conversion at the policy boundary (the
        # estimates are materialized before the buffer is compacted)
        estimates = self._score_chunks(
            rows, lambda lo, hi: buf[lo:hi], lambda x: score(features=x)
        )
        rem = self._pending_rows - rows
        if rem:
            self._buf[:rem] = self._buf[rows : self._pending_rows].copy()
        self._pending_rows = rem
        return self._decide(estimates)

    def submit_scored(self, estimates: np.ndarray) -> List[StepDecision]:
        """Decide a block of already-scored frames in arrival order — the
        seam for fleet runtimes that score all streams centrally through the
        sharded data plane (``repro.fleet.plane``) and fan the estimates out
        to per-shard sessions.  Mixing with buffered unscored arrivals would
        let scored frames jump the queue, so pending rows must be flushed
        first."""
        if self._pending_rows:
            raise RuntimeError(
                f"submit_scored() with {self._pending_rows} unscored frames "
                "pending — flush() first"
            )
        est = np.asarray(estimates, np.float64).ravel()
        self._next_step += est.size
        return self._decide(est)

    def _decide(self, estimates: np.ndarray) -> List[StepDecision]:
        """Run already-scored estimates through the session policy in
        arrival order and account them in the telemetry (phase
        ``session.decide``)."""
        # the queue held exactly the arrivals not yet decided, so the drained
        # rows are the arrival indices trailing the still-pending ones
        first = self._next_step - self._pending_rows - len(estimates)
        prof = self._profiler
        if prof is not None:
            pt0 = prof.begin("session.decide", first, len(estimates))
        if getattr(self.policy, "batch_budget", False):
            # a per-batch budget (topk) would make streaming decisions
            # depend on micro-batch/flush boundaries (and offload nothing
            # at micro_batch=1) — such policies keep the per-item
            # semantics of decide()
            offload = np.fromiter(
                (self.policy.decide(float(e)) for e in estimates),
                dtype=bool, count=len(estimates),
            )
        else:
            # decide_batch is buffer-invariant here: vectorized for
            # threshold, internally sequential for token_bucket
            offload = np.asarray(self.policy.decide_batch(estimates), bool)
        n_off = int(offload.sum())
        self._processed.inc(len(estimates))
        self._offloaded.inc(n_off)
        self._estimate_sum.inc(float(estimates.sum()))
        self._window.extend(bool(o) for o in offload)
        if self._tracer is not None:
            now = self._tracer.clock()
            t0 = now if self._flush_t0 is None else self._flush_t0
            self._tracer.add_span(
                "session.flush", t0, now, tid=self._tid,
                args={"frames": len(estimates), "offloaded": n_off},
            )
            self._flush_t0 = now if self._pending_rows else None
        out = [
            StepDecision(step=first + i, estimate=float(est), offload=bool(off))
            for i, (est, off) in enumerate(zip(estimates, offload))
        ]
        if prof is not None:
            prof.add("session.decide", pt0)
        return out

    # --------------------------------------------------------------- control

    def set_ratio(self, ratio: float) -> None:
        """Mid-stream budget change — affects only this session's policy."""
        self._ratio = float(ratio)
        self.policy.set_ratio(self._ratio)

    def recalibrate(self, calibration_scores: Optional[np.ndarray] = None) -> None:
        """Refresh the session policy's calibration distribution mid-stream
        (closed-loop adaptation: the engine's scores just moved).  Stateful
        policies with a sorted ``_cal`` array (the netsim/video/online
        controllers) are patched in place so integral budget state survives;
        anything else is rebuilt with the same runtime wiring."""
        cal = (
            self.engine.calibration_scores
            if calibration_scores is None
            else calibration_scores
        )
        if cal is None:
            raise RuntimeError("recalibrate() with no calibration scores")
        sorted_cal = np.sort(np.asarray(cal, np.float64))
        if hasattr(self.policy, "_cal"):
            self.policy._cal = sorted_cal
        else:
            self.policy = make_policy(
                self.engine.policy_name,
                sorted_cal,
                self._ratio,
                **self._policy_build_kwargs,
            )

    @property
    def ratio(self) -> float:
        return self._ratio

    def record_reward(self, reward: float) -> None:
        """Account a realized per-frame reward (e.g. observed quality delta)
        into the session telemetry."""
        self._reward_sum.inc(float(reward))
        self._rewards_recorded.inc()

    def record_staleness(self, staleness: float) -> None:
        """Account one frame served from a propagated (stale) edge result;
        ``staleness`` is the age of that result in frames."""
        self._staleness_sum.inc(float(staleness))
        self._covered_frames.inc()

    def record_effective_accuracy(self, accuracy: float) -> None:
        """Account one frame's effective accuracy — the AP of whatever was
        actually served for it (weak output or propagated edge result)."""
        self._accuracy_sum.inc(float(accuracy))
        self._effective_frames.inc()

    def record_rtt(self, rtt: float) -> None:
        """Account one completed offload's measured round trip."""
        self._rtt.observe(float(rtt))

    def record_bandwidth(self, bandwidth: float) -> None:
        """Account one measured uplink goodput sample (bits per time unit)."""
        self._bandwidth_sum.inc(float(bandwidth))
        self._bandwidth_samples.inc()

    def record_update(self) -> None:
        """Account one closed-loop model update visible to this stream."""
        self._online_updates.inc()

    def record_budget_share(self, share: float) -> None:
        """Stamp the stream's current share of the fleet-wide offload
        budget (see :class:`repro.fleet.budget.FleetBudget`)."""
        self._budget_share.set(float(share))

    def record_redistribution(self) -> None:
        """Account one fleet budget redistribution applied to this stream."""
        self._budget_redistributions.inc()

    def record_handover(self) -> None:
        """Account one mid-stream edge migration (serving edge changed)."""
        self._handovers.inc()

    def record_coverage(self, dbm: float) -> None:
        """Account one received-signal-strength sample from the stream's
        serving base station (dBm; see :mod:`repro.mobility.coverage`)."""
        self._coverage_sum.inc(float(dbm))
        self._coverage_samples.inc()
        self._coverage_dbm.set(float(dbm))

    # ------------------------------------------------------------- telemetry

    @property
    def telemetry(self) -> SessionTelemetry:
        # a *view* over the metric instruments: every field derives from
        # instrument state the same way the old scalar counters did, so
        # payloads are byte-stable with observability on, off, or absent
        n = self._processed.value
        offloaded = self._offloaded.value
        covered = self._covered_frames.value
        effective = self._effective_frames.value
        bw_samples = self._bandwidth_samples.value
        roll = list(self._window)
        return SessionTelemetry(
            processed=n,
            offloaded=offloaded,
            realized_ratio=offloaded / n if n else 0.0,
            rolling_ratio=float(np.mean(roll)) if roll else 0.0,
            mean_estimate=self._estimate_sum.value / n if n else 0.0,
            target_ratio=self._ratio,
            pending=self._pending_rows,
            reward_sum=float(self._reward_sum.value),
            rewards_recorded=self._rewards_recorded.value,
            covered_frames=covered,
            mean_staleness=(
                self._staleness_sum.value / covered if covered else 0.0
            ),
            effective_frames=effective,
            mean_effective_accuracy=(
                self._accuracy_sum.value / effective if effective else 0.0
            ),
            rtt_samples=self._rtt.n,
            mean_rtt=self._rtt.mean,
            bandwidth_samples=bw_samples,
            mean_bandwidth=(
                self._bandwidth_sum.value / bw_samples if bw_samples else 0.0
            ),
            online_updates=self._online_updates.value,
            budget_share=float(self._budget_share.value),
            budget_redistributions=self._budget_redistributions.value,
            handovers=self._handovers.value,
            coverage_samples=self._coverage_samples.value,
            mean_coverage_dbm=(
                self._coverage_sum.value / self._coverage_samples.value
                if self._coverage_samples.value else 0.0
            ),
        )
