"""`repro.obs` — one observability plane for the serve stack.

Everything the runtimes emit flows through a single :class:`Obs` handle
threaded as an optional ``obs=`` argument through ``OffloadSession``,
``OffloadRuntime``, ``EdgeWorker``/``MultiEdgeDispatcher``,
``FleetRuntime``, ``VideoRuntime``, and ``AdaptiveEngine.maybe_update``:

    from repro.obs import Obs
    obs = Obs()
    trace = simulate(engine, features, obs=obs)
    print(obs.metrics.to_prometheus())
    obs.tracer.export("trace.json")     # open in Perfetto
    print(obs.profiler.format_report())

``obs=None`` (the default everywhere) is the noop: instrumented code
guards every emission behind one ``is None`` check, so the disabled cost
is one branch per emission site.

Three sub-planes, each independently disableable:

- :attr:`Obs.metrics` — a :class:`~repro.obs.metrics.MetricsRegistry`
  (counters/gauges/fixed-bucket histograms, Prometheus-text + JSON
  exporters).  Session telemetry counters become registry-backed
  instruments when an obs handle is attached, so ``to_prometheus()``
  exposes live realized ratios, offload decisions, queue depths, and RTT
  histograms with no double accounting.
- :attr:`Obs.tracer` — a :class:`~repro.obs.trace.Tracer` stamping
  nested spans from the simulation's ``ManualClock`` (byte-identical
  traces under a fixed seed) or ``perf_counter`` in benchmarks,
  exported as Chrome-trace JSON.
- :attr:`Obs.profiler` — a :class:`~repro.obs.profiler.DispatchProfiler`
  attributing host-loop wall time to named serve phases.
  ``Obs(annotate=True)`` builds an
  :class:`~repro.obs.profiler.AnnotatingProfiler` instead, which also
  writes each phase into a running ``jax.profiler`` trace.

JIT visibility rides along for free: kernels register their jit entry
points with :mod:`repro.obs.jit_stats` at import time; ``Obs`` snapshots
the process-global cache sizes at construction and exports
``repro_jit_retraces_total{site=...}`` as the growth since then.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.obs import jit_stats
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    DEFAULT_TIME_BUCKETS,
)
from repro.obs.profiler import AnnotatingProfiler, DispatchProfiler
from repro.obs.trace import SIM_TS_SCALE, WALL_TS_SCALE, Tracer

__all__ = [
    "Obs",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "Tracer",
    "DispatchProfiler",
    "AnnotatingProfiler",
    "jit_stats",
    "DEFAULT_TIME_BUCKETS",
    "SIM_TS_SCALE",
    "WALL_TS_SCALE",
]


class Obs:
    """The observability handle runtimes accept as ``obs=``.

    ``Obs()`` enables all three planes.  ``Obs(tracing=False)`` etc.
    disable one — the corresponding attribute is ``None`` and
    instrumented code skips its emissions (the same guard as
    ``obs=None``, applied per plane).  :meth:`Obs.noop` disables all
    three while still exercising the seam — what the overhead bench
    measures against.  ``annotate=True`` makes every profiler phase a
    ``jax.profiler.TraceAnnotation`` as well (it needs ``profiling``).
    """

    __slots__ = ("metrics", "tracer", "profiler", "_jit_baseline")

    def __init__(
        self,
        *,
        metrics: bool = True,
        tracing: bool = True,
        profiling: bool = True,
        annotate: bool = False,
        clock: Optional[Callable[[], float]] = None,
    ):
        if annotate and not profiling:
            raise ValueError(
                "annotate=True writes the profiler's phases; it needs profiling=True"
            )
        self.metrics: Optional[MetricsRegistry] = MetricsRegistry() if metrics else None
        self.tracer: Optional[Tracer] = Tracer(clock=clock) if tracing else None
        self.profiler: Optional[DispatchProfiler] = (
            (AnnotatingProfiler() if annotate else DispatchProfiler())
            if profiling else None
        )
        # retraces are reported relative to handle construction: jit caches
        # are process-global, the handle's lifetime scopes them to a run
        self._jit_baseline = jit_stats.snapshot()
        if self.metrics is not None:
            self.metrics.collector(self._collect_jit)

    @classmethod
    def noop(cls) -> "Obs":
        """All planes disabled — the seam is exercised, nothing is
        recorded."""
        return cls(metrics=False, tracing=False, profiling=False)

    @property
    def enabled(self) -> bool:
        return (
            self.metrics is not None
            or self.tracer is not None
            or self.profiler is not None
        )

    def bind_clock(
        self, clock: Callable[[], float], ts_scale: float = SIM_TS_SCALE
    ) -> None:
        """Attach the simulation clock (runtimes call this so spans are
        stamped in simulated, not wall, time)."""
        if self.tracer is not None:
            self.tracer.bind_clock(clock, ts_scale)

    # ------------------------------------------------------------ jit plane

    def jit_delta(self) -> Dict[str, Tuple[int, int]]:
        """Per-site ``(retraces, calls)`` since this handle was built."""
        return jit_stats.delta(self._jit_baseline, jit_stats.snapshot())

    def _collect_jit(self) -> List[Tuple[str, Dict[str, str], Any, str]]:
        rows: List[Tuple[str, Dict[str, str], Any, str]] = []
        for site, (retraces, calls) in sorted(self.jit_delta().items()):
            rows.append(
                ("repro_jit_retraces_total", {"site": site}, retraces, "counter")
            )
            if calls:
                rows.append(
                    ("repro_jit_calls_total", {"site": site}, calls, "counter")
                )
        return rows
