"""`DispatchProfiler` — wall-clock attribution of host-loop time to named
phases.

The serve loop's cost is host-side Python (the ROADMAP's dispatcher fps
regression is "runtime-, not kernel-bound"), so the profiler measures
``perf_counter`` intervals and accumulates them per phase name.  The
instrumentation pattern keeps the disabled path to a single ``is None``
check per phase:

    prof = obs.profiler if obs is not None else None
    ...
    t0 = prof.begin("phase_name", step, frames) if prof is not None else 0.0
    do_phase()
    if prof is not None:
        prof.add("phase_name", t0)

``begin``/``add`` are bound-method calls around ``perf_counter`` — no
context-manager frames, no dict churn beyond one setdefault-free lookup
(phase cells are created on first use and reused).  ``step`` (the first
arrival index the phase works on) and ``frames`` (how many) are optional;
the plain profiler ignores them.

:class:`AnnotatingProfiler` (``Obs(annotate=True)``) also writes every
phase as a ``jax.profiler.TraceAnnotation`` of the same name, carrying
``step`` and ``frames`` as stats.  While a ``jax.profiler`` trace runs,
the phases then sit on the host plane on the clock of the device events,
so an idle gap of the device can be put down to the phase the host was
in.  An annotation costs about a microsecond even with no trace running,
so only the annotating profiler builds one.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax


class DispatchProfiler:
    """Accumulates ``perf_counter`` seconds per named phase: the total, the
    number of intervals and the longest single interval."""

    __slots__ = ("_acc", "_clock")

    def __init__(self) -> None:
        # phase -> [total_seconds, count, max_seconds]
        self._acc: Dict[str, List[float]] = {}
        self._clock = time.perf_counter

    def begin(
        self, phase: str, step: Optional[int] = None, frames: Optional[int] = None
    ) -> Any:
        return self._clock()

    def add(self, phase: str, t0: Any) -> None:
        self._record(phase, self._clock() - t0)

    def _record(self, phase: str, dt: float) -> None:
        cell = self._acc.get(phase)
        if cell is None:
            cell = self._acc[phase] = [0.0, 0, 0.0]
        cell[0] += dt
        cell[1] += 1
        if dt > cell[2]:
            cell[2] = dt

    # ------------------------------------------------------------- reporting

    def totals(self) -> Dict[str, float]:
        return {phase: cell[0] for phase, cell in self._acc.items()}

    def report(self) -> Dict[str, Dict[str, float]]:
        """Per-phase ``{total_ms, count, mean_us, max_ms, share}`` sorted by
        cost (dict order = descending total)."""
        grand = sum(cell[0] for cell in self._acc.values()) or 1.0
        rows = sorted(self._acc.items(), key=lambda kv: -kv[1][0])
        return {
            phase: {
                "total_ms": cell[0] * 1e3,
                "count": int(cell[1]),
                "mean_us": (cell[0] / cell[1] * 1e6) if cell[1] else 0.0,
                "max_ms": cell[2] * 1e3,
                "share": cell[0] / grand,
            }
            for phase, cell in rows
        }

    def format_report(self) -> str:
        lines = [
            f"{'phase':<28}{'total ms':>10}{'count':>10}{'mean µs':>10}"
            f"{'max ms':>10}{'share':>8}"
        ]
        for phase, row in self.report().items():
            lines.append(
                f"{phase:<28}{row['total_ms']:>10.2f}{row['count']:>10d}"
                f"{row['mean_us']:>10.2f}{row['max_ms']:>10.3f}{row['share']:>7.1%}"
            )
        return "\n".join(lines)

    def clear(self) -> None:
        self._acc.clear()


class AnnotatingProfiler(DispatchProfiler):
    """A :class:`DispatchProfiler` whose phases are also
    ``jax.profiler.TraceAnnotation`` host events of the same name, with
    ``step`` and ``frames`` as stats where given."""

    __slots__ = ()

    def begin(
        self, phase: str, step: Optional[int] = None, frames: Optional[int] = None
    ) -> Any:
        stats = {}
        if step is not None:
            stats["step"] = int(step)
        if frames is not None:
            stats["frames"] = int(frames)
        ann = jax.profiler.TraceAnnotation(phase, **stats)
        ann.__enter__()
        return self._clock(), ann

    def add(self, phase: str, t0: Any) -> None:
        start, ann = t0
        self._record(phase, self._clock() - start)
        ann.__exit__(None, None, None)
