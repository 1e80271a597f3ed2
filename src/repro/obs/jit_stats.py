"""Per-callsite JAX retrace/compile accounting with zero hot-path cost.

``jax.jit`` objects expose ``_cache_size()`` — the number of distinct
traces the wrapped function has accumulated (one per unique
shape/dtype/static-arg combination).  A growing cache size *is* the
retrace count, so instead of wrapping every call (which would put a
Python frame on the serve hot path), registration just remembers the jit
object and reads its cache size on demand:

    _score_jit = register_jit("score_pipeline.lax", jax.jit(fn))

``snapshot()`` walks the registry; ``delta(before, after)`` is how a
bench or a serve run reports "this phase retraced N times".  Sites whose
jits are rebuilt per call (``FleetPlane`` builds shard closures inside
each ``score``) can't be registered once — they call :func:`count_call`,
a plain dict increment, to at least expose call frequency.

The registry is module-global on purpose: jit caches are process-global
(module-level jits in the kernels are shared by every engine), so
per-run scoping happens by snapshot-delta, not by registry instance —
:class:`~repro.obs.Obs` captures a baseline at construction and exports
``current - baseline``.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Tuple

_SITES: Dict[str, Any] = {}
_CALLS: Dict[str, int] = {}
_CALLS_LOCK = threading.Lock()


def register_jit(site: str, fn: Any) -> Any:
    """Register a jitted callable under ``site`` and return it unchanged
    (safe to wrap the jit-construction expression in place).  Anything
    without a jit cache is refused here, so a snapshot never has to guess
    a site's retrace count."""
    if not callable(getattr(fn, "_cache_size", None)):
        raise TypeError(f"register_jit({site!r}) needs a jax.jit callable, got {fn!r}")
    _SITES[str(site)] = fn
    return fn


def count_call(site: str, n: int = 1) -> None:
    """Manual call counter for sites that rebuild their jits per call
    (shard_map closures) or that count one route of a jit — a locked dict
    increment, since scoring calls may come from several threads."""
    with _CALLS_LOCK:
        _CALLS[site] = _CALLS.get(site, 0) + n


def snapshot() -> Dict[str, Tuple[int, int]]:
    """``{site: (traces, calls)}`` — ``traces`` is the jit cache size
    (distinct compiled specializations so far), ``calls`` the manual
    counter (0 unless the site uses :func:`count_call`)."""
    with _CALLS_LOCK:
        calls = dict(_CALLS)
    out: Dict[str, Tuple[int, int]] = {}
    for site, fn in _SITES.items():
        out[site] = (int(fn._cache_size()), calls.get(site, 0))
    for site, n in calls.items():
        if site not in _SITES:
            out[site] = (0, n)
    return out


def delta(
    before: Dict[str, Tuple[int, int]], after: Dict[str, Tuple[int, int]]
) -> Dict[str, Tuple[int, int]]:
    """Per-site ``(retraces, calls)`` between two snapshots.  Sites new in
    ``after`` count from zero."""
    out: Dict[str, Tuple[int, int]] = {}
    for site, (traces, calls) in after.items():
        b_traces, b_calls = before.get(site, (0, 0))
        out[site] = (traces - b_traces, calls - b_calls)
    return out


def sites() -> Tuple[str, ...]:
    """Registered site names (tests use this to assert coverage)."""
    return tuple(sorted(set(_SITES) | set(_CALLS)))
