"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-program
device time and the ``breakdown`` of a traced run.

Read with ``jax.profiler.ProfileData``: each chip is a plane named
``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per device
operation and its ``XLA Modules`` line one per program run.  The host
plane ``/host:CPU`` holds the harness's ``bench.*`` spans (written by
``jax.profiler.TraceAnnotation``) on the clock the device events use.

Busy time of a chip is the union of its operation intervals.  An idle gap
is an interval between two merged operation intervals of chip 0; it is put
down to the ``bench.*`` span open at its midpoint, or to ``host`` when none
is.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def short_name(name: str) -> str:
    """``%fusion.2 f32[25600,4]`` for an op's HLO text, ``jit_f`` for a
    program's ``jit_f(<fingerprint>)``."""
    if " = " in name:
        head, rest = name.split(" = ", 1)
        return f"{head} {re.sub(r'{[^}]*}', '', rest.split(' ', 1)[0]).rstrip(',')}"
    return re.sub(r"\(\d+\)$", "", name)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)) for e in line.events]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def load(path: str) -> Dict:
    """Planes of the trace as plain lists: ``{"devices": {i: {"ops": [...],
    "modules": [...]}}, "spans": [...]}``, events as ``(name, start_ns,
    end_ns)``."""
    pd = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln) if e[0].startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def reduce(path: str, n_chips: int, window_s: float = None) -> Optional[Dict]:
    """Busy seconds (mean over the chips used), program seconds (summed
    over them) and the ``breakdown``; ``None`` for a trace that holds no
    TPU plane, so that no device metric is read from it."""
    t = load(path)
    chips = [t["devices"][i] for i in sorted(t["devices"])[:n_chips]]
    if not chips:
        return None
    busy, ops, modules = [], defaultdict(float), defaultdict(float)
    lo, hi = float("inf"), float("-inf")
    for chip in chips:
        merged = _union([(a, b) for _, a, b in chip["ops"]])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        for name, a, b in chip["ops"]:
            ops[short_name(name)] += (b - a) * 1e-9
            lo, hi = min(lo, a), max(hi, b)
        for name, a, b in chip["modules"]:
            modules[short_name(name)] += (b - a) * 1e-9
    spans = sorted(t["spans"], key=lambda s: s[1])
    starts = [s[1] for s in spans]
    if spans:
        lo = min(lo, spans[0][1])
        hi = max(hi, max(s[2] for s in spans))
    traced_s = (hi - lo) * 1e-9 if hi > lo else 0.0
    gaps = defaultdict(float)
    merged0 = _union([(a, b) for _, a, b in chips[0]["ops"]])
    for (_, end), (start, _) in zip(merged0, merged0[1:]):
        mid = 0.5 * (end + start)
        i = bisect.bisect_right(starts, mid) - 1
        owner = spans[i][0] if i >= 0 and spans[i][2] >= mid else "host"
        gaps[owner] += (start - end) * 1e-9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / len(busy),
        "busy_per_chip_s": busy,
        "window_s": window_s if window_s is not None else traced_s,
        "traced_s": traced_s,
        "modules": dict(modules),
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }
