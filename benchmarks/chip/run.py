#!/usr/bin/env python3
"""The chip benchmark's one command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(weights, pool, calibration, every shape the window uses), then an
open-loop window of ``--seconds``, then the check against the plain
reference.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its limit,
which are also the last lines of stderr.

Everything a cell needs is found by name: ``configs/<config>.json`` (the
deployment, naming its ``entries/<entry>.py``), ``traffic/<mix>.json``
(read by ``traffic/generator.py``) and ``metrics/<metric>.py`` (one reader
per metric).  A machine without a TPU, or with fewer chips than the cell
asks for, gets exit code 3 and no result line.

``--keep-trace DIR`` copies a traced run's profile into ``DIR``.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from core import device, trace as trace_mod  # noqa: E402
from core.spans import Spans  # noqa: E402
from traffic import generator  # noqa: E402

#: seconds at the end of a traced window that the profiler records
TRACE_SECONDS = 3.0


def load_spec(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: Dict, name: str) -> Dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> Dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def metrics_for(spec: Dict, cell: Dict, trace: bool) -> List[Dict]:
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries if cell["name"] in m.get("workloads", [cell["name"]])]


class GcClock:
    """Pauses of the interpreter's garbage collector while ``on``."""

    def __init__(self) -> None:
        self.on, self.t0, self.pauses = False, 0.0, []
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: Dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self.t0 = time.perf_counter()
        else:
            self.pauses.append((time.perf_counter() - self.t0, info.get("generation")))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


class Profile:
    """Starts the profiler once the window reaches ``start_at`` seconds."""

    def __init__(self, start_at: Optional[float]):
        self.start_at = start_at
        self.dir: Optional[str] = None
        self.t_on = self.t_off = None

    def __call__(self, now: float) -> None:
        if self.start_at is not None and self.dir is None and now >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the harness's annotations, not JAX's own
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_on = now

    def stop(self, now: float) -> None:
        if self.dir is not None and self.t_off is None:
            jax.profiler.stop_trace()
            self.t_off = now


def run_cell(spec: Dict, cell: Dict, seed: int, seconds: float, traced: bool,
             devices, *, t_start: float = T_START, keep_trace: Optional[str] = None,
             config_overrides: Optional[Dict] = None,
             mix_overrides: Optional[Dict] = None) -> Tuple[Dict, List, object]:
    """Set up, run the window and check one cell; returns the result
    object, the compared numbers ``[(name, value, limit)]`` and the served
    cell (released: only what the check reads is left)."""
    cfg = dict(load_config(cell["config"]), **(config_overrides or {}))
    mix = dict(generator.load_mix(cell["traffic"]), **(mix_overrides or {}))
    entry = importlib.import_module(f"entries.{cfg['entry']}")
    spans = Spans(annotate=traced)
    served = entry.Served(cfg, mix, seed, devices, spans, profile=traced)
    due = generator.arrivals(np.random.default_rng([seed, 9]), mix, seconds)
    compiles = device.CompileCounter()
    profile = Profile(max(0.0, seconds - TRACE_SECONDS) if traced else None)
    spans.reset()
    setup_s = time.time() - t_start
    gcs = GcClock()
    compiles.start()
    gcs.on = True
    win = served.window(due, seconds, profile)
    compiles.stop()
    gcs.on = False
    gcs.close()
    profile.stop(win["end"])
    compiles.close()
    mem = device.memory_peak(devices)
    lat = win["lat"]
    per_frame_due = lat.size // len(due) if len(due) else 1
    due_f = np.repeat(due, per_frame_due)
    failed = int(np.sum(np.isnan(lat)))
    # a frame never decided counts as waiting until the run stopped
    lat = np.where(np.isnan(lat), win["end"] - due_f, lat)
    returned = due_f + lat
    layer = served.layer_seconds(dict(spans.seconds))
    served.release()

    reduced = None
    if traced and profile.dir is not None:
        files = glob.glob(os.path.join(profile.dir, "**", "*.xplane.pb"), recursive=True)
        if keep_trace and files:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(files[0], os.path.join(keep_trace, f"{cell['name']}.xplane.pb"))
        if files:
            reduced = trace_mod.reduce(files[0], n_chips=len(devices),
                                       window_s=profile.t_off - profile.t_on)
        shutil.rmtree(profile.dir, ignore_errors=True)
    ctx = SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, seconds=float(seconds), setup_s=setup_s,
        latency_s=lat, decided_in_window=int(np.sum(returned <= seconds)),
        frames_decided=int(win["decided"]), window_end=float(win["end"]), layer_seconds=layer,
        compiles=compiles.count, retraces=compiles.retraces, trace=reduced, devices=devices,
        scoring_calls=(served.scoring_calls(win["blocks"], profile.t_on, profile.t_off)
                       if profile.t_on is not None else []),
        traced_frames=sum(n for t, _, n in win["blocks"]
                          if profile.t_on is not None and profile.t_on <= t <= profile.t_off),
    )
    metrics = {}
    for m in metrics_for(spec, cell, traced):
        value = importlib.import_module(f"metrics.{m['name']}").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # the generator's own lateness: when each block was picked up
    picked = np.array([t for t, _, _ in win["blocks"]]) if win["blocks"] else np.zeros(1)
    print(f"window: due={lat.size} decided={win['decided']} failed={failed} "
          f"blocks={len(win['blocks'])} mean_block={lat.size / max(len(win['blocks']), 1):.1f} "
          f"end_s={win['end']:.3f} compiles={compiles.count} retraces={compiles.retraces} "
          f"first_pickup_s={float(picked[0]):.6f}", file=sys.stderr)
    worst = sorted(gcs.pauses, reverse=True)[:3]
    print(f"gc: collections={len(gcs.pauses)} total_s={sum(p for p, _ in gcs.pauses):.4f} "
          f"longest={[(round(p, 4), g) for p, g in worst]}", file=sys.stderr)
    slow = sorted(win["blocks"], key=lambda b: b[0] - b[1])[:5]
    print("slowest blocks (picked_s, served_s, frames): "
          + " ".join(f"({a:.3f},{b - a:.4f},{n})" for a, b, n in slow), file=sys.stderr)

    print("setup: " + " ".join(f"{k}={v:.3f}" for k, v in getattr(served, "setup_phases", []))
          + f" total={setup_s:.3f}", file=sys.stderr)
    checks = served.check()
    print(f"informational: max_abs_estimate_gap={served.est_gap!r}", file=sys.stderr)
    dev = dict(device.describe(devices), memory_peak_bytes=mem)
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    result = {
        "correct": bool(all(v <= lim for _, v, lim in checks) and failed == 0),
        "attempted": int(lat.size),
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    return result, checks, served


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    spec = load_spec()
    cell = find_cell(spec, args.workload)
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 3
    device.configure_cache(ROOT)
    result, checks, _ = run_cell(spec, cell, args.seed, args.seconds, bool(args.trace),
                              devices, keep_trace=args.keep_trace)
    for name, value, limit in checks:
        print(f"check {name}={value!r} limit={limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
