#!/usr/bin/env python3
"""Find a cell's knee on the chip: one set-up, then open-loop windows at
rising offered rates, each reporting its tail and whether the backlog grew.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 5000,10000 --seconds 5

The knee is the highest rate at which the backlog does not grow across the
window: the frames due in its last tenth wait no longer than twice those
due in its first tenth, plus 10 ms.  A cell's traffic file is then given
four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys

import run  # noqa: E402
from core import device  # noqa: E402
from traffic import generator  # noqa: E402

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    args = ap.parse_args()
    spec = run.load_spec()
    cell = run.find_cell(spec, args.workload)
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    device.configure_cache(run.ROOT)
    cfg = run.load_config(cell["config"])
    mix = generator.load_mix(cell["traffic"])
    import importlib
    from core.spans import Spans
    entry = importlib.import_module(f"entries.{cfg['entry']}")
    served = entry.Served(cfg, mix, args.seed, devices, Spans())
    for rate in [float(r) for r in args.rates.split(",")]:
        due = generator.arrivals(np.random.default_rng(int(rate)), dict(mix, rate=rate),
                                 args.seconds)
        win = served.window(due, args.seconds, lambda now: None)
        per = win["lat"].size // len(due)
        lat = win["lat"]
        n = lat.size
        head, tail = lat[: max(n // 10, 1)], lat[-max(n // 10, 1):]
        grew = bool(np.nanmedian(tail) > 2 * np.nanmedian(head) + 0.010) or bool(np.isnan(lat).any())
        print("rate " + json.dumps({
            "rate": rate, "frames_per_s": rate * per, "decided": win["decided"], "due": n,
            "p50_ms": float(np.nanpercentile(lat, 50) * 1e3),
            "p95_ms": float(np.nanpercentile(lat, 95) * 1e3),
            "head_p50_ms": float(np.nanmedian(head) * 1e3),
            "tail_p50_ms": float(np.nanmedian(tail) * 1e3),
            "drain_s": win["end"] - args.seconds, "blocks": len(win["blocks"]),
            "backlog_grew": grew}), flush=True)
        if grew and float(np.nanmedian(tail)) > 2.0:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
