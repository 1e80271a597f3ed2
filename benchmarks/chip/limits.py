#!/usr/bin/env python3
"""Readings for the check's limits, on the chip: the program's numbers on
many seeds and the control's on the same rows.

    python3 benchmarks/chip/limits.py --workload <cell> --seeds 12 --seconds 4

Each seed runs the cell as ``run.py`` does (a short window at the cell's
own load, at its own sizes) and prints the compared numbers; with
``--control-seeds`` of the seeds the entry's control is then put in the
program's place on the same sampled rows and goes through the same check
(``Served.check_control``), which has to find it not correct.  All seeds
run in one process, so only the first compiles.

The summary gives, for each number the entry's check compares, the
program's largest reading and all its readings sorted, the control's
smallest reading and the limit.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: E402  (puts the harness and the program on the path)
from core import device  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 4099)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    spec = run.load_spec()
    cell = run.find_cell(spec, args.workload)
    try:
        devices = device.require_chips(int(cell["chips"]))
    except device.NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 3
    device.configure_cache(run.ROOT)
    rows_out = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.time()
        result, checks, served = run.run_cell(spec, cell, seed, args.seconds, False,
                                              devices, t_start=t)
        row = {"seed": seed, "correct": result["correct"],
               "program": {n: v for n, v, _ in checks},
               "max_abs_estimate_gap": served.est_gap,
               "metrics": {k: v["value"] for k, v in result["metrics"].items()}}
        if i < args.control_seeds:
            ctrl = served.check_control()
            row["control"] = {n: v for n, v, _ in ctrl}
            row["control"]["max_abs_estimate_gap"] = served.est_gap
            row["control_correct"] = all(v <= lim for _, v, lim in ctrl)
        row["wall_s"] = time.time() - t
        rows_out.append(row)
        print("reading " + json.dumps(row), flush=True)
    summary = {"workload": args.workload}
    for name, _, limit in checks:
        prog = [r["program"][name] for r in rows_out]
        ctrl = [r["control"][name] for r in rows_out if "control" in r]
        summary.update({f"program_{name}_max": max(prog), f"program_{name}_sorted": sorted(prog),
                        f"control_{name}_min": min(ctrl) if ctrl else None,
                        f"limit_{name}": limit})
    summary["all_correct"] = all(r["correct"] for r in rows_out)
    summary["control_ever_correct"] = any(r.get("control_correct", False) for r in rows_out)
    print("summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
