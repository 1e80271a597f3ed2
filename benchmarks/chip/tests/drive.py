"""Drive one cell end to end on whatever devices JAX has, skipping the
harness's look for a chip, optionally with a fault planted in the timed
path; prints the result line.

    python drive.py <cell> <config overrides json> <mix overrides json> <trace 0|1> <seconds> [fault]

A cell is one of ``BENCHMARK.json`` or, where that has none of the name,
one of ``fixtures/city/spec.json`` (the city cells held out of the
benchmark), whose configuration and mix are read from that directory.
Upper-case keys among the configuration overrides set the entry module's
constants of the same name (its pool and calibration sizes).

A fault is planted by the faults module of the cell's entry,
``faults/<entry>.py``, which names the faults it knows.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
import jax  # noqa: E402
from traffic import generator  # noqa: E402

HELD = os.path.join(HERE, "fixtures", "city")


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def spec_and_cell(name: str):
    """The spec that names cell ``name``, and the cell."""
    spec = run.load_spec()
    if not any(c["name"] == name for c in spec["workloads"]):
        spec = _json(os.path.join(HELD, "spec.json"))
        run.load_config = lambda n: _json(os.path.join(HELD, f"{n}.json"))
        generator.load_mix = lambda n: _json(os.path.join(HELD, f"{n}.json"))
    return spec, run.find_cell(spec, name)


def plant(entry: str, fault: str) -> None:
    """Plant ``fault`` through the entry's own faults, ``faults/<entry>.py``,
    whose ``plant`` raises on a name it does not know."""
    importlib.import_module(f"faults.{entry}").plant(fault)


def main() -> int:
    cell_name, over, mix = sys.argv[1], json.loads(sys.argv[2]), json.loads(sys.argv[3])
    traced, seconds = bool(int(sys.argv[4])), float(sys.argv[5])
    fault = sys.argv[6] if len(sys.argv) > 6 else ""
    spec, cell = spec_and_cell(cell_name)
    entry = run.load_config(cell["config"])["entry"]
    module = importlib.import_module(f"entries.{entry}")
    for k in [k for k in over if k.isupper()]:
        setattr(module, k, over.pop(k))
    if fault:
        plant(entry, fault)
    result, _, _ = run.run_cell(spec, cell, 2**31 + 2024, seconds, traced,
                                jax.devices()[: int(cell["chips"])], t_start=time.time(),
                                config_overrides=over, mix_overrides=mix)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
