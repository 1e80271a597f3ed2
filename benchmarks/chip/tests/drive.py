"""Drive one cell end to end on whatever devices JAX has, skipping the
harness's look for a chip, optionally with a fault planted in the timed
path; prints the result line.

    python drive.py <cell> <config overrides json> <mix overrides json> <trace 0|1> <seconds> [fault]

A cell is one of ``BENCHMARK.json`` or, where that has none of the name,
one of ``fixtures/city/spec.json`` (the city cells held out of the
benchmark), whose configuration and mix are read from that directory.
Upper-case keys among the configuration overrides set the entry module's
constants of the same name (its pool and calibration sizes).

Faults (each breaks what the check has to catch):

``estimate``   an estimate altered where the scoring path produces it
``decision``   an offload decision flipped where the policy makes it
``exchange``   the sharded plane's gather left out: every shard's rows
               read as the first shard's
``control``    the reference in bfloat16 put in the scoring path's place
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
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
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


def plant(fault: str) -> None:
    from repro.api.engine import OffloadEngine
    from repro.fleet.plane import FleetPlane
    from repro.runtime.session import OffloadSession

    if fault == "estimate":
        score_device, plane_score = OffloadEngine.score_device, FleetPlane.score

        def bad_device(self, *a, **kw):
            return jnp.asarray(score_device(self, *a, **kw)).at[0].add(0.25)

        def bad_plane(self, engine, features):
            out = np.array(plane_score(self, engine, features))
            out[::7] = np.clip(out[::7] + 0.25, 0.0, 1.0)
            return out

        OffloadEngine.score_device, FleetPlane.score = bad_device, bad_plane
    elif fault == "decision":
        decide = OffloadSession._decide

        def bad(self, estimates):
            out = decide(self, estimates)
            if out:
                d = out[0]
                out[0] = type(d)(step=d.step, estimate=d.estimate, offload=not d.offload)
            return out

        OffloadSession._decide = bad
    elif fault == "exchange":
        plane_score = FleetPlane.score

        def bad(self, engine, features):
            out = np.array(plane_score(self, engine, features))
            per, _ = self.shard_sizes(len(out))
            for s in range(1, self.n_devices):
                out[s * per:(s + 1) * per] = out[:per][: len(out[s * per:(s + 1) * per])]
            return out

        FleetPlane.score = bad
    elif fault == "control":
        from reference import control, estimator as ref
        score_device, plane_score = OffloadEngine.score_device, FleetPlane.score

        def params_of(engine):
            p = engine.reward_model.pipeline_params()
            return {k: np.asarray(v, np.float64) for k, v in p.items()}

        def ctrl_device(self, weak_outputs=None, **kw):
            fx = self.feature_extractor
            rows = {k: getattr(weak_outputs, k) for k in ("boxes", "scores", "classes", "mask")}
            cfg = {"num_classes": fx.num_classes, "top_k": fx.top_k, "image_size": fx.image_size}
            return jnp.asarray(control.estimates(rows, params_of(self), cfg), jnp.float32)

        def ctrl_plane(self, engine, features):
            return np.asarray(ref.forward(jnp.asarray(features), params_of(engine),
                                          xp=jnp, dtype=jnp.bfloat16), np.float64)

        OffloadEngine.score_device, FleetPlane.score = ctrl_device, ctrl_plane
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")


def main() -> int:
    cell_name, over, mix = sys.argv[1], json.loads(sys.argv[2]), json.loads(sys.argv[3])
    traced, seconds = bool(int(sys.argv[4])), float(sys.argv[5])
    fault = sys.argv[6] if len(sys.argv) > 6 else ""
    spec, cell = spec_and_cell(cell_name)
    module = importlib.import_module(f"entries.{run.load_config(cell['config'])['entry']}")
    for k in [k for k in over if k.isupper()]:
        setattr(module, k, over.pop(k))
    plant(fault)
    result, _, _ = run.run_cell(spec, cell, 2**31 + 2024, seconds, traced,
                                jax.devices()[: int(cell["chips"])], t_start=time.time(),
                                config_overrides=over, mix_overrides=mix)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
