"""Faults of the detector offload path, which both the ``session`` and the
``fleet`` entries serve.  Each breaks what the check has to catch:

``estimate``   an estimate altered where the scoring path produces it
``decision``   an offload decision flipped where the policy makes it
``exchange``   the sharded plane's gather left out: every shard's rows
               read as the first shard's
``control``    the reference in bfloat16 put in the scoring path's place
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


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
