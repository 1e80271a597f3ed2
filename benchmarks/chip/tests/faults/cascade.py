"""Faults of the ``cascade`` entry, the LM early-exit cascade.  Each
breaks what the check has to catch:

``estimate``   an estimate altered where the scoring path produces it
``decision``   an offload decision flipped where the policy makes it
``latent``     every decode step writes its latent one slot off (and
               attends over the slots shifted with it)
``expert``     every token's last routed expert dropped
``control``    the reference rounded to float8_e4m3fn put in the
               program's place for the check
"""
from __future__ import annotations

import jax.numpy as jnp

from faults.detector import plant as plant_detector


def plant(fault: str) -> None:
    if fault in ("estimate", "decision"):
        plant_detector(fault)
    elif fault == "latent":
        from repro.models import lm

        decode = lm.mla_decode

        def off_by_one(params, cfg, x, cache_c, cache_kr, pos):
            # the write lands at pos + 1: shift the slots under the step
            out, c, kr = decode(params, cfg, x, jnp.roll(cache_c, -1, 1),
                                jnp.roll(cache_kr, -1, 1), pos)
            return out, jnp.roll(c, 1, 1), jnp.roll(kr, 1, 1)

        lm.mla_decode = off_by_one
    elif fault == "expert":
        from repro.models import layers

        topk = layers._topk_gates

        def drop_last(probs, cfg):
            gate, ids = topk(probs, cfg)
            return gate.at[..., -1].set(0.0), ids

        layers._topk_gates = drop_last
    elif fault == "control":
        from entries.cascade import Served

        check = Served.check
        Served.check = lambda self, control=False: check(self, control=True)
    elif fault:
        raise ValueError(f"unknown fault {fault!r}")
