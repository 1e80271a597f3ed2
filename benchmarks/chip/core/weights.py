"""The estimator a cell serves: seeded weights made on the chip in one
jitted call, in float32 as they are served, and feature scales taken from a
seeded calibration block through the plain reference.

The estimator is not fitted: a fit would cost set-up time in every run and
its weights would depend on the fit's own rounding.  ``W1`` is He-normal
(the program's own initializer's scale).  A fitted estimator regresses
MORIC ranks (the paper's Eq. 6), which are uniform on [0, 1], so its
estimates spread over the whole interval: log-odds with the logistic
spread of about 1.8.  ``w2`` is He-normal times 3, which gives that spread
on these features (1.6 to 2.2 over seeds), where He-normal alone gives
about 0.6 and keeps every estimate within [0.03, 0.98].
Each feature is standardized by its calibration mean and by
``sqrt(var + 0.01)``: the ridge keeps a slot that is almost always empty
from turning float32 rounding into a large standardized value.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference.estimator import features as ref_features


def key_for(seed: int, stream: int) -> jax.Array:
    words = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@partial(jax.jit, static_argnames=("F", "H"))
def _make(key, F: int, H: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "w1": jax.random.normal(k1, (F, H), jnp.float32) * jnp.sqrt(2.0 / F),
        "b1": 0.1 * jax.random.normal(k2, (H,), jnp.float32),
        "w2": jax.random.normal(k3, (H,), jnp.float32) * 3.0 * jnp.sqrt(2.0 / H),
        "b2": 0.1 * jax.random.normal(k4, (), jnp.float32),
    }


def make(seed: int, stats_block: Dict, *, num_classes: int, top_k: int,
         image_size: float, hidden: int) -> Dict:
    """``{w1, b1, w2, b2}`` on the chip and ``{mu, sigma}`` on the host."""
    x = ref_features(stats_block, num_classes=num_classes, top_k=top_k,
                     image_size=image_size)
    F = x.shape[1]
    p = dict(_make(key_for(seed, 7), F, hidden))
    p["mu"] = x.mean(axis=0).astype(np.float32)
    p["sigma"] = np.sqrt(x.var(axis=0) + 0.01).astype(np.float32)
    return p


def artifact(p: Dict, *, hidden: int):
    """The ``(arrays, meta)`` pair ``MLPRewardModel.from_state`` loads."""
    F = int(p["w1"].shape[0])
    arrays = {
        "params": {
            "layer0": {"w": p["w1"], "b": p["b1"]},
            "layer1": {"w": p["w2"][:, None], "b": p["b2"][None]},
        },
        "mu": p["mu"],
        "sigma": p["sigma"],
    }
    meta = {
        "kind": "mlp",
        "in_dim": F,
        "use_fused": True,
        "config": {"hidden": [hidden], "sigmoid_out": True, "standardize": True},
    }
    return arrays, meta


def host_copy(p: Dict) -> Dict[str, np.ndarray]:
    """The weights as float64 host arrays, for the reference."""
    return {k: np.asarray(v, np.float64) for k, v in p.items()}
