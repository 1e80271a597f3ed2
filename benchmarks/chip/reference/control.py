"""The control of the check: the plain reference put in the program's
place and computed in bfloat16, the precision below the float32 that the
configurations state.  It runs on the chip through ``jax.numpy``; every
array of the features and of the estimator, and every intermediate, is
bfloat16.  Its estimates have to fail the check."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from reference import estimator as ref


def estimates(rows: Dict, params: Dict, cfg: Dict, block: int = 512) -> np.ndarray:
    kw = dict(num_classes=cfg["num_classes"], top_k=cfg["top_k"],
              image_size=float(cfg["image_size"]))
    n = len(rows["scores"])
    out = np.empty(n)
    for lo in range(0, n, block):
        part = {k: jnp.asarray(v[lo: lo + block]) for k, v in rows.items()}
        x = ref.features(part, xp=jnp, dtype=jnp.bfloat16, **kw)
        out[lo: lo + block] = np.asarray(
            ref.forward(x, params, xp=jnp, dtype=jnp.bfloat16), np.float64)
    return out
