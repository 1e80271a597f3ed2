"""Prompts of a document-QA mix: their lengths and token ids, drawn from
the run's seed.  A mix's ``prompts`` group gives a log-normal length
(``length_median``, ``length_sigma``) clipped to ``[length_min,
length_max]``; token ids are uniform over the model's whole vocabulary,
so no two prompts share a prefix.  Arrivals come from ``generator.py``."""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def draw(rng: np.random.Generator, n: int, mix: Dict, vocab: int) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` prompts as one flat int32 token buffer and their (n + 1,)
    offsets into it: prompt ``i`` is ``tokens[offsets[i]:offsets[i + 1]]``."""
    p = mix["prompts"]
    lengths = np.clip(
        np.round(rng.lognormal(np.log(p["length_median"]), p["length_sigma"], n)),
        p["length_min"], p["length_max"],
    ).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    tokens = rng.integers(0, vocab, int(offsets[-1]), dtype=np.int32)
    return tokens, offsets
