"""Feature-extractor protocol + registered adapters (OffloadEngine inputs).

A ``FeatureExtractor`` turns a batch of *weak* model outputs into the fixed
(B, F) float matrix the reward estimator consumes — the paper's constraint
that the estimator reads only the weak detector's result.  Adapters register
under a string name so a saved engine can reconstruct its extractor.
"""
from __future__ import annotations

from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Protocol,
    Sequence,
    Union,
    runtime_checkable,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.features import extract_features_batch, feature_dim
from repro.detection.batch import DetectionsBatch
from repro.detection.map_engine import Detections


@runtime_checkable
class FeatureExtractor(Protocol):
    """Batch of weak outputs -> (B, F) feature matrix."""

    name: str

    def __call__(self, weak_outputs: Any) -> np.ndarray: ...

    def spec(self) -> Dict[str, Any]:
        """Constructor kwargs sufficient to rebuild this extractor."""
        ...


_EXTRACTORS: Dict[str, Callable[..., FeatureExtractor]] = {}


def register_feature_extractor(name: str):
    """Class decorator: register under ``name`` for save/load resolution."""

    def deco(cls):
        cls.name = name
        _EXTRACTORS[name] = cls
        return cls

    return deco


def list_feature_extractors() -> List[str]:
    """Registered extractor names (for runtime configs and error messages)."""
    return sorted(_EXTRACTORS)


def make_feature_extractor(name: str, **kwargs) -> FeatureExtractor:
    if name not in _EXTRACTORS:
        raise KeyError(
            f"unknown feature extractor {name!r}; have {list_feature_extractors()}"
        )
    return _EXTRACTORS[name](**kwargs)


@register_feature_extractor("detection_boxes")
class DetectionBoxFeatures:
    """Top-K box features + global summary stats of a weak detector ([13]-style).

    Accepts either a padded :class:`repro.detection.batch.DetectionsBatch`
    (the batched data plane — no per-image Python) or a ragged list of
    ``Detections`` (padded on entry); both run the one jitted feature
    kernel in ``repro.core.features``.
    """

    def __init__(self, num_classes: int, top_k: int = 25, image_size: float = 1.0):
        self.num_classes = int(num_classes)
        self.top_k = int(top_k)
        self.image_size = float(image_size)

    @property
    def feature_dim(self) -> int:
        return feature_dim(self.num_classes, self.top_k)

    def __call__(
        self, weak_outputs: Union[Sequence[Detections], DetectionsBatch]
    ) -> np.ndarray:
        return extract_features_batch(
            weak_outputs, self.num_classes, self.top_k, self.image_size
        )

    def spec(self) -> Dict[str, Any]:
        return {
            "num_classes": self.num_classes,
            "top_k": self.top_k,
            "image_size": self.image_size,
        }


def _logit_feature_sums(logits: jnp.ndarray, vmask: jnp.ndarray, top_k: int):
    """Per-row sums over the positions ``vmask`` keeps of entropy, margin,
    top-1 and the top-k probs, and the masked max entropy, from (B, S, V)
    logits in float32."""
    lf = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    entropy = -(jnp.exp(lf) * lf).sum(-1)  # (B,S)
    # (B,S,k), the k largest probs; top_k over 2-D rows (a TPU lowers a
    # 3-D top_k over a wide vocabulary to a full sort)
    V = lf.shape[-1]
    topv = jnp.exp(jax.lax.top_k(lf.reshape(-1, V), top_k)[0]).reshape(lf.shape[:-1] + (top_k,))
    v = vmask.astype(jnp.float32)
    return (
        (entropy * v).sum(-1),
        jnp.max(entropy * v, axis=-1),
        ((topv[..., 0] - topv[..., 1]) * v).sum(-1),
        (topv[..., 0] * v).sum(-1),
        (topv * v[..., None]).sum(1),
        v.sum(-1),
    )


def _features_of_sums(ent, ent_max, margin, top1, topk, count) -> jnp.ndarray:
    denom = jnp.maximum(count, 1.0)
    return jnp.concatenate(
        [
            (ent / denom)[:, None],
            ent_max[:, None],
            (margin / denom)[:, None],
            (top1 / denom)[:, None],
            topk / denom[:, None],  # mean top-k probs
        ],
        axis=-1,
    )


def logits_features(
    logits: jnp.ndarray, labels: Optional[jnp.ndarray] = None, top_k: int = 8
) -> np.ndarray:
    """Per-request features from WEAK-head logits only (deployable inputs):
    mean/max entropy, mean margin, mean top-k probs, mean max-prob.

    ``labels`` marks valid positions (>= 0); ``None`` treats every position
    as valid (the decode-time case where no gold labels exist).
    """
    vmask = jnp.ones(logits.shape[:-1], bool) if labels is None else labels >= 0
    return np.asarray(_features_of_sums(*_logit_feature_sums(logits, vmask, top_k)))


def chunked_logits_features(
    head: Callable[[jnp.ndarray], jnp.ndarray],
    h: jnp.ndarray,
    vmask: jnp.ndarray,
    top_k: int = 8,
    chunk: int = 512,
) -> jnp.ndarray:
    """``logits_features`` of ``head(h)`` without its (B, S, V) logits:
    the positions of the (B, S, M) hidden states go through ``head``
    ((B, c, M) -> (B, c, V) float32 logits) ``chunk`` at a time and only
    per-row sums leave each chunk.  ``vmask`` (B, S) marks the positions
    that count.  Returns a device (B, 4 + top_k) array."""
    B, S, M = h.shape
    c = chunk if 0 < chunk < S and S % chunk == 0 else S
    n = S // c
    parts = jax.lax.map(
        lambda a: _logit_feature_sums(head(a[0]), a[1], top_k),
        (jnp.moveaxis(h.reshape(B, n, c, M), 1, 0), jnp.moveaxis(vmask.reshape(B, n, c), 1, 0)),
    )
    ent, ent_max, margin, top1, topk, count = parts
    return _features_of_sums(
        ent.sum(0), ent_max.max(0), margin.sum(0), top1.sum(0), topk.sum(0), count.sum(0)
    )


@register_feature_extractor("lm_logits")
class LMLogitsFeatures:
    """Entropy/margin/top-k summary of weak-head logits (the LM analogue of
    top-25 box confidences).  Accepts ``(logits, labels)`` tuples or dicts
    with ``logits``/``labels`` keys; ``labels`` may be None at decode time."""

    def __init__(self, top_k: int = 8):
        self.top_k = int(top_k)

    @property
    def feature_dim(self) -> int:
        return 4 + self.top_k

    def __call__(self, weak_outputs: Any) -> np.ndarray:
        if isinstance(weak_outputs, dict):
            logits, labels = weak_outputs["logits"], weak_outputs.get("labels")
        else:
            logits, labels = weak_outputs
        return logits_features(logits, labels, self.top_k)

    def spec(self) -> Dict[str, Any]:
        return {"top_k": self.top_k}
