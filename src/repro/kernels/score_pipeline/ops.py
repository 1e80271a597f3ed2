"""One device-resident dispatch from padded ``DetectionsBatch`` blocks to
reward estimates — the serve-time hot path with no host materialization
between stages.

The composed path exits to numpy twice per block (features →
``np.asarray``, scores → ``np.asarray``) and re-enters jit three times.
Here the whole pipeline — top-k feature extraction, standardize, estimator
MLP — runs as ONE jitted dispatch:

``"lax"``
    The portable composition: ``score_pipeline_ref`` under ``jax.jit``.
    Because ``estimator_mlp`` resolves to the same plain-jnp MLP math on
    CPU, this path is **bit-identical** to the composed
    ``extract_features_batch → MLPRewardModel.predict`` route (the
    property tests pin this down), while fusing away the host round-trips.
``"pallas"`` / ``"pallas_interpret"``
    The fused Pallas kernel (``kernel.py``): confidence top-k gather stays
    outside (data-dependent ``argsort``), everything downstream — per-box
    features, global stats, standardize, both MLP layers — is one kernel
    with intermediates resident in VMEM.

``path=None`` auto-resolves: ``"pallas"`` where a compiled lowering exists
(TPU/GPU), ``"lax"`` on CPU (the interpreter would be slower than the jit
— the same reasoning as ``repro.kernels.dispatch.resolve_path``).

A block of host (numpy) arrays crosses to the device as ONE packed
``uint8`` buffer (:func:`pack_detections`), unpacked bit for bit inside the
jitted program; arrays already on the device go in as they are.  The
``image_size`` divisor is a cached device scalar, so a scoring call makes
at most one host→device transfer.

No path donates its inputs: a caller may score the same device-resident
block twice (``decide`` after ``score_device``, or a plain reference next
to the fused path), and a donated block would be deleted by the first call.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.features import feature_dim
from repro.detection.batch import DetectionsBatch
from repro.kernels.score_pipeline.kernel import (
    N_BOX_STATS,
    N_GLOBAL_STATS,
    score_pipeline_pallas,
)
from repro.kernels.score_pipeline.ref import score_pipeline_ref
from repro.obs.jit_stats import count_call, register_jit

PIPELINE_PATHS = ("lax", "pallas", "pallas_interpret")


def resolve_pipeline_path(path: Optional[str] = None) -> str:
    """``None`` → ``"pallas"`` on TPU/GPU, ``"lax"`` on CPU."""
    if path is None:
        return "lax" if jax.default_backend() == "cpu" else "pallas"
    if path not in PIPELINE_PATHS:
        raise ValueError(
            f"unknown score-pipeline path {path!r}; use one of {PIPELINE_PATHS}"
        )
    return path


def pipeline_params(model) -> Dict[str, jnp.ndarray]:
    """The device param bundle ``score_pipeline`` consumes, from a *fused*
    ``MLPRewardModel`` (one hidden layer + sigmoid head).  This is the
    uncached builder; ``MLPRewardModel.pipeline_params`` wraps it with an
    identity-keyed cache (safe because weight updates install fresh
    arrays) so the serve hot path skips the eager slicing below."""
    if not getattr(model, "fused", False):
        raise ValueError(
            "score_pipeline needs a fused reward model (single hidden "
            "layer + sigmoid head); score through the composed path instead"
        )
    est = model.estimator
    p = est.params
    w1 = p["layer0"]["w"]
    if model.config.standardize:
        mu = jnp.asarray(est._mu, jnp.float32)
        sigma = jnp.asarray(est._sigma, jnp.float32)
    else:
        # (x - 0) / 1 is exact in IEEE float32: the no-standardize engine
        # keeps bit-identity through the same fused trace
        mu = jnp.zeros((w1.shape[0],), jnp.float32)
        sigma = jnp.ones((w1.shape[0],), jnp.float32)
    return {
        "w1": w1,
        "b1": p["layer0"]["b"],
        "w2": p["layer1"]["w"][:, 0],
        "b2": p["layer1"]["b"][0],
        "mu": mu,
        "sigma": sigma,
    }


_score_pipeline_lax = register_jit(
    "score_pipeline.lax",
    jax.jit(score_pipeline_ref, static_argnames=("num_classes", "top_k")),
)


def _ceil_to(n: int, multiple: int) -> int:
    return -(-max(n, 1) // multiple) * multiple


@functools.partial(
    jax.jit, static_argnames=("num_classes", "top_k", "tile_b", "interpret")
)
def _score_pipeline_pallas(
    boxes, scores, classes, mask, w1, b1, w2, b2, mu, sigma,
    image_size, num_classes, top_k, tile_b, interpret,
):
    K = scores.shape[1]
    if K < top_k:  # the kernel slices a fixed top_k window
        pad = top_k - K
        boxes = jnp.pad(boxes, ((0, 0), (0, pad), (0, 0)))
        scores = jnp.pad(scores, ((0, 0), (0, pad)))
        classes = jnp.pad(classes, ((0, 0), (0, pad)), constant_values=-1)
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    # confidence top-k: data-dependent gather, outside the kernel — same
    # selection rule as box_feature_stack (stable, invalid slots sink)
    keys = jnp.where(mask, scores, -jnp.inf)
    order = jnp.argsort(-keys, axis=1, stable=True)[:, :top_k]
    m = jnp.take_along_axis(mask, order, axis=1).astype(jnp.float32)
    s = jnp.take_along_axis(scores, order, axis=1) * m
    cls = jnp.clip(jnp.take_along_axis(classes, order, axis=1), 0, num_classes - 1)
    bx = jnp.take_along_axis(boxes, order[:, :, None], axis=1) / image_size

    B = s.shape[0]
    H = w1.shape[1]
    T = N_BOX_STATS + num_classes  # per-box feature planes
    G = N_GLOBAL_STATS + num_classes  # global feature columns
    Bp, Kp = _ceil_to(B, tile_b), _ceil_to(top_k, 8)
    Hp, Gp = _ceil_to(H, 128), _ceil_to(G, 128)
    s_p = jnp.zeros((Bp, Kp), jnp.float32).at[:B, :top_k].set(s)
    m_p = jnp.zeros((Bp, Kp), jnp.float32).at[:B, :top_k].set(m)
    cls_p = jnp.zeros((Bp, Kp), jnp.int32).at[:B, :top_k].set(cls)
    bx_p = jnp.zeros((4, Bp, Kp), jnp.float32).at[:, :B, :top_k].set(
        jnp.moveaxis(bx, 2, 0)
    )
    # feature f = k * T + t of the box block feeds plane t at slot k
    nb = top_k * T
    w1_box = jnp.zeros((T, Kp, Hp), jnp.float32).at[:, :top_k, :H].set(
        w1[:nb].reshape(top_k, T, H).transpose(1, 0, 2)
    )
    mu_box = jnp.zeros((T, Kp), jnp.float32).at[:, :top_k].set(
        mu[:nb].reshape(top_k, T).T
    )
    sig_box = jnp.ones((T, Kp), jnp.float32).at[:, :top_k].set(
        sigma[:nb].reshape(top_k, T).T
    )
    w1_glob = jnp.zeros((Gp, Hp), jnp.float32).at[:G, :H].set(w1[nb:])
    mu_glob = jnp.zeros((1, Gp), jnp.float32).at[0, :G].set(mu[nb:])
    sig_glob = jnp.ones((1, Gp), jnp.float32).at[0, :G].set(sigma[nb:])
    b1_p = jnp.zeros((1, Hp), jnp.float32).at[0, :H].set(b1)
    w2_p = jnp.zeros((Hp, 128), jnp.float32).at[:H, 0].set(w2)
    b2_p = jnp.zeros((1, 128), jnp.float32).at[0, 0].set(b2)
    out = score_pipeline_pallas(
        s_p, m_p, cls_p, bx_p, w1_box, mu_box, sig_box,
        w1_glob, mu_glob, sig_glob, b1_p, w2_p, b2_p,
        num_classes=num_classes, top_k=top_k, tile_b=tile_b,
        interpret=interpret,
    )
    return out[:B, 0]


register_jit("score_pipeline.pallas", _score_pipeline_pallas)

#: dtypes of the four detection arrays, in packing order: boxes, scores,
#: classes, mask — the dtypes ``DetectionsBatch`` holds
_PACKED_DTYPES = (np.dtype(np.float32), np.dtype(np.float32),
                  np.dtype(np.int32), np.dtype(np.bool_))
#: bytes of one detection slot in a packed row: boxes 16, score 4, class 4,
#: mask 1
PACKED_SLOT_BYTES = 25


def pack_detections(boxes, scores, classes, mask) -> np.ndarray:
    """One fresh ``(B, 25 K)`` ``uint8`` buffer holding a host detection
    block, planar: each row is its boxes' bytes, then its scores', classes'
    and mask's.  A new buffer per call: a transfer may still be reading the
    last one when the next chunk is packed."""
    B = scores.shape[0]
    return np.concatenate(
        [np.ascontiguousarray(a).reshape(B, -1).view(np.uint8)
         for a in (boxes, scores, classes, mask)],
        axis=1,
    )


def unpack_detections(packed):
    """Inverse of :func:`pack_detections` inside a jitted program: the
    (boxes, scores, classes, mask) arrays, bit for bit."""
    B, K = packed.shape[0], packed.shape[1] // PACKED_SLOT_BYTES

    def words(lo, hi, shape, dtype):  # bytes [lo K, hi K) of every row
        seg = packed[:, lo * K:hi * K].reshape((B, K) + shape + (4,))
        return lax.bitcast_convert_type(seg, dtype)

    return (
        words(0, 16, (4,), jnp.float32),
        words(16, 20, (), jnp.float32),
        words(20, 24, (), jnp.int32),
        packed[:, 24 * K:] != 0,
    )


@functools.partial(
    jax.jit, static_argnames=("num_classes", "top_k", "tile_b", "path")
)
def _score_packed(
    packed, w1, b1, w2, b2, mu, sigma, image_size,
    num_classes, top_k, tile_b, path,
):
    """The scoring program of a packed host block: unpack, then exactly the
    body the four-array route runs on ``path``."""
    det = unpack_detections(packed)
    if path == "lax":
        return score_pipeline_ref(
            *det, w1, b1, w2, b2, mu, sigma, image_size, num_classes, top_k
        )
    return _score_pipeline_pallas(
        *det, w1, b1, w2, b2, mu, sigma, image_size,
        num_classes, top_k, tile_b, path == "pallas_interpret",
    )


register_jit("score_pipeline.packed", _score_packed)


@functools.lru_cache(maxsize=16)
def _device_scalar(value: float) -> jax.Array:
    """The float32 ``value`` on the device, put there once.  Not a static
    argument: a constant divisor would let XLA rewrite the division and
    break bit-identity with the composed route."""
    return jax.device_put(np.float32(value))


def score_pipeline(
    batch: Union[DetectionsBatch, Tuple],
    params: Dict[str, jnp.ndarray],
    *,
    num_classes: int,
    top_k: int = 25,
    image_size: float = 1.0,
    path: Optional[str] = None,
    tile_b: int = 128,
) -> jnp.ndarray:
    """(B,) device-resident reward estimates for a padded detection block.

    ``batch`` is a :class:`DetectionsBatch` or a ``(boxes, scores,
    classes, mask)`` tuple of (possibly already device-resident) arrays;
    ``params`` comes from :func:`pipeline_params`.  Host arrays of the
    batch's dtypes cross as one packed buffer (call site
    ``score_pipeline.packed``); any other arrays go into the jit as they
    are.  The result stays a ``jnp`` array — callers convert once at the
    policy boundary.
    """
    if isinstance(batch, DetectionsBatch):
        arrays = (batch.boxes, batch.scores, batch.classes, batch.mask)
    else:
        arrays = tuple(batch)
    boxes, scores, classes, mask = arrays
    F = int(params["w1"].shape[0])
    expect = feature_dim(int(num_classes), int(top_k))
    if F != expect:
        raise ValueError(
            f"reward model expects {F} features but the detection extractor "
            f"produces {expect} (num_classes={num_classes}, top_k={top_k})"
        )
    if scores.shape[0] == 0:
        return jnp.zeros((0,), jnp.float32)
    resolved = resolve_pipeline_path(path)
    weights = tuple(params[k] for k in ("w1", "b1", "w2", "b2", "mu", "sigma"))
    size = _device_scalar(float(image_size))
    if all(
        isinstance(a, np.ndarray) and a.dtype == d
        for a, d in zip(arrays, _PACKED_DTYPES)
    ):
        count_call("score_pipeline.packed")
        return _score_packed(
            pack_detections(*arrays), *weights, size, int(num_classes),
            int(top_k), int(tile_b), resolved,
        )
    # device-resident (or other-dtype) arrays go straight into the jit
    if resolved == "lax":
        return _score_pipeline_lax(
            *arrays, *weights, size, int(num_classes), int(top_k),
        )
    return _score_pipeline_pallas(
        *arrays, *weights, size, int(num_classes), int(top_k),
        int(tile_b), resolved == "pallas_interpret",
    )
