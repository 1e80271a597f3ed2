"""Pallas kernel: the fused serve-time score pipeline.

One kernel per batch tile takes the *gathered* top-k detection arrays
(selection by confidence is a data-dependent ``argsort`` and stays outside,
see ``ops.py``) and produces the reward estimate with every intermediate —
per-box features, global stats, standardized features, hidden activation —
living only in VMEM:

    per-box [s, cx, cy, w, h, area, aspect, onehot(class)]
    global  [n/K, mean, max, entropy, class histogram]
    x   = (concat - mu) / sigma
    out = sigmoid(gelu(x @ W1 + b1) @ W2 + b2)

Everything is **planar**: boxes arrive as four ``(TB, K)`` coordinate
planes, and each per-box feature column (score, cx, ..., one class of the
one-hot) is one ``(TB, K)`` plane.  The flattened ``(TB, K*(7+C))`` feature
row is never built; instead ``x @ W1`` is the sum over feature types of
``plane_t @ W1_t``, where ``W1_t`` (``(K, H)``) holds the rows of W1 that
type ``t`` feeds (``ops.py`` regroups W1, mu and sigma once per call).  The
``4 + C`` global columns are placed into one 128-lane row by iota-select
and take one more matmul.  A 3-D per-box block such as ``(TB, K, 4)`` or
``(TB, K, 7+C)`` would pad its minor dim to 128 lanes: at ``TB=128`` those
temporaries need more than the 16 MiB scoped VMEM of a v5e core, which is
why the kernel keeps every array 2-D.

Layouts mirror ``estimator_mlp``: W2 is padded to (H, 128) so the MXU sees
a 128-lane output, column 0 carries the scalar; H is padded to a 128
multiple and K to a sublane multiple by ops.py.  Padded box slots have
zero validity, zero mean and unit scale, and zero W1 rows, so they
contribute exact zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

#: per-box feature planes before the one-hot ones
N_BOX_STATS = 7
#: global feature columns before the class histogram
N_GLOBAL_STATS = 4


def _make_kernel(num_classes: int, top_k: int):
    def kernel(s_ref, m_ref, cls_ref, bx_ref, w1b_ref, mub_ref, sigb_ref,
               w1g_ref, mug_ref, sigg_ref, b1_ref, w2_ref, b2_ref, out_ref):
        s = s_ref[...]  # (TB, Kp) gathered masked scores
        m = m_ref[...]  # (TB, Kp) gathered validity as float
        cls = cls_ref[...]  # (TB, Kp) gathered clipped classes
        x0, y0, x1, y1 = bx_ref[0], bx_ref[1], bx_ref[2], bx_ref[3]
        TB = s.shape[0]

        def add_plane(hid, t, plane):
            z = (plane - mub_ref[t : t + 1, :]) / sigb_ref[t : t + 1, :]
            return hid + jnp.dot(z, w1b_ref[t], preferred_element_type=jnp.float32)

        cx = (x0 + x1) / 2
        cy = (y0 + y1) / 2
        w = jnp.maximum(x1 - x0, 0.0)
        h = jnp.maximum(y1 - y0, 0.0)
        area = w * h
        aspect = jnp.clip(w / jnp.maximum(h, 1e-6), 0.0, 10.0) / 10.0
        hid = jnp.zeros((TB, w1b_ref.shape[2]), jnp.float32)
        for t, plane in enumerate(
            (s, cx * m, cy * m, w * m, h * m, area * m, aspect * m)
        ):
            hid = add_plane(hid, t, plane)

        n = m.sum(axis=1, keepdims=True)  # (TB, 1)
        nonempty = n > 0
        safe_n = jnp.maximum(n, 1.0)
        lane = lax.broadcasted_iota(jnp.int32, (TB, mug_ref.shape[1]), 1)
        g = jnp.zeros((TB, mug_ref.shape[1]), jnp.float32)
        for c in range(num_classes):
            onehot = jnp.where(cls == c, m, 0.0)
            hid = add_plane(hid, N_BOX_STATS + c, onehot)
            hist = onehot.sum(axis=1, keepdims=True) / safe_n
            g = jnp.where(lane == N_GLOBAL_STATS + c, hist, g)

        s_sum = s.sum(axis=1, keepdims=True)
        p = s / jnp.maximum(s_sum, 1e-9)
        entropy = -(p * jnp.log(jnp.maximum(p, 1e-12))).sum(axis=1, keepdims=True)
        s_max = jnp.max(jnp.where(m > 0, s, -jnp.inf), axis=1, keepdims=True)
        for j, col in enumerate((n / top_k, s_sum / safe_n, s_max, entropy)):
            g = jnp.where(lane == j, col, g)
        # an image with no live box has all-zero global stats (hist included)
        g = jnp.where(nonempty, g, 0.0)
        z = (g - mug_ref[...]) / sigg_ref[...]
        hid = hid + jnp.dot(z, w1g_ref[...], preferred_element_type=jnp.float32)
        hid = jax.nn.gelu(hid + b1_ref[...])
        o = jnp.dot(hid, w2_ref[...], preferred_element_type=jnp.float32)
        out_ref[...] = jax.nn.sigmoid(o + b2_ref[...])

    return kernel


def score_pipeline_pallas(
    s: jnp.ndarray,  # (B, Kp) gathered masked scores, B % tile_b == 0
    m: jnp.ndarray,  # (B, Kp) float32 gathered validity
    cls: jnp.ndarray,  # (B, Kp) int32 gathered clipped classes
    bx: jnp.ndarray,  # (4, B, Kp) gathered normalized box coordinate planes
    w1_box: jnp.ndarray,  # (7 + C, Kp, Hp) W1 rows regrouped per box feature
    mu_box: jnp.ndarray,  # (7 + C, Kp)  zero-padded
    sigma_box: jnp.ndarray,  # (7 + C, Kp)  one-padded
    w1_glob: jnp.ndarray,  # (Gp, Hp) W1 rows of the global columns
    mu_glob: jnp.ndarray,  # (1, Gp)  zero-padded
    sigma_glob: jnp.ndarray,  # (1, Gp)  one-padded
    b1: jnp.ndarray,  # (1, Hp)
    w2: jnp.ndarray,  # (Hp, 128)  col 0 = real weights
    b2: jnp.ndarray,  # (1, 128)
    num_classes: int,
    top_k: int,  # live box slots (Kp may pad past it)
    tile_b: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    B, Kp = s.shape
    T, _, Hp = w1_box.shape
    Gp = w1_glob.shape[0]
    row = pl.BlockSpec((tile_b, Kp), lambda i: (i, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    return pl.pallas_call(
        _make_kernel(num_classes, top_k),
        grid=(B // tile_b,),
        in_specs=[
            row,
            row,
            row,
            pl.BlockSpec((4, tile_b, Kp), lambda i: (0, i, 0)),
            whole((T, Kp, Hp)),
            whole((T, Kp)),
            whole((T, Kp)),
            whole((Gp, Hp)),
            whole((1, Gp)),
            whole((1, Gp)),
            whole((1, Hp)),
            whole((Hp, 128)),
            whole((1, 128)),
        ],
        out_specs=pl.BlockSpec((tile_b, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 128), jnp.float32),
        interpret=interpret,
    )(s, m, cls, bx, w1_box, mu_box, sigma_box, w1_glob, mu_glob, sigma_glob,
      b1, w2, b2)
