"""jit'd wrappers: pad to tile multiples, transpose to the lane-aligned
``(..., 4, N)`` layout, call the Pallas kernel, crop.

``interpret=`` takes a kernel path as
:func:`repro.kernels.dispatch.resolve_path` defines it.

Tile sizes default per path (``None``): the compiled lowering keeps the
MXU-friendly 256-lane tiles; interpreter mode shrinks tiles to the padded
problem so small inputs stop paying for 256x256 zero-padding.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_path
from repro.kernels.iou_matrix.kernel import iou_matrix_batch_pallas, iou_matrix_pallas
from repro.kernels.iou_matrix.ref import iou_matrix_batch_ref, iou_matrix_ref
from repro.obs.jit_stats import register_jit

__all__ = [
    "iou_matrix",
    "iou_matrix_batch",
    "resolve_path",
]

_iou_ref_jit = register_jit("iou_matrix.ref", jax.jit(iou_matrix_ref))
_iou_batch_ref_jit = register_jit(
    "iou_matrix.batch_ref", jax.jit(iou_matrix_batch_ref)
)


def _ceil_to(n: int, multiple: int) -> int:
    return -(-max(n, 1) // multiple) * multiple


def _default_tile(n: int, compiled_tile: int, path: str) -> int:
    """Interpreter tiles shrink to the 128-padded problem (fewer wasted
    lanes, same few grid steps); the compiled lowering keeps full tiles."""
    if path == "interpret":
        return min(compiled_tile, _ceil_to(n, 128))
    return compiled_tile


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_m", "interpret"))
def _iou_matrix(a, b, tile_n, tile_m, interpret):
    N, M = a.shape[0], b.shape[0]
    Np = _ceil_to(N, tile_n)
    Mp = _ceil_to(M, tile_m)
    # pad with degenerate boxes (zero area -> IoU 0)
    a_p = jnp.zeros((Np, 4), a.dtype).at[:N].set(a)
    b_p = jnp.zeros((Mp, 4), b.dtype).at[:M].set(b)
    out = iou_matrix_pallas(a_p.T, b_p.T, tile_n, tile_m, interpret=interpret)
    return out[:N, :M]


register_jit("iou_matrix.pallas", _iou_matrix)


def iou_matrix(
    a: jnp.ndarray,  # (N, 4)
    b: jnp.ndarray,  # (M, 4)
    tile_n: Optional[int] = None,
    tile_m: Optional[int] = None,
    interpret: Union[None, bool, str] = None,
) -> jnp.ndarray:
    path = resolve_path(interpret)
    if path == "reference":
        return _iou_ref_jit(a, b)
    tile_n = _default_tile(a.shape[0], 256, path) if tile_n is None else tile_n
    tile_m = _default_tile(b.shape[0], 256, path) if tile_m is None else tile_m
    return _iou_matrix(a, b, tile_n, tile_m, path == "interpret")


@functools.partial(
    jax.jit, static_argnames=("tile_b", "tile_n", "tile_m", "interpret")
)
def _iou_matrix_batch(a, b, tile_b, tile_n, tile_m, interpret):
    B, K, M = a.shape[0], a.shape[1], b.shape[1]
    Bp = _ceil_to(B, tile_b)
    Kp = _ceil_to(K, tile_n)
    Mp = _ceil_to(M, tile_m)
    a_p = jnp.zeros((Bp, Kp, 4), a.dtype).at[:B, :K].set(a)
    b_p = jnp.zeros((Bp, Mp, 4), b.dtype).at[:B, :M].set(b)
    out = iou_matrix_batch_pallas(
        a_p.transpose(0, 2, 1), b_p.transpose(0, 2, 1),
        tile_b, tile_n, tile_m, interpret=interpret,
    )
    return out[:B, :K, :M]


register_jit("iou_matrix.batch_pallas", _iou_matrix_batch)


def iou_matrix_batch(
    a: jnp.ndarray,  # (B, K, 4) per-image boxes
    b: jnp.ndarray,  # (B, M, 4)
    tile_b: int = 8,
    tile_n: int = 128,
    tile_m: int = 128,
    interpret: Union[None, bool, str] = None,
) -> jnp.ndarray:
    """Per-image pairwise IoU, image i matched only against its own row:
    ``out[i] = iou(a[i], b[i])`` with shape (B, K, M)."""
    path = resolve_path(interpret)
    if path == "reference":
        return _iou_batch_ref_jit(a, b)
    return _iou_matrix_batch(a, b, tile_b, tile_n, tile_m, path == "interpret")
