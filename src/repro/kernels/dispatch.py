"""The one kernel-path rule: every kernel in this package, and the video
tracker, resolves its ``interpret=`` / ``path=`` argument to one of:

``"compiled"``
    The real Pallas lowering — TPU (and GPU triton).
``"interpret"``
    The Pallas interpreter: one Python step per grid cell.  Correctness
    testing of the kernel semantics on hosts without a compiled lowering.
``"reference"``
    The pure-jnp oracle under ``jax.jit`` — the fastest *correct* path on
    CPU, and trivially shard-safe (elementwise/per-row math, no grid-shape
    compilation regimes).

``resolve_path(None)`` picks ``"reference"`` on CPU (where the interpreter
is slower than the jnp oracle) and ``"compiled"`` everywhere else;
``True`` → interpreter, ``False`` → compiled.  The resolution is
deterministic per process — no runtime autotuning — because the fleet
plane's bit-identity contract compares results across processes.
"""
from __future__ import annotations

from typing import Union

import jax

KERNEL_PATHS = ("compiled", "interpret", "reference")


def resolve_path(interpret: Union[None, bool, str] = None) -> str:
    """Resolve an ``interpret=`` argument to one of :data:`KERNEL_PATHS`.

    ``None`` → ``"reference"`` on CPU, ``"compiled"`` on TPU/GPU;
    ``True``/``False`` → ``"interpret"``/``"compiled"``; a path string
    passes through (validated).
    """
    if interpret is None:
        return "reference" if jax.default_backend() == "cpu" else "compiled"
    if isinstance(interpret, str):
        if interpret not in KERNEL_PATHS:
            raise ValueError(
                f"unknown kernel path {interpret!r}; use one of {KERNEL_PATHS}"
            )
        return interpret
    return "interpret" if interpret else "compiled"
