"""The chip under the benchmark: finding it, its compile cache, its memory,
and the programs JAX builds and the jits it retraces while a window runs."""
from __future__ import annotations

import os
from typing import List

import jax

from repro.obs import jit_stats


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> List:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) < n:
        raise NoChip(f"needs {n} TPU chips; JAX found {len(devices)}")
    return devices[:n]


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when that is set, else at a fixed ``.jax_cache`` in the checkout; every
    program is kept, however quick its compile, so a second run of a cell
    compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class CompileCounter:
    """Between ``start`` and ``stop``: programs JAX builds (compiled, or
    read from the persistent cache), one ``backend_compile_duration`` event
    each, and retraces of the program's registered jits, the growth of
    their caches that ``repro_jit_retraces_total`` exports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.on = False
        self.count = 0
        self.seconds = 0.0
        self.retraces = 0
        self._jits = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def start(self) -> None:
        self._jits = jit_stats.snapshot()
        self.on = True

    def stop(self) -> None:
        self.on = False
        grown = jit_stats.delta(self._jits, jit_stats.snapshot())
        self.retraces = sum(traces for traces, _ in grown.values())

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT and self.on:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
