"""Host spans the harness records around its calls into each layer: wall
seconds summed per name, and, in a traced run, a
``jax.profiler.TraceAnnotation`` of the same name on the profiler's clock so
that device idle gaps can be put down to what the host was doing."""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict

import jax


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: "Spans", name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        if self.spans.annotate:
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.spans.seconds[self.name] += time.perf_counter() - self.t0
        if self.spans.annotate:
            self.ann.__exit__(*exc)
        return False


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.seconds: Dict[str, float] = defaultdict(float)

    def __call__(self, name: str) -> _Span:
        return _Span(self, name)

    def reset(self) -> None:
        self.seconds.clear()
