"""Wall milliseconds per 1000 frames decided spent dispatching offloads
to the edges (``MultiEdgeDispatcher.dispatch``), from the harness's
``bench.dispatch`` span."""


def read(ctx):
    s = ctx.layer_seconds.get("dispatch")
    if not s or not ctx.frames_decided:
        return None
    return s * 1e6 / ctx.frames_decided
