"""Device milliseconds per 1000 frames scored inside the traced part of
the window: the summed durations of the scoring programs on every chip
used, from the profiler trace."""
from core import scoring


def read(ctx):
    s = scoring.device_seconds(ctx)
    if not s or not ctx.traced_frames:
        return None
    return s * 1e6 / ctx.traced_frames
