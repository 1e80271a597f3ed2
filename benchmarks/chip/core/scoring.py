"""Shared by the scoring metrics: which device programs score frames.

Every program a window runs on the chip belongs to the scoring path (the
fused score pipeline, the feature kernel, the estimator MLP, the gathers
and pads around them, the sharded plane's ``shard_map`` body), so the
scoring device time is the time of every program in the trace's
``XLA Modules`` lines.
"""


def device_seconds(ctx):
    t = ctx.trace
    if not t:
        return None
    return sum(t["modules"].values()) or None
