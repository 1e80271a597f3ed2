"""Wall milliseconds per 1000 frames decided in ``engine.features`` (the
feature kernel and its host copies), from the harness's
``bench.featurize`` span."""


def read(ctx):
    s = ctx.layer_seconds.get("featurize")
    if not s or not ctx.frames_decided:
        return None
    return s * 1e6 / ctx.frames_decided
