"""Wall milliseconds per 1000 frames decided in the fleet's per-district
decisions and dispatch: the program's own ``DispatchProfiler`` phase
``fleet.decide_dispatch`` inside ``FleetRuntime.step``."""


def read(ctx):
    s = ctx.layer_seconds.get("fleet_decide")
    if not s or not ctx.frames_decided:
        return None
    return s * 1e6 / ctx.frames_decided
