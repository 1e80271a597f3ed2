"""Wall milliseconds per 1000 frames decided inside the serve loop's call:
``OffloadSession.submit_batch`` (camera fleet) or ``FleetRuntime.step``
(city), from the harness's ``bench.submit`` / ``bench.fleet_step`` spans."""


def read(ctx):
    s = ctx.layer_seconds.get("serve")
    if not s or not ctx.frames_decided:
        return None
    return s * 1e6 / ctx.frames_decided
