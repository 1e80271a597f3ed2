"""Share of the traced window in which no operation ran on the device:
one minus the union of device-operation intervals over the window, mean
over the chips used."""


def read(ctx):
    t = ctx.trace
    if not t or not t["window_s"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
