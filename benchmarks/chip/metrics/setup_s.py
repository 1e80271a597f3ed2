"""Seconds from the process's start to the window's start: JAX start-up,
the pool, the weights, calibration and every program the window uses."""


def read(ctx):
    return ctx.setup_s
