"""Frames decided per second: every frame due in the window, over the
window's seconds or, where the last of them returned after the window
closed, over the time until it did (all the work over all its time)."""


def read(ctx):
    return ctx.frames_decided / max(ctx.seconds, ctx.window_end)
