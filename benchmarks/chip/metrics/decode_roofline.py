"""The LM cascade's decode loops' share of their roofline in the traced
part of the window, in percent: the least time the chip needs for the
decode steps the traced blocks ran (each step's FLOPs and the bytes of
the weights and expected experts it touches and the latent cache it
reads, ``roofline/lm_cost.py``, at ``roofline/peaks.json``) over the
device time of the decode programs (``XLA Modules`` ``jit__decode``)."""
from roofline import cost, lm_cost


def read(ctx):
    t = ctx.trace
    if not t or not ctx.scoring_calls:
        return None
    s = t["modules"].get("jit__decode")
    calls = [c for c in ctx.scoring_calls if c[0] == "decode"]
    if not s or not calls:
        return None
    peak = cost.peaks(ctx.devices[0].device_kind)
    least = sum(lm_cost.decode_least_seconds(ctx.cfg, ctx.mix, *c[1:], peak) for c in calls)
    return 100.0 * least / s
