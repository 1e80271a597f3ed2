"""Model FLOP utilization of the LM cascade's programs in the traced part
of the window, in percent: the model FLOPs of the weak passes, prefills
and decode loops the traced blocks ran (``roofline/lm_cost.py``, at their
real prompt and answer tokens, pads not counted) at the chip's bf16 peak,
over the device time of those programs (``XLA Modules`` named
``jit__weak_pass``, ``jit__prefill``, ``jit__decode``)."""
from roofline import cost, lm_cost


def read(ctx):
    t = ctx.trace
    if not t or not ctx.scoring_calls:
        return None
    s = sum(v for k, v in t["modules"].items() if k in lm_cost.PROGRAMS)
    if not s:
        return None
    peak = cost.peaks(ctx.devices[0].device_kind)
    flops = sum(lm_cost.call_flops(ctx.cfg, ctx.mix, *c) for c in ctx.scoring_calls)
    return 100.0 * flops / peak["flops_per_s"] / s
