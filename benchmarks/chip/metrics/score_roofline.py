"""Scoring's share of its roofline inside the traced part of the window,
in percent: the least time the chip needs for the scoring calls made there
(their algorithmic operations and bytes at the chip's peaks, from the
cell's shapes alone, whatever kernels implement them) over the device time
of the scoring programs."""
from core import scoring
from roofline import cost


def read(ctx):
    s = scoring.device_seconds(ctx)
    if not s or not ctx.scoring_calls:
        return None
    cfg = ctx.cfg
    peak = cost.peaks(ctx.devices[0].device_kind)
    shape = (int(cfg["max_dets"]), int(cfg["top_k"]), int(cfg["num_classes"]), int(cfg["hidden"]))
    least = 0.0
    for rows, chips in ctx.scoring_calls:
        one = cost.least_seconds(cost.call_flops(rows, *shape), cost.call_bytes(rows, *shape), peak)
        least += chips * one["seconds"]
    return 100.0 * least / s
