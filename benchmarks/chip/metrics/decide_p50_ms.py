"""Median over every frame due in the window of decision return minus due
time, in milliseconds."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latency_s, 50)) * 1e3
