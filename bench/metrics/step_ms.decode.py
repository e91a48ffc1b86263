"""Median wall milliseconds of the window's decode-only engine steps,
timed around Engine.step_once by the benchmark."""

import numpy as np


def read(ctx):
    t = [s.t1 - s.t0 for s in ctx.steps if not s.mixed]
    return float(np.median(t)) * 1e3 if t else None
