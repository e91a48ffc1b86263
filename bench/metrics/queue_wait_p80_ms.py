"""80th percentile of the wait in the engine's queue before a slot
(``Request.admit_t - arrival_t``, on the engine's clock) over the
requests due in the window; a request with no slot by the close enters
at its wait so far.  Nothing where the engine stamps no admissions."""

import numpy as np

from bench.loop import due_in_window


def read(ctx):
    waits = []
    for s in due_in_window(ctx.sent, ctx.w0, ctx.w1):
        if s.req is None:
            continue
        if not hasattr(s.req, "admit_t"):
            return None
        t = s.req.admit_t
        if t is None or t > ctx.w1:
            t = ctx.w1
        waits.append(t - s.req.arrival_t)
    return float(np.percentile(waits, 80)) * 1e3 if waits else None
