"""80th percentile of time to first token over the requests due in the
window, timed from when each was due; a request with no first token by
the close enters at its wait so far."""

import numpy as np

from bench.loop import time_to_first_token


def read(ctx):
    t = time_to_first_token(ctx.sent, ctx.w0, ctx.w1)
    return float(np.percentile(t, 80)) * 1e3 if len(t) else None
