"""95th percentile of every gap between consecutive tokens of one
request, over all requests, for the gaps that end in the window."""

import numpy as np

from bench.loop import inter_token_gaps


def read(ctx):
    gaps = inter_token_gaps(ctx.sent, ctx.w0, ctx.w1)
    return float(np.percentile(gaps, 95)) * 1e3 if len(gaps) else None
