"""Share of device busy time spent in collectives with no other
operation running beside them (bench/trace.py:exposed_collective_s),
taken on each device of the trace and averaged over the devices.

Operations that only hold others (the layer loop's ``while``) are no
cover: every all-reduce of a step runs inside that loop, so counted as
cover they would hide all of them.  None where the trace holds no
collective: a cell on one chip has none."""

from bench import trace


def read(ctx):
    tr = ctx.trace
    if tr is None or not any(trace.COLLECTIVE.search(n)
                             for v in tr.ops.values() for _, _, n in v):
        return None
    inner = trace.Trace(
        ops={d: [iv for iv in v if not trace.CONTAINER.match(iv[2])]
             for d, v in tr.ops.items()},
        spans=tr.spans, window=tr.window)
    shares = []
    for d in tr.ops:
        busy = trace.busy_s(tr, d)
        if busy > 0:
            shares.append(trace.exposed_collective_s(inner, d)[0] / busy)
    return sum(shares) / len(shares) if shares else None
