#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one engine, the cell's traffic at
a list of fixed rates, one JSON line per rate.

    python3 bench/sweep.py --workload phi4-code-open --rates 2,2.5,3 \\
        --seconds 30

For each rate: the traffic runs ``warmup.min_s`` seconds, then a window
of ``--seconds``; the line gives the offered and completed request
rates, tokens per second, the median and 90th percentile of time to
first token, and the backlog (requests sent with no first token) at the
window's start and end.  A backlog that grows through the window means
the rate is above what the engine sustains.  The engine is drained
between rates.  The knee is chosen once by hand from these lines and
written into the traffic file as its fixed ``rate``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                str(Path(__file__).resolve().parent.parent)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np
    from bench import loop, run, spec, traffic
    from repro.launch.mesh import make_mesh_compat

    cell = spec.load_cell(args.workload)
    devices = run.chips_or_fail(cell.chips)
    run.use_compile_cache()
    mcfg = run.model_config(cell.config)
    mesh = make_mesh_compat((1, len(devices)), ("data", "model"),
                            devices=devices)
    params = run.make_params(cell.config, mcfg, mesh, args.seed)
    eng = run.make_engine(cell, mcfg, params, mesh)
    loop.warm_compile(eng, mcfg.vocab_size, int(cell.engine["chunk_tokens"]),
                      args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.make_plan(dict(cell.traffic, rate=rate),
                                 mcfg.vocab_size, args.seed)
        d = loop.Driver(eng, plan)
        d.start()
        d.run_until(lambda: d.clock() - d.t_start >=
                    float(cell.traffic["warmup"].get("min_s", 10)), 600)

        def backlog():
            return sum(1 for s in d.sent
                       if s.req is not None and not s.req.token_ts)
        b0, w0 = backlog(), d.clock()
        d.run_until(lambda: d.clock() - w0 >= args.seconds, 600)
        w1, b1 = d.clock(), backlog()
        ttft = loop.time_to_first_token(d.sent, w0, w1)
        due = loop.due_in_window(d.sent, w0, w1)
        done = [s for s in d.sent if s.req is not None and s.req.done_t
                and w0 < s.req.done_t <= w1]
        print(json.dumps({
            "rate": rate, "offered_req_s": len(due) / (w1 - w0),
            "completed_req_s": len(done) / (w1 - w0),
            "tok_s": loop.window_tokens(d.sent, w0, w1) / (w1 - w0),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "backlog_start": b0, "backlog_end": b1,
            "mixed_steps": sum(s.mixed for s in d.steps
                               if w0 <= s.t0 < w1),
            "steps": sum(1 for s in d.steps if w0 <= s.t0 < w1)}),
            flush=True)
        d.submitting = False
        d.run_until(lambda: eng.drained, 600)
        time.sleep(0.1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
