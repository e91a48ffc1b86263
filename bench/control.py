#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: for each seed, one run
of the cell with a short window in which the float8 control stands in
the program's place (``run_cell(..., control=True)``), so ``correct``
judges the control's picks against the limit and must read false.  Each
line also gives the widest gap of the program's own served tokens in
that run.  All seeds run in one process.

    python3 bench/control.py --workload phi4-conv-overrun \\
        --seeds 11,12,13 --seconds 10

Prints one JSON line per seed.  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent / "src"),
                str(Path(__file__).resolve().parent.parent)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    args = ap.parse_args(argv)

    from bench import run, spec
    cell = spec.load_cell(args.workload)
    devices = run.chips_or_fail(cell.chips)
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = run.run_cell(cell, seed, args.seconds, False, devices=devices,
                           control=True, t_start=t0)
        gap = out["checks"]["max_logit_gap"]
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_gap": gap["value"],
                          "program_gap": out.get("program_gap"),
                          "limit": gap["limit"],
                          "seconds": time.perf_counter() - t0}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
