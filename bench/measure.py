"""What a metric reader is given, and how the harness calls readers.

Every metric, end-to-end or per-layer, is a file ``bench/metrics/<name>.py``
with one function ``read(ctx) -> float | None``.  A reader that finds
nothing to read returns None and the metric is left out of the result.
Kernel work is counted by ``bench/roofline/<kernel>.py``: its
``TRACE_NAME`` (a pattern of the kernel's operation names in the device
trace) and ``work(ctx) -> (flops, bytes)`` over the window's steps.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from bench import spec as spec_mod
from bench import trace as trace_mod
from bench import weights as W


@dataclasses.dataclass
class Ctx:
    cell: Any                         # spec.Cell
    peaks: Dict[str, float]
    chips: int
    t_start: float                    # process start, host clock
    w0: float                         # window opens
    w1: float                         # window closes
    sent: List[Any]                   # loop.Sent records
    steps: List[Any]                  # loop.StepRec of the window's steps
    stats: Dict[str, int]             # engine counters, change in window
    pager_bytes: int                  # far-tier traffic in the window
    memory_peak_bytes: int
    trace: Optional[trace_mod.Trace] = None

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    @property
    def dims(self) -> Dict[str, int]:
        return W.dims(self.cell.config)

    def roofline(self, kernel: str) -> Optional[float]:
        """Share (0-1) of the roofline the kernel reached in the traced
        window: the least time its work needs at the chip's peaks over
        its summed device time.  None where it did not run."""
        if self.trace is None or not self.peaks:
            return None
        mod = spec_mod.load_module("roofline", kernel)
        t = trace_mod.op_seconds(self.trace, mod.TRACE_NAME)
        flops, nbytes = mod.work(self)
        if t <= 0 or flops <= 0:
            return None
        least = max(flops / self.peaks["bf16_flops"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return least / t


def read_metrics(ctx: Ctx, metrics: List[Dict[str, Any]]) \
        -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        v = spec_mod.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
