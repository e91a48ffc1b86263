"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time, time per kernel, exposed collective time, and
the idle gaps with what the host was doing in them.

The trace is the JAX profiler's ``.xplane.pb``, read with
``jax.profiler.ProfileData``.  A device is a plane named
``/device:TPU:<n>``; its operations are the events of its ``XLA Ops``
line, named by their HLO instruction.  The host's spans are the
benchmark's own ``TraceAnnotation``s (names starting ``bench/``) on the
host plane.  The traced window is the
``bench/window`` span.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"      # host side of a launch
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter", re.I)

# operations that only hold others (the layer scan's while loop): left
# out of the ranking of operations, whose bodies are listed themselves
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")

Interval = Tuple[float, float, str]         # start ns, end ns, name


def op_name(event_name: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction
    (``%paged_decode_attention.6 = bf16[...] custom-call(...)``); keep
    the instruction's own name (``paged_decode_attention.6``)."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]          # device id -> its operations
    spans: List[Interval]                   # bench/ spans on the host
    window: Tuple[float, float]             # the traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clip(self, ivs: Iterable[Interval]) -> List[Interval]:
        a, b = self.window
        return [(max(s, a), min(e, b), n) for s, e, n in ivs
                if e > a and s < b]


def find_xplane(root: str) -> str:
    files = sorted(glob.glob(f"{root}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def load(path: str) -> Trace:
    """Device operations (shifted onto the host's clock, see
    :func:`clock_offset`) and the bench's host spans of a trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[float]] = {}
    spans: List[Interval] = []
    launches: List[float] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     op_name(e.name)) for e in line.events)
            elif m and line.name == MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(
                    e.start_ns for e in line.events)
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith("bench/"):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
                    elif e.name == LAUNCH and line.name != "python3":
                        launches.append(e.start_ns)
    for d in ops:
        dt = clock_offset(launches, modules.get(d, []))
        ops[d] = [(a + dt, b + dt, n) for a, b, n in ops[d]]
    return from_events(ops, spans)


def clock_offset(launches: List[float], starts: List[float]) -> float:
    """Nanoseconds to add to a device's times to put them on the host's
    clock.  The profiler's device and host clocks differ by about a
    millisecond; a program cannot start on the device before the host
    launched it, so with one launch per program the shift is the largest
    launch-to-start lead.  0 where launches and programs do not pair."""
    launches, starts = sorted(launches), sorted(starts)
    if not starts or len(launches) != len(starts):
        return 0.0
    return max(0.0, max(a - b for a, b in zip(launches, starts)))


def from_events(ops: Dict[int, List[Interval]],
                spans: List[Interval]) -> Trace:
    win = [s for s in spans if s[2] == "bench/window"]
    if win:
        window = (win[0][0], win[0][1])
    else:
        allops = [iv for v in ops.values() for iv in v]
        window = (min(s for s, _, _ in allops), max(e for _, e, _ in allops))
    return Trace(ops={d: sorted(v) for d, v in ops.items()},
                 spans=sorted(s for s in spans if s[2] != "bench/window"),
                 window=window)


def union(ivs: Iterable[Interval]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e, _ in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def _length(segs) -> float:
    return sum(b - a for a, b in segs)


def busy_s(tr: Trace, device: int) -> float:
    return _length(union(tr.clip(tr.ops.get(device, [])))) * 1e-9


def mean_busy_s(tr: Trace) -> float:
    return float(np.mean([busy_s(tr, d) for d in tr.ops])) if tr.ops \
        else 0.0


def op_seconds(tr: Trace, pattern: str) -> float:
    """Summed device time of the operations whose name matches
    ``pattern``, over every device."""
    rx = re.compile(pattern)
    return sum(e - s for v in tr.ops.values()
               for s, e, n in tr.clip(v) if rx.search(n)) * 1e-9


def _subtract(segs, cover) -> float:
    """Length of ``segs`` not covered by the sorted disjoint ``cover``."""
    total, j = 0.0, 0
    for a, b in segs:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            total += b - cur
    return total


def exposed_collective_s(tr: Trace, device: int) -> Tuple[float, float]:
    """(collective time with no other operation running, busy time) on
    ``device``, seconds."""
    ivs = tr.clip(tr.ops.get(device, []))
    coll = union(iv for iv in ivs if COLLECTIVE.search(iv[2]))
    other = union(iv for iv in ivs if not COLLECTIVE.search(iv[2]))
    return _subtract(coll, other) * 1e-9, _length(union(ivs)) * 1e-9


def device_ops(tr: Trace, top: int = 10) -> List[List]:
    """The operations that took most device time, summed over devices
    and divided by their number."""
    tot: Dict[str, float] = defaultdict(float)
    for v in tr.ops.values():
        for s, e, n in tr.clip(v):
            if not CONTAINER.match(n):
                tot[n] += (e - s) * 1e-9
    n_dev = max(1, len(tr.ops))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / n_dev] for n, t in ranked]


def _label(span: Interval, a: float, b: float,
           busy: List[Tuple[float, float]]) -> str:
    """What the host was doing in the idle stretch [a, b) of ``span``;
    inside a step, whether the device had not yet started the step's
    work, had finished it, or was between programs."""
    s, e, n = span
    if n != "bench/step":
        return n
    before = any(x < a and y > s for x, y in busy)
    after = any(y > b and x < e for x, y in busy)
    where = ("between device work" if before and after else
             "before device work" if after else
             "after device work" if before else "no device work")
    return f"bench/step: {where}"


def idle_gaps(tr: Trace, device: int = 0, top: int = 10) -> List[List]:
    """Idle time on ``device`` in the window, split by the bench span
    open on the host and summed by what the host was doing, largest
    first."""
    busy = union(tr.clip(tr.ops.get(device, [])))
    a, b = tr.window
    gaps, cur = [], a
    for x, y in busy:
        if x > cur:
            gaps.append((cur, x))
        cur = max(cur, y)
    if cur < b:
        gaps.append((cur, b))
    tot: Dict[str, float] = defaultdict(float)
    for ga, gb in gaps:
        covered = 0.0
        for span in tr.spans:
            lo, hi = max(ga, span[0]), min(gb, span[1])
            if hi > lo:
                tot[_label(span, lo, hi, busy)] += (hi - lo) * 1e-9
                covered += hi - lo
        if gb - ga > covered:
            tot["no bench span"] += (gb - ga - covered) * 1e-9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:top]


def describe(path: str, top: int = 40) -> str:
    """Planes, lines and the most frequent event names of a trace: what
    to look at before trusting the reduction on a new backend."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            names: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
            for e in line.events:
                names[e.name][0] += 1
                names[e.name][1] += e.duration_ns * 1e-9
            out.append(f"{plane.name} | {line.name} | {len(names)} names")
            for n, (c, t) in sorted(names.items(),
                                    key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {t:10.6f}s {c:7d}x {n[:160]}")
    return "\n".join(out)
