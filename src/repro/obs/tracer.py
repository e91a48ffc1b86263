"""Tracer: structured spans/instants/counter samples on one clock.

Every event carries a ``(pid, tid)`` track pair — process/thread names
in the Chrome-trace sense — and a timestamp from the *injected* clock:
the engine's clock, which is its own :class:`VirtualClock` by default
(the pager's simulated AMU backend advances in lockstep, so AMU
transfer spans, pager actions and request lifecycle spans share one
deterministic time axis) or the wall clock a deployment injects.

:meth:`Tracer.span` times host work as it happens (the engine's step
phases, its frame transfers).  With ``profile`` set it also enters a
``jax.profiler.TraceAnnotation`` named ``<pid>/<name>``, so the span
lands on the host plane of a running profiler trace, on the clock the
device's events are read against.  ``profile`` alone, with the tracer
otherwise disabled, writes only those annotations: the pager's and the
requests' events stay off.
:meth:`~Tracer.complete` and :meth:`~Tracer.instant` never reach the
profiler: their timestamps may be virtual.

Design constraints from the issue:

  * **default-off-cheap** — every method starts with one attribute test
    (``if not self.enabled: return``); hot call sites additionally guard
    with ``if tracer.enabled:`` before building an args dict, so a
    disabled tracer costs one branch and zero allocations,
  * **allocation-light when on** — events are plain tuples appended to
    one list; no per-event objects, no string formatting until export,
  * **well-formed spans** — ``begin`` returns a span id tracked in
    ``open_spans`` until ``end`` pops it, so tests (and the exporter)
    can assert every open span closes; ``span`` is a context manager,
    so its spans nest and record the id of the span they opened in.

Event tuple layout: ``(ph, pid, tid, name, ts, dur_or_value, args)``
with ``ph`` one of ``"X"`` (complete span), ``"i"`` (instant), ``"C"``
(counter sample).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = ["Tracer", "NULL_TRACER"]

Event = Tuple[str, str, str, str, float, float, Optional[dict]]


def _zero_clock() -> float:
    return 0.0


class _NullSpan:
    """What a disabled tracer's :meth:`Tracer.span` returns: one shared
    object whose enter and exit do nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One :meth:`Tracer.span`: while ``enabled``, an ``X`` event on the
    tracer's clock with args ``id`` and ``parent`` (the id of the span
    open around it, 0 at the top); while ``profile``, a profiler
    annotation."""

    __slots__ = ("_tr", "_pid", "_name", "_args", "_sid", "_parent",
                 "_t0", "_ann")

    def __init__(self, tr: "Tracer", pid: str, name: str,
                 args: dict) -> None:
        self._tr, self._pid, self._name, self._args = tr, pid, name, args

    def __enter__(self) -> "_Span":
        tr = self._tr
        self._sid = 0
        if tr.enabled:
            self._sid = sid = tr._next_sid
            tr._next_sid = sid + 1
            stack = tr._stack
            self._parent = stack[-1] if stack else 0
            stack.append(sid)
        self._ann = None
        if tr.profile:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation(f"{self._pid}/{self._name}",
                                        **self._args)
            self._ann.__enter__()
        if self._sid:
            self._t0 = tr.clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        if self._sid:
            t1 = tr.clock()
            tr._stack.pop()
            tr._append(("X", self._pid, "host", self._name, self._t0,
                        max(0.0, t1 - self._t0),
                        {**self._args, "id": self._sid,
                         "parent": self._parent}))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    __slots__ = ("enabled", "profile", "clock", "events", "open_spans",
                 "_next_sid", "_append", "_last_counter", "_stack")

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 enabled: bool = True) -> None:
        self.enabled = enabled
        #: :meth:`span` also annotates the profiler's trace
        #: (``jax.profiler.TraceAnnotation``); on its own, without
        #: ``enabled``, nothing else is recorded or kept in memory
        self.profile = False
        self.clock = clock if clock is not None else _zero_clock
        self.events: List[Event] = []
        # bound once: the hot emission paths run per simulated transfer,
        # so one attribute lookup per event is worth saving
        self._append = self.events.append
        #: (pid, name) -> last emitted counter value, for sample dedup
        self._last_counter: Dict[Tuple[str, str], float] = {}
        #: sid -> (pid, tid, name, t0, args) for spans begun but not ended
        self.open_spans: Dict[int, Tuple[str, str, str, float,
                                         Optional[dict]]] = {}
        self._next_sid = 1
        self._stack: List[int] = []             # ids of open span()s

    # -- emission -------------------------------------------------------------

    def instant(self, pid: str, tid: str, name: str,
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        self._append(("i", pid, tid, name, self.clock(), 0.0, args))

    def counter(self, pid: str, name: str, value: float) -> None:
        """One sample of a counter track (e.g. per-QoS window occupancy);
        rendered as a stepped area chart in Perfetto.  Samples equal to
        the track's previous value are dropped — a stepped chart renders
        identically, and periodic samplers (the pager polls
        ``free_frames`` every tick) stop flooding the trace."""
        if not self.enabled:
            return
        v = float(value)
        key = (pid, name)
        if self._last_counter.get(key) == v:
            return
        self._last_counter[key] = v
        self._append(("C", pid, name, name, self.clock(), v, None))

    def begin(self, pid: str, tid: str, name: str,
              args: Optional[dict] = None) -> int:
        """Open a span at ``clock()``; returns a span id for :meth:`end`
        (0 when disabled — ``end(0)`` is a no-op, so call sites need no
        branch)."""
        if not self.enabled:
            return 0
        sid = self._next_sid
        self._next_sid = sid + 1
        self.open_spans[sid] = (pid, tid, name, self.clock(), args)
        return sid

    def end(self, sid: int, args: Optional[dict] = None) -> None:
        if not sid:
            return
        ent = self.open_spans.pop(sid, None)
        if ent is None:
            return
        pid, tid, name, t0, a0 = ent
        if args:
            a0 = {**a0, **args} if a0 else dict(args)
        self._append(("X", pid, tid, name, t0,
                      max(0.0, self.clock() - t0), a0))

    def span(self, pid: str, name: str, **args: Any):
        """Context manager timing the host work inside it.  While
        ``enabled``: an ``X`` event on track ``(pid, "host")`` with
        ``args`` plus its own ``id`` and its enclosing span's ``parent``
        id.  While ``profile``: the profiler annotation ``<pid>/<name>``
        (args as its stats).  With neither: the one shared no-op span,
        so a call site pays one branch::

            with tracer.span("engine", "step"):
                ...
        """
        if not (self.enabled or self.profile):
            return _NULL_SPAN
        return _Span(self, pid, name, args)

    def complete(self, pid: str, tid: str, name: str, t0: float,
                 t1: Optional[float] = None,
                 args: Optional[dict] = None) -> None:
        """Record a span whose start time is already known (e.g. an AMU
        request's ``issue_t`` at retire time) without open-span tracking."""
        if not self.enabled:
            return
        if t1 is None:
            t1 = self.clock()
        self._append(("X", pid, tid, name, t0,
                      max(0.0, t1 - t0), args))

    def flush_open(self, args: Optional[dict] = None) -> int:
        """Close any spans still open (e.g. requests in flight when the
        run stops); returns how many were force-closed."""
        dangling = list(self.open_spans)
        for sid in dangling:
            self.end(sid, args)
        return len(dangling)


#: Shared disabled tracer: instrumented code holds a tracer attribute
#: unconditionally and pays one `enabled` branch when telemetry is off.
NULL_TRACER = Tracer(enabled=False)
