"""The client side of a run: submits a plan's requests to the engine,
drives ``Engine.step_once``, and records what each step did.

Only the benchmark's own calls are timed and annotated; nothing inside
the program is wrapped.  Each step record holds the step's wall time,
whether it was a mixed (prefill + decode) step, the context length of
every decode row, and the offset and length of every prompt chunk (and
whether it was the prompt's last),
read from the public fields of the engine's requests before and after
the step.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Backend compile seconds, stamped on the wall clock."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.events: List[Tuple[float, float, str]] = []
        self._clock = clock
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((self._clock(), secs,
                                str(kw.get("fun_name", "?"))))

    def names(self, t0: float, t1: float) -> List[str]:
        """The programs compiled (or loaded from the cache) in [t0, t1]."""
        return [n for t, _, n in self.events if t0 <= t <= t1]


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    mixed: bool
    decode_ctx: List[int]               # KV tokens each decode row reads
    chunks: List[Tuple[int, int, bool]]  # (offset, length, last) a chunk


@dataclasses.dataclass
class Sent:
    """One request as the client sent it."""

    index: int                          # position in the plan
    due: float                          # when it was due (clock seconds)
    sent: float                         # when submit() was called
    req: Any = None                     # the engine's Request; None: refused


class Driver:
    """Closed or open loop over one engine, on one wall clock."""

    def __init__(self, eng, plan, clock: Callable[[], float] =
                 time.perf_counter):
        self.eng, self.plan, self.clock = eng, plan, clock
        self.sent: List[Sent] = []
        self.inflight: Dict[int, Sent] = {}
        self.steps: List[StepRec] = []
        self.submitting = True
        self.t_start: Optional[float] = None
        self.annotate = False

    def _span(self, name: str):
        return (jax.profiler.TraceAnnotation(name) if self.annotate
                else contextlib.nullcontext())

    # -- sending --------------------------------------------------------------
    def start(self) -> None:
        self.t_start = self.clock()

    def _send(self, due: float) -> None:
        from repro.paging import PagingError
        i = len(self.sent)
        if i >= len(self.plan):
            raise RuntimeError("the traffic plan ran out of requests")
        plan = self.plan
        now = self.clock()
        try:
            rid = self.eng.submit(plan.prompt(i),
                                  max_new_tokens=int(plan.output_len[i]))
        except PagingError:
            self.sent.append(Sent(i, due, now))
            return
        req = self.eng.queue[-1]
        assert req.rid == rid
        s = Sent(i, due, now, req)
        self.sent.append(s)
        self.inflight[rid] = s

    def next_due(self) -> Optional[float]:
        if self.plan.loop != "open":
            return None
        return self.t_start + float(self.plan.offsets[len(self.sent)])

    def send_due(self) -> None:
        if not self.submitting:
            return
        with self._span("bench/submit"):
            if self.plan.loop == "closed":
                while len(self.inflight) < self.plan.concurrency:
                    self._send(self.clock())
            else:
                while self.next_due() <= self.clock():
                    self._send(self.next_due())

    # -- stepping -------------------------------------------------------------
    def iterate(self) -> bool:
        """Send what is due, then run one engine step if there is work.
        Returns False when there was nothing to do."""
        self.send_due()
        eng = self.eng
        if eng.drained:
            due = self.next_due() if self.submitting else None
            if due is None:
                return False
            with self._span("bench/wait"):
                time.sleep(max(0.0, min(due - self.clock(), 0.005)))
            return True
        before = [(s.req, s.req.prefill_pos, len(s.req.generated))
                  for s in self.inflight.values()]
        mixed0 = eng.stats["mixed_steps"]
        with self._span("bench/step"):
            t0 = self.clock()
            eng.step_once()
            t1 = self.clock()
        ctx, chunks = [], []
        for req, pp, ng in before:
            if req.prefill_pos > pp:
                chunks.append((pp, req.prefill_pos - pp,
                               req.prefill_pos >= req.target_len))
            if ng >= 1 and len(req.generated) > ng:
                ctx.append(len(req.prompt) + ng)
        self.steps.append(StepRec(t0, t1, eng.stats["mixed_steps"] > mixed0,
                                  ctx, chunks))
        for rid in [r for r in self.inflight if r in eng.finished]:
            del self.inflight[rid]
        return True

    def run_until(self, done: Callable[[], bool], cap_s: float) -> bool:
        """Iterate until ``done()``; False if ``cap_s`` passed first."""
        t_end = self.clock() + cap_s
        while not done():
            if self.clock() > t_end:
                return False
            if not self.iterate() and not done():
                return False
        return True


def warm_compile(eng, vocab: int, chunk: int, seed: int) -> None:
    """Compile the decode and mixed steps and the graduation path: two
    prompts of more than one chunk, served to the end."""
    rng = np.random.default_rng([seed, 2])
    for n in (chunk + chunk // 2, chunk + 3):
        eng.submit(rng.integers(0, vocab, n).astype(np.int32),
                   max_new_tokens=4)
    for _ in range(1000):
        if eng.drained:
            return
        eng.step_once()
    raise RuntimeError("warm-up requests did not drain")


# -- what the window measured -------------------------------------------------

def window_tokens(sent: List[Sent], w0: float, w1: float) -> int:
    return sum(1 for s in sent if s.req is not None
               for t in s.req.token_ts if w0 < t <= w1)


def inter_token_gaps(sent: List[Sent], w0: float, w1: float) -> np.ndarray:
    """Every gap between consecutive tokens of one request that ends in
    the window."""
    out = []
    for s in sent:
        if s.req is None:
            continue
        ts = np.asarray(s.req.token_ts)
        if len(ts) < 2:
            continue
        gaps = np.diff(ts)
        ends = ts[1:]
        out.append(gaps[(ends > w0) & (ends <= w1)])
    return np.concatenate(out) if out else np.zeros(0)


def time_to_first_token(sent: List[Sent], w0: float, w1: float) \
        -> np.ndarray:
    """TTFT from the due time of every request due in the window; one
    with no first token by the close counts its wait so far."""
    out = []
    for s in sent:
        if not (w0 <= s.due < w1) or s.req is None:
            continue
        first = s.req.token_ts[0] if s.req.token_ts else None
        if first is None or first > w1:
            out.append(w1 - s.due)
        else:
            out.append(first - s.due)
    return np.asarray(out)


def due_in_window(sent: List[Sent], w0: float, w1: float) -> List[Sent]:
    return [s for s in sent if w0 <= s.due < w1]


def failed(s: Sent) -> bool:
    """Refused by the engine, or ended with fewer tokens than asked."""
    return s.req is None or (bool(s.req.done_t) and not s.req.done)
