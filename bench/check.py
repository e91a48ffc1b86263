"""How ``correct`` is decided: served tokens against the plain reference.

After the window closes, a sample of the requests served in it is drawn
from the seed, always with the longest request that finished in the
window, and (where the traffic file asks) the request parked to the far
tier and resumed that served most tokens.  Sampled requests still
running are served on with no new traffic, for at most the traffic
file's ``drain_s`` seconds; a token that comes late is late, not wrong,
and every token served by then is compared.  The reference then runs
once over each prompt with its served tokens, and the number compared
is the widest gap by which a served token's reference logit lies below
the reference's best logit at its position.  Served tokens are greedy,
so a correct program only ever picks a token whose logit is within its
rounding of the best.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from bench.loop import Sent


def choose_sample(sent: Sequence[Sent], w0: float, w1: float,
                  sample: Dict[str, Any], seed: int) -> List[Sent]:
    """The requests whose served tokens are compared."""
    rng = np.random.default_rng([seed, 3])
    cands = [s for s in sent if s.req is not None
             and any(w0 < t <= w1 for t in s.req.token_ts)]
    if not cands:
        return []
    done = [s for s in cands if s.req.done_t and s.req.done_t <= w1
            and len(s.req.generated) >= s.req.max_new_tokens]

    def total(s):
        return len(s.req.prompt) + s.req.max_new_tokens

    def left(s):
        return s.req.max_new_tokens - len(s.req.generated)

    picks: List[Sent] = []

    def add(s):
        if s is not None and all(p is not s for p in picks):
            picks.append(s)

    add(max(done, key=total) if done else None)
    if sample.get("resumed"):
        resumed = [s for s in cands
                   if s.req.n_preempts > 0 and not s.req.parked]
        add(max(resumed, key=lambda s: len(s.req.generated))
            if resumed else None)
    rest = list(rng.permutation(len(done))) if done else []
    # then those nearest their end (or, with no time to serve them on,
    # those that served most)
    near = (left if float(sample.get("drain_s", 0)) > 0
            else (lambda s: -len(s.req.generated)))
    order = [done[i] for i in rest] + sorted(cands, key=near)
    for s in order:
        if sum(len(p.req.generated) for p in picks) >= \
                int(sample.get("min_tokens", 256)):
            break
        if len(picks) >= int(sample.get("max_requests", 8)):
            break
        add(s)
    return picks


def sequences(picks: Sequence[Sent]):
    """``(prompt + served tokens, prompt length)`` of each pick, copied
    off the engine's records."""
    return [(np.concatenate([np.asarray(s.req.prompt, np.int32),
                             np.asarray(s.req.generated, np.int32)]),
             len(s.req.prompt)) for s in picks if s.req.generated]


def widest_gap(results: Sequence[Dict[str, np.ndarray]],
               key: str = "served_gap") -> float:
    return float(max(float(np.max(r[key])) for r in results))
