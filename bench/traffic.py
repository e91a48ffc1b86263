"""The one traffic generator: a traffic file's parameters to a plan of
requests, deterministically from ``--seed``.

Every seed sends the same schedule: the lengths and gaps are drawn once
from the traffic file's ``schedule_seed``, and ``--seed`` draws only the
prompts' token ids (and the weights).  With the order drawn from
``--seed`` instead, two seeds differed by which requests a window caught
(TTFT p90 spread 25% across seeds against 1% for one seed run twice),
so the seed changed the work.  Lengths and gaps come in stratified
blocks: each block of ``strata`` requests takes the distribution's
values at the midpoints of ``strata`` equal-probability bins, in a
shuffled order, and gaps are scaled so that each block's mean gap is
exactly ``1 / rate``.

The distributions are those of ``repro.serve.workload`` (Gamma gaps
with a stated coefficient of variation, lognormal lengths), with uniform
lengths added; here they are evaluated by their inverse distribution
functions.

A traffic file holds::

    {"loop": "closed", "concurrency": 24,            # or
     "loop": "open", "rate": 2.5, "arrivals": {"dist": "gamma", "cv": 2},
     "prompt": {"dist": "lognormal", "median": 1500, "sigma": 0.8,
                "lo": 64, "hi": 3840},
     "output": {"dist": "uniform", "lo": 8, "hi": 128},
     "strata": 16, "schedule_seed": 0, "warmup": {...}, "sample": {...}}
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np
from scipy import stats


def _mid_quantiles(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def inverse_cdf(dist: Dict[str, Any], q: np.ndarray) -> np.ndarray:
    """Values of ``dist`` at probabilities ``q`` (integers for lengths,
    seconds for gaps)."""
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = int(dist["lo"]), int(dist["hi"])
        return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi).astype(int)
    if kind == "lognormal":
        v = float(dist["median"]) * np.exp(
            float(dist["sigma"]) * stats.norm.ppf(q))
        return np.clip(np.round(v), int(dist["lo"]), int(dist["hi"])) \
            .astype(int)
    if kind == "gamma":
        # mean 1/rate, coefficient of variation cv (serve/workload.py)
        cv2 = float(dist["cv"]) ** 2
        return stats.gamma.ppf(q, 1.0 / cv2, scale=cv2 / float(dist["rate"]))
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: Dict[str, Any], n: int, strata: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` draws: blocks of the ``strata`` bin midpoints, each block
    in its own seeded order."""
    block = inverse_cdf(dist, _mid_quantiles(strata))
    if dist["dist"] == "gamma":
        block = block / block.mean() / float(dist["rate"])
    blocks = -(-n // strata)
    return np.concatenate([rng.permutation(block)
                           for _ in range(blocks)])[:n]


@dataclasses.dataclass
class Plan:
    """The requests of one run, in the order they are sent."""

    loop: str                       # "closed" | "open"
    prompt_len: np.ndarray          # (n,) int
    output_len: np.ndarray          # (n,) int
    offsets: Optional[np.ndarray]   # open loop: due time from the start, s
    concurrency: int
    vocab: int
    seed: int

    def __len__(self) -> int:
        return len(self.prompt_len)

    def prompt(self, i: int) -> np.ndarray:
        """Request ``i``'s prompt token ids, from the seed alone."""
        rng = np.random.default_rng([self.seed, 1, i])
        return rng.integers(0, self.vocab, int(self.prompt_len[i])) \
            .astype(np.int32)


def make_plan(traffic: Dict[str, Any], vocab: int, seed: int,
              n: int = 4096) -> Plan:
    """The request plan of a traffic file for ``seed``."""
    strata = int(traffic.get("strata", 16))
    rng = np.random.default_rng([int(traffic.get("schedule_seed", 0)), 0])
    plen = stratified(traffic["prompt"], n, strata, rng)
    olen = stratified(traffic["output"], n, strata, rng)
    offsets = None
    if traffic["loop"] == "open":
        arr = dict(traffic["arrivals"], rate=traffic["rate"])
        offsets = np.cumsum(stratified(arr, n, strata, rng))
    elif traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return Plan(loop=traffic["loop"], prompt_len=plen, output_len=olen,
                offsets=offsets,
                concurrency=int(traffic.get("concurrency", 0)),
                vocab=int(vocab), seed=int(seed))


def describe(plan: Plan, k: int = 64) -> List[str]:
    """A few lines on the plan's first ``k`` requests, for the log."""
    p, o = plan.prompt_len[:k], plan.output_len[:k]
    out = [f"prompt len p50 {np.median(p):.0f} [{p.min()}, {p.max()}], "
           f"output len p50 {np.median(o):.0f} [{o.min()}, {o.max()}]"]
    if plan.offsets is not None:
        out.append(f"offered rate {k / plan.offsets[k - 1]:.3f} req/s over "
                   f"the first {k} requests")
    return out
