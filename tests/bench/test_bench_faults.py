"""The harness catches a broken timed path: a whole run at the SMOKE
sizes, without the look for a chip, with a fault planted under the
engine, must come out not correct."""

import jax
import jax.numpy as jnp
import pytest

import bench.run as run_mod
from smoke_cell_bench import smoke_cell

SEED = 2**31 + 555


def _stale_decode(eng):
    """Decode steps return the state they were given: the new token's
    K/V is never written and the positions never advance."""
    step = eng._decode

    def decode(params, cache, toks):
        logits, _ = step(params, jax.tree_util.tree_map(jnp.copy, cache),
                         toks)
        return logits, cache
    eng._decode = decode


def _altered_token(eng):
    """Each request's third token is replaced where it is produced; the
    engine serves it and decodes on from it."""
    step, done = eng.step_once, set()

    def step_once():
        step()
        for req in list(eng.active.values()):
            if len(req.generated) == 3 and req.rid not in done:
                done.add(req.rid)
                req.generated[-1] = (req.generated[-1] + 257) % 512
    eng.step_once = step_once


@pytest.mark.parametrize("fault", [_stale_decode, _altered_token])
def test_fault_is_not_correct(monkeypatch, fault):
    make = run_mod.make_engine

    def faulty(*a, **kw):
        eng = make(*a, **kw)
        fault(eng)
        return eng
    monkeypatch.setattr(run_mod, "make_engine", faulty)
    cell = smoke_cell(warmup={"min_s": 0.5},
                      sample={"min_tokens": 60, "max_requests": 3,
                              "drain_s": 30})
    out = run_mod.run_cell(cell, SEED, 1.5, False)
    c = out["checks"]["max_logit_gap"]
    assert not out["correct"], c
    assert c["value"] > c["limit"]
