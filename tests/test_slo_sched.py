"""SLO-aware scheduling tests: token exactness, starvation freedom,
goodput dominance, and the EngineConfig construction API.

The scheduler redesign's acceptance surface (PR 6):

  * whatever the SLO policy decides — EDF chunk ordering, batch-tier
    shedding, deadline-aware preemption onto QoS windows — the tokens
    generated must equal those of the same engine run without
    scheduling pressure, bit for bit (scheduling changes *when*, never
    *what*),
  * batch-tier requests are shed first under pressure but never starve:
    every admitted request completes,
  * under overload the SLO policy's interactive goodput must dominate
    watermark-FIFO's,
  * an ``EngineConfig`` is the one way to configure an ``Engine``: flat
    or unknown keywords raise ``TypeError``.
"""

import warnings

import jax
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.configs import get_smoke
from repro.models import init_params
from repro.paging import pages_for
from repro.serve import (ChunkingConfig, Engine, EngineConfig, PagingConfig,
                         SchedulerConfig, Tier, VirtualClock)
from repro.serve.workload import WorkloadSpec, generate


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("phi4-mini-3.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, {}


def _trace_requests(cfg, seed, n=6):
    """A small workload trace + deterministic prompt tokens for it."""
    spec = WorkloadSpec(rate=2000.0, prompt_median=8.0, prompt_sigma=0.5,
                        max_prompt=16, min_output=2, max_output=8,
                        interactive_frac=0.5, ttft_slo=20e-3, tpot_slo=5e-3)
    rng = np.random.default_rng(seed)
    out = []
    for wr in generate(n, spec, seed=seed):
        prompt = rng.integers(0, cfg.vocab_size,
                              wr.prompt_len).astype(np.int32)
        out.append((wr, prompt))
    return out


def _unpressured_reference(cfg, params, cache, reqs, page_size):
    """The same chunked paged engine (same page size and chunk length,
    so the same compute shapes) with a pool holding every slot's whole
    capacity, FIFO scheduling and every request submitted at once:
    nothing is shed, parked or preempted."""
    key = ("chunked", page_size,
           tuple((tuple(int(t) for t in p), wr.output_len) for wr, p in reqs))
    if key not in cache:
        eng = Engine(cfg, params, EngineConfig(
            max_batch=3, max_len=64, prefill_buckets=(16,),
            paging=PagingConfig(page_size=page_size),
            chunking=ChunkingConfig(chunk_tokens=4)))
        for wr, prompt in reqs:
            eng.submit(prompt, max_new_tokens=wr.output_len)
        cache[key] = eng.run()
        assert eng.stats["preemptions"] == 0
    return cache[key]


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**16),
       page_size=st.sampled_from([4, 8]),
       spare_pages=st.integers(0, 2))
# an exact bf16 tie between two logits of request 2 at position 14: a
# 1-ulp difference between the dense-prefill and chunked-prefill
# programs flips its argmax, so the dense engine is no reference here
@example(seed=62240, page_size=4, spare_pages=0)
def test_property_slo_schedule_token_exact(setup, seed, page_size,
                                           spare_pages):
    """Random traces on a pool tight enough to force shedding and
    deadline-aware preemption: the SLO scheduler's outputs must equal,
    token for token, those of the same engine run without any
    scheduling pressure — scheduling changes *when*, never *what* — and
    page accounting must drain.

    The reference shares the engine's compute shapes.  The dense
    engine does not: its whole-prompt prefill reduces in a different
    order than 4-token chunks, and on XLA's CPU backend that moves the
    last bit of some activations, which flips the argmax wherever two
    bf16 logits tie exactly (the pinned example)."""
    cfg, params, ref_cache = setup
    reqs = _trace_requests(cfg, seed)
    ref = _unpressured_reference(cfg, params, ref_cache, reqs, page_size)

    need = max(pages_for(min(len(p) + wr.output_len, 64), page_size)
               for wr, p in reqs)
    eng = Engine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(page_size=page_size,
                            device_pages=need + spare_pages),
        chunking=ChunkingConfig(chunk_tokens=4),
        scheduler=SchedulerConfig(policy="slo")))
    for wr, prompt in reqs:
        eng.submit(prompt, max_new_tokens=wr.output_len, tier=wr.tier,
                   ttft_slo=wr.ttft_slo, tpot_slo=wr.tpot_slo,
                   arrival_t=wr.arrival_t)
    out = eng.run()

    assert out == ref
    assert eng.stats["resumes"] == eng.stats["preemptions"]
    assert eng.page_pool.n_free == eng.page_pool.n_pages


def test_slo_schedule_no_starvation(setup):
    """Sustained interactive pressure sheds batch admissions, but every
    batch request still completes once the pressure drains (shedding
    defers, never drops)."""
    cfg, params, _ = setup
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(page_size=4, device_pages=8),
        chunking=ChunkingConfig(chunk_tokens=4),
        scheduler=SchedulerConfig(policy="slo", ttft_slo=10e-3,
                                  tpot_slo=5e-3)))
    rng = np.random.default_rng(3)
    n_batch, n_inter = 3, 9
    batch_rids = [eng.submit(rng.integers(0, cfg.vocab_size, 8),
                             max_new_tokens=6, tier=Tier.BATCH,
                             arrival_t=0.0)
                  for _ in range(n_batch)]
    for i in range(n_inter):
        eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new_tokens=4,
                   tier=Tier.INTERACTIVE, arrival_t=i * 1e-3)
    out = eng.run()
    assert len(out) == n_batch + n_inter
    for rid in batch_rids:
        assert len(out[rid]) == 6            # batch finished, not dropped
    rep = eng.slo_report()
    assert rep["interactive"]["n"] == n_inter
    assert rep["batch"]["n"] == n_batch


def test_slo_goodput_dominates_watermark_in_sim():
    """The CI-gated acceptance: >= 1.2x interactive goodput over
    watermark-FIFO at 4x oversubscription on the production trace
    (deterministic virtual clock), and no loss at moderate load."""
    from repro.paging.sim import simulate_slo_schedule
    r4 = simulate_slo_schedule(4.0)
    assert r4["goodput_ratio"] >= 1.2
    assert r4["int_attain_slo"] >= r4["int_attain_wm"]
    r2 = simulate_slo_schedule(2.0)
    assert r2["goodput_ratio"] >= 1.0


def test_slo_beats_watermark_on_engine_trace(setup):
    """Same workload trace through the real engine under both policies:
    the SLO scheduler's interactive attainment is at least watermark's
    (engine-level sanity for the sim's head-to-head)."""
    cfg, params, _ = setup
    reqs = _trace_requests(cfg, 11, n=10)

    def run(policy):
        eng = Engine(cfg, params, EngineConfig(
            max_batch=2, max_len=64, prefill_buckets=(16,),
            paging=PagingConfig(page_size=4, device_pages=10),
            chunking=ChunkingConfig(chunk_tokens=4),
            scheduler=SchedulerConfig(policy=policy)))
        for wr, prompt in reqs:
            eng.submit(prompt, max_new_tokens=wr.output_len, tier=wr.tier,
                       ttft_slo=wr.ttft_slo, tpot_slo=wr.tpot_slo,
                       arrival_t=wr.arrival_t)
        eng.run()
        return eng.slo_report()

    wm = run("watermark")
    slo = run("slo")
    assert slo["interactive"]["attainment"] >= wm["interactive"]["attainment"]


def test_engine_takes_only_engine_config(setup):
    """An EngineConfig is the one way to configure an Engine: an unknown
    keyword and a flat field keyword (``max_batch=2``) both raise
    TypeError, and the config path serves without a warning."""
    cfg, params, _ = setup

    with pytest.raises(TypeError, match="no_such_knob"):
        Engine(cfg, params, no_such_knob=1)
    with pytest.raises(TypeError, match="max_batch"):
        Engine(cfg, params, max_batch=2)

    prompts = [np.arange(6) % cfg.vocab_size, np.arange(9) % cfg.vocab_size]
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # config path must not warn
        eng = Engine(cfg, params, EngineConfig(
            max_batch=2, max_len=64, prefill_buckets=(16,),
            paging=PagingConfig(page_size=8, device_pages=6)))
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        out = eng.run()
    assert [len(out[r]) for r in rids] == [4, 4]


def test_one_clock_stamps_every_timestamp(setup):
    """Every request timestamp rides the engine's one injected clock:
    with a shared VirtualClock, arrival/first-token/per-token/completion
    are all on its axis and monotone per request."""
    cfg, params, _ = setup
    clk = VirtualClock()
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(page_size=8, device_pages=8),
        scheduler=SchedulerConfig(clock=clk)))
    eng.submit(np.arange(5), max_new_tokens=4, arrival_t=0.0)
    eng.submit(np.arange(7), max_new_tokens=4, arrival_t=2e-3)
    eng.run()
    assert eng.clock is clk
    for r in eng.finished.values():
        assert r.token_ts and r.token_ts == sorted(r.token_ts)
        assert r.token_ts[0] >= r.arrival_t
        assert r.done_t >= r.token_ts[-1]
        assert r.ttft >= 0.0
        assert clk.now >= r.done_t


def test_cli_flags_generated_from_config():
    """launch/serve's flags come from the dataclass fields: a knob in
    the config is a flag on the CLI, help text included."""
    import argparse
    from repro.serve.config import add_config_args, config_from_args
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    args = ap.parse_args(["--max-batch", "8", "--page-size", "4",
                          "--chunk-tokens", "16", "--policy", "slo",
                          "--ttft-slo", "0.05"])
    ec = config_from_args(args, paging_enabled=False)
    assert ec.max_batch == 8
    assert ec.paging.page_size == 4 and ec.paging.enabled is False
    assert ec.chunking.chunk_tokens == 16
    assert ec.scheduler.policy == "slo"
    assert ec.scheduler.ttft_slo == pytest.approx(0.05)
