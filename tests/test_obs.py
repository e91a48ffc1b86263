"""Telemetry-layer tests: spans, histograms, exporters, invariants.

The observability PR's acceptance surface:

  * spans are well-formed — every ``begin`` is closed by ``end``/
    ``flush_open``, durations are non-negative, nothing outruns the
    shared :class:`VirtualClock`,
  * histogram percentiles track a numpy reference within the
    log-bucketing bound (growth 1.05 ⇒ ≲5% relative error); count/sum/
    min/max are exact,
  * the Chrome-trace export passes ``tools/trace_report.py``'s schema
    validation — including the no-overlap-per-track rule the exporter's
    AMU lane packing exists to satisfy,
  * a disabled tracer is free: no events, no open spans, sid 0,
  * ``CounterView`` keeps every ``collections.Counter`` idiom the old
    ad-hoc stats dicts relied on,
  * property: the SLO report rebuilt *from the trace alone* equals the
    engine's own ``slo_report()``, and the preempt/resume +
    window-acquire/release conservation invariants hold after any run
    — including AMU fault storms.
"""

import glob
import importlib.util
import json
import pathlib
import time

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke
from repro.core.amu import AMU, QoS, SimBackend
from repro.models import init_params
from repro.obs import (CounterView, Histogram, MetricsRegistry, NULL_TRACER,
                       Tracer, to_chrome_trace)
from repro.paging import (EventKind, PagePool, PageState, PageTable, Pager,
                          PagingError)
from repro.paging.sim import simulate_paged_serving
from repro.serve import (ChunkingConfig, Engine, EngineConfig, PagingConfig,
                         SchedulerConfig, VirtualClock)
from repro.serve.config import ObsConfig, SpeculationConfig
from repro.serve.workload import WorkloadSpec, generate
# imported here, not inside the property test that uses it: importing a
# module that defines @given tests from inside a running @given test is
# hypothesis's nested-given health-check failure
from tests.test_spec_decode import _proposer_factory

# tools/trace_report.py is deliberately standalone (stdlib only, no repro
# import) so CI can run it on artifacts; load it here by path.
_TR_PATH = (pathlib.Path(__file__).resolve().parent.parent
            / "tools" / "trace_report.py")
_spec = importlib.util.spec_from_file_location("trace_report", _TR_PATH)
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


# -- metrics ------------------------------------------------------------------

def test_counterview_counter_compat():
    """Every idiom the old ad-hoc Counter/dict stats relied on."""
    reg = MetricsRegistry()
    stats = reg.counters("pager")
    assert stats["missing"] == 0              # Counter: missing reads as 0
    assert "missing" not in stats             # ... without being created
    stats["writeback"] += 1
    stats["writeback"] += 2
    assert stats["writeback"] == 3
    assert stats.get("writeback") == 3
    assert stats.get("nope", 7) == 7
    assert dict(stats) == {"writeback": 3}
    assert stats == {"writeback": 3}          # tests compare against dicts
    # two views of one group share storage; EventKind keys export by name
    other = reg.counters("pager")
    other[EventKind.PREEMPT] += 1
    assert stats[EventKind.PREEMPT] == 1
    snap = reg.snapshot()
    assert snap["counters"]["pager"]["PREEMPT"] == 1
    assert snap["counters"]["pager"]["writeback"] == 3


def test_counters_initial_seeds_without_clobbering():
    reg = MetricsRegistry()
    reg.counters("engine")["steps"] = 5
    view = reg.counters("engine", initial={"steps": 0, "prefills": 0})
    assert view["steps"] == 5                 # existing value kept
    assert view["prefills"] == 0              # new key seeded


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16), n=st.integers(1, 400),
       spread=st.floats(0.1, 6.0))
def test_histogram_percentiles_vs_numpy(seed, n, spread):
    """Log-bucketed percentiles vs the numpy order-statistic reference.

    The histogram's rank walk selects the bucket holding the
    ``ceil(rank)`` order statistic, i.e. numpy's ``method="higher"``;
    the returned geometric bucket midpoint is then within a factor
    ``sqrt(growth)`` of that sample.  min/max/count/sum are exact.
    """
    rng = np.random.default_rng(seed)
    samples = np.exp(rng.normal(-6.0, spread, n))     # latency-shaped
    h = Histogram("t", growth=1.05)
    for v in samples:
        h.observe(float(v))
    assert h.count == n
    assert h.min == samples.min()
    assert h.max == samples.max()
    assert h.mean == pytest.approx(samples.mean())
    for q in (50.0, 95.0, 99.0):
        ref = float(np.percentile(samples, q, method="higher"))
        got = h.percentile(q)
        assert got == pytest.approx(ref, rel=0.055), (q, ref, got)
    # max is the operative tail stat and must carry no bucketing error
    assert h.percentile(100.0) == samples.max()


def test_histogram_empty_and_floor():
    h = Histogram()
    assert h.p50 == 0.0 and h.max == 0.0 and h.mean == 0.0
    h.observe(0.0)                            # at/below floor: bucket 0
    assert h.p50 == 0.0
    assert h.count == 1


# -- tracer -------------------------------------------------------------------

def test_spans_wellformed_on_virtual_clock():
    clock = VirtualClock()
    tr = Tracer(clock=clock)
    a = tr.begin("p", "t1", "outer")
    clock.advance(1.0)
    b = tr.begin("p", "t2", "inner", {"k": 1})
    clock.advance(0.5)
    tr.end(b, {"extra": True})
    tr.instant("p", "t1", "tick")
    clock.advance(0.25)
    tr.end(a)
    assert not tr.open_spans                  # every begin was closed
    now = clock()
    for ph, pid, tid, name, ts, dur, args in tr.events:
        assert ts >= 0.0
        if ph == "X":
            assert dur >= 0.0
            assert ts + dur <= now + 1e-12    # nothing outruns the clock
    # the inner span merged its end args into its begin args
    inner = next(e for e in tr.events if e[3] == "inner")
    assert inner[6] == {"k": 1, "extra": True}
    # double-end and unknown sids are tolerated no-ops
    tr.end(b)
    tr.end(12345)


def test_flush_open_closes_dangling_spans():
    clock = VirtualClock()
    tr = Tracer(clock=clock)
    tr.begin("p", "t", "dangling")
    clock.advance(2.0)
    doc = to_chrome_trace(tr)
    assert doc["otherData"]["open_spans_flushed"] == 1
    assert not tr.open_spans
    sp = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert sp["args"]["incomplete"] is True
    assert sp["dur"] == pytest.approx(2.0 * 1e6)


def test_disabled_tracer_is_free():
    tr = Tracer(enabled=False)
    assert tr.begin("p", "t", "x") == 0       # sid 0: end(0) is a no-op
    tr.end(0)
    tr.instant("p", "t", "i")
    tr.counter("p", "c", 1.0)
    tr.complete("p", "t", "x", 0.0, 1.0)
    assert tr.events == [] and not tr.open_spans
    assert NULL_TRACER.events == []           # the shared instance too
    assert to_chrome_trace(tr)["traceEvents"] == []


# -- exporter schema ----------------------------------------------------------

def test_sim_trace_passes_schema_validation():
    """A real paging-sim run exports valid Chrome-trace JSON: every
    pid/tid named, spans non-overlapping per track (the AMU lane
    packing), per-QoS window-occupancy counter tracks present."""
    tracer = Tracer()
    metrics = MetricsRegistry()
    simulate_paged_serving(2.0, n_seqs=4, pages_per_seq=4, new_tokens=8,
                           tracer=tracer, metrics=metrics)
    doc = to_chrome_trace(tracer, metrics=metrics)
    assert trace_report.validate(doc) == []
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "C"} <= phases
    # round-trips through JSON (the --trace-out payload)
    doc2 = json.loads(json.dumps(doc))
    assert trace_report.validate(doc2) == []
    counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert "window/LATENCY" in counters
    # AMU transfer spans landed, tagged with the queueing breakdown
    pids, tids = trace_report.track_names(doc["traceEvents"])
    amu_spans = [e for e in doc["traceEvents"]
                 if e["ph"] == "X" and pids[e["pid"]] == "amu"]
    assert amu_spans
    assert all("queued_us" in e["args"] for e in amu_spans)
    assert metrics.histograms                 # per-kind/QoS latency hists


def test_validator_rejects_malformed_docs():
    assert trace_report.validate([]) != []
    assert trace_report.validate({"traceEvents": [{"ph": "Z"}]}) != []
    # overlapping spans on one unnamed track: two problems at least
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "name": "a", "ts": 0.0, "dur": 10.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "b", "ts": 5.0, "dur": 10.0},
    ]}
    probs = trace_report.validate(bad)
    assert any("overlaps" in p for p in probs)


# -- pager invariants under faults --------------------------------------------

def test_pager_invariants_survive_fault_storm():
    """Window acquire/release must balance even when every transfer
    faults: the ``{kind}_failed`` reap path releases windows and
    reverts ARRIVING pages, so ``check_invariants`` stays green."""
    fail = {"on": True}

    def latency_fn(req):
        if fail["on"]:
            raise RuntimeError("injected far-memory fault")
        return 5e-6

    pool = PagePool(8, 4)
    table = PageTable(pool)
    metrics = MetricsRegistry()
    amu = AMU(backend=SimBackend(base_latency=5e-6, bandwidth=10e9,
                                 latency_fn=latency_fn), max_outstanding=64)
    pager = Pager(pool, table, amu, page_nbytes=1 << 12,
                  tracer=Tracer(), metrics=metrics)
    table.register_parked("s", 4)
    for l in range(4):
        pager.store_far("s", l, None)
    assert pager.prefetch_seq("s") == 4
    pager.advance(1.0)                        # reaps all four failures
    pager.check_invariants()
    assert pager.stats["aload_failed"] == 4
    assert table.logical_pages("s", PageState.PARKED) == [0, 1, 2, 3]
    fail["on"] = False                        # fault clears: retry fills
    pager.prefetch_seq("s")
    pager.advance(1.0)
    pager.check_invariants()
    assert table.resident("s")
    # fault instants were traced on both the AMU and pager tracks
    faults = [e for e in pager.tracer.events
              if e[0] == "i" and e[3] == "fault" and e[1] == "amu"]
    assert len(faults) == 4
    pager_faults = [e for e in pager.tracer.events
                    if e[0] == "i" and e[3] == "fault" and e[1] == "pager"]
    assert len(pager_faults) == 4


# -- engine: trace-derived SLO report == the engine's own ---------------------

@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("phi4-mini-3.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run_traced(cfg, params, seed, device_pages):
    ec = EngineConfig(
        max_batch=3, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(device_pages=device_pages, page_size=8),
        chunking=ChunkingConfig(chunk_tokens=8),
        scheduler=SchedulerConfig(policy="slo", step_dt=2e-3),
        obs=ObsConfig(trace=True))
    eng = Engine(cfg, params, ec)
    spec = WorkloadSpec(rate=2000.0, prompt_median=8.0, prompt_sigma=0.5,
                        max_prompt=16, min_output=2, max_output=8,
                        interactive_frac=0.5, ttft_slo=20e-3, tpot_slo=5e-3)
    rng = np.random.default_rng(seed)
    for wr in generate(8, spec, seed=seed):
        prompt = rng.integers(0, cfg.vocab_size,
                              wr.prompt_len).astype(np.int32)
        eng.submit(prompt, max_new_tokens=wr.output_len, tier=wr.tier,
                   ttft_slo=wr.ttft_slo, tpot_slo=wr.tpot_slo,
                   arrival_t=wr.arrival_t)
    eng.run()
    return eng


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 2**16), device_pages=st.sampled_from([8, 10]))
def test_property_trace_reproduces_slo_report(setup, seed, device_pages):
    """The whole point of the telemetry layer: the trace is a complete
    record.  ``slo_report()`` recomputed from the exported JSON alone
    (by the standalone trace_report tool) must match the engine's —
    attainment and goodput exactly, TTFT percentiles to float noise —
    and the conservation invariants must hold on the drained engine."""
    cfg, params = setup
    eng = _run_traced(cfg, params, seed, device_pages)
    eng.check_invariants()                    # preempt/resume + windows
    doc = json.loads(json.dumps(eng.export_trace()))
    assert trace_report.validate(doc) == []
    assert doc["otherData"]["open_spans_flushed"] == 0
    derived = trace_report.report_from_trace(doc)
    own = eng.slo_report()
    assert derived["elapsed"] == pytest.approx(own["elapsed"])
    for tier in ("interactive", "batch"):
        d, o = derived[tier], own[tier]
        assert d["n"] == o["n"]
        assert d["attained"] == o["attained"]
        assert d["attainment"] == pytest.approx(o["attainment"])
        assert d["good_tokens"] == o["good_tokens"]
        assert d["goodput"] == pytest.approx(o["goodput"])
        for q in ("ttft_p50", "ttft_p95", "ttft_p99"):
            assert d[q] == pytest.approx(o[q], abs=1e-9)
    # preemption storms leave their pager/residency signature on the trace
    if eng.stats["preemptions"]:
        counts = trace_report.lifecycle_counts(doc)
        assert counts.get("pager/PARKED", 0) > 0
        assert counts.get("requests/parked", 0) == eng.stats["preemptions"]


# -- speculation accounting: trace-derived == the engine's own ----------------

def _run_spec_traced(cfg, params, seed, kind):
    rng = np.random.default_rng(seed)
    requests = [(rng.integers(0, cfg.vocab_size,
                              int(rng.integers(4, 13))).astype(np.int32),
                 int(rng.integers(4, 11))) for _ in range(4)]
    dense = Engine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(enabled=False)))
    for p, n in requests:
        dense.submit(p, max_new_tokens=n)
    ref = dense.run()
    eng = Engine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(device_pages=24, page_size=4),
        speculation=SpeculationConfig(
            speculate_k=3,
            proposer_factory=_proposer_factory(kind, ref, requests,
                                               cfg.vocab_size)),
        obs=ObsConfig(trace=True)))
    for p, n in requests:
        eng.submit(p, max_new_tokens=n)
    out = eng.run()
    assert out == ref
    return eng


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 2**16),
       kind=st.sampled_from(["oracle", "wrong", "first"]))
def test_property_trace_reproduces_spec_accounting(setup, seed, kind):
    """The speculation accounting recomputed from the exported trace
    alone — cumulative ``spec_*`` counter tracks (which the exporter
    dedups) plus per-step ``verify`` instants — must equal the engine's
    own stats across accept-all, reject-all, and partial proposers."""
    cfg, params = setup
    eng = _run_spec_traced(cfg, params, seed, kind)
    eng.check_invariants()
    doc = json.loads(json.dumps(eng.export_trace()))
    assert trace_report.validate(doc) == []
    sp = trace_report.speculation_report(doc)
    assert sp["consistent"]
    assert sp["verify_steps"] == eng.stats["spec_steps"]
    assert sp["drafted"] == eng.stats["drafted"]
    assert sp["accepted"] == eng.stats["accepted"]
    assert sp["rejected"] == eng.stats["rejected"]
    if eng.stats["spec_steps"]:
        assert sp["mean_accepted_k"] == pytest.approx(
            eng.stats["accepted"] / eng.stats["spec_steps"])


def test_validator_flags_broken_spec_tracks():
    """Cumulative spec counters must be monotone and sum-consistent."""
    meta = [{"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "engine"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "spec"}}]

    def c(name, value, ts):
        return {"ph": "C", "pid": 1, "tid": 0, "name": name, "ts": ts,
                "args": {"value": value}}

    backwards = {"traceEvents": meta + [c("spec_drafted", 5, 0.0),
                                        c("spec_drafted", 3, 1.0)]}
    assert any("went backwards" in p
               for p in trace_report.validate(backwards))
    inconsistent = {"traceEvents": meta + [c("spec_drafted", 5, 0.0),
                                           c("spec_accepted", 2, 0.0),
                                           c("spec_rejected", 2, 0.0)]}
    assert any("accounting broken" in p
               for p in trace_report.validate(inconsistent))


def test_spec_report_empty_without_speculation(setup):
    cfg, params = setup
    eng = _run_traced(cfg, params, 1, 10)
    doc = eng.export_trace()
    assert trace_report.speculation_report(doc) == {}


def test_engine_invariant_check_detects_imbalance(setup):
    cfg, params = setup
    eng = _run_traced(cfg, params, 0, 10)
    eng.check_invariants()
    eng.stats["preemptions"] += 1             # corrupt the books
    with pytest.raises(PagingError, match="imbalance"):
        eng.check_invariants()


def test_engine_tracing_off_by_default(setup):
    """Default EngineConfig: tracer disabled, stats still registry-backed
    (one shared metrics export), trace export empty but valid."""
    cfg, params = setup
    ec = EngineConfig(max_batch=2, max_len=64, prefill_buckets=(16,),
                      chunking=ChunkingConfig(chunk_tokens=8))
    eng = Engine(cfg, params, ec)
    assert not eng.tracer.enabled
    assert not eng.config.obs.tracing
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                   max_new_tokens=3)
    eng.run()
    eng.check_invariants()
    assert eng.tracer.events == []            # zero allocations kept
    doc = eng.export_trace()
    assert trace_report.validate(doc) == []
    snap = eng.export_metrics()
    assert snap["counters"]["engine"]["admitted"] == 3
    assert "events" in snap["counters"]       # EventLoop shares the registry
    assert eng.stats["admitted"] == 3         # CounterView reads unchanged


# -- Tracer.span: step phases and frame transfers -----------------------------

@pytest.mark.parametrize("enabled", [False, True])
def test_span_nests_or_costs_nothing(enabled):
    """Disabled: ``span`` hands back one shared no-op and records
    nothing.  Enabled: nested ``X`` events on the tracer's clock, each
    naming the span it opened in as ``parent``."""
    clock = VirtualClock()
    tr = Tracer(clock=clock, enabled=enabled)
    with tr.span("engine", "step") as outer:
        clock.advance(1.0)
        with tr.span("transfer", "read_frame", phys=3, nbytes=64) as inner:
            clock.advance(0.5)
        clock.advance(0.25)
    if not enabled:
        assert outer is inner is tr.span("engine", "other")
        assert tr.events == [] and not tr._stack
        return
    (i_ev, o_ev) = tr.events               # the inner span closes first
    assert i_ev[:5] == ("X", "transfer", "host", "read_frame", 1.0)
    assert i_ev[5] == pytest.approx(0.5)
    assert o_ev[:5] == ("X", "engine", "host", "step", 0.0)
    assert o_ev[5] == pytest.approx(1.75)
    assert o_ev[6]["parent"] == 0
    assert i_ev[6] == {"phys": 3, "nbytes": 64, "id": i_ev[6]["id"],
                       "parent": o_ev[6]["id"]}
    assert not tr._stack


def _overrun_engine(cfg, params, clock, **obs):
    """Three slots over a 10-page pool of 8-token pages, no hot tail:
    every preemption writes its pages back and every resume lands them."""
    eng = Engine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(page_size=8, device_pages=10, hot_tail_pages=0),
        chunking=ChunkingConfig(chunk_tokens=8),
        scheduler=SchedulerConfig(clock=clock), obs=ObsConfig(**obs)))
    rng = np.random.default_rng(0)
    for _ in range(4):
        eng.submit(rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                   max_new_tokens=16)
    return eng


@pytest.mark.parametrize("own_clock", [True, False])
def test_amu_bound_only_to_the_engines_own_clock(setup, own_clock):
    """On its own virtual clock the engine records the simulated AMU's
    transfer spans and latencies; on an injected wall clock the AMU is
    left unbound (its ticks are not wall time), while the pager, the
    step spans and the frame transfers still record."""
    cfg, params = setup
    eng = _overrun_engine(cfg, params, None if own_clock else
                          time.perf_counter, trace=True)
    eng.run()
    assert eng.stats["preemptions"] > 0
    pids = {e[1] for e in eng.tracer.events}
    hists = [h for h in eng.metrics.snapshot()["histograms"]
             if h.startswith("amu/latency_s/")]
    assert ("amu" in pids) is own_clock
    assert bool(hists) is own_clock
    assert {"engine", "transfer", "pager"} <= pids
    # every frame transfer ran inside one of the engine's own spans
    spans = {e[6]["id"]: e for e in eng.tracer.events
             if e[0] == "X" and e[2] == "host"}
    moves = [e for e in spans.values() if e[1] == "transfer"]
    assert {e[3] for e in moves} == {"read_frame", "land_frame"}
    assert all(spans[e[6]["parent"]][1] == "engine" for e in moves)
    assert {e[3] for e in spans.values() if e[1] == "engine"} >= {
        "step", "admit", "grow", "park", "resume", "prepare", "launch",
        "wait_device", "logits_to_host", "sample", "finish_chunks", "tick"}
    # the exported timeline stays valid with the nested step spans in it
    assert trace_report.validate(eng.export_trace()) == []


def test_profile_puts_spans_on_the_profilers_host_plane(setup, tmp_path):
    """With ``profile`` set, the step and transfer spans are profiler
    annotations: a ``jax.profiler`` trace of a small overrun run on the
    CPU holds them on its host plane, named ``<pid>/<name>``.  Set on
    its own, nothing is kept in the tracer's memory."""
    from jax.profiler import ProfileData
    cfg, params = setup
    eng = _overrun_engine(cfg, params, time.perf_counter)
    eng.tracer.profile = True
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host") for line in plane.lines
             for e in line.events}
    assert {"engine/step", "engine/park", "transfer/read_frame",
            "transfer/land_frame"} <= names
    assert eng.tracer.events == [] and not eng.tracer._stack
