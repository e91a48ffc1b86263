"""Serving driver: continuous-batching engine over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve \
      --arch phi4-mini-3.8b --smoke --requests 16 --max-new 12 \
      --chunk-tokens 16

Every ``--`` engine flag below is auto-generated from the
:class:`repro.serve.config.EngineConfig` dataclass fields (one flag per
knob, help text included), so the CLI cannot drift from the config API
— including ``--role`` (fused / prefill / decode).  Driver-level
extras:

  * ``--workload`` replaces the uniform synthetic requests with the
    production traffic model (:mod:`repro.serve.workload`): bursty
    diurnal arrivals, lognormal prompts, Zipf outputs, and an
    interactive/batch tier split with per-request TTFT/TPOT SLOs,
  * ``--slo`` is shorthand for ``--policy slo`` — goodput scheduling
    (EDF chunk order, batch shedding, deadline-aware preemption onto
    the pager's QoS windows); combine with ``--workload`` to see the
    per-tier attainment report,
  * ``--disagg`` runs the disaggregated walkthrough in one process: a
    PREFILL and a DECODE engine over ONE shared far tier, driven by
    :func:`repro.serve.disagg.run_disaggregated` (prefill graduates
    each request at its first token and BULK-parks its pages; decode
    adopts it through the resume machinery),
  * ``--role prefill --handoff-spool d.pkl`` runs the prefill half
    alone and spools records *plus their tier entries* to a file;
    ``--role decode --handoff-spool d.pkl`` adopts that spool in a
    separate process — the two-process version of ``--disagg``,
  * ``--dense`` / ``--kernel-impl`` A/B the paged decode path against
    the dense per-slot cache and the kernel backends,
  * ``--trace-out t.json`` writes a Perfetto-loadable timeline of the
    run (AMU transfer spans, pager actions, per-QoS window occupancy,
    request lifecycles — one virtual clock); ``--metrics-out m.json``
    dumps every counter/histogram.  See ``tools/trace_report.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.dist.steps import init_params_sharded
from repro.launch.mesh import make_mesh_compat
from repro.serve.config import add_config_args, config_from_args
from repro.serve.disagg import (make_shared_tier, run_disaggregated,
                                spool_load, spool_save, tier_pager_factory)
from repro.serve.engine import Engine
from repro.serve.workload import WorkloadSpec, generate


#: the checkout this module runs from (src/repro/launch/serve.py)
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = REPO_ROOT) -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself; nothing else is set here).  Otherwise the cache is
    ``<root>/.jax_cache`` — a fixed path, since the path is part of what
    a later process must match to find its entries again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _submit_requests(eng, args, cfg, econf, rng) -> None:
    """Queue the synthetic or workload-model requests on ``eng``."""
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix)
    if args.workload:
        spec = WorkloadSpec(rate=args.workload_rate,
                            max_prompt=max(4, econf.max_len // 2))
        for wr in generate(args.requests, spec, seed=args.seed):
            plen = min(wr.prompt_len, econf.max_len - wr.output_len - 1)
            prompt = np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, max(1, plen))])
            kwargs = {}
            if cfg.family == "encdec":
                kwargs["src_embeds"] = rng.standard_normal(
                    (len(prompt), cfg.d_model)).astype(np.float32)
            eng.submit(prompt, max_new_tokens=wr.output_len,
                       tier=wr.tier, ttft_slo=wr.ttft_slo,
                       tpot_slo=wr.tpot_slo, arrival_t=wr.arrival_t,
                       **kwargs)
    else:
        for _ in range(args.requests):
            plen = int(rng.integers(4, min(32, econf.max_len // 2)))
            prompt = np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, plen)])
            kwargs = {}
            if cfg.family == "encdec":
                kwargs["src_embeds"] = rng.standard_normal(
                    (plen, cfg.d_model)).astype(np.float32)
            eng.submit(prompt, max_new_tokens=args.max_new, **kwargs)


def _report(eng, econf, out, wall) -> None:
    total_new = sum(len(v) for v in out.values())
    lat = [r.done_t - r.submitted_t for r in eng.finished.values()]
    ttft = [r.first_token_t - r.submitted_t for r in eng.finished.values()]
    print(f"[serve] {len(out)} requests, {total_new} tokens in {wall:.2f}s "
          f"({total_new / wall:.1f} tok/s)")
    print(f"[serve] decode steps {eng.stats['steps']} "
          f"(batch occupancy "
          f"{total_new / max(1, eng.stats['steps'] * econf.max_batch):.2f})")
    if lat:
        print(f"[serve] mean TTFT {np.mean(ttft)*1e3:.0f} ms, "
              f"mean latency {np.mean(lat)*1e3:.0f} ms")
    if eng.paging:
        print(f"[serve] page pool {eng.page_pool.n_pages} x "
              f"{eng.page_size} tok: preemptions {eng.stats['preemptions']}, "
              f"resumes {eng.stats['resumes']}, pager {dict(eng.pager.stats)}")
    if eng.chunking:
        print(f"[serve] chunked prefill: {eng.stats['chunks']} chunks of "
              f"<= {eng.chunk_tokens} tok across "
              f"{eng.stats['mixed_steps']} mixed steps "
              f"({eng.stats['prefills']} dense-prefill fallbacks)")
    if eng.speculating:
        s = eng.stats
        mean_k = s["accepted"] / max(1, s["spec_steps"])
        rate = s["accepted"] / max(1, s["drafted"])
        print(f"[serve] speculation k={eng.speculate_k}: "
              f"{s['spec_steps']} verify steps, "
              f"{s['drafted']} drafted / {s['accepted']} accepted "
              f"({rate:.0%}), mean accepted-K {mean_k:.2f}")
    if eng.prefix is not None:
        print(f"[serve] prefix cache: {eng.stats['prefix_hits']} page hits "
              f"({eng.stats['prefix_far_hits']} far), "
              f"{eng.stats['prefix_tokens_saved']} prefill tokens saved, "
              f"{eng.prefix.stats['interned']} pages interned")


def _role_config(econf, role: str, factory, board=None):
    """The fused CLI config re-targeted at one disaggregated role."""
    return dataclasses.replace(
        econf, role=role, handoff=board,
        paging=dataclasses.replace(econf.paging, pager_factory=factory))


def _run_disagg(args, econf, cfg, params, rng):
    """In-process PREFILL + DECODE walkthrough over one shared tier."""
    tier = make_shared_tier()
    factory = tier_pager_factory(tier)
    pre = Engine(cfg, params, _role_config(econf, "prefill", factory))
    dec = Engine(cfg, params, _role_config(econf, "decode", factory,
                                           board=pre.handoff))
    _submit_requests(pre, args, cfg, econf, rng)
    t0 = time.time()
    out = run_disaggregated(pre, dec)
    wall = time.time() - t0
    total_new = sum(len(v) for v in out.values())
    print(f"[serve] disaggregated: {len(out)} requests, {total_new} "
          f"tokens in {wall:.2f}s ({total_new / wall:.1f} tok/s)")
    print(f"[serve] prefill: {pre.stats['handoffs']} handoffs, "
          f"{pre.stats['chunks']} chunks, "
          f"pager {dict(pre.pager.stats)}")
    print(f"[serve] decode:  {dec.stats['handoffs']} adoptions, "
          f"{dec.stats['resumes']} resumes, "
          f"{dec.stats['steps']} steps, pager {dict(dec.pager.stats)}")
    print(f"[serve] shared tier: {dict(tier.stats)}")
    return out


def _run_role(args, econf, cfg, params, rng):
    """One disaggregated half in this process, handing off via a spool
    file (``--role prefill`` writes it, ``--role decode`` adopts it)."""
    if not args.handoff_spool:
        raise SystemExit(
            "--role prefill/decode needs --handoff-spool PATH (or use "
            "--disagg to run both halves in one process)")
    tier = make_shared_tier()
    factory = tier_pager_factory(tier)
    eng = Engine(cfg, params, _role_config(econf, econf.role, factory))
    t0 = time.time()
    if econf.role == "prefill":
        _submit_requests(eng, args, cfg, econf, rng)
        eng.run()
        recs = eng.handoff.poll()
        spool_save(args.handoff_spool, recs, tier)
        wall = time.time() - t0
        print(f"[serve] prefill: {len(recs)} handoff records "
              f"(+ tier entries) spooled to {args.handoff_spool} "
              f"in {wall:.2f}s")
        print(f"[serve] prefill pager {dict(eng.pager.stats)}")
        return {rec.rid: list(rec.generated) for rec in recs}
    recs = spool_load(args.handoff_spool, tier)
    for rec in recs:
        eng.admit_handoff(rec)
    out = eng.run()
    wall = time.time() - t0
    print(f"[serve] decode: adopted {len(recs)} records from "
          f"{args.handoff_spool}")
    _report(eng, econf, out, wall)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12,
                    help="new tokens per request (uniform mode)")
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-slot KV cache (A/B "
                         "reference for the paged decode path)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical prefix tokens to "
                         "every synthetic prompt (system-prompt traffic "
                         "model, makes --prefix-cache visible)")
    ap.add_argument("--workload", action="store_true",
                    help="draw requests from the production traffic "
                         "model (bursty/diurnal arrivals, heavy-tailed "
                         "lengths, interactive/batch tiers with SLOs)")
    ap.add_argument("--workload-rate", type=float, default=200.0,
                    help="mean arrival rate for --workload "
                         "(requests per virtual second)")
    ap.add_argument("--slo", action="store_true",
                    help="shorthand for --policy slo (goodput "
                         "scheduling; pairs with --workload)")
    ap.add_argument("--disagg", action="store_true",
                    help="run the disaggregated walkthrough: a PREFILL "
                         "and a DECODE engine over one shared far tier "
                         "in this process (see --role/--handoff-spool "
                         "for the two-process version)")
    ap.add_argument("--handoff-spool", default=None, metavar="PATH",
                    help="with --role prefill: write handoff records + "
                         "tier entries here after the run; with --role "
                         "decode: adopt them from here")
    ap.add_argument("--seed", type=int, default=0)
    add_config_args(ap)     # one --flag per EngineConfig field
    args = ap.parse_args(argv)

    overrides = {}
    if args.dense:
        overrides["paging_enabled"] = False
    if args.slo:
        overrides["scheduler_policy"] = "slo"
    econf = config_from_args(args, **overrides)

    use_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = econf.mesh if econf.mesh is not None else \
        make_mesh_compat((1, 1), ("data", "model"))
    econf = dataclasses.replace(econf, mesh=mesh)
    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    if args.disagg:
        return _run_disagg(args, econf, cfg, params, rng)
    if econf.role != "fused":
        return _run_role(args, econf, cfg, params, rng)

    eng = Engine(cfg, params, econf)
    t0 = time.time()
    _submit_requests(eng, args, cfg, econf, rng)
    out = eng.run()
    wall = time.time() - t0

    _report(eng, econf, out, wall)
    if econf.paging.offload_finished:
        print(f"[serve] far-tier AMU stats: {dict(eng.far_tier.amu.stats)}")
    if args.workload or args.slo:
        rep = eng.slo_report()
        for tier in ("interactive", "batch"):
            tr = rep[tier]
            print(f"[serve] {tier}: {tr['n']} reqs, "
                  f"attainment {tr['attainment']:.2f}, "
                  f"goodput {tr['goodput']:.1f} tok/s (virtual), "
                  f"ttft p95 {tr['ttft_p95']*1e3:.1f} ms")
        print(f"[serve] scheduler: policy={econf.scheduler.policy} "
              f"shed={eng.stats['shed_admissions']} "
              f"deadline_misses={eng.stats['deadline_misses']}")
    # eng.run() already wrote the files (EngineConfig.obs); just say where
    if econf.obs.trace_out:
        print(f"[serve] trace written to {econf.obs.trace_out} "
              "(load in https://ui.perfetto.dev, or run "
              "tools/trace_report.py on it)")
    if econf.obs.metrics_out:
        print(f"[serve] metrics written to {econf.obs.metrics_out}")
    return out


if __name__ == "__main__":
    main()
