#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a model configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  A run:

1. refuses to run, printing no result, unless JAX finds a TPU with as many
   chips as the cell asks for;
2. makes the bf16 weights on the device from ``--seed``, one layer at a
   time into the engine's shardings, and builds ``repro.serve.Engine`` on
   them (Pallas kernels, the default pager, a wall clock);
3. warms up: two requests that compile every step program, then the
   cell's own traffic until the traffic file's warm-up holds;
4. measures for ``--seconds`` (with ``--trace 1``: a shorter window under
   the profiler, and per-layer metrics in place of end-to-end ones);
5. serves the sampled requests to their end, reads the peak memory, frees
   the engine and checks the served tokens against the plain float32
   reference (``bench/check.py``).  ``run_cell(..., control=True)`` puts
   the float8 control in the program's place: its picks are compared in
   place of the served tokens, so ``correct`` reads the control.

The last line of standard output is the result; the numbers compared for
``correct`` are also the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profile and a description of its "
                         "planes and operation names to this directory")
    return ap.parse_args(argv)


def chips_or_fail(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}, not a "
                     "TPU; nothing was run")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed directory ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    from bench import weights as W
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the dense family serves SwiGLU (silu) only")
    z = W.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=z["L"],
        d_model=z["d"], num_heads=z["H"], num_kv_heads=z["Hkv"],
        head_dim=z["D"], d_ff=z["ff"], vocab_size=int(cfg["vocab_size"]),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        param_dtype="bfloat16", compute_dtype="bfloat16")


def weight_programs(cfg: dict, mcfg, mesh):
    """The jitted programs that make the bench's weights on ``mesh``, in
    the shardings the engine's step programs take: ``({top path:
    program(key)}, zeros(), fill(layers, key, l))``.

    ``fill`` draws layer ``l`` (traced: it compiles once) and writes it
    into the stacked arrays, which it is given donated; each top leaf has
    a program of its own.  No program draws more than one layer's leaves
    or one table, so no whole stacked leaf is ever held in float32 (drawn
    at once, Mistral-Nemo's stacked MLP leaf alone takes 11.7 GB of
    float32 on each of four devices)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.dist.sharding import param_specs
    from repro.dist.steps import abstract_params
    from bench import weights as W

    L = W.dims(cfg)["L"]
    stacked = {p: (L,) + shape for p, shape, _ in W.layer_leaves(cfg)}
    tops = {p: shape for p, shape, _ in W.top_leaves(cfg)}
    want = abstract_params(mcfg)
    dtype = str(jnp.dtype(W.DTYPE))
    mine = W.nest({**{p: (s, dtype) for p, s in tops.items()},
                   **{f"layers/{p}": (s, dtype) for p, s in stacked.items()}})
    if jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                              want) != mine:
        raise ValueError("bench/weights.py no longer matches the engine's "
                         "parameter tree")
    specs = param_specs(mesh, want)

    def sharding(path):
        node = specs
        for k in path.split("/"):
            node = node[k]
        return NamedSharding(mesh, node)

    top = {p: jax.jit(lambda k, p=p: W.top(cfg, k)[p],
                      out_shardings=sharding(p)) for p in tops}
    into = {p: sharding(f"layers/{p}") for p in stacked}
    zeros = jax.jit(lambda: {p: jnp.zeros(s, W.DTYPE)
                             for p, s in stacked.items()},
                    out_shardings=into)
    fill = jax.jit(
        lambda st, k, l: {p: jax.lax.dynamic_update_index_in_dim(
            st[p], v, l, 0) for p, v in W.layer(cfg, k, l).items()},
        donate_argnums=0, out_shardings=into)
    return top, zeros, fill


def make_params(cfg: dict, mcfg, mesh, seed: int):
    """The bench's weights, made on the device from the seed one layer at
    a time into the engine's shardings (:func:`weight_programs`).  The
    values are those of ``bench/weights.py``'s key scheme, which the
    reference remakes layer by layer."""
    import jax
    import jax.numpy as jnp
    from bench import weights as W

    top, zeros, fill = weight_programs(cfg, mcfg, mesh)
    key = W.base_key(seed)
    tree = W.nest({p: program(key) for p, program in top.items()})
    layers = zeros()
    for l in range(W.dims(cfg)["L"]):
        layers = fill(layers, key, jnp.int32(l))
    tree["layers"] = W.nest(layers)
    return jax.block_until_ready(tree)


def make_engine(cell, mcfg, params, mesh):
    from repro.serve import (ChunkingConfig, Engine, EngineConfig,
                             PagingConfig)
    from repro.serve.config import SchedulerConfig
    e = cell.engine
    return Engine(mcfg, params, EngineConfig(
        max_batch=int(e["max_batch"]), max_len=int(e["max_len"]),
        mesh=mesh, kernel_impl="auto",
        paging=PagingConfig(page_size=int(e["page_size"]),
                            device_pages=e.get("device_pages")),
        chunking=ChunkingConfig(chunk_tokens=int(e["chunk_tokens"]),
                                chunk_slots=int(e.get("chunk_slots", 2))),
        scheduler=SchedulerConfig(clock=time.perf_counter)))


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def pager_bytes(eng) -> int:
    return int(sum(v for k, v in eng.pager.stats.items()
                   if k.startswith("bytes_moved_")))


def counters(eng) -> dict:
    """The engine's counters, and the pager's as ``pager.<name>``."""
    out = dict(eng.stats)
    if eng.pager is not None:
        out.update({f"pager.{k}": v for k, v in eng.pager.stats.items()})
    return out


def warm_up(driver, warm: dict, compiles) -> None:
    """The traffic's own warm-up: at least ``min_s`` seconds, until every
    counter in ``until`` (see :func:`counters`) has risen by its amount
    and every program named in ``compiled`` has been compiled (or loaded
    from the cache) in this process, or until ``max_s`` passed."""
    eng = driver.eng
    s0 = counters(eng)
    t0 = driver.clock()
    until = warm.get("until", {})
    programs = set(warm.get("compiled", []))

    def grown():
        now = counters(eng)
        return {k: now.get(k, 0) - s0.get(k, 0) for k in until}

    def done():
        el = driver.clock() - t0
        if el >= float(warm.get("max_s", 300)):
            return True
        return el >= float(warm.get("min_s", 0)) and all(
            g >= until[k] for k, g in grown().items()) and \
            programs <= set(compiles.names(t0, driver.clock()))

    driver.run_until(done, float(warm.get("max_s", 300)) + 1)
    got = grown()
    missing = programs - set(compiles.names(t0, driver.clock()))
    log(f"[warm] {driver.clock() - t0:.3f} s of traffic, {got}")
    if any(got[k] < v for k, v in until.items()) or missing:
        log(f"[warm] warning: max_s ended the warm-up before {until}, "
            f"{sorted(missing)}")


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             devices=None, keep_trace=None, control: bool = False,
             t_start: float = T_START) -> dict:
    """One run of ``cell``; returns the result object (not printed)."""
    import jax
    from repro.launch.mesh import make_mesh_compat
    from bench import check, loop, measure, reference, spec, traffic
    from bench import trace as trace_mod

    devices = devices or jax.devices()[:cell.chips]
    e = cell.engine
    peaks = spec.peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else {}
    mcfg = model_config(cell.config)
    mesh = make_mesh_compat((1, len(devices)), ("data", "model"),
                            devices=devices)
    clock = time.perf_counter
    compiles = loop.CompileClock(clock)

    params = make_params(cell.config, mcfg, mesh, seed)
    log(f"[weights] made at {clock() - t_start:.3f} s; peak so far on the "
        f"fullest chip {memory_peak(devices)} B")
    eng = make_engine(cell, mcfg, params, mesh)
    loop.warm_compile(eng, mcfg.vocab_size, int(e["chunk_tokens"]), seed)
    plan = traffic.make_plan(cell.traffic, mcfg.vocab_size, seed)
    for line in traffic.describe(plan):
        log(f"[traffic] {line}")
    driver = loop.Driver(eng, plan, clock)
    driver.start()
    warm_up(driver, cell.traffic.get("warmup", {}), compiles)

    win_s = min(seconds, float(cell.traffic.get("trace_seconds", 8))) \
        if trace else seconds
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # it would slow the host it traces
        jax.profiler.start_trace(tdir, profiler_options=opts)
        driver.annotate = True
    stats0, bytes0 = dict(eng.stats), pager_bytes(eng)
    first_step = len(driver.steps)
    w0 = clock()
    with driver._span("bench/window"):
        while clock() < w0 + win_s:
            if not driver.iterate():
                time.sleep(0.001)
    w1 = clock()
    if trace:
        jax.profiler.stop_trace()
        driver.annotate = False
    stats = {k: eng.stats[k] - stats0.get(k, 0) for k in eng.stats}
    nbytes = pager_bytes(eng) - bytes0
    steps = driver.steps[first_step:]
    compiled = compiles.names(w0, w1)
    log(f"[setup] {w0 - t_start:.3f} s to the window")
    log(f"[window] {w1 - w0:.3f} s, {len(steps)} steps "
        f"({sum(s.mixed for s in steps)} mixed), compiled in the window: "
        f"{compiled}, engine {stats}, pager bytes {nbytes}")
    if plan.loop == "open":
        late = [s.sent - s.due for s in loop.due_in_window(driver.sent,
                                                           w0, w1)]
        if late:
            log(f"[window] generator lateness max {max(late) * 1e3:.3f} ms,"
                f" mean {sum(late) / len(late) * 1e3:.3f} ms")

    # -- the sample: served to its end, then compared -------------------------
    driver.submitting = False
    picks = check.choose_sample(driver.sent, w0, w1,
                                cell.traffic.get("sample", {}), seed)
    finished = driver.run_until(lambda: all(s.req.rid in eng.finished
                                            for s in picks),
                                float(cell.traffic.get("sample", {})
                                      .get("drain_s", 120)))
    seqs = check.sequences(picks)
    resumed = sum(1 for s in picks if s.req.n_preempts > 0)
    mem = memory_peak(devices)
    due = loop.due_in_window(driver.sent, w0, w1)
    n_failed = sum(1 for s in due if loop.failed(s))
    sent = driver.sent
    for s in sent:                  # keep the records, drop the engine
        if s.req is not None:
            s.req.residue = s.req.chunk_rows = None
    del driver, eng, params
    gc.collect()

    # with ``control``, the float8 control's picks stand in the program's
    # place and are judged as the served tokens are
    compared = "control_gap" if control else "served_gap"
    t_ref = clock()
    results = reference.logit_gaps(cell.config, seed, seqs,
                                   control=control) if seqs else []
    gap = check.widest_gap(results, compared) if results else None
    limit = cell.limit("max_logit_gap")
    n_served = sum(len(t) - n for t, n in seqs)
    log(f"[check] {len(seqs)} requests ({resumed} parked and resumed), "
        f"{n_served} served tokens, all finished {finished}, reference "
        f"{clock() - t_ref:.3f} s")

    ctx = measure.Ctx(cell=cell, peaks=peaks, chips=len(devices),
                      t_start=t_start, w0=w0, w1=w1, sent=sent, steps=steps,
                      stats=stats, pager_bytes=nbytes,
                      memory_peak_bytes=mem)
    out = {"correct": gap is not None and gap <= limit,
           "attempted": len(due), "failed": n_failed}
    if trace:
        path = trace_mod.find_xplane(tdir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            if os.path.getsize(path) < 16 << 20:
                shutil.copy(path, Path(keep_trace) / f"{cell.name}.xplane.pb")
            (Path(keep_trace) / f"{cell.name}.trace.txt").write_text(
                trace_mod.describe(path))
        ctx.trace = trace_mod.load(path)
        shutil.rmtree(tdir, ignore_errors=True)
        out["metrics"] = measure.read_metrics(ctx, cell.per_layer)
    else:
        out["metrics"] = measure.read_metrics(ctx, cell.end_to_end)
    dev = devices[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        out["device"].update(busy_s=trace_mod.mean_busy_s(ctx.trace),
                             window_s=ctx.trace.window_s)
        out["breakdown"] = {"device_ops": trace_mod.device_ops(ctx.trace),
                            "idle_gaps": trace_mod.idle_gaps(ctx.trace)}
    if control and results:
        out["program_gap"] = check.widest_gap(results)
    out["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import spec
    try:
        cell = spec.load_cell(args.workload)
        devices = chips_or_fail(cell.chips)
        spec.peaks_for(devices[0].device_kind)
    except (NoChip, spec.SpecError) as e:
        log(f"bench/run.py: {e}")
        return 2
    import jax
    log(f"[device] {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)} of {len(jax.devices())}; compile cache "
        f"{use_compile_cache()}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices=devices, keep_trace=args.keep_trace)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
