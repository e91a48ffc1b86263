#!/usr/bin/env python3
"""Smoke run of the serving path on TPU: a published model through Engine.

    python chip_smoke.py              # one chip: phases (a)-(e)

One chip serves phi4-mini-3.8b at its published widths (32 layers,
d_model 3072, 24/8 heads, head_dim 128, d_ff 8192, vocab 200064) with
random weights made from ``--seed``.  The one change from the config:
parameters are bf16.  In float32 its 4.45 B parameters take 17.8 GB,
more than a v5e chip's 16 GiB of HBM; in bf16 they take 8.9 GB.  Every
phase goes through ``Engine`` (paged pool, pager, the ``dist.steps``
programs) with ``kernel_impl="auto"``, which on a TPU is the compiled
Pallas kernels:

  (a) device check: no TPU, no run;
  (b) fused paged serving: 32 requests, prompts of 512-3072 tokens, 32
      new tokens each, chunked prefill, on a 2 GiB pool that demand
      overruns, so requests are preempted to the far tier and resumed;
  (c) speculation (speculate_k=3) on prompts of repeated n-grams, so
      the verify kernel runs and drafts are accepted;
  (d) the int8-quantized pool;
  (e) each paged kernel against its XLA reference on the pool contents
      phases (c) and (d) left.

The tensor-parallel path (mistral-nemo-12b over four chips) is served
and checked against the float32 reference by the benchmark cell
``nemo-tp4-chat`` (``bench/run.py``).

Times are wall-clock readings of a smoke run, not a benchmark.  Any
failed phase ends the run with a non-zero exit.  The last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

PHI4 = "phi4-mini-3.8b"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Serving shapes of a run (the engine's compiled batch and cache)."""

    max_batch: int = 16
    max_len: int = 4096
    page_size: int = 16
    chunk_tokens: int = 512
    device_pages: int = 1024          # phi4-mini: 2 MiB a page -> 2 GiB


class PhaseError(RuntimeError):
    """A phase produced a wrong or incomplete result."""


class CompileClock:
    """Backend compile seconds, stamped on the wall clock."""

    def __init__(self) -> None:
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), secs))

    def since(self, t0: float) -> float:
        return sum(s for t, s in self.events if t >= t0)


def bf16(cfg):
    return dataclasses.replace(cfg, param_dtype="bfloat16")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def make_engine(cfg, params, mesh, sizes: Sizes, **kw):
    from repro.serve import (ChunkingConfig, Engine, EngineConfig,
                             PagingConfig)
    from repro.serve.config import SpeculationConfig

    return Engine(cfg, params, EngineConfig(
        max_batch=sizes.max_batch, max_len=sizes.max_len, mesh=mesh,
        kernel_impl="auto",
        paging=PagingConfig(page_size=sizes.page_size,
                            device_pages=sizes.device_pages,
                            kv_quant=kw.get("kv_quant", "none")),
        chunking=ChunkingConfig(chunk_tokens=sizes.chunk_tokens),
        speculation=SpeculationConfig(speculate_k=kw.get("speculate_k", 0))))


def serve(label: str, eng, requests, clock: CompileClock) -> dict:
    """Submit ``requests`` ((prompt, max_new) pairs), drain the engine and
    print one wall-clock line.  Raises PhaseError unless every request
    ends with exactly its ``max_new_tokens`` and the engine's
    conservation invariants hold."""
    t0 = time.perf_counter()
    rids = {eng.submit(p, max_new_tokens=n): n for p, n in requests}
    t_first = None
    made_at_first = 0
    step_ms = {True: [], False: []}       # mixed step? -> wall ms
    while not eng.drained:
        t_step = time.perf_counter()
        mixed = eng.stats["mixed_steps"]
        eng.step_once()
        if not clock.since(t_step):       # steps that compiled are left out
            step_ms[eng.stats["mixed_steps"] > mixed].append(
                1e3 * (time.perf_counter() - t_step))
        if t_first is None:
            made = sum(len(r.generated) for r in
                       list(eng.active.values()) + list(eng.finished.values()))
            if made:
                t_first, made_at_first = time.perf_counter(), made
        check(eng.stats["steps"] < 20_000, f"{label}: engine did not drain")
    t_end = time.perf_counter()
    eng.check_invariants()
    for rid, n in rids.items():
        got = len(eng.finished[rid].generated) if rid in eng.finished else 0
        check(got == n, f"{label}: request {rid} ended with {got} of {n} "
              "tokens")
    made = sum(rids.values())
    steady_s = t_end - t_first - clock.since(t_first)
    s = eng.stats
    moved = sum(v for k, v in eng.pager.stats.items()
                if k.startswith("bytes_moved_"))
    out = {"first_token_s": t_first - t0, "compile_s": clock.since(t0),
           "steady_tok_s": (made - made_at_first) / steady_s,
           "tokens": made, "steps": s["steps"],
           "mixed_steps": s["mixed_steps"], "preemptions": s["preemptions"],
           "resumes": s["resumes"], "pager_bytes_moved": moved,
           "decode_step_ms_median": float(np.median(step_ms[False] or [0])),
           "mixed_step_ms_median": float(np.median(step_ms[True] or [0])),
           "peak_bytes_in_use": peak_bytes()}
    if eng.speculating:
        out.update(drafted=s["drafted"], accepted=s["accepted"])
    print(f"[{label}] wall-clock, smoke run: " +
          " ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                   for k, v in out.items()), flush=True)
    return out


def random_requests(rng, vocab: int, n: int, lo: int, hi: int, new: int):
    return [(rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
             .astype(np.int32), new) for _ in range(n)]


def ngram_requests(rng, vocab: int, n: int, lo: int, hi: int, new: int):
    """Prompts that repeat one short random n-gram: the prompt-lookup
    proposer drafts from the first repeat on."""
    out = []
    for _ in range(n):
        gram = rng.integers(0, vocab, int(rng.integers(8, 33)))
        length = int(rng.integers(lo, hi + 1))
        out.append((np.resize(gram, length).astype(np.int32), new))
    return out


def pool_layer(eng, layer: int):
    """One layer of the engine's pool frames (and scales, if quantized)."""
    kv = eng.cache.kv
    pool = {"k_pages": kv["k_pages"][layer], "v_pages": kv["v_pages"][layer]}
    if "k_scales" in kv:
        pool.update(k_scales=kv["k_scales"][layer],
                    v_scales=kv["v_scales"][layer])
    return jax.block_until_ready(pool)


# Tolerance of phase (e), as max |kernel - reference| / max |reference|.
# Both sides read the same pool values; the reference runs at "highest"
# matmul precision.  The kernel's f32 products may take one bf16 pass on
# the MXU (2**-8 relative error per product), and its online softmax
# rescales in another order; sums over up to 4096 keys keep that to a
# few times 2**-8 of the output's scale.
KERNEL_TOL = 2e-2


def kernel_phase(label: str, pool: dict, cfg, sizes: Sizes, rng,
                 impl: str = "pallas") -> None:
    """Phase (e): each page-gather kernel against its XLA reference."""
    from repro.kernels import ops

    kp, vp = pool["k_pages"], pool["v_pages"]
    scales = {k: pool[k] for k in ("k_scales", "v_scales") if k in pool}
    N = kp.shape[0]
    H, D = cfg.num_heads, cfg.head_dim
    B, pps = sizes.max_batch, sizes.max_len // sizes.page_size
    cap = pps * sizes.page_size

    def q(*shape):
        return jnp.asarray(rng.standard_normal(shape),
                                 jnp.bfloat16)

    rows = jnp.asarray(rng.integers(0, N - 1, (B, pps)), np.int32)
    base = rng.integers(0, cap - 4, B)
    verify_len = np.minimum(base[:, None] + np.arange(4)[None] + 1, cap)
    C, T = 2, sizes.chunk_tokens
    offset = np.array([0, cap // 2], np.int32)
    length = np.array([T, T // 2 + 7], np.int32)
    cases = {
        "decode": (ops.paged_decode_attention,
                   (q(B, H, D), kp, vp, rows,
                    jnp.asarray(rng.integers(1, cap + 1, B), np.int32)),
                   None),
        "verify": (ops.paged_verify_attention,
                   (q(B, 4, H, D), kp, vp, rows,
                    jnp.asarray(verify_len, np.int32)), None),
        # prefill rows past a chunk's length are don't-care in both paths
        "prefill": (ops.paged_prefill_attention,
                    (q(C, T, H, D), kp, vp, rows[:C],
                     jnp.asarray(offset), jnp.asarray(length)),
                    length),
    }
    for name, (fn, args, valid) in cases.items():
        got = np.asarray(jax.block_until_ready(
            fn(*args, impl=impl, **scales)), np.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(fn(*args, impl="xla", **scales), np.float32)
        if valid is not None:
            keep = np.arange(T)[None, :] < valid[:, None]
            got, ref = got[keep], ref[keep]
        check(np.isfinite(got).all(), f"{label} {name}: non-finite output")
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        print(f"[{label}] {name} kernel vs XLA reference: rel_err={err:.2e} "
              f"(tol {KERNEL_TOL:.0e}) impl={impl} "
              f"pool={np.dtype(kp.dtype).name}", flush=True)
        check(err <= KERNEL_TOL, f"{label} {name}: rel_err {err:.3e} > "
              f"{KERNEL_TOL}")


def one_chip(cfg, sizes: Sizes, seed: int, clock: CompileClock,
             impl: str = "pallas") -> None:
    """Phases (b)-(e) on ``cfg`` (the published phi4-mini by default)."""
    from repro.dist.steps import init_params_sharded
    from repro.launch.mesh import make_mesh_compat

    mesh = make_mesh_compat((1, 1), ("data", "model"),
                            devices=jax.devices()[:1])
    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    V, L = cfg.vocab_size, sizes.max_len
    layer = cfg.num_layers // 2

    eng = make_engine(cfg, params, mesh, sizes)
    b = serve("b fused paged", eng,
              random_requests(rng, V, 32, L // 8, 3 * L // 4, 32), clock)
    check(b["preemptions"] > 0 and b["resumes"] > 0,
          "b: demand never overran the pool (no preemption/resume)")
    del eng
    gc.collect()

    eng = make_engine(cfg, params, mesh, sizes, speculate_k=3)
    c = serve("c speculation k=3", eng,
              ngram_requests(rng, V, 16, L // 8, L // 4, 48), clock)
    check(c["accepted"] > 0, "c: no drafted token was accepted")
    pool_c = pool_layer(eng, layer)
    del eng
    gc.collect()

    eng = make_engine(cfg, params, mesh, sizes, kv_quant="int8")
    serve("d int8 pool", eng,
          random_requests(rng, V, 16, L // 8, L // 2, 32), clock)
    pool_d = pool_layer(eng, layer)
    del eng
    gc.collect()

    kernel_phase("e bf16 pool", pool_c, cfg, sizes, rng, impl)
    kernel_phase("e int8 pool", pool_d, cfg, sizes, rng, impl)
    print(f"[e] peak_bytes_in_use={peak_bytes()}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.serve import use_compile_cache

    print(f"[a] device: {platform} {devices[0].device_kind} x{len(devices)};"
          f" compile cache {use_compile_cache(ROOT)}", flush=True)
    clock = CompileClock()
    one_chip(bf16(get_config(PHI4)), Sizes(), args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
