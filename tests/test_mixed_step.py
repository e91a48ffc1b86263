"""Chunked paged prefill + mixed prefill/decode step tests.

Acceptance for the chunk-queue engine (PR 4): prompts computed chunk by
chunk directly on the pool layout, fused with decode in one mixed step,
must generate exactly the tokens a dense non-paged engine generates —
at every page/chunk boundary, under mid-prefill preemption/resume, and
across the dense / hybrid / enc-dec families.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.amu import AMU, SimBackend
from repro.models import init_params
from repro.paging import Pager
from repro.serve.config import ChunkingConfig, EngineConfig, PagingConfig
from repro.serve.engine import Engine


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("phi4-mini-3.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, prompts, *, max_new=6, src=None,
         paging=PagingConfig(), chunking=ChunkingConfig()):
    eng = Engine(cfg, params, EngineConfig(
        max_batch=3, max_len=64, prefill_buckets=(16, 32),
        paging=paging, chunking=chunking))
    for i, p in enumerate(prompts):
        kw2 = {"src_embeds": src[i]} if src is not None else {}
        eng.submit(p, max_new_tokens=max_new, **kw2)
    return eng, eng.run()


def _slow_pager_factory(base_latency):
    def factory(pool, table, *, page_nbytes):
        amu = AMU(backend=SimBackend(base_latency=base_latency,
                                     bandwidth=10e9),
                  max_outstanding=64)
        return Pager(pool, table, amu, page_nbytes=page_nbytes)
    return factory


def test_chunk_boundaries_match_dense(setup):
    """Prompt lengths at exact page (4) and chunk (4/8) multiples +/- 1:
    every boundary case rides one engine run and must match the dense
    engine token-for-token."""
    cfg, params = setup
    lengths = [3, 4, 5, 7, 8, 9, 11, 12, 13, 15, 16, 17]
    prompts = [(np.arange(n) + n) % cfg.vocab_size for n in lengths]
    _, ref = _run(cfg, params, prompts,
                  paging=PagingConfig(enabled=False))
    for chunk in (4, 8):
        eng, out = _run(cfg, params, prompts,
                        paging=PagingConfig(page_size=4),
                        chunking=ChunkingConfig(chunk_tokens=chunk,
                                                chunk_slots=2))
        assert out == ref, f"chunk_tokens={chunk}"
        assert eng.stats["chunks"] > len(prompts)      # actually chunked
        assert eng.stats["prefills"] == 0              # no dense fallback
        assert eng.page_pool.n_free == eng.page_pool.n_pages


def test_single_chunk_covers_whole_prompt(setup):
    """chunk_tokens >= prompt: one chunk per prompt, still on the pool
    layout (the admission path never materialises dense KV)."""
    cfg, params = setup
    prompts = [np.arange(7) % cfg.vocab_size, np.arange(13) % cfg.vocab_size]
    _, ref = _run(cfg, params, prompts,
                  paging=PagingConfig(enabled=False))
    eng, out = _run(cfg, params, prompts,
                    paging=PagingConfig(page_size=4),
                    chunking=ChunkingConfig(chunk_tokens=64))
    assert out == ref
    assert eng.stats["chunks"] == len(prompts)
    assert eng.stats["prefills"] == 0


def test_mid_prefill_preemption_resumes_exactly(setup):
    """A half-prefilled sequence preempted by pool pressure parks its
    completed chunks, resumes, finishes the prompt and decodes — output
    identical to the dense engine (no prefill work redone densely)."""
    cfg, params = setup
    prompts = [(np.arange(16) % cfg.vocab_size),
               (np.arange(16) + 3) % cfg.vocab_size,
               (np.arange(12) + 5) % cfg.vocab_size]
    _, ref = _run(cfg, params, prompts, max_new=8,
                  paging=PagingConfig(enabled=False))
    eng, out = _run(cfg, params, prompts, max_new=8,
                    paging=PagingConfig(page_size=4, device_pages=6,
                                        hot_tail_pages=0),
                    chunking=ChunkingConfig(chunk_tokens=4,
                                            chunk_slots=2))
    assert eng.stats["prefill_preempts"] > 0   # cancelled mid-prefill
    assert eng.stats["resumes"] == eng.stats["preemptions"]
    assert out == ref
    assert eng.page_pool.n_free == eng.page_pool.n_pages


def test_mid_prefill_preemption_slow_pager(setup):
    """Same churn with multi-tick fetch latency: resumed prefills wait
    out ARRIVING pages before their next chunk runs."""
    cfg, params = setup
    prompts = [(np.arange(16) % cfg.vocab_size),
               (np.arange(16) + 3) % cfg.vocab_size,
               (np.arange(12) + 5) % cfg.vocab_size]
    _, ref = _run(cfg, params, prompts, max_new=8,
                  paging=PagingConfig(enabled=False))
    eng, out = _run(cfg, params, prompts, max_new=8,
                    paging=PagingConfig(
                        page_size=4, device_pages=6, hot_tail_pages=0,
                        pager_factory=_slow_pager_factory(2.5e-3)),
                    chunking=ChunkingConfig(chunk_tokens=4,
                                            chunk_slots=2))
    assert eng.stats["preemptions"] > 0
    assert out == ref


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "seamless-m4t-medium"])
def test_mixed_step_other_families(arch):
    """Hybrid (SSM carry threaded between chunks host-side) and enc-dec
    (cross-KV installed once at admission) also chunk-prefill on the
    pool layout, bit-compatible with their dense engines — including
    under preemption churn."""
    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.arange(8) % cfg.vocab_size,
               (np.arange(5) + 2) % cfg.vocab_size,
               (np.arange(8) + 4) % cfg.vocab_size]
    src = None
    if cfg.family == "encdec":
        rng = np.random.default_rng(0)
        src = [rng.standard_normal((len(p), cfg.d_model)).astype(np.float32)
               for p in prompts]

    def run(**kw):
        eng = Engine(cfg, params, EngineConfig(
            max_batch=2, max_len=32, prefill_buckets=(8,), **kw))
        for i, p in enumerate(prompts):
            kw2 = {"src_embeds": src[i]} if src is not None else {}
            eng.submit(p, max_new_tokens=6, **kw2)
        return eng, eng.run()

    _, ref = run(paging=PagingConfig(enabled=False))
    eng, out = run(paging=PagingConfig(page_size=4, device_pages=5,
                                       hot_tail_pages=1),
                   chunking=ChunkingConfig(chunk_tokens=4, chunk_slots=2))
    assert eng.chunking and eng.stats["chunks"] > 0
    assert eng.stats["preemptions"] > 0
    assert out == ref
    assert eng.page_pool.n_free == eng.page_pool.n_pages


def test_mixed_step_on_mesh_matches_dense_mesh_engine(setup):
    """On a real (2, 4) mesh the chunk-queue engine matches the legacy
    dense engine running on the same mesh (also a guard against the
    rope-over-sharded-projection SPMD miscompile jax 0.4.37 had, which
    scaled the chunk K by the data-axis size so every token diverged)."""
    import jax as _jax
    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 forced host devices")
    from repro.launch.mesh import make_mesh_compat
    cfg, params = setup
    mesh = make_mesh_compat((2, 4), ("data", "model"))
    prompts = [np.arange(7) % cfg.vocab_size,
               np.arange(13) % cfg.vocab_size,
               np.arange(16) % cfg.vocab_size]

    def run(**kw):
        eng = Engine(cfg, params, EngineConfig(
            max_batch=3, max_len=64, prefill_buckets=(16,), mesh=mesh,
            **kw))
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        return eng.run()

    ref = run(paging=PagingConfig(enabled=False))
    out = run(paging=PagingConfig(page_size=4),
              chunking=ChunkingConfig(chunk_tokens=4, chunk_slots=2))
    assert out == ref


def test_paged_prefill_kernel_matches_xla():
    """The scalar-prefetch flash kernel (interpret mode) agrees with the
    XLA gather path on valid rows, including windowed (SWA) masks and
    inert length-0 rows."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    N, page, Hkv, D, H, C, T, pps = 9, 4, 2, 16, 4, 3, 8, 6
    kp = jnp.asarray(rng.standard_normal((N, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((N, page, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((C, T, H, D)), jnp.float32)
    pt = np.full((C, pps), N - 1, np.int32)
    pt[0, :4] = [0, 1, 2, 3]
    pt[1, :2] = [4, 5]
    pt = jnp.asarray(pt)
    offset = jnp.asarray([8, 0, 0], jnp.int32)
    length = jnp.asarray([8, 5, 0], jnp.int32)
    for window in (0, 3):
        a = np.asarray(ops.paged_prefill_attention(
            q, kp, vp, pt, offset, length, window=window, impl="xla"))
        b = np.asarray(ops.paged_prefill_attention(
            q, kp, vp, pt, offset, length, window=window,
            impl="interpret"))
        for c, n in enumerate([8, 5, 0]):
            if n:
                np.testing.assert_allclose(a[c, :n], b[c, :n],
                                           atol=2e-6, rtol=2e-6)


def test_mixed_batch_sweep_ttft_improves():
    """The bench's acceptance row: at 2x request oversubscription the
    chunk-queue engine improves mean TTFT over serial dense prefill
    without losing decode throughput (deterministic virtual clock)."""
    from repro.paging.sim import simulate_mixed_batching
    r = simulate_mixed_batching(2.0)
    assert r["ttft_speedup"] > 1.0
    assert r["throughput_speedup"] >= 1.0
    # the gain grows with load: continuous batching is a queueing win
    r4 = simulate_mixed_batching(4.0)
    assert r4["ttft_speedup"] >= r["ttft_speedup"] * 0.95


def test_step_programs_carry_their_names(setup):
    """The decode and mixed programs lower to modules named after their
    functions (``jit_serve_decode``, ``jit_serve_mixed``): a profile
    tells them apart by the name of their ``XLA Modules`` events."""
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_len=64, prefill_buckets=(16,),
        paging=PagingConfig(page_size=8),
        chunking=ChunkingConfig(chunk_tokens=8)))
    toks = jnp.zeros((2, 1), jnp.int32)
    C = eng.chunk_slots
    chunk = {"tokens": jnp.zeros((C, 8), jnp.int32),
             "offset": jnp.zeros((C,), jnp.int32),
             "length": jnp.zeros((C,), jnp.int32),
             "page_rows": jnp.zeros((C, eng.pages_per_seq), jnp.int32)}
    decode = eng._decode.lower(params, eng.cache, toks).as_text()
    mixed = eng._mixed.lower(params, eng.cache, toks, chunk).as_text()
    assert "@jit_serve_decode" in decode
    assert "@jit_serve_mixed" in mixed
