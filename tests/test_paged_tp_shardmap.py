"""The paged attention kernels under ``shard_map`` at Mistral-Nemo's
tensor-parallel split: 32 query and 8 KV heads of 128 over ``model=4``,
so each device attends 8 query and 2 KV heads of a bf16 pool, as a v5e
chip does in ``bench/configs/mistral-nemo-12b.json``'s deployment.

The kernels run in interpret mode through ``kernels/ops.py:_paged_kernel``
inside a jitted call on a (data=1, model=4) mesh of four forced host
devices, in a subprocess of their own (the device count is fixed when
JAX starts): this file is also the worker,

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tests/test_paged_tp_shardmap.py

Each kernel is compared with its XLA path (no mesh) on ragged lengths
that begin and end the decode kernel's blocks of frames, with a shuffled
page table whose dead entries name a trash frame far larger than live KV.

The worker also builds an ``Engine`` on that mesh and reads where its
pool lies before the first step: each device must hold only its own KV
heads.  A pool made whole on one device and split by the first step
cannot be built at Mistral-Nemo's size: 10.7 GB whole beside 6.1 GB of
weights on a 16 GB v5e chip.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

H, HKV, D, PAGE, PPS = 32, 8, 128, 16, 40   # 40 pages: two blocks of 32
MODEL = 4
TOL = 2e-5


def _pool(rng, n):
    import jax.numpy as jnp
    kf = rng.standard_normal((n, PAGE, HKV, D)).astype(np.float32)
    vf = rng.standard_normal((n, PAGE, HKV, D)).astype(np.float32)
    kf[n - 1], vf[n - 1] = 300.0, 1e4           # the trash frame
    return jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)


def _table(rng, extents, n):
    pt = np.full((len(extents), PPS), n - 1, np.int32)
    frames = rng.permutation(n - 1)
    for b, tokens in enumerate(extents):
        live = -(-int(tokens) // PAGE)
        pt[b, :live] = frames[b * PPS:b * PPS + live]
    return pt


def _cases(rng):
    """(kernel, args, valid mask over the output) for each of decode,
    verify and chunked prefill."""
    import jax.numpy as jnp
    full = PPS * PAGE
    base = np.array([1, 511, 513, full, 0], np.int32)  # block edges at 512
    ver = np.minimum(base[:, None] + np.arange(4)[None, :], full)
    ver[base == 0] = 0
    n = base.size * PPS + 1
    kp, vp = _pool(rng, n)
    pt = jnp.asarray(_table(rng, ver.max(1), n))
    q = rng.standard_normal((base.size, 4, H, D)).astype(np.float32)
    live = base > 0
    out = [("decode", (jnp.asarray(q[:, 0]), kp, vp, pt, jnp.asarray(base)),
            live),
           ("verify", (jnp.asarray(q), kp, vp, pt, jnp.asarray(ver)), live)]
    # two prompt chunks of 64: the first from the start with 64 valid
    # tokens, the second at position 300 with 37
    offset, lengths, T = np.array([0, 300], np.int32), \
        np.array([64, 37], np.int32), 64
    n = 2 * PPS + 1
    kp, vp = _pool(rng, n)
    pt = jnp.asarray(_table(rng, offset + T, n))
    qc = rng.standard_normal((2, T, H, D)).astype(np.float32)
    valid = np.arange(T)[None, :] < lengths[:, None]
    out.append(("prefill", (jnp.asarray(qc), kp, vp, pt,
                            jnp.asarray(offset), jnp.asarray(lengths)),
                valid))
    return out


def worker():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels import ops
    from repro.launch.mesh import make_mesh_compat

    seen = {}
    for name in ("_paged_decode_attn", "_paged_verify_attn",
                 "_paged_prefill_flash"):
        def record(q, kp, *a, _f=getattr(ops, name), _n=name, **kw):
            seen[_n] = {"q": list(q.shape), "pool": list(kp.shape)}
            return _f(q, kp, *a, **kw)
        setattr(ops, name, record)
    fns = {"decode": ops.paged_decode_attention,
           "verify": ops.paged_verify_attention,
           "prefill": ops.paged_prefill_attention}
    heads_axis = {"decode": 1, "verify": 2, "prefill": 2}
    mesh = make_mesh_compat((1, MODEL), ("data", "model"),
                            devices=jax.devices()[:MODEL])
    for kernel, args, valid in _cases(np.random.default_rng(16)):
        fn = fns[kernel]
        want = np.asarray(fn(*args, impl="xla"))
        seen.clear()
        q_spec = P(*("model" if d == heads_axis[kernel] else None
                     for d in range(args[0].ndim)))
        pool = NamedSharding(mesh, P(None, None, "model", None))
        placed = (jax.device_put(args[0], NamedSharding(mesh, q_spec)),
                  jax.device_put(args[1], pool),
                  jax.device_put(args[2], pool)) + args[3:]
        with mesh:
            got = jax.jit(lambda *a: fn(*a, impl="interpret"))(*placed)
        got = np.asarray(got)
        err = float(np.max(np.abs(got[valid] - want[valid])))
        print("RESULT " + json.dumps({
            "kernel": kernel, "max_err": err,
            "scale": float(np.max(np.abs(want[valid]))),
            "dead_rows_zero": bool(np.all(got[~valid] == 0.0))
            if kernel != "prefill" else True,
            "local": next(iter(seen.values()), None)}), flush=True)


def engine_pool():
    import dataclasses

    import jax
    from repro.configs import get_smoke
    from repro.dist.steps import init_params_sharded
    from repro.launch.mesh import make_mesh_compat
    from repro.serve import Engine, EngineConfig, PagingConfig

    cfg = dataclasses.replace(get_smoke("mistral-nemo-12b"), num_heads=16,
                              num_kv_heads=8)
    mesh = make_mesh_compat((1, MODEL), ("data", "model"),
                            devices=jax.devices()[:MODEL])
    eng = Engine(cfg, init_params_sharded(cfg, mesh, jax.random.PRNGKey(0)),
                 EngineConfig(max_batch=2, max_len=64, mesh=mesh,
                              paging=PagingConfig(page_size=16,
                                                  device_pages=8)))
    pool = {n: sorted({(s.device.id, s.data.shape[3])
                       for s in eng.cache.kv[n].addressable_shards})
            for n in ("k_pages", "v_pages")}
    for p in ([1, 2, 3], [4, 5]):
        eng.submit(np.array(p), max_new_tokens=3)
    done = eng.run()
    print("RESULT " + json.dumps({
        "kernel": "engine", "pool": pool, "heads": cfg.num_kv_heads,
        "served": sorted(len(t) for t in done.values())}), flush=True)


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parent.parent / "src"))
    out = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr[-4000:]}"
    res = [json.loads(line.split(" ", 1)[1])
           for line in out.stdout.splitlines() if line.startswith("RESULT ")]
    return {r["kernel"]: r for r in res}


@pytest.mark.parametrize("kernel", ["decode", "verify", "prefill"])
def test_kernel_under_shard_map_matches_xla(results, kernel):
    r = results[kernel]
    # each device ran the kernel on its own 8 query and 2 KV heads
    assert r["local"]["q"][1 if kernel == "prefill" else -2] == H // MODEL
    assert r["local"]["pool"][2] == HKV // MODEL
    assert r["max_err"] <= TOL, r
    assert r["scale"] < 100, r      # the trash frame was never folded in
    assert r["dead_rows_zero"], r


def test_engine_pool_is_made_in_its_shards(results):
    r = results["engine"]
    share = r["heads"] // MODEL
    for name in ("k_pages", "v_pages"):
        assert r["pool"][name] == [[d, share] for d in range(MODEL)], r
    assert r["served"] == [3, 3]


def test_one_device_mesh_takes_the_plain_call():
    """A mesh of one device (the one-chip cells) calls the kernel on all
    heads, outside ``shard_map``."""
    import jax
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh_compat

    args, seen = _cases(np.random.default_rng(1))[0][1], []
    real = ops._paged_decode_attn

    def record(q, kp, *a, **kw):
        seen.append((q.shape, kp.shape))
        return real(q, kp, *a, **kw)

    ops._paged_decode_attn = record
    try:
        with make_mesh_compat((1, 1), ("data", "model"),
                              devices=jax.devices()[:1]):
            got = ops.paged_decode_attention(*args, impl="interpret")
    finally:
        ops._paged_decode_attn = real
    want = ops.paged_decode_attention(*args, impl="xla")
    assert seen == [((5, H, D), (args[1].shape[0], PAGE, HKV, D))]
    live = np.asarray(args[4]) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], rtol=TOL, atol=TOL)


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    import jax
    if len(jax.devices()) < MODEL:
        sys.exit(f"needs {MODEL} devices, JAX found {len(jax.devices())}")
    worker()
    engine_pool()
