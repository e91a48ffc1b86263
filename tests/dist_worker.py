"""Worker script for distributed tests — run via subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process keeps its single-device view.

Each mode exercises one distributed behaviour on a real (2,4) or (2,2,2)
host-device mesh and prints machine-checkable lines.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def make_mesh(shape, axes):
    from repro.launch.mesh import make_mesh_compat
    return make_mesh_compat(shape, axes)


def mode_train_step():
    """Tiny model, real sharded train step on (data=2, model=4)."""
    from repro.configs import get_smoke
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.dist.steps import make_train_step
    from repro.models.model import init_params
    from repro.optim.adamw import adamw_init

    cfg = get_smoke("olmoe-1b-7b")          # MoE: exercises EP sharding
    shape = ShapeConfig("t", 32, 4, "train")
    tcfg = TrainConfig(microbatches=2, grad_compression="bf16", zero1=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh:
        fn, specs = make_train_step(cfg, tcfg, mesh, shape)
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=jax.tree_util.tree_map(
                             lambda s: NamedSharding(mesh, s),
                             specs["params"]))(jax.random.PRNGKey(0))
        opt = jax.jit(adamw_init, out_shardings=jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs["opt"]))(params)
        batch = {
            "tokens": jnp.zeros((4, 32), jnp.int32),
            "labels": jnp.ones((4, 32), jnp.int32),
        }
        batch = {k: jax.device_put(v, NamedSharding(mesh, specs["batch"][k]))
                 for k, v in batch.items()}
        p2, o2, metrics = fn(params, opt, batch)
        loss1 = float(metrics["loss"])
        p3, o3, metrics = fn(p2, o2, batch)
        loss2 = float(metrics["loss"])
    # some leaf must actually be sharded over model
    sharded = any(
        "model" in str(leaf.sharding.spec)
        for leaf in jax.tree_util.tree_leaves(p3)
        if hasattr(leaf, "sharding"))
    print(f"RESULT train loss1={loss1:.4f} loss2={loss2:.4f} "
          f"finite={np.isfinite(loss1) and np.isfinite(loss2)} "
          f"improved={loss2 < loss1} sharded={sharded}")


def mode_serve_step():
    from repro.configs import get_smoke
    from repro.configs.base import ShapeConfig
    from repro.dist.steps import make_serve_step, make_prefill_step
    from repro.models.model import init_params

    cfg = get_smoke("mistral-nemo-12b")
    shape = ShapeConfig("d", 64, 4, "decode")
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh:
        pf, pspecs = make_prefill_step(cfg, mesh,
                                       ShapeConfig("p", 64, 4, "prefill"))
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=jax.tree_util.tree_map(
                             lambda s: NamedSharding(mesh, s),
                             pspecs["params"]))(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.zeros((4, 64), jnp.int32),
                 "labels": jnp.zeros((4, 64), jnp.int32)}
        logits, cache = pf(params, batch)
        fn, _ = make_serve_step(cfg, mesh, shape)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        l2, cache = fn(params, cache, tok)
        l3, cache = fn(params, cache,
                       jnp.argmax(l2, -1)[:, None].astype(jnp.int32))
    print(f"RESULT serve finite={bool(jnp.isfinite(l3).all())} "
          f"pos={int(cache.pos[0])} shape={l3.shape[0]}x{l3.shape[1]}")


def mode_prefill_equality():
    """Full-sequence prefill on a (2, 4) mesh must match single-device
    prefill — the regression guard for the jax-0.4.37 SPMD rope
    miscompile on the prefill/train path: there, rope on a
    model-sharded projection brought layer-0 k back scaled by exactly
    the data-axis size (2x) on this mesh shape."""
    from repro.configs import get_smoke
    from repro.configs.base import ShapeConfig
    from repro.dist.steps import make_prefill_step
    from repro.models.model import init_params, prefill

    cfg = get_smoke("phi4-mini-3.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = np.arange(16, dtype=np.int32)[None, :] % cfg.vocab_size
    batch = {"tokens": jnp.asarray(np.repeat(toks, 2, axis=0))}
    logits_ref, cache_ref = jax.jit(
        lambda p, b: prefill(p, cfg, b, max_len=32))(params, batch)
    kref = np.asarray(cache_ref.kv["k"])

    mesh = make_mesh((2, 4), ("data", "model"))
    pf, _ = make_prefill_step(cfg, mesh, ShapeConfig("p", 16, 2, "prefill"),
                              max_len=32)
    logits, cache = pf(params, batch)
    k = np.asarray(cache.kv["k"])
    # the miscompile scales k by the data-axis size; bf16 layer compute
    # leaves only rounding-level differences when correct
    ratio = float(np.abs(k).sum() / np.abs(kref).sum())
    logits_ok = bool(np.allclose(np.asarray(logits), np.asarray(logits_ref),
                                 atol=2e-2))
    k_ok = bool(np.abs(k - kref).max() < 0.1)
    print(f"RESULT prefill_eq ratio={ratio:.3f} logits_ok={logits_ok} "
          f"k_ok={k_ok}")


def mode_engine():
    """Serving engine with its decode step mesh-sharded over (2, 4):
    the Engine builds its step via dist.steps.make_serve_step, so params
    placed TP-sharded must stay sharded across decode steps."""
    from repro.configs import get_smoke
    from repro.dist.sharding import param_specs
    from repro.dist.steps import abstract_params
    from repro.models.model import init_params
    from repro.serve.config import EngineConfig, PagingConfig
    from repro.serve.engine import Engine

    cfg = get_smoke("mistral-nemo-12b")
    mesh = make_mesh((2, 4), ("data", "model"))
    pspecs = param_specs(mesh, abstract_params(cfg))
    with mesh:
        params = jax.jit(lambda k: init_params(cfg, k),
                         out_shardings=jax.tree_util.tree_map(
                             lambda s: NamedSharding(mesh, s),
                             pspecs))(jax.random.PRNGKey(0))
        eng = Engine(cfg, params, EngineConfig(
            max_batch=3, max_len=64, mesh=mesh, prefill_buckets=(16,),
            paging=PagingConfig(page_size=8, device_pages=9)))
        for i in range(5):
            eng.submit(np.arange(4 + i) % cfg.vocab_size, max_new_tokens=6)
        out = eng.run()
    sharded = any("model" in str(leaf.sharding.spec)
                  for leaf in jax.tree_util.tree_leaves(params)
                  if hasattr(leaf, "sharding"))
    lens = sorted(len(v) for v in out.values())
    print(f"RESULT engine done={len(out)} lens={lens} sharded={sharded} "
          f"steps={eng.stats['steps']} shared={eng.stats['steps'] < 5 * 6}")


def mode_elastic():
    """Save on (2,4), restore and step on (1,4): elastic DP shrink."""
    import tempfile
    from repro.checkpoint.checkpoint import restore, save
    from repro.configs import get_smoke
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.dist.steps import make_train_step
    from repro.models.model import init_params
    from repro.optim.adamw import adamw_init
    from repro.runtime.fault_tolerance import elastic_plan

    cfg = get_smoke("phi4-mini-3.8b")
    shape = ShapeConfig("t", 32, 4, "train")
    tcfg = TrainConfig()
    d = tempfile.mkdtemp()

    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh:
        fn, specs = make_train_step(cfg, tcfg, mesh, shape, donate=False)
        shard = lambda t, s: jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), t, s)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.ones((4, 32), jnp.int32)}
        p2, o2, m = fn(shard(params, specs["params"]),
                       shard(opt, specs["opt"]), shard(batch, specs["batch"]))
        save(d, 1, (p2, o2), metadata={"step": 1, "loss": float(m["loss"])})

    plan = elastic_plan((2, 4), ("data", "model"), 4)
    mesh2 = make_mesh(plan.new_shape, plan.axes)
    with mesh2:
        fn2, specs2 = make_train_step(cfg, tcfg, mesh2, shape, donate=False)
        (p_r, o_r), meta = restore(d, target=(params, opt))
        p_r = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(jnp.asarray(x),
                                         NamedSharding(mesh2, sp)),
            p_r, specs2["params"])
        o_r = jax.tree_util.tree_map(
            lambda x, sp: jax.device_put(jnp.asarray(x),
                                         NamedSharding(mesh2, sp)),
            o_r, specs2["opt"])
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.ones((4, 32), jnp.int32)}
        p3, o3, m2 = fn2(p_r, o_r, jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(
                mesh2, P())), batch))
        print(f"RESULT elastic new_shape={plan.new_shape} "
              f"step={int(o3.step)} finite={bool(np.isfinite(float(m2['loss'])))}")


def mode_multipod_specs():
    """Param/opt specs on a (2,2,2) pod mesh: ZeRO over (pod,data)."""
    from repro.configs import get_config
    from repro.dist.sharding import opt_state_specs, param_specs
    from repro.dist.steps import abstract_params

    cfg = get_config("mistral-nemo-12b")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    pshapes = abstract_params(cfg)
    pspecs = param_specs(mesh, pshapes)
    ospecs = opt_state_specs(mesh, pshapes, zero1=True)
    flat_p = jax.tree_util.tree_leaves(pspecs)
    flat_m = jax.tree_util.tree_leaves(ospecs.m)
    n_model = sum("model" in str(s) for s in flat_p)
    n_zero = sum(("pod" in str(s) or "data" in str(s)) for s in flat_m)
    print(f"RESULT specs model_sharded={n_model} zero_sharded={n_zero} "
          f"total={len(flat_p)}")


if __name__ == "__main__":
    {"train": mode_train_step, "serve": mode_serve_step,
     "engine": mode_engine, "elastic": mode_elastic,
     "specs": mode_multipod_specs,
     "prefill_eq": mode_prefill_equality}[sys.argv[1]]()
