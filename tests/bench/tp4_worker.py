"""The harness on a (data=1, model=4) mesh of four forced host devices,
in a process of its own (the device count is fixed when JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tests/bench/tp4_worker.py

Prints one ``RESULT <mode> <json>`` line for each of:

* ``weights``: the layer-at-a-time tree against the one-call witness, leaf
  by leaf, bit for bit and sharding for sharding;
* ``guard``: the weight programs compiled (not run) at Mistral-Nemo's
  widths from ``bench/configs/mistral-nemo-12b.json``, their temporary
  bytes per device, and the one-call program's;
* ``cell``: a run of the Nemo-shaped smoke cell with the float8 control in
  the program's place, and the program's own widest gap in that run;
* ``fault``: a run with each fault the cell can have planted under the
  engine: decode that keeps its state, a token altered where it is
  produced, the exchange between devices left out.
"""

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent.parent / "src"), str(HERE.parent.parent),
                str(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import bench.run as run_mod  # noqa: E402
from bench import spec  # noqa: E402
from bench import weights as W  # noqa: E402
from repro.launch.mesh import make_mesh_compat  # noqa: E402
from smoke_cell_bench import (NEMO_CONFIG, nemo_smoke_cell,  # noqa: E402
                              one_call_program)

SEED = 2**33 + 15


def result(mode, **kw):
    print(f"RESULT {mode} {json.dumps(kw)}", flush=True)


def mesh4():
    return make_mesh_compat((1, 4), ("data", "model"),
                            devices=jax.devices()[:4])


def weights():
    cfg, mesh = NEMO_CONFIG, mesh4()
    mcfg = run_mod.model_config(cfg)
    new = run_mod.make_params(cfg, mcfg, mesh, SEED)
    old = one_call_program(cfg, mcfg, mesh)(W.base_key(SEED))
    flat_new = jax.tree_util.tree_leaves_with_path(new)
    flat_old = dict(jax.tree_util.tree_leaves_with_path(old))
    differ = [jax.tree_util.keystr(p) for p, a in flat_new
              if not (a.sharding.is_equivalent_to(flat_old[p].sharding,
                                                  a.ndim)
                      and np.array_equal(
                          np.asarray(a).view(np.uint16),
                          np.asarray(flat_old[p]).view(np.uint16)))]
    sharded = sum(1 for _, a in flat_new
                  if len(a.sharding.device_set) == 4
                  and not a.sharding.is_fully_replicated)
    result("weights", leaves=len(flat_new), same_leaves=len(flat_old),
           differ=differ, sharded=sharded)


def guard():
    cfg = spec.load_json(spec.BENCH / "configs" / "mistral-nemo-12b.json")
    mesh = mesh4()
    mcfg = run_mod.model_config(cfg)
    top, zeros, fill = run_mod.weight_programs(cfg, mcfg, mesh)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    layers = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(zeros), zeros.lower().compile().output_shardings)
    temp = {f"top:{p}": prog.lower(key) for p, prog in top.items()}
    temp["zeros"] = zeros.lower()
    temp["fill"] = fill.lower(layers, key,
                              jax.ShapeDtypeStruct((), jnp.int32))
    temp["one_call"] = one_call_program(cfg, mcfg, mesh).lower(key)
    result("guard", temp_bytes={
        name: int(low.compile().memory_analysis().temp_size_in_bytes)
        for name, low in temp.items()})


def cell():
    out = run_mod.run_cell(nemo_smoke_cell(), SEED + 1, 1.5, False,
                           devices=jax.devices()[:4], control=True)
    result("cell", correct=out["correct"], program_gap=out["program_gap"],
           checks=out["checks"], attempted=out["attempted"],
           failed=out["failed"], metrics=sorted(out["metrics"]),
           device=out["device"])


def _exchange_left_out(eng):
    """Each row-parallel projection (o, down) as chip 0's partial sum
    alone, as if no all-reduce had added the other chips' parts: the
    input rows the other three chips hold are zeroed."""
    for group, name in (("attn", "o"), ("mlp", "down")):
        leaf = eng.params["layers"][group][name]
        w = leaf["w"]
        keep = (jnp.arange(w.shape[1]) < w.shape[1] // 4)[None, :, None]
        leaf["w"] = jax.jit(lambda w: jnp.where(keep, w, 0),
                            out_shardings=w.sharding)(w)


def fault():
    """Each fault the cell can have, planted under the engine."""
    from test_bench_faults import _altered_token, _stale_decode
    make = run_mod.make_engine
    got = {}
    for i, plant in enumerate((_stale_decode, _altered_token,
                               _exchange_left_out)):
        def faulty(*a, **kw):
            eng = make(*a, **kw)
            plant(eng)
            return eng
        run_mod.make_engine = faulty
        try:
            out = run_mod.run_cell(nemo_smoke_cell(), SEED + 2 + i, 1.5,
                                   False, devices=jax.devices()[:4])
        finally:
            run_mod.make_engine = make
        got[plant.__name__.strip("_")] = {
            "correct": out["correct"], **out["checks"]["max_logit_gap"]}
    result("fault", **got)


if __name__ == "__main__":
    if len(jax.devices()) < 4:
        sys.exit(f"needs 4 devices, JAX found {len(jax.devices())}; set "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for mode in sys.argv[1:] or ("weights", "guard", "cell", "fault"):
        globals()[mode]()
