"""The harness at Mistral-Nemo's tensor-parallel shape: weights made a
layer at a time equal the one-call witness bit for bit (one device here,
four forced host devices in ``tp4_worker.py``), the weight programs fit a
v5e chip at Nemo's widths, a run on a (1, 4) mesh is correct while the
float8 control and a run with a fault planted under the engine (the
exchange between devices left out among them) are not."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import bench.run as run_mod
from bench import spec
from bench import weights as W
from repro.launch.mesh import make_mesh_compat
from smoke_cell_bench import CONFIG, NEMO_CONFIG, one_call_program

WORKER = Path(__file__).resolve().parent / "tp4_worker.py"
GB = 1e9


@pytest.fixture(scope="module")
def tp4():
    """The worker's RESULT lines, by mode (one process for all four)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(WORKER)], capture_output=True,
                         text=True, timeout=900, env=env, cwd=spec.ROOT)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr[-4000:]}"
    res = {}
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            _, mode, body = line.split(" ", 2)
            res[mode] = json.loads(body)
    return res


@pytest.mark.parametrize("cfg", [CONFIG, NEMO_CONFIG],
                         ids=["phi4-smoke", "nemo-smoke"])
def test_layer_at_a_time_equals_one_call_on_one_device(cfg):
    mcfg = run_mod.model_config(cfg)
    mesh = make_mesh_compat((1, 1), ("data", "model"),
                            devices=jax.devices()[:1])
    seed = 2**32 + 2**31 + 7
    new = run_mod.make_params(cfg, mcfg, mesh, seed)
    old = one_call_program(cfg, mcfg, mesh)(W.base_key(seed))
    assert jax.tree_util.tree_structure(new) == \
        jax.tree_util.tree_structure(old)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert a.dtype == b.dtype == W.DTYPE
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))


def test_layer_at_a_time_equals_one_call_on_four_devices(tp4):
    r = tp4["weights"]
    assert r["leaves"] == r["same_leaves"] == 12
    assert r["differ"] == []
    assert r["sharded"] == 9                # all but the three norms


def test_weight_programs_fit_a_chip_at_nemo_widths(tp4):
    temp = tp4["guard"]["temp_bytes"]
    assert {"fill", "zeros", "top:embed/table", "top:lm_head/table"} <= \
        set(temp)
    for name, n in temp.items():
        if name != "one_call":
            assert n <= 3 * GB, (name, n)
    # the guard holds the path it replaced: 27 GB a device at these widths
    assert temp["one_call"] > 3 * GB


def test_tp4_run_is_correct_and_control_is_not(tp4):
    r = tp4["cell"]
    limit = r["checks"]["max_logit_gap"]["limit"]
    assert r["program_gap"] <= limit, r
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == 4
    assert r["metrics"] == ["output_tok_s", "setup_s"]
    assert r["correct"] is False, r
    assert r["checks"]["max_logit_gap"]["value"] > limit


@pytest.mark.parametrize("fault", ["stale_decode", "altered_token",
                                   "exchange_left_out"])
def test_tp4_fault_is_not_correct(tp4, fault):
    r = tp4["fault"][fault]
    assert r["correct"] is False, r
    assert r["value"] > r["limit"]
