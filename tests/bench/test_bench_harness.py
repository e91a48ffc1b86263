"""A whole run of the harness on the CPU at the phi4-mini SMOKE sizes,
called through its functions (the command refuses a CPU): the loop, the
warm-up into an overrun pool, the window, the sample with a parked and
resumed request, and the comparison with the reference and its float8
control."""

import os
import subprocess
import sys

import numpy as np

from bench import check, reference, spec
from bench.run import run_cell
from smoke_cell_bench import CONFIG, smoke_cell

SEED = 2**31 + 99


def test_run_is_correct_and_control_is_not():
    cell = smoke_cell()
    out = run_cell(cell, SEED, 1.5, False, control=True)
    limit = cell.limit("max_logit_gap")
    # the program's own served tokens lie within the limit
    assert out["program_gap"] <= limit, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    # the float8 control, put in the program's place on the same tokens,
    # is judged by the run's own comparison and comes out not correct
    assert out["correct"] is False, out
    assert out["checks"]["max_logit_gap"]["value"] > limit


def test_traced_run_reports_per_layer_metrics():
    out = run_cell(smoke_cell(), SEED + 1, 1.5, True)
    assert out["correct"], out
    m = out["metrics"]
    assert {"step_ms.decode.tok", "preempt_per_ktok",
            "pager_mb_per_ktok"} <= set(m)
    # device metrics need a device trace: none on the CPU, none reported
    assert "device_idle_frac.tok" not in m and "mfu" not in m
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _numpy_logits(cfg, seed, toks):
    """The same equations in float64 numpy, written out once more."""
    import jax
    from bench import weights as W
    z = W.dims(cfg)
    key = W.base_key(seed)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    top = {k: f64(v) for k, v in W.top(cfg, key).items()}
    H, Hkv, D = z["H"], z["Hkv"], z["D"]
    S = len(toks)
    x = top["embed/table"][toks]
    inv = 1.0 / 10000.0 ** (np.arange(0, D, 2) / D)
    ang = np.arange(S)[:, None] * inv
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

    def rope(t):
        t1, t2 = t[..., :D // 2], t[..., D // 2:]
        return np.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    def rms(t):
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True) + 1e-5)

    for l in range(z["L"]):
        w = {k: f64(v) for k, v in W.layer(cfg, key, l).items()}
        h = rms(x)
        q = rope((h @ w["attn/q/w"]).reshape(S, H, D))
        k = rope((h @ w["attn/k/w"]).reshape(S, Hkv, D))
        v = (h @ w["attn/v/w"]).reshape(S, Hkv, D)
        out = np.zeros((S, H, D))
        for hh in range(H):
            s = q[:, hh] @ k[:, hh // (H // Hkv)].T / np.sqrt(D)
            s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            kv = hh // (H // Hkv)
            out[:, hh] = (p / p.sum(-1, keepdims=True)) @ v[:, kv]
        x = x + out.reshape(S, H * D) @ w["attn/o/w"]
        h = rms(x)
        g = h @ w["mlp/gate/w"]
        x = x + (g / (1 + np.exp(-g)) * (h @ w["mlp/up/w"])) @ w["mlp/down/w"]
    del jax
    return rms(x) @ top.get("lm_head/table", top["embed/table"]).T


def test_reference_follows_the_equations():
    cfg = dict(CONFIG, num_hidden_layers=2)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 512, 24).astype(np.int32)
    logits = _numpy_logits(cfg, 7, toks)
    n_prompt = 16
    res = reference.logit_gaps(cfg, 7, [(toks, n_prompt)], control=True)[0]
    rows = logits[n_prompt - 1:len(toks) - 1]
    want = rows.max(-1) - rows[np.arange(len(rows)), toks[n_prompt:]]
    assert np.allclose(res["served_gap"], want, atol=1e-4)
    assert (res["control_gap"] >= 0).all()
    # the reference's own best next token reads a gap of 0
    best = np.append(toks[:n_prompt], rows[0].argmax())
    res = reference.logit_gaps(cfg, 7, [(best, n_prompt)])[0]
    assert res["served_gap"].tolist() == [0.0]


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "phi4-conv-overrun", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=spec.ROOT, env=env)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert out.stdout.strip() == ""
