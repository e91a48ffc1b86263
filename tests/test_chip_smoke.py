"""The chip path refuses to run anywhere but a TPU, and caches compiles
where it is told to.

* ``chip_smoke.py`` on the CPU exits non-zero, names the platform it
  found, and prints no result line;
* ``Engine`` on a TPU backend refuses the ``interpret`` and ``xla``
  kernel paths instead of running them;
* the persistent compilation cache goes to ``JAX_COMPILATION_CACHE_DIR``
  when it is set, else to one fixed directory in the checkout.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke
from repro.kernels import ops
from repro.models import init_params
from repro.serve import Engine, EngineConfig

ROOT = Path(__file__).resolve().parent.parent


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), **extra)
    return env


def test_chip_smoke_refuses_cpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         cwd=ROOT, env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "not a TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_engine_refuses_reference_paths_on_tpu(monkeypatch, impl):
    cfg = get_smoke("phi4-mini-3.8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="TPU backend"):
        Engine(cfg, params, EngineConfig(max_batch=2, max_len=64,
                                         kernel_impl=impl))


_CACHE_PROBE = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
sys.path.insert(0, {src!r})
from repro.launch.serve import use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(use_compile_cache(Path({root!r})))
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
"""


def _probe(tmp_path, **env):
    code = _CACHE_PROBE.format(src=str(ROOT / "src"),
                               root=str(tmp_path / "checkout"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=_env(**env))
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_follows_env(tmp_path):
    target = tmp_path / "cache_from_env"
    path = _probe(tmp_path, JAX_PLATFORMS="cpu",
                  JAX_COMPILATION_CACHE_DIR=str(target))
    assert path == str(target)
    assert any(target.iterdir())
    assert not (tmp_path / "checkout").exists()


def test_compile_cache_defaults_to_checkout(tmp_path):
    path = _probe(tmp_path, JAX_PLATFORMS="cpu")
    assert path == str(tmp_path / "checkout" / ".jax_cache")
    assert any(Path(path).iterdir())
