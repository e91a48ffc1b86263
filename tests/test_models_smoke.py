"""Per-architecture smoke tests: reduced same-family config, one forward
+ one backward (train) step and a prefill+decode step on CPU; asserts
output shapes and absence of NaNs.  The full configs are checked here
only for analytical invariants, with nothing allocated."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.models import (decode_step, init_params, prefill, train_loss,
                          count_params)


def _batch(cfg, B=2, S=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "encdec":
        batch["src_embeds"] = jax.random.normal(ks[2], (B, S, cfg.d_model),
                                                jnp.float32)
    if cfg.mrope_sections:
        batch["positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert count_params(params) > 0
    batch = _batch(cfg)

    def loss_fn(p):
        loss, metrics = train_loss(p, cfg, batch, remat="block")
        return loss, metrics

    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves, f"{arch}: empty grads"
    for g in leaves:
        assert jnp.isfinite(g).all(), f"{arch}: non-finite grad"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_smoke(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, cache = jax.jit(lambda p, b: prefill(p, cfg, b))(params, batch)
    assert logits.shape == (B, cfg.padded_vocab)
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    for _ in range(3):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert logits.shape == (B, cfg.padded_vocab)
    assert jnp.isfinite(logits).all()
    assert int(cache.pos[0]) == S + 3


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_sanity(arch):
    """Full configs: no allocation — only analytical invariants."""
    cfg = get_config(arch)
    assert cfg.padded_vocab % 256 == 0
    assert cfg.param_count() > 0
    if cfg.num_experts:
        assert cfg.active_param_count() < cfg.param_count()
    if cfg.family in ("dense", "moe"):
        assert cfg.num_heads % cfg.num_kv_heads == 0
