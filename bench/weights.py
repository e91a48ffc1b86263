"""Random weights of a dense decoder, made from ``--seed`` by the
benchmark itself.

The tree has the layout the serving engine takes (layer stacks stacked
on axis 0).  Every leaf is drawn from its own key, and every layer of a
stacked leaf from ``fold_in(leaf key, layer)``, so the engine's tree
(``bench/run.py:make_params``) and the plain reference are both made
one layer at a time and hold the same numbers.  Scales: norms are ones,
the embedding and output tables are ``N(0, 0.02^2)``, each projection
``N(0, 1 / d_in)``.  Values are drawn in float32 and rounded once to the
served dtype (bfloat16).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def padded_vocab(cfg: Dict[str, Any]) -> int:
    """The engine's vocabulary: ``vocab_size`` rounded up to 256."""
    return -(-int(cfg["vocab_size"]) // 256) * 256


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes of a configuration file, under short names."""
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": H,
            "Hkv": int(cfg["num_key_value_heads"]),
            "D": int(cfg.get("head_dim") or d // H),
            "ff": int(cfg["intermediate_size"]), "V": padded_vocab(cfg)}


# (path, shape of one layer, kind); kinds: "norm" ones, "table" 0.02,
# "proj" 1/sqrt(d_in) with d_in the first dimension
def layer_leaves(cfg) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
    z = dims(cfg)
    d, H, Hkv, D, ff = z["d"], z["H"], z["Hkv"], z["D"], z["ff"]
    return (("attn_norm/scale", (d,), "norm"),
            ("attn/q/w", (d, H * D), "proj"),
            ("attn/k/w", (d, Hkv * D), "proj"),
            ("attn/v/w", (d, Hkv * D), "proj"),
            ("attn/o/w", (H * D, d), "proj"),
            ("mlp_norm/scale", (d,), "norm"),
            ("mlp/gate/w", (d, ff), "proj"),
            ("mlp/up/w", (d, ff), "proj"),
            ("mlp/down/w", (ff, d), "proj"))


def top_leaves(cfg) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
    z = dims(cfg)
    out = [("embed/table", (z["V"], z["d"]), "table"),
           ("final_norm/scale", (z["d"],), "norm")]
    if not cfg.get("tie_word_embeddings", False):
        out.append(("lm_head/table", (z["V"], z["d"]), "table"))
    return tuple(out)


def base_key(seed: int):
    """A key from any whole number (more than 32 bits may be given)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, kind):
    if kind == "norm":
        return jnp.ones(shape, DTYPE)
    scale = 0.02 if kind == "table" else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(DTYPE)


def _leaf_key(key, group: int, i: int):
    return jax.random.fold_in(jax.random.fold_in(key, group), i)


def layer(cfg, key, l) -> Dict[str, jnp.ndarray]:
    """Layer ``l`` as a flat ``{path: array}`` dict."""
    return {path: _draw(jax.random.fold_in(_leaf_key(key, 1, i), l),
                        shape, kind)
            for i, (path, shape, kind) in enumerate(layer_leaves(cfg))}


def top(cfg, key) -> Dict[str, jnp.ndarray]:
    """Embedding, final norm and output table, flat."""
    return {path: _draw(_leaf_key(key, 0, i), shape, kind)
            for i, (path, shape, kind) in enumerate(top_leaves(cfg))}


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """``{"a/b": v}`` to ``{"a": {"b": v}}``."""
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out
