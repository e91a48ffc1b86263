"""Benchmark cells at the phi4-mini SMOKE sizes and at a Nemo-shaped smoke
size (four devices), for runs on the CPU, and the one-call weight program
that the layer-at-a-time weights are checked against."""

import json

from bench import spec

CONFIG = {
    "name": "phi4-smoke", "hidden_act": "silu", "hidden_size": 96,
    "intermediate_size": 256, "num_attention_heads": 6,
    "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": True,
    # 24 pages of 16 under 4 slots of 256 tokens: the pool overruns
    "engine": {"max_batch": 4, "max_len": 256, "page_size": 16,
               "chunk_tokens": 32, "device_pages": 24},
    # sound runs read 6e-3 to 1.3e-2 at this size, the float8 control
    # 7e-2 to 1e-1
    "correct": {"max_logit_gap": 0.03},
}

TRAFFIC = {
    "loop": "closed", "concurrency": 6,
    "prompt": {"dist": "uniform", "lo": 40, "hi": 80},
    "output": {"dist": "uniform", "lo": 20, "hi": 40},
    "warmup": {"min_s": 0.5, "until": {"preemptions": 1, "resumes": 1,
                                       "pager.bytes_moved_latency": 1},
               "max_s": 60},
    "sample": {"min_tokens": 60, "max_requests": 3, "resumed": True,
               "drain_s": 60},
    "trace_seconds": 1.5,
}


def _cell(cell, chips, config, traffic):
    """``config`` and ``traffic`` run as the cell ``cell`` of
    ``BENCHMARK.json``, with its metrics."""
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    return spec.Cell(
        name=cell, chips=chips, config_name=config["name"],
        traffic_name="smoke", config=config, traffic=traffic,
        end_to_end=[m for m in b["end_to_end"]
                    if cell in m.get("workloads", [cell])],
        per_layer=[m for m in b["per_layer"]
                   if cell in m.get("workloads", [cell])])


def smoke_cell(**traffic):
    return _cell("phi4-conv-overrun", 1, CONFIG, dict(TRAFFIC, **traffic))


# Mistral-Nemo's shape at a smoke size: head_dim is not hidden / heads,
# the output table is untied, rope theta 1e6; on a (1, 4) mesh each
# device holds 4 query and 2 KV heads, as a v5e chip does in the four-chip
# deployment of bench/configs/mistral-nemo-12b.json
NEMO_CONFIG = {
    "name": "nemo-smoke", "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "num_attention_heads": 16, "head_dim": 32,
    "num_key_value_heads": 8, "num_hidden_layers": 3, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "tie_word_embeddings": False,
    # the pool holds every slot's pages, as that deployment's does
    "engine": {"max_batch": 4, "max_len": 256, "page_size": 16,
               "chunk_tokens": 32, "device_pages": 64},
    # sound runs on 4 host devices read 0 to 6.7e-3 at this size, the
    # float8 control 8.9e-2 to 1.25e-1
    "correct": {"max_logit_gap": 0.03},
}

NEMO_TRAFFIC = {
    "loop": "closed", "concurrency": 6,
    "prompt": {"dist": "uniform", "lo": 40, "hi": 80},
    "output": {"dist": "uniform", "lo": 20, "hi": 40},
    "warmup": {"min_s": 0.5, "max_s": 60},
    "sample": {"min_tokens": 60, "max_requests": 3, "drain_s": 60},
    "trace_seconds": 1.5,
}


def nemo_smoke_cell():
    """Four devices, reporting the metrics of the conversation cell."""
    return _cell("phi4-conv-overrun", 4, NEMO_CONFIG, NEMO_TRAFFIC)


def one_call_program(cfg, mcfg, mesh):
    """The program that made the weights before the harness made them a
    layer at a time: every layer drawn at once under ``vmap``, the whole
    tree in one jitted call (of the key) into the engine's shardings.
    The witness that the layer-at-a-time tree holds the same numbers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.dist.sharding import param_specs
    from repro.dist.steps import abstract_params
    from bench import weights as W

    def tree(key):
        L = W.dims(cfg)["L"]
        out = W.nest(W.top(cfg, key))
        out["layers"] = W.nest(
            jax.vmap(lambda l: W.layer(cfg, key, l))(jnp.arange(L)))
        return out

    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_specs(mesh, abstract_params(mcfg)))
    return jax.jit(tree, out_shardings=shardings)
