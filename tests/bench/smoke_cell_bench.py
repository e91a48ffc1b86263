"""A benchmark cell at the phi4-mini SMOKE sizes, for runs on the CPU."""

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


def smoke_cell(**traffic):
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    cell = "phi4-conv-overrun"
    return spec.Cell(
        name=cell, chips=1, config_name=CONFIG["name"],
        traffic_name="smoke", config=CONFIG, traffic=dict(TRAFFIC, **traffic),
        end_to_end=[m for m in b["end_to_end"]
                    if cell in m.get("workloads", [cell])],
        per_layer=[m for m in b["per_layer"]
                   if cell in m.get("workloads", [cell])])
