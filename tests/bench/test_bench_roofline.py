"""The operation and byte counters at a known shape."""

from types import SimpleNamespace

from bench import measure, spec
from bench.loop import StepRec

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 128, "num_hidden_layers": 2,
       "vocab_size": 300}


def ctx(steps, trace=None, peaks=None):
    cell = SimpleNamespace(config=CFG)
    return measure.Ctx(cell=cell, peaks=peaks or {}, chips=1, t_start=0.0,
                       w0=0.0, w1=2.0, sent=[], steps=steps, stats={},
                       pager_bytes=0, memory_peak_bytes=0, trace=trace)


STEPS = [StepRec(0.0, 0.1, False, [10, 20], []),
         StepRec(0.1, 0.3, True, [5], [(0, 8, False), (32, 4, True)])]


def test_decode_attention_work():
    flops, nbytes = spec.load_module("roofline",
                                     "paged_decode_attention").work(ctx(STEPS))
    H, Hkv, D, L = 4, 2, 16, 2
    n = 10 + 20 + 5
    assert flops == L * 4 * H * D * n
    assert nbytes == L * (2 * n * Hkv * D * 2 + 3 * 2 * H * D * 2)


def test_prefill_attention_work():
    flops, nbytes = spec.load_module("roofline",
                                     "paged_prefill_flash").work(ctx(STEPS))
    H, Hkv, D, L = 4, 2, 16, 2
    pairs = (0 * 8 + 8 * 9 // 2) + (32 * 4 + 4 * 5 // 2)
    assert flops == L * 4 * H * D * pairs
    kv = (0 + 8) + (32 + 4)
    assert nbytes == L * (2 * kv * Hkv * D * 2 + 2 * (8 + 4) * H * D * 2)


def test_model_step_flops():
    flops, _ = spec.load_module("roofline", "model_step").work(ctx(STEPS))
    d, H, Hkv, D, ff, L, V = 64, 4, 2, 16, 128, 2, 512   # V padded to 256s
    per_layer = d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff
    dec = sum(2 * per_layer * L + 4 * H * D * n * L + 2 * d * V
              for n in (10, 20, 5))
    chunk = sum(2 * per_layer * L * T
                + 4 * H * D * (o * T + T * (T + 1) // 2) * L
                for o, T in ((0, 8), (32, 4))) + 2 * d * V
    assert flops == dec + chunk


def test_roofline_share_and_silence():
    from bench import trace
    # 35 decode rows' work at 1 FLOP/ns and 1 byte/ns "peaks"
    tr = trace.from_events({0: [(0.0, 1e9, "paged_decode_attention.3")]},
                           [(0.0, 2e9, "bench/window")])
    c = ctx(STEPS, tr, {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9})
    flops, nbytes = spec.load_module("roofline",
                                     "paged_decode_attention").work(c)
    assert c.roofline("paged_decode_attention") == max(flops, nbytes) / 1e9
    # a kernel that did not run in the trace gives no reading, never 0
    assert c.roofline("paged_prefill_flash") is None
    assert ctx(STEPS).roofline("paged_decode_attention") is None
