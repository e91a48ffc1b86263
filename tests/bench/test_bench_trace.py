"""The reduction from a device trace to busy time, kernel time, exposed
collective time and idle gaps: on hand-made events, and on a small
trace recorded on a TPU (tests/bench/data/record_trace.py)."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
MS = 1e6                                    # ns


def _made():
    ops = {0: [(0 * MS, 2 * MS, "fusion.1"),
               (1 * MS, 3 * MS, "all-reduce.7"),      # 1 ms exposed
               (5 * MS, 6 * MS, "paged_decode_attention.2"),
               (8 * MS, 9 * MS, "all-gather.1")],     # all exposed
           1: [(0 * MS, 4 * MS, "fusion.1")]}
    spans = [(0 * MS, 10 * MS, "bench/window"),
             (0 * MS, 7 * MS, "bench/step"),
             (7 * MS, 10 * MS, "bench/wait")]
    return trace.from_events(ops, spans)


def test_busy_idle_and_kernel_time():
    tr = _made()
    assert tr.window_s == pytest.approx(0.010)
    assert trace.busy_s(tr, 0) == pytest.approx(0.005)
    assert trace.busy_s(tr, 1) == pytest.approx(0.004)
    assert trace.mean_busy_s(tr) == pytest.approx(0.0045)
    assert trace.op_seconds(tr, "^paged_decode_attention") == \
        pytest.approx(0.001)
    assert trace.op_seconds(tr, "fusion") == pytest.approx(0.006)


def test_exposed_collective():
    exposed, busy = trace.exposed_collective_s(_made(), 0)
    assert exposed == pytest.approx(0.002)
    assert busy == pytest.approx(0.005)


def test_breakdown():
    tr = _made()
    ops = dict((n, t) for n, t in trace.device_ops(tr))
    assert ops["fusion.1"] == pytest.approx(0.003)      # (2 + 4) / 2 devices
    gaps = dict((n, t) for n, t in trace.idle_gaps(tr, 0))
    # 3-5 ms: inside the step, device work before and after
    assert gaps["bench/step: between device work"] == pytest.approx(0.002)
    # 6-7 ms: the step's device work is over
    assert gaps["bench/step: after device work"] == pytest.approx(0.001)
    # 7-8 and 9-10 ms: the client was waiting
    assert gaps["bench/wait"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(tr.window_s
                                               - trace.busy_s(tr, 0))


def test_op_names_and_containers():
    assert trace.op_name("%paged_decode_attention.6 = bf16[16,8,3,128] "
                         "custom-call(s32[16,256] %copy-done)") == \
        "paged_decode_attention.6"
    tr = trace.from_events({0: [(0.0, 4 * MS, "while.2"),
                                (1 * MS, 2 * MS, "fusion.3")]},
                           [(0.0, 4 * MS, "bench/window")])
    assert [n for n, _ in trace.device_ops(tr)] == ["fusion.3"]
    assert trace.busy_s(tr, 0) == pytest.approx(0.004)


def test_window_clips_events():
    tr = trace.from_events({0: [(-5 * MS, 1 * MS, "x"), (9 * MS, 20 * MS,
                                                          "y")]},
                           [(0.0, 10 * MS, "bench/window")])
    assert trace.busy_s(tr, 0) == pytest.approx(0.002)


def test_recorded_tpu_trace():
    if not DATA.exists():
        pytest.fail(f"{DATA} is missing: record it on a TPU with "
                    "tests/bench/data/record_trace.py")
    tr = trace.load(str(DATA))
    assert tr.ops, "no TPU device plane with an XLA Ops line"
    assert [n for _, _, n in tr.spans].count("bench/step") == 3
    busy = trace.busy_s(tr, min(tr.ops))
    assert 0 < busy < tr.window_s
    # three steps each leave at least 2 ms of host work after the device
    gaps = dict((n, t) for n, t in trace.idle_gaps(tr, min(tr.ops)))
    assert gaps.get("bench/step: after device work", 0) >= 0.006
    assert gaps.get("bench/wait", 0) >= 0.009
    assert trace.device_ops(tr)
