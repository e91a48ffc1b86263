"""``tp_collective_frac`` on a hand-made trace of four devices: a
collective that runs under a matmul is hidden and not counted, one that
runs alone is exposed and counted (also inside the layer loop's
``while``, which holds every op of a step), and the share is each
device's exposed time over its busy time, averaged over the devices.
And the four-chip cell loads, with its correctness limit from its
traffic file."""

import pytest

from bench import measure, spec, trace

MS = 1e6                                    # ns


def _ctx(ops):
    tr = trace.from_events(ops, [(0.0, 20 * MS, "bench/window")])
    return measure.Ctx(cell=spec.load_cell("nemo-tp4-chat"), peaks={},
                       chips=len(ops), t_start=0.0, w0=0.0, w1=0.02,
                       sent=[], steps=[], stats={}, pager_bytes=0,
                       memory_peak_bytes=0, trace=tr)


def _read(ctx):
    return spec.load_module("metrics", "tp_collective_frac").read(ctx)


def test_hidden_collective_is_not_counted():
    ops = {d: [(0.0, 4 * MS, "convolution.3"),
               (1 * MS, 3 * MS, "all-reduce.12")] for d in range(4)}
    assert _read(_ctx(ops)) == pytest.approx(0.0)


def test_exposed_collective_is_counted_and_averaged_over_devices():
    # device d: 4 ms of matmul, then (d + 1) ms of an all-reduce alone,
    # half of whose first millisecond still overlaps the matmul
    ops = {d: [(0.0, 4 * MS, "fusion.7"),
               (3.5 * MS, (4 + d + 1) * MS, "all-reduce-start.2")]
           for d in range(4)}
    want = [(d + 1) / (4 + d + 1) for d in range(4)]
    assert _read(_ctx(ops)) == pytest.approx(sum(want) / 4)
    for d in range(4):
        exposed, busy = trace.exposed_collective_s(_ctx(ops).trace, d)
        assert exposed == pytest.approx((d + 1) * 1e-3)
        assert busy == pytest.approx((4 + d + 1) * 1e-3)


def test_the_layer_loop_is_no_cover():
    """A step's all-reduces run inside the layer loop's ``while``, which
    spans them all: they are exposed when nothing else runs beside
    them, and hidden under a matmul that does."""
    ops = {d: [(0.0, 10 * MS, "while.33"),
               (0.0, 4 * MS, "fusion.248"),
               (4 * MS, 6 * MS, "all-reduce.17"),        # exposed
               (6 * MS, 9 * MS, "fusion.250"),
               (7 * MS, 8 * MS, "all-reduce.18")]        # hidden
           for d in range(4)}
    assert _read(_ctx(ops)) == pytest.approx(2 / 10)


def test_no_collective_and_no_trace_read_nothing():
    ops = {0: [(0.0, 4 * MS, "fusion.7")]}
    assert _read(_ctx(ops)) is None
    ctx = _ctx(ops)
    ctx.trace = None
    assert _read(ctx) is None


def test_four_chip_cell_takes_its_limit_from_the_traffic_file():
    cell = spec.load_cell("nemo-tp4-chat")
    assert cell.chips == 4 and cell.config_name == "mistral-nemo-12b"
    assert cell.engine["mesh"] == {"data": 1, "model": 4}
    assert "correct" not in cell.config
    assert cell.limit("max_logit_gap") == \
        cell.traffic["correct"]["max_logit_gap"]
    assert "tp_collective_frac" in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"output_tok_s",
                                                     "setup_s"}
