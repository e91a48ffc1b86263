"""BENCHMARK.json and the files it names hold together: every name is
well formed, every configuration, traffic mix and metric has its file,
each metric's cells report the end-to-end metric it moves, and the
table of peaks refuses an unknown device."""

import json
import re

import pytest

from bench import spec, weights

B = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][1] == "bench/run.py" and len(B["command"]) <= 32
    assert all((spec.ROOT / p).is_dir() for p in B["paths"])
    assert 1 <= B["run_seconds"] <= 51
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    n4 = sum(w["chips"] == 4 for w in B["workloads"])
    assert n4 <= max(1, len(B["workloads"]) // 2)


def test_names_units_and_lines():
    entries = B["configs"] + B["workloads"] + B["end_to_end"] + \
        B["per_layer"]
    names = [e["name"] for e in entries]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in B[group]]
        assert len(ns) == len(set(ns))
    for e in B["configs"] + B["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_reports(w):
    cell = spec.load_cell(w["name"])
    assert cell.chips in (1, 4)
    assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    cfg = cell.config
    entry = next(c for c in B["configs"] if c["name"] == w["config"])
    assert cfg["reduced"] == entry["reduced"] and cfg["source"] == \
        entry["source"]
    assert weights.dims(cfg)["V"] % 256 == 0
    assert cell.limit("max_logit_gap") > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], w["name"])
    for m in cell.end_to_end + cell.per_layer:
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").exists()
        assert hasattr(spec.load_module("metrics", m["name"]), "read")


def test_metric_layers_are_named_alike():
    layers = {}
    for m in B["per_layer"]:
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")
