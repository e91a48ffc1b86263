"""What a run reads: the cell from ``BENCHMARK.json``, its configuration
and traffic files, and the table of peaks.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   the model and engine sizes as run;
* ``bench/traffic/<traffic>.json``  the parameters of the traffic mix;
* ``bench/metrics/<metric>.py``     the reader of one per-layer metric;
* ``bench/roofline/<kernel>.py``    the operations and bytes of one kernel.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """The benchmark's files do not describe the requested run."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def engine(self) -> Dict[str, Any]:
        return self.config["engine"]

    def limit(self, key: str) -> float:
        """A correctness limit: the traffic file's, else the config's."""
        for src in (self.traffic, self.config):
            if key in src.get("correct", {}):
                return float(src["correct"][key])
        raise SpecError(f"no correctness limit {key!r} for {self.name}")


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench_json: Optional[Path] = None) -> Cell:
    spec = load_json(bench_json or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=load_json(ROOT / cfg_entry["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; an unknown
    device is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def load_module(kind: str, name: str):
    """The reader ``bench/<kind>/<name>.py`` as a module (names may
    hold dots, so it is loaded by path)."""
    path = BENCH / kind / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no {kind} file for {name!r}: {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
