"""The benchmark's manifest, read by name: ``BENCHMARK.json`` at the root
of the checkout, the configuration file and the traffic file of one cell.

Nothing here knows a cell, a configuration or a metric by name: a later
cell is a manifest entry plus data files under ``chipbench/``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]         # the configuration file, as run
    traffic: Dict[str, Any]        # the traffic file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str, e2e_names) -> bool:
    listed = metric.get("workloads")
    if listed is not None:
        return cell in listed
    # per-layer metrics without a list follow the end-to-end metric they move
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if m.get("workloads") is None or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)
