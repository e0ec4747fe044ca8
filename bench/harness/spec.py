"""Find a cell's configuration, traffic, metrics and limits by name.

Everything that belongs to one configuration, one traffic mix, one metric or
one cell lives in a file of its own, found from the names in
``BENCHMARK.json``:

    bench/configs/<config>.json   sizes, source, cut, optimizer (file named
                                  in BENCHMARK.json)
    bench/models/<family>.py      the configuration's plain reference, its
                                  weight layout and its FLOP count
    bench/traffic/<traffic>.json  batch, sequence, token mix, elastic events
    bench/events/<kind>.py        what an elastic event does in the window
    bench/metrics/<metric>.py     a reader that takes one metric from a run
    bench/limits/<cell>.json      the limits of the comparison that decides
                                  ``correct``
"""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict                    # the configuration file's contents
    traffic: dict                   # the traffic file's contents
    end_to_end: List[dict]          # metric entries this cell reports
    per_layer: List[dict]
    limits: Dict[str, Optional[float]] = field(default_factory=dict)

    @property
    def model(self):
        """The configuration's plain model module (reference, layout, FLOPs)."""
        return importlib.import_module(f"bench.models.{self.config['family']}")


def _reported(entry: dict, cell: str, e2e_names: Optional[set]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return e2e_names is None or entry["moves"] in e2e_names


def resolve(name: str, benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names."""
    bm = benchmark or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    e2e = [m for m in bm["end_to_end"] if _reported(m, name, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _reported(m, name, names)]
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(os.path.join(ROOT, conf["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer,
        limits=load_json(os.path.join(BENCH, "limits", name + ".json")))
