"""Where a cell's parts live, and how they are found by name.

``BENCHMARK.json`` at the root of the checkout names each cell
(``workloads``), its configuration and its traffic.  Everything that
belongs to one of them sits in a file of its own under this directory:

* ``configs/<config>.json``  - the model as it is run (sizes, dtypes,
  optimizer), with its source, the keys cut from it and the sizes assumed;
* ``traffic/<traffic>.json`` - the parameters the one generator in
  ``traffic.py`` reads;
* ``cells/<workload>.json``  - what belongs to the pair: the limits of
  the comparison that decides ``correct``;
* ``metrics/<metric>.py``    - one reader per per-layer metric.

A later cell, configuration, traffic mix or metric is added as new files;
no file here needs an edit for it.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read(kind: str, name: str) -> Dict[str, Any]:
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def metrics_for(bench: Dict[str, Any], kind: str, workload: str
                ) -> List[Dict[str, Any]]:
    """The metrics of ``kind`` (end_to_end / per_layer) this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {[w['name'] for w in bench['workloads']]})")
    cell = _read("cells", workload)
    for key in ("config", "traffic"):
        if cell[key] != entry[key]:
            raise ValueError(f"cells/{workload}.json names {key} "
                             f"{cell[key]!r}, BENCHMARK.json {entry[key]!r}")
    return Cell(name=workload, chips=entry["chips"],
                config=_read("configs", entry["config"]),
                traffic=_read("traffic", entry["traffic"]),
                limits=cell["limits"],
                end_to_end=metrics_for(bench, "end_to_end", workload),
                per_layer=metrics_for(bench, "per_layer", workload))
