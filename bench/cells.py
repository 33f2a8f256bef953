"""Resolve a cell named in ``BENCHMARK.json`` to its files.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own and is found by name:

* configuration: the ``file`` its ``configs`` entry names (sizes, dtype,
  family, and which ``reference/<module>.py`` computes it);
* traffic mix: ``traffic/<mix>.json``, read by ``traffic/<kind>.py``;
* per-layer metric ``<base>[.<suffix>]``: ``metrics/<base>.py``;
* FLOP counts: ``flops/<family>.py``; kernel costs: ``kernels/<name>.py``,
  imported by the metric reader that needs them;
* comparison limits: ``limits/<cell>.json``.

A new cell over existing files is one ``workloads`` entry and its limits
file; a new mix or configuration is one more file each.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int = 1
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)
    limits: Dict[str, Any] = field(default_factory=dict)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: Dict[str, Any], workload: str,
            root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    lim = BENCH / "limits" / f"{workload}.json"
    limits = load_json(lim) if lim.exists() else {}
    return Cell(
        name=workload, config=config, traffic=traffic, chips=w["chips"],
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        limits=limits)


def generator(traffic: Dict[str, Any]):
    return importlib.import_module(f"bench.traffic.{traffic['kind']}")


def reader(metric_name: str):
    """The reader of a per-layer metric: ``metrics/<base>.py`` for the
    name's part before the first dot."""
    return importlib.import_module(
        f"bench.metrics.{metric_name.split('.')[0]}")


def flops(config: Dict[str, Any]):
    return importlib.import_module(f"bench.flops.{config['family']}")


def reference(config: Dict[str, Any]):
    return importlib.import_module(f"bench.reference.{config['reference']}")

