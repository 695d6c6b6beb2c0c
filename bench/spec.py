"""Find a cell's configuration, traffic mix and per-layer metrics by name.

Everything is looked up from ``BENCHMARK.json``: the cell names its
configuration and its traffic, the configuration entry names its file,
the traffic is ``bench/traffic/<traffic>.json``, and each per-layer
metric that lists the cell (or lists no cells) is read by
``bench/metrics/<name>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable  # per-layer: read(window) -> Optional[float]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict     # the configuration file's contents
    traffic: dict    # the traffic file's contents
    end_to_end: List[dict]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def _load_reader(name: str) -> Callable:
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench.metrics." + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"choose from {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      f"{w['traffic']}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[Metric(m["name"], m["unit"], _load_reader(m["name"]))
                   for m in bench["per_layer"] if _applies(m, name)])


__all__ = ["BENCH_DIR", "Cell", "Metric", "ROOT", "SpecError", "load_cell"]
