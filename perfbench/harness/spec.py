"""Find a cell's configuration, traffic and metrics by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration and
traffic mix; ``perfbench/configs/<config>.json`` and
``perfbench/traffic/<traffic>.json`` hold them (the mix's ``kind`` names its
loop, ``perfbench/traffic_kinds/<kind>.py``), ``perfbench/cells/<cell>.json``
the comparison's limits, ``perfbench/references/<name>.py`` a
configuration's plain reference, and ``perfbench/metrics/<metric>.py`` each
per-layer metric's reader.  Adding a cell, a mix or a metric adds files;
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the end-to-end metrics this cell reports
    per_layer: List[dict]       # the per-layer metrics this cell reports


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _benchmark() -> dict:
    return _load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def load_config(name: str) -> dict:
    files = {c["name"]: c["file"] for c in _benchmark()["configs"]}
    return _load_json(os.path.join(CHECKOUT, files[name]))


def load_traffic(name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def load_cell(name: str) -> Cell:
    bench = _benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(by_name)})")
    w = by_name[name]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, name)]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_config(w["config"]),
        traffic=load_traffic(w["traffic"]),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(name: str) -> Callable:
    """``read(ctx) -> float | None`` from ``perfbench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(config: dict):
    """The configuration's plain reference, ``perfbench/references/<name>.py``."""
    return importlib.import_module(f"references.{config['reference']}")


def readers(cell: Cell) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"]) for m in cell.per_layer}


def load_limits(cell: str) -> dict:
    """The comparison's limits for a cell, ``perfbench/cells/<cell>.json``."""
    return _load_json(os.path.join(BENCH_DIR, "cells", cell + ".json"))["limits"]
