"""Find a cell's parts by name.

Everything that belongs to one configuration, traffic mix, per-layer
metric, reference family or cell lives in a file of its own, named
after it, so that a new cell, configuration or metric is added by
adding files and `BENCHMARK.json` entries:

    BENCHMARK.json                      cells and metrics
    bench/configs/<config>.json         model configuration as it is run
    bench/traffic/<traffic>.json        traffic mix parameters
    bench/limits/<cell>.json            limits of the check of `correct`
    bench/metrics/<metric>.py           reader of one per-layer metric
    bench/reference/<family>.py         plain reference of a model family
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _json(BENCH / "traffic" / f"{name}.json")


def limits(cell_name: str) -> Dict[str, Any]:
    return _json(BENCH / "limits" / f"{cell_name}.json")


def _module(path: Path, name: str) -> ModuleType:
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    """The module whose ``read(window)`` returns the metric's value, or
    None where the run holds nothing to read it from."""
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def reference(family: str) -> ModuleType:
    return _module(BENCH / "reference" / f"{family}.py",
                   f"bench_reference_{family}")


def _covers(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(cell_name: str) -> List[Dict[str, Any]]:
    """The end-to-end metrics this cell reports."""
    return [m for m in benchmark()["end_to_end"] if _covers(m, cell_name)]


def per_layer(cell_name: str) -> List[Dict[str, Any]]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end(cell_name)}
    return [m for m in benchmark()["per_layer"]
            if _covers(m, cell_name) and m["moves"] in reported]
