"""Finding things by name: the manifest, configurations, mixes, metrics.

``BENCHMARK.json`` names cells, configurations and metrics; everything else
about them lives in a file of its own under ``chipbench/``, found by that
name. Nothing in the code lists cells, mixes or metrics.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return _load(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return _load(os.path.join(HERE, "traffic", name + ".json"))


def metric_files() -> Dict[str, Dict[str, Any]]:
    """Every ``chipbench/metrics/*.json``, by metric name."""
    out = {}
    folder = os.path.join(HERE, "metrics")
    for fn in sorted(os.listdir(folder)):
        if fn.endswith(".json"):
            m = _load(os.path.join(folder, fn))
            out[m["name"]] = m
    return out


def reader(name: str) -> Callable[[Dict[str, Any]], Any]:
    """``chipbench/readers/<name>.py``'s ``read`` function."""
    return importlib.import_module(f"chipbench.readers.{name}").read


def metrics_for(workload: str, group: str) -> List[Dict[str, Any]]:
    """The manifest's metrics of ``group`` (``end_to_end`` or
    ``per_layer``) that ``workload`` reports: those that list it, and, for
    a metric with no list, every cell that reports what it moves."""
    bench = benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reports(m) -> bool:
        if "workloads" in m:
            return workload in m["workloads"]
        if m["name"] in e2e:
            return True
        return reports(e2e[m["moves"]])

    return [m for m in bench[group] if reports(m)]
