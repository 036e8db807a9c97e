"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own:

* ``bench/configs/<config>.json``: the deployment (dtype, precision,
  output, guarantees, limits of the comparison), and
  ``entry``, the module ``bench/entries/<entry>.py`` that drives the
  program's entry point and its plain reference;
* ``bench/traffic/<traffic>.json``: the mix's parameters, and ``driver``,
  the general generator ``bench/drivers/<driver>.py`` that reads them;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

So a cell, a mix or a metric is added with files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List

__all__ = ["ROOT", "Spec", "load_benchmark", "resolve",
           "metric_path", "load_metric", "per_layer_for"]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Spec:
    """One cell as ``BENCHMARK.json`` and its files define it."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    driver: Any
    entry: Any
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def metric_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "metrics", f"{name}.py")


def load_metric(name: str, root: str = ROOT):
    """The reader module of per-layer metric ``name``."""
    path = metric_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reported(bench: dict, workload: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_for(bench: dict, workload: str) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in _reported(bench, workload)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in e2e
                                 else [])]


def resolve(workload: str, root: str = ROOT) -> Spec:
    """The cell ``workload`` of ``BENCHMARK.json``, its files and the
    metrics it reports."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    return Spec(name=workload, chips=w["chips"], config=cfg, traffic=mix,
                driver=importlib.import_module(f"bench.drivers.{mix['driver']}"),
                entry=importlib.import_module(f"bench.entries.{cfg['entry']}"),
                end_to_end=_reported(bench, workload),
                per_layer=per_layer_for(bench, workload))
