"""Finds a cell's files by the names in ``BENCHMARK.json``.

- the cell (an entry of ``workloads``) names a configuration and a
  traffic mix;
- the configuration's ``file`` is stated in ``configs``;
- the traffic mix is ``benchmark/traffic/<traffic>.json``; its ``kind``
  names the generator ``benchmark/generators/<kind>.py`` and its
  ``family`` the cell runner ``benchmark/cells/<family>.py``;
- a metric is ``benchmark/metrics/<name>.py`` (a module with
  ``read(obs)``) or ``benchmark/metrics/<name>.json`` (a ratio of
  counters). A metric belongs to a cell when it has no ``workloads`` key
  or lists the cell.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT,
                   file: str = "BENCHMARK.json") -> Dict[str, Any]:
    with open(os.path.join(root, file)) as f:
        return json.load(f)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def resolve_cell(bench: Dict[str, Any], workload: str,
                 root: str = ROOT) -> Dict[str, Any]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(
        BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": metrics_of(bench, "end_to_end", workload),
            "per_layer": metrics_of(bench, "per_layer", workload)}


def metrics_of(bench: Dict[str, Any], group: str,
               workload: str) -> List[Dict[str, Any]]:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def generator(kind: str):
    return importlib.import_module(f"benchmark.generators.{kind}")


def cell_runner(family: str):
    return importlib.import_module(f"benchmark.cells.{family}")


def _ratio_reader(spec: Dict[str, Any]) -> Callable[[Dict], Optional[float]]:
    def read(obs: Dict[str, Any]) -> Optional[float]:
        c = obs.get("counters")
        if not c:
            return None
        num = sum(c[k] for k in spec["num"])
        den = sum(c[k] for k in spec["den"])
        return spec.get("scale", 1.0) * num / den if den else None

    return read


def metric_reader(name: str) -> Callable[[Dict], Optional[float]]:
    js = os.path.join(BENCH_DIR, "metrics", name + ".json")
    if os.path.exists(js):
        return _ratio_reader(_read_json(js))
    # by path, not by import: a metric's name may hold dots
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"metric {name!r} has no reader: add benchmark/metrics/"
            f"{name}.py (read(obs)) or {name}.json (a ratio of counters)")
    mod_spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[Dict[str, Any]], obs: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """A reader that finds nothing to read returns None, and the metric
    is left out of the line."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"])(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def model_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes lib/flops.py needs, under their Hugging Face names."""
    m = dict(config)
    m.setdefault("head_dim", m["hidden_size"] // m["num_attention_heads"])
    return m
