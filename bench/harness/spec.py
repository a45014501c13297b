"""Resolve a cell of ``BENCHMARK.json`` into its configuration, traffic
and metric readers, all by name."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable      # (Context) -> Optional[float]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict        # the configuration file as run
    traffic_name: str
    traffic: dict       # the traffic file
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    def metrics(self, traced: bool) -> List[Metric]:
        return self.per_layer if traced else self.end_to_end


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, metrics_dir: str) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` (or of
    ``benchmark``), with its files read from under ``<root>``."""
    bench = benchmark if benchmark is not None else load_json(
        os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[w["config"]]
    bench_dir = os.path.join(root, "bench")
    config = load_json(os.path.join(root, conf_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    metrics_dir = os.path.join(bench_dir, "metrics")
    mk = lambda m: Metric(m["name"], m["unit"],
                          load_reader(m["name"], metrics_dir))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[mk(m) for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[mk(m) for m in bench["per_layer"]
                           if _applies(m, name)])
