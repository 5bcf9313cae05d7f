"""Resolve a workload name to its files, by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration entry
names its file, the traffic mix is ``bench/traffic/<traffic>.json``, its
``driver`` is ``bench/drivers/<driver>.py``, and each per-layer metric is
``bench/metrics/<metric>.py``.  Adding a cell, a mix, a configuration or
a metric is adding files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    seed: int
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json that apply
    per_layer: list
    data: dict | None = None

    @property
    def t(self) -> int:
        return int(self.config["t"])

    @property
    def max_len(self) -> int:
        return int(self.config["max_len"])

    @property
    def limits(self) -> dict:
        return self.traffic["limits"]


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, seed: int, root: str = ROOT) -> Cell:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return from_files(name, configs[w["config"]]["file"], w["traffic"],
                      seed, int(w["chips"]), spec, root)


def from_files(name: str, config_file: str, traffic: str, seed: int,
               chips: int = 1, spec: dict | None = None,
               root: str = ROOT) -> Cell:
    """A cell from its configuration file and traffic name; its metrics are
    those of ``spec`` (``BENCHMARK.json``'s entries) that apply to it."""
    with open(os.path.join(root, config_file)) as f:
        config = json.load(f)
    with open(traffic_file(traffic, root)) as f:
        mix = json.load(f)
    spec = spec or {"end_to_end": [], "per_layer": []}
    return Cell(
        name=name, seed=int(seed), chips=chips, config=config, traffic=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def traffic_file(traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{traffic}.json")


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell, root: str = ROOT):
    d = cell.traffic["driver"]
    return _module(os.path.join(root, "bench", "drivers", f"{d}.py"),
                   f"bench_driver_{d}")


def metric_reader(metric: str, root: str = ROOT):
    return _module(os.path.join(root, "bench", "metrics", f"{metric}.py"),
                   "bench_metric_" + metric.replace(".", "_").replace("-", "_"))
