"""Cell discovery: everything is found by the name `BENCHMARK.json` gives
it. A cell's configuration is `bench/configs/<config>.json`, its traffic
mix `bench/traffic/<traffic>.json`, its own limits
`bench/workloads/<cell>.json`, and each per-layer metric a reader
`bench/metrics/<metric>.py` with a `read(ctx)` function."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field, replace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)    # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str, e2e_names=None) -> bool:
    """A metric with a `workloads` list applies to those cells; without one,
    an end-to-end metric applies to every cell and a per-layer metric to
    every cell that reports the end-to-end metric it `moves`."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def make_cell(name: str, config: str, traffic: str, chips: int = 1,
              root: str = ROOT) -> Cell:
    """A cell from its configuration and traffic names, with the limits of
    `bench/workloads/<name>.json` where that file exists."""
    here = os.path.join(root, "bench")
    cell_file = os.path.join(here, "workloads", name + ".json")
    return Cell(name=name, chips=int(chips),
                config=_load_json(os.path.join(here, "configs",
                                               config + ".json")),
                traffic=_load_json(os.path.join(here, "traffic",
                                                traffic + ".json")),
                limits=(_load_json(cell_file)["limits"]
                        if os.path.exists(cell_file) else {}))


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `BENCHMARK.json` names, with its metrics."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    cell = make_cell(name, entry["config"], entry["traffic"], entry["chips"],
                     root)
    cell.end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in cell.end_to_end}
    cell.per_layer = [m for m in bench["per_layer"]
                      if _applies(m, name, names)]
    return cell


def with_config(cell: Cell, config: str, root: str = ROOT) -> Cell:
    """The cell with `bench/configs/<config>.json` in place of its own
    configuration; its name says so."""
    return replace(cell, name=f"{cell.name}@{config}",
                   config=_load_json(os.path.join(root, "bench", "configs",
                                                  config + ".json")))


def metric_reader(name: str, root: str = ROOT):
    """The `read(ctx)` function of `bench/metrics/<name>.py`."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
