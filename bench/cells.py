"""Find the benchmark's cells, configurations, traffic mixes and metric
readers by name.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric sits in a file of its own under ``bench/``; this module is
the only place that maps a name to its file, so a later cell or metric is
added by adding files and entries, never by editing code.

* ``BENCHMARK.json``             the cells, metrics and bounds
* ``bench/configs/<c>.json``     model sizes as run, source, cuts
* ``bench/traffic/<t>.json``     traffic parameters, read by one generator
* ``bench/workloads/<w>.json``   a cell: configuration, traffic, chips,
                                 mesh, exchange, correctness limits
* ``bench/metrics/<m>.py``       a per-layer metric's reader
* ``bench/end_to_end/<m>.py``    an end-to-end metric's reader

A reader module defines ``read(run) -> float | None``; ``None`` means the
run holds nothing for it to read, and the metric is left out.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")


def _load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    with open(path) as f:
        data = json.load(f)
    if data.get("name") != name:
        raise ValueError(f"{path} names itself {data.get('name')!r}")
    return data


def load_benchmark(path: str = BENCHMARK_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


@dataclasses.dataclass
class Cell:
    """One cell as the harness runs it."""

    name: str
    config: dict
    traffic: dict
    chips: int
    mesh: Optional[dict]
    a2a_impl: Optional[str]
    plan_cluster: Optional[List[int]]
    correct: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def metrics_for(bench: dict, cell: str):
    """The end-to-end and per-layer metric entries a cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    data = _load_json("workloads", name)
    for key in ("config", "traffic", "chips"):
        if data[key] != entry[key]:
            raise ValueError(f"cell {name}: {key} {data[key]!r} in its file, "
                             f"{entry[key]!r} in BENCHMARK.json")
    e2e, layer = metrics_for(bench, name)
    return Cell(name=name, config=load_config(data["config"]),
                traffic=load_traffic(data["traffic"]), chips=data["chips"],
                mesh=data.get("mesh"), a2a_impl=data.get("a2a_impl"),
                plan_cluster=data.get("plan_cluster"),
                correct=data["correct"], end_to_end=e2e, per_layer=layer)


def reader_path(name: str, directory: str) -> str:
    return os.path.join(directory, f"{name}.py")


def load_reader(name: str, directory: str) -> Callable:
    """``read`` of the reader module ``<directory>/<name>.py``."""
    path = reader_path(name, directory)
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(entries: List[dict], run, directory: str) -> Dict:
    """{name: {"value", "unit"}} for every entry whose reader finds
    something in ``run``."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], directory)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
