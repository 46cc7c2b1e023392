"""Resolve a cell of ``BENCHMARK.json`` to its data files, by name alone.

A cell ``<config>.<mix>`` needs no code of its own: its configuration is the
file that ``BENCHMARK.json`` names for the config, its traffic is
``bench/traffic/<mix>.json``, its correctness limits are
``bench/limits/<cell>.json`` and each per-layer metric is reduced by
``bench/metrics/<metric>.py``.  A missing file is an error.

A metric named ``<quantity>.<qualifier>`` is that quantity, reported in a
set of cells under a name and a bound of its own (``gcells_per_s.step1``
is ``gcells_per_s`` in the cells that step one step a call); its reducer is
``bench/metrics/<quantity>.py`` unless it has a file of its own.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``, with every file it needs loaded."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(root, "bench", "limits",
                                     f"{name}.json"))
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def quantity(metric: str) -> str:
    """``gcells_per_s`` for ``gcells_per_s.step1``; a plain name itself."""
    return metric.rsplit(".", 1)[0]


def reducer(metric: str, root: str = ROOT):
    """The ``reduce(ctx)`` function of ``bench/metrics/<metric>.py``, or of
    its quantity's file where the metric has none of its own."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    if not os.path.exists(path) and "." in metric:
        return reducer(quantity(metric), root)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.reduce


def reference(config: dict, root: str = ROOT):
    """The plain reference module the configuration names."""
    path = os.path.join(root, "bench", "references",
                        f"{config['reference']}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{config['reference']}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
