"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of
its own, found by name, so a later cell is new files plus new entries:

* ``bench/configs/<config>.json`` — the configuration as it is run;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by the
  one general generator (``bench/traffic.py``) and the cell's driver;
* ``bench/limits/<cell>.json`` — the limits that decide ``correct``;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric, with
  ``read(record) -> float | None`` (``None``: nothing to read here).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads``, with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list   # metric entries this cell reports with --trace 0
    per_layer: list    # metric entries this cell reports with --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def config_file(name: str, m: dict) -> str:
    for c in m["configs"]:
        if c["name"] == name:
            return os.path.join(ROOT, c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def cell(name: str, m: dict = None) -> Cell:
    m = manifest() if m is None else m
    entry = next((w for w in m["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in m['workloads']]}")
    limits_path = os.path.join(BENCH, "limits", f"{name}.json")
    return Cell(
        name=name,
        chips=entry["chips"],
        config=load_json(config_file(entry["config"], m)),
        traffic=load_json(os.path.join(BENCH, "traffic", f"{entry['traffic']}.json")),
        limits=load_json(limits_path) if os.path.exists(limits_path) else {},
        end_to_end=[x for x in m["end_to_end"] if _applies(x, name)],
        per_layer=[x for x in m["per_layer"] if _applies(x, name)],
    )


def metric_module(metric: str):
    """``bench/metrics/<metric>.py``, loaded (its name may hold dots)."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(record)`` function of ``bench/metrics/<metric>.py``."""
    return metric_module(metric).read
