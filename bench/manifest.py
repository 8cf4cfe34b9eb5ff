"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Each lives in a file of
its own, found by name, so a later cell is new files plus new entries:

* ``bench/configs/<config>.json`` — the configuration as it is run,
  which names its ``architecture``;
* ``bench/archs/<architecture>.py`` — everything that depends on the
  backbone's architecture (see ``arch_module``);
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by the
  one general generator (``bench/traffic.py``) and the cell's driver;
* ``bench/limits/<cell>.json`` — the limits that decide ``correct``;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric, with
  ``read(record) -> float | None`` (``None``: nothing to read here).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
ARCHS = os.path.join(BENCH, "archs")


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


def _load(kind: str, name: str, path: str):
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_path(config: dict) -> str:
    return os.path.join(ARCHS, f"{config['architecture']}.py")


def load_config(path: str) -> dict:
    """A configuration file, refused where it names no architecture or
    one that ``ARCHS`` does not hold."""
    config = load_json(path)
    if "architecture" not in config:
        raise KeyError(f"{path} names no architecture")
    if not os.path.exists(arch_path(config)):
        raise KeyError(f"{path} names architecture {config['architecture']!r}, "
                       f"which has no module {arch_path(config)}")
    return config


def arch_module(config: dict):
    """``bench/archs/<architecture>.py`` of a configuration, loaded. Of
    the backbone's architecture, the harness knows only what it gives:

    * ``program_config(config)`` — the program's ``ArchConfig``,
      registered under ``model.program_arch(config)``;
    * ``stacks(arch)`` — the backbone's stacks of layers
      (``weights.Stack``: a count and a maker of one layer's flat f32
      leaves, under the module's own leaf ids);
    * ``backbone_tree(rest, stacked)`` — ``rest`` (embedding, final norm,
      head) and the stacked layers in the program's ``backbone`` layout;
    * ``depth_order(arch)`` — (stack, index) of each layer from the
      bottom up, in groups: the program's tap follows each group;
    * ``side_windows(arch)`` — the attention window (None: full) of each
      dense block the side network runs after a tap;
    * ``reference_layer(p, x, arch, stack, prompt_len=None)`` — one
      layer of the plain reference from flat leaves at the stated
      precision; ``prompt_len``, the served path's attention, may be
      left out, and ``bench/serve.py`` then refuses the configuration;
    * ``frozen_flops_per_token(arch, seq)`` — forward FLOPs of the
      frozen backbone per token of a causal row of ``seq`` tokens;
    * ``quant_matmul_calls(arch, M)`` — (M, K, N) of every
      ``quant_matmul`` call of one forward over ``M`` rows;
    * ``paged_attention_work(arch, ctx)`` — (FLOPs, bytes) of the paged
      attention of one decode step over contexts ``ctx`` (serving only);
    * ``SMALL`` — the ``arch`` section at a test's size.
    """
    return _arch_at(arch_path(config))


@functools.lru_cache(maxsize=None)
def _arch_at(path: str):
    return _load("arch", os.path.splitext(os.path.basename(path))[0], path)


def side(config: dict) -> tuple:
    """(taps, windows) of the side network: one tap per group of the
    backbone's ``depth_order``, one dense block per window after each."""
    mod = arch_module(config)
    return len(mod.depth_order(config["arch"])), tuple(mod.side_windows(config["arch"]))


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
        config=load_config(config_file(entry["config"], m)),
        traffic=load_json(os.path.join(BENCH, "traffic", f"{entry['traffic']}.json")),
        limits=load_json(limits_path) if os.path.exists(limits_path) else {},
        end_to_end=[x for x in m["end_to_end"] if _applies(x, name)],
        per_layer=[x for x in m["per_layer"] if _applies(x, name)],
    )


def metric_module(metric: str):
    """``bench/metrics/<metric>.py``, loaded (its name may hold dots)."""
    return _load("metric", metric, os.path.join(BENCH, "metrics", f"{metric}.py"))


def reader(metric: str):
    """The ``read(record)`` function of ``bench/metrics/<metric>.py``."""
    return metric_module(metric).read
