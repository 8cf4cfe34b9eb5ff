"""Small cells for the benchmark's CPU tests: the configuration files'
structure at a test's size (each architecture's ``SMALL``, one side
network's widths for all), in interpret mode."""

from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import manifest  # noqa: E402

SMALL_ADAPTER = {"d_model": 32, "n_heads": 1, "n_kv_heads": 1, "head_dim": 32, "d_ff": 128}


def shrink(config: dict) -> None:
    """Cut ``config`` to a test's size in place: its architecture's
    ``SMALL`` widths, the side network's, its own registry name."""
    config.update(name=config["name"] + "-benchtest",
                  arch=dict(manifest.arch_module(config).SMALL), adapter=dict(SMALL_ADAPTER))


def small_training_cell(cell: manifest.Cell) -> manifest.Cell:
    """A training cell at a test's size: its configuration shrunk, and
    jobs of 8 rows of 32 tokens in batches of 2."""
    cell = copy.deepcopy(cell)
    shrink(cell.config)
    cell.traffic.update(rows_per_job=8, seq=32, batch=2)
    return cell


def small_cell(name: str) -> manifest.Cell:
    """The training cell ``name`` of BENCHMARK.json at a test's size."""
    return small_training_cell(manifest.cell(name))


def small_serve_cell() -> manifest.Cell:
    """The chat mix (``bench/traffic/chat.json``, not a cell of
    BENCHMARK.json yet) at a test's size: short prompts and answers, a
    small engine and bank."""
    config = manifest.load_config(manifest.config_file("internlm2-1.8b", manifest.manifest()))
    cell = manifest.Cell(
        name="internlm2-1.8b.chat", chips=1, config=config,
        traffic=manifest.load_json(os.path.join(manifest.BENCH, "traffic", "chat.json")),
        limits={}, end_to_end=[], per_layer=[])
    shrink(cell.config)
    cell.traffic.update(
        rate_per_s=20.0, n_adapters=4,
        prompt_len={"median": 20, "sigma": 0.5, "min": 16, "max": 32},
        output_len={"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        engine=dict(cell.traffic["engine"], max_batch=2, max_len=48),
        sample_requests=4, drain_s=30)
    return cell
