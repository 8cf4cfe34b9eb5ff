"""BENCHMARK.json and the files it names: every piece found by name."""

import json
import os
import re

import pytest

import benchcells  # noqa: F401  (puts the checkout's root on sys.path)
from bench import manifest, peaks

M = manifest.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def test_names_units_and_keys_are_well_formed():
    assert M["command"] == ["python3", "bench/run.py"]
    assert all(os.path.isdir(os.path.join(manifest.ROOT, p)) for p in M["paths"])
    names = [m["name"] for m in METRICS] + CELLS + [c["name"] for c in M["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in M["workloads"]]:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
    assert {m["name"] for m in M["end_to_end"]} >= {"setup_s"}
    moves = {m["moves"] for m in M["per_layer"]}
    assert moves <= {m["name"] for m in M["end_to_end"]}
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_pieces_are_found_by_name(name):
    cell = manifest.cell(name, M)
    assert cell.config["name"] == next(w["config"] for w in M["workloads"] if w["name"] == name)
    assert cell.traffic["kind"] in ("train", "serve")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"} and len(cell.end_to_end) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(manifest.reader(m["name"]))
    assert cell.limits, f"no limits for {name}"
    for lim in cell.limits.values():
        assert lim["lower"] < lim["limit"] < lim["upper"]


@pytest.mark.parametrize("name", [m["name"] for m in M["per_layer"]])
def test_reader_returns_nothing_without_a_trace(name):
    cell = manifest.cell(M["workloads"][0]["name"], M)
    record = {"config": cell.config, "traffic": cell.traffic, "trace": None}
    assert manifest.reader(name)(record) is None


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        manifest.cell("no-such-cell", M)
    with pytest.raises(KeyError):
        manifest.config_file("no-such-config", M)


def test_peaks_reject_an_unknown_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
