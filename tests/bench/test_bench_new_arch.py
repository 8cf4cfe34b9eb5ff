"""An architecture is added as new files alone: a test-only module and its
configuration file, written to a directory of their own, run whole
capture and cached cells through ``run.run_cell`` with no file of the
harness edited. Two interleaved stacks: full attention, then attention
over a window of 8 positions (at 32-token rows), each under its own leaf
ids; a tap after each pair."""

import json

import jax
import pytest

import benchcells
from bench import calibrate, manifest, run, serve

SEED = 2 ** 33 + 9
ARCH = "local_global_test"
# the dense model's cells lend the new architecture their traffic and limits
DENSE = {t: manifest.cell(f"internlm2-1.8b.{t}") for t in ("capture", "cached")}

MODULE = '''
"""Test-only: a dense decoder whose layers alternate full attention and
attention over the last WINDOW positions, one stack each."""

from bench import model, reference
from bench import weights as W

WINDOW = 8
LEAF_IDS = [{"ln1": 40, "wq": 41, "wk": 42, "wv": 43, "wo": 44,
             "ln2": 45, "wi": 46, "wg": 47, "wo_ff": 48},
            {"ln1": 50, "wq": 51, "wk": 52, "wv": 53, "wo": 54,
             "ln2": 55, "wi": 56, "wg": 57, "wo_ff": 58}]
WINDOWS = (None, WINDOW)
SMALL = {"n_layers": 4, "d_model": 256, "n_heads": 4, "n_kv_heads": 2, "head_dim": 64,
         "d_ff": 1024, "vocab": 512, "rope_theta": 1000000.0, "norm_eps": 1e-05}


def program_config(config):
    from repro.configs import ArchConfig, LayerSpec, register

    a = config["arch"]
    return register(ArchConfig(
        name=model.program_arch(config), family="dense", n_layers=a["n_layers"],
        d_model=a["d_model"], n_heads=a["n_heads"], n_kv_heads=a["n_kv_heads"],
        head_dim=a["head_dim"], d_ff=a["d_ff"], vocab=a["vocab"],
        pattern=tuple(LayerSpec(kind="attn", window=w) for w in WINDOWS),
        rope_theta=a["rope_theta"], norm_eps=a["norm_eps"], source=config["source"]))


def stacks(arch):
    n = arch["n_layers"] // 2
    return [W.Stack(n, lambda key, i, ids=ids: W.make_layer(key, arch, i, ids))
            for ids in LEAF_IDS]


def backbone_tree(rest, stacked):
    return dict(rest, blocks=[W.nest(s) for s in stacked])


def depth_order(arch):
    return [[(0, i), (1, i)] for i in range(arch["n_layers"] // 2)]


def side_windows(arch):
    return WINDOWS


def reference_layer(p, x, arch, stack):
    return reference.block(p, x, arch, arch["norm_eps"], arch["rope_theta"],
                           window=WINDOWS[stack])


def frozen_flops_per_token(arch, seq):
    return arch["n_layers"] // 2 * sum(W.layer_flops(arch, W.causal_context(seq, w))
                                       for w in WINDOWS)


def quant_matmul_calls(arch, M):
    layer = [(M, *s) for s, _ in W.layer_shapes(arch).values() if len(s) == 2]
    return layer * arch["n_layers"]
'''


@pytest.fixture
def config(tmp_path, monkeypatch):
    """The configuration file of the test-only architecture, loaded with
    ``manifest``'s architecture directory pointed at ``tmp_path``."""
    (tmp_path / f"{ARCH}.py").write_text(MODULE)
    monkeypatch.setattr(manifest, "ARCHS", str(tmp_path))
    path = tmp_path / "local-global-test.json"
    path.write_text(json.dumps(dict(DENSE["capture"].config, name="local-global-test",
                                    architecture=ARCH)))
    return manifest.load_config(str(path))


def _cell(config, traffic):
    dense = DENSE[traffic]
    return benchcells.small_training_cell(manifest.Cell(
        name=f"local-global-test.{traffic}", chips=1, config=config, traffic=dense.traffic,
        limits=dense.limits, end_to_end=dense.end_to_end, per_layer=[]))


@pytest.mark.parametrize("fault", [None, "half_batch"])
@pytest.mark.parametrize("traffic", ["capture", "cached"])
def test_new_architecture_runs_whole_cells(config, traffic, fault):
    cell = _cell(config, traffic)
    assert manifest.side(cell.config) == (2, (None, 8))
    if fault is None:
        res = run.run_cell(cell, SEED, 0.5, False, jax.devices()[:1], run.T_START)
        assert res["correct"], res["checks"]
        assert res["attempted"] >= 1 and res["failed"] == 0
    else:
        with getattr(calibrate, fault)():
            res = run.run_cell(cell, SEED, 0.5, False, jax.devices()[:1], run.T_START)
        assert not res["correct"]


def test_architecture_without_a_served_path_is_refused(config):
    with pytest.raises(ValueError, match="served path"):
        serve.check_served_path(config)


@pytest.mark.parametrize("change", [{"architecture": "no_such_arch"}, None])
def test_configuration_must_name_a_known_architecture(tmp_path, change):
    dense = DENSE["capture"].config
    config = dict(dense, **change) if change else {
        k: v for k, v in dense.items() if k != "architecture"}
    path = tmp_path / "c.json"
    path.write_text(json.dumps(config))
    with pytest.raises(KeyError, match=str(path)):
        manifest.load_config(str(path))
