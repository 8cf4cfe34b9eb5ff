"""The dense GQA decoder's weights and reference, pinned: values recorded
at the small cells' size and one seed, which every change to the
harness's code must leave as they are. A digest of every leaf of
``model.make_backbone``; ``Reference.train``'s three losses and the
norms of its first gradient's leaves over three batches of job 0."""

import hashlib

import jax
import numpy as np
import pytest

import benchcells
from bench import compare, model, traffic
from bench.reference import Reference

SEED = 2 ** 33 + 7
CELL = benchcells.small_cell("internlm2-1.8b.capture")

BACKBONE = {
    "['blocks'][0]['ffn']['wg'][<flat index 0>]": "f624a56138bd02a8",
    "['blocks'][0]['ffn']['wg'][<flat index 1>]": "ef916f45560e4388",
    "['blocks'][0]['ffn']['wi'][<flat index 0>]": "faff1231b8987a90",
    "['blocks'][0]['ffn']['wi'][<flat index 1>]": "331955fdc166c83d",
    "['blocks'][0]['ffn']['wo'][<flat index 0>]": "52693e4ed85c2f03",
    "['blocks'][0]['ffn']['wo'][<flat index 1>]": "7da93839e0382adf",
    "['blocks'][0]['ln1']": "ab0ce8936cee7b69",
    "['blocks'][0]['ln2']": "3f3e0deec115a8c6",
    "['blocks'][0]['mixer']['wk'][<flat index 0>]": "782c9fecafa4c1e1",
    "['blocks'][0]['mixer']['wk'][<flat index 1>]": "68e4a7b30eedeafb",
    "['blocks'][0]['mixer']['wo'][<flat index 0>]": "efd88f7d99dfee02",
    "['blocks'][0]['mixer']['wo'][<flat index 1>]": "12824eca543b765d",
    "['blocks'][0]['mixer']['wq'][<flat index 0>]": "d949a1bbfaa8f903",
    "['blocks'][0]['mixer']['wq'][<flat index 1>]": "e5150096ce94d9db",
    "['blocks'][0]['mixer']['wv'][<flat index 0>]": "9a0fb1577404fc32",
    "['blocks'][0]['mixer']['wv'][<flat index 1>]": "1dc1b2366e95b5c2",
    "['embed'][<flat index 0>]": "91789858638924c3",
    "['embed'][<flat index 1>]": "87588c995980ac40",
    "['final_norm']": "8621f94edebf3b97",
    "['lm_head'][<flat index 0>]": "4f6543ab75da3dc3",
    "['lm_head'][<flat index 1>]": "c5405beb75699115",
}
LOSSES = [6.870154857635498, 6.751284599304199, 6.903177261352539]
GRAD_NORMS = {
    "['blocks'][0]['ffn']['wg']": 0.15881652847511157,
    "['blocks'][0]['ffn']['wi']": 0.15377039438658852,
    "['blocks'][0]['ffn']['wo']": 0.3200758950353246,
    "['blocks'][0]['ln1']": 0.04405950961444433,
    "['blocks'][0]['ln2']": 0.038896917693422024,
    "['blocks'][0]['mixer']['wk']": 0.06271187294406926,
    "['blocks'][0]['mixer']['wo']": 0.2275243832942833,
    "['blocks'][0]['mixer']['wq']": 0.0683617804258663,
    "['blocks'][0]['mixer']['wv']": 0.2573851858305683,
    "['downs']": 0.8139124968156866,
    "['lambda']": 0.012898914472925488,
    "['out_norm']": 0.04069957530609255,
    "['up']": 0.2330929724196878,
}


def test_backbone_leaves_are_pinned():
    config = CELL.config
    flat, _ = jax.tree_util.tree_flatten_with_path(
        model.make_backbone(config, SEED, config["run"]["quant"]))
    got = {jax.tree_util.keystr(p): hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for p, x in flat}
    assert got == BACKBONE


def test_reference_losses_and_first_gradient_are_pinned():
    config, tr = CELL.config, CELL.traffic
    tokens = traffic.job_tokens(SEED, 0, tr, config["arch"]["vocab"])
    batches = [(tokens[i:i + 2, :-1], tokens[i:i + 2, 1:]) for i in range(0, 6, 2)]
    losses, g1, _, _ = Reference(config, SEED).train(batches)
    assert losses == pytest.approx(LOSSES, rel=1e-6)
    assert compare.leaf_norms(g1) == pytest.approx(GRAD_NORMS, rel=1e-6)
