"""Model FLOP/s utilisation of the training window, against bf16 peak.

Required operations of the window's steps, from shapes; recomputation is
not counted. Capture: the frozen forward (the architecture's
``frozen_flops_per_token``), the LM head forward and its backward to the
hidden state, and the side network forward and backward. Cached: the
head and the side network only. The side network's backward is twice its
forward, except for the down projections, whose inputs (the taps) need
no gradient: once.
"""

from bench import manifest
from bench import weights as W


def flops_per_token(config, traffic):
    a, ad, seq = config["arch"], config["adapter"], traffic["seq"]
    d, da, V = a["d_model"], ad["d_model"], a["vocab"]
    taps, windows = manifest.side(config)
    head = 2 * d * V * 2  # forward, and backward to the hidden state
    blocks = taps * sum(W.layer_flops(ad, W.causal_context(seq, w)) for w in windows)
    side = 3 * (blocks + 2 * da * d) + 2 * (taps + 1) * 2 * d * da
    frozen = 0
    if traffic["phase"] == "capture":
        frozen = manifest.arch_module(config).frozen_flops_per_token(a, seq)
    return frozen + head + side


def read(record):
    tr = record.get("trace")
    if not tr or record["traffic"]["kind"] != "train":
        return None
    flops = record["tokens"] * flops_per_token(record["config"], record["traffic"])
    return 100.0 * flops / (record["window_s"] * record["peaks"]["bf16_flops"])
