"""Model FLOP/s utilisation of the training window, against bf16 peak.

Required operations of the window's steps, from shapes; recomputation is
not counted. Capture: the frozen forward (projections and causal
attention), the LM head forward and its backward to the hidden state,
and the side network forward and backward. Cached: the head and the
side network only. The side network's backward is twice its forward,
except for the down projections, whose inputs (the taps) need no
gradient: once.
"""


def _layer_flops(w, seq):
    """Forward FLOPs per token of one decoder layer of widths ``w``
    (matmuls, plus causal attention averaged over a ``seq``-long row)."""
    d, hd = w["d_model"], w["head_dim"]
    hq, hkv = w["n_heads"] * hd, w["n_kv_heads"] * hd
    proj = 2 * d * (hq + 2 * hkv) + 2 * hq * d + 3 * 2 * d * w["d_ff"]
    attn = 2 * 2 * hq * (seq + 1) / 2
    return proj + attn


def flops_per_token(config, traffic):
    a, ad, seq = config["arch"], config["adapter"], traffic["seq"]
    L, d, da, V = a["n_layers"], a["d_model"], ad["d_model"], a["vocab"]
    head = 2 * d * V * 2  # forward, and backward to the hidden state
    side = 3 * (L * _layer_flops(ad, seq) + 2 * da * d) + 2 * (L + 1) * 2 * d * da
    frozen = L * _layer_flops(a, seq) if traffic["phase"] == "capture" else 0
    return frozen + head + side


def read(record):
    tr = record.get("trace")
    if not tr or record["traffic"]["kind"] != "train":
        return None
    flops = record["tokens"] * flops_per_token(record["config"], record["traffic"])
    return 100.0 * flops / (record["window_s"] * record["peaks"]["bf16_flops"])
