"""Model FLOP/s utilisation of the engine's steps, against bf16 peak:
the forward FLOPs of the prompt tokens prefilled and of the tokens
decoded in the traced window's engine steps, over the summed wall of
those steps. Per token: the backbone's projections and causal
attention over its context, the side network's forward, and one LM-head
row for every token produced."""


def _proj(w):
    d, hd = w["d_model"], w["head_dim"]
    hq, hkv = w["n_heads"] * hd, w["n_kv_heads"] * hd
    return 2 * d * (hq + 2 * hkv) + 2 * hq * d + 3 * 2 * d * w["d_ff"], hq


def token_flops(config, ctx):
    """Forward FLOPs of one token that attends over ``ctx`` positions,
    without the head."""
    a, ad = config["arch"], config["adapter"]
    L, d, da = a["n_layers"], a["d_model"], ad["d_model"]
    p, hq = _proj(a)
    pa, hqa = _proj(ad)
    return L * (p + 4 * hq * ctx) + L * (pa + 4 * hqa * ctx) + 2 * (L + 1) * d * da + 2 * da * d


def step_flops(config, step):
    head = 2 * config["arch"]["d_model"] * config["arch"]["vocab"]
    flops = sum(token_flops(config, c) + head for c in step["decode_ctx"])
    for P in step["prefill"]:
        flops += sum(token_flops(config, c) for c in range(1, P + 1)) + head
    return flops


def read(record):
    tr, steps = record.get("trace"), record.get("engine_steps")
    if not tr or not steps:
        return None
    flops = sum(step_flops(record["config"], s) for s in steps)
    wall = sum(s["wall_s"] for s in steps)
    return 100.0 * flops / (wall * record["peaks"]["bf16_flops"])
