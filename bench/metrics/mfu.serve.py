"""Model FLOP/s utilisation of the engine's steps, against bf16 peak:
the forward FLOPs of the prompt tokens prefilled and of the tokens
decoded in the traced window's engine steps, over the summed wall of
those steps. Per token: the frozen backbone (the architecture's
``frozen_flops_per_token``) over its context, the side network's
forward, and one LM-head row for every token produced."""

from bench import manifest
from bench import weights as W


def token_flops(config, ctx):
    """Forward FLOPs of one token that attends over ``ctx`` positions,
    without the head. The backbone's is the ``ctx``-th token's share of a
    causal row: ctx F(ctx) - (ctx - 1) F(ctx - 1), F its per-token mean."""
    a, ad = config["arch"], config["adapter"]
    d, da = a["d_model"], ad["d_model"]
    taps, windows = manifest.side(config)
    frozen = manifest.arch_module(config).frozen_flops_per_token
    backbone = ctx * frozen(a, ctx) - ((ctx - 1) * frozen(a, ctx - 1) if ctx > 1 else 0)
    side = taps * sum(W.layer_flops(ad, ctx if w is None else min(ctx, w)) for w in windows)
    return backbone + side + 2 * (taps + 1) * d * da + 2 * da * d


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
