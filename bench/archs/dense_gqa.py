"""Dense GQA decoder: InternLM2's block, one kind of layer throughout.

Each of ``n_layers`` layers: RMSNorm (``1 + w`` gain), attention with
rotary embedding over the two halves of each head and grouped KV heads,
RMSNorm, a SiLU-gated MLP of width ``d_ff`` (``reference.block``, the
dense block the side network is made of too). One stack of
``n_layers``; a tap after every layer.

The ``arch`` section's keys: ``n_layers``, ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab``, ``rope_theta``,
``norm_eps``.
"""

from __future__ import annotations

from bench import model, reference
from bench import weights as W

# the backbone's leaf ids (its own key, weights.STREAM_BACKBONE)
LEAF_IDS = {"ln1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14,
            "ln2": 15, "wi": 16, "wg": 17, "wo_ff": 18}

SMALL = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 2, "head_dim": 64,
         "d_ff": 1024, "vocab": 512, "rope_theta": 1000000.0, "norm_eps": 1e-05}


def program_config(config: dict):
    """The program's ArchConfig for ``config``, registered from the file
    alone: a dense decoder of attention layers at the file's widths."""
    from repro.configs import ArchConfig, LayerSpec, register

    arch = config["arch"]
    return register(ArchConfig(
        name=model.program_arch(config), family="dense", n_layers=arch["n_layers"],
        d_model=arch["d_model"], n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
        head_dim=arch["head_dim"], d_ff=arch["d_ff"], vocab=arch["vocab"],
        pattern=(LayerSpec(kind="attn"),), rope_theta=arch["rope_theta"],
        norm_eps=arch["norm_eps"], source=config["source"]))


def stacks(arch: dict) -> list:
    return [W.Stack(arch["n_layers"], lambda key, i: W.make_layer(key, arch, i, LEAF_IDS))]


def backbone_tree(rest: dict, stacked: list) -> dict:
    return dict(rest, blocks=[W.nest(s) for s in stacked])


def depth_order(arch: dict) -> list:
    return [[(0, i)] for i in range(arch["n_layers"])]


def side_windows(arch: dict) -> tuple:
    return (None,)


def reference_layer(p, x, arch: dict, stack: int, prompt_len=None):
    return reference.block(p, x, arch, arch["norm_eps"], arch["rope_theta"], prompt_len)


def frozen_flops_per_token(arch: dict, seq: int) -> float:
    """Projections and causal attention, averaged over a ``seq``-long row."""
    return arch["n_layers"] * W.layer_flops(arch, W.causal_context(seq))


def quant_matmul_calls(arch: dict, M: int) -> list:
    """Seven per layer: q, k, v, o; gate, up, down."""
    layer = [(M, *s) for s, _ in W.layer_shapes(arch).values() if len(s) == 2]
    return layer * arch["n_layers"]


def paged_attention_work(arch: dict, ctx: list) -> tuple:
    """Per layer, for a row whose context is ``c`` tokens: 4 x n_heads x
    head_dim x c operations (scores and values) and the int8 keys and
    values of its ``c`` tokens with their f32 scales per KV head, plus
    the f32 query and output rows."""
    hq, hkv, hd = arch["n_heads"] * arch["head_dim"], arch["n_kv_heads"], arch["head_dim"]
    flops = sum(4 * hq * c for c in ctx)
    kv = sum(c * hkv * (2 * hd + 2 * 4) for c in ctx)
    return arch["n_layers"] * flops, arch["n_layers"] * (kv + len(ctx) * 2 * 4 * hq)
