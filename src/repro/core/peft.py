"""Baseline PEFT techniques the paper compares against (§II, §VI):

* **Full fine-tuning** — every backbone parameter trainable.
* **LoRA** (Hu et al.) — low-rank ΔW = B·A on W_q and W_v, A Gaussian,
  B zero (the initialisation PAC+'s §IV-C analysis starts from).
* **Adapters** (Houlsby et al.) — bottleneck MLP inserted after each
  layer's FFN, residual around it.

Both LoRA and Adapters keep trainable structures *inside* the backbone,
so gradients must backpropagate through the whole model — the
inefficiency PAC+ removes. We implement them faithfully to reproduce the
paper's FLOPs/memory/time comparison tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantization import maybe_dequantize_tree
from repro.models.backbone import embed_inputs, logits_from_hidden
from repro.models.layers import (
    attention_forward,
    mlp_forward,
    rms_norm,
)
from repro.models.moe import moe_forward
from repro.models import ssm

# ---------------------------------------------------------------------------
# LoRA
# ---------------------------------------------------------------------------

LORA_TARGETS = ("wq", "wv")  # paper follows Hu et al.: q and v projections


def init_lora(rng, cfg, rank: int = 8, dtype=jnp.float32) -> dict:
    """One (A, B) pair per attention-ish layer position, stacked over periods."""
    n_p = cfg.n_periods
    d = cfg.d_model
    out = []
    for i, spec in enumerate(cfg.pattern):
        k = jax.random.fold_in(rng, i)
        if spec.kind == "attn":
            dq = cfg.n_heads * cfg.hd
            dkv = cfg.n_kv_heads * cfg.hd
        elif spec.kind in ("mlstm", "slstm"):
            dq = dkv = cfg.n_heads * cfg.hd if spec.kind == "mlstm" else d
        else:  # mamba: adapt the in/out projections
            dq, dkv = 2 * cfg.d_inner, d
        ka, kb = jax.random.split(k)
        out.append(
            {
                "a_q": (jax.random.normal(ka, (n_p, d, rank)) * d ** -0.5).astype(dtype),
                "b_q": jnp.zeros((n_p, rank, dq), dtype),
                "a_v": (jax.random.normal(kb, (n_p, d, rank)) * d ** -0.5).astype(dtype),
                "b_v": jnp.zeros((n_p, rank, dkv), dtype),
            }
        )
    return {"layers": out, "alpha": jnp.float32(2.0 * rank)}


def lora_delta(lp, x, which: str, rank_scale):
    a, b = lp[f"a_{which}"], lp[f"b_{which}"]
    return ((x @ a) @ b) * rank_scale


def apply_block_lora(p, lp, x, cfg, spec, positions, rank_scale):
    """Block forward with LoRA deltas on the q/v-ish projections."""
    p = maybe_dequantize_tree(p)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn":
        mixer = dict(p["mixer"])
        # materialised-ΔW variant keeps the flash path untouched
        mixer["wq"] = mixer["wq"] + (lp["a_q"] @ lp["b_q"]) * rank_scale
        mixer["wv"] = mixer["wv"] + (lp["a_v"] @ lp["b_v"]) * rank_scale
        mix = attention_forward(mixer, h, cfg, spec, positions)
    elif spec.kind == "mamba":
        mixer = dict(p["mixer"])
        mixer["in_proj"] = mixer["in_proj"] + (lp["a_q"] @ lp["b_q"]) * rank_scale
        mix = ssm.mamba_forward(mixer, h, cfg)
    elif spec.kind == "mlstm":
        mixer = dict(p["mixer"])
        mixer["wq"] = mixer["wq"] + (lp["a_q"] @ lp["b_q"]) * rank_scale
        mixer["wv"] = mixer["wv"] + (lp["a_v"] @ lp["b_v"]) * rank_scale
        mix = ssm.mlstm_forward(mixer, h, cfg)
    else:  # slstm
        mixer = dict(p["mixer"])
        mixer["wz"] = mixer["wz"] + (lp["a_q"] @ lp["b_q"]) * rank_scale
        mix = ssm.slstm_forward(mixer, h, cfg)
    x = x + mix
    if "ffn" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.moe and cfg.moe is not None:
            x = x + moe_forward(p["ffn"], h, cfg.moe)
        else:
            x = x + mlp_forward(p["ffn"], h)
    return x


def lora_logits(backbone_params, lora_params, cfg, batch):
    x, positions = embed_inputs(backbone_params, cfg, batch)
    rank = lora_params["layers"][0]["a_q"].shape[-1]
    rank_scale = lora_params["alpha"] / rank

    def period_fn(carry, xs):
        h = carry
        block_slice, lora_slice = xs
        for i, spec in enumerate(cfg.pattern):
            h = apply_block_lora(block_slice[i], lora_slice[i], h, cfg, spec, positions, rank_scale)
        return h, None

    x, _ = jax.lax.scan(
        period_fn, x, (tuple(backbone_params["blocks"]), tuple(lora_params["layers"]))
    )
    return logits_from_hidden(backbone_params, cfg, x)


# ---------------------------------------------------------------------------
# Houlsby Adapters (serial bottleneck inside the backbone)
# ---------------------------------------------------------------------------


def init_houlsby(rng, cfg, bottleneck: int = 64, dtype=jnp.float32) -> dict:
    n_p = cfg.n_periods
    d = cfg.d_model
    out = []
    for i in range(len(cfg.pattern)):
        k1, k2 = jax.random.split(jax.random.fold_in(rng, i))
        out.append(
            {
                "down": (jax.random.normal(k1, (n_p, d, bottleneck)) * d ** -0.5).astype(dtype),
                "up": jnp.zeros((n_p, bottleneck, d), dtype),  # zero-init = identity start
                "ln": jnp.zeros((n_p, d), dtype),
            }
        )
    return {"layers": out}


def houlsby_logits(backbone_params, adapters, cfg, batch):
    from repro.models.backbone import apply_block

    x, positions = embed_inputs(backbone_params, cfg, batch)

    def period_fn(carry, xs):
        h = carry
        block_slice, ad_slice = xs
        for i, spec in enumerate(cfg.pattern):
            h = apply_block(block_slice[i], h, cfg, spec, positions)
            a = rms_norm(h, ad_slice[i]["ln"], cfg.norm_eps)
            h = h + jax.nn.gelu(a @ ad_slice[i]["down"]) @ ad_slice[i]["up"]
        return h, None

    x, _ = jax.lax.scan(
        period_fn, x, (tuple(backbone_params["blocks"]), tuple(adapters["layers"]))
    )
    return logits_from_hidden(backbone_params, cfg, x)


def peft_param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params) if hasattr(x, "size"))
