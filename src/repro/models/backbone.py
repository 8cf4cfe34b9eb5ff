"""Pattern-driven decoder backbone.

One configurable decoder covers all 10 assigned architectures: an
:class:`~repro.configs.base.ArchConfig` declares a *period* of
heterogeneous :class:`LayerSpec`s (attention / mamba / mLSTM / sLSTM,
dense-FFN / MoE / no-FFN) which is tiled ``n_periods`` times. Parameters
are stacked over periods and the forward pass is a ``jax.lax.scan`` over
the stack, so the lowered HLO is depth-independent: compile time does
not grow with the layer count.

The forward pass optionally emits **taps** — the hidden state after every
period — which are exactly the invariant activations ``b_i`` the PAC+
Parallel Adapters consume (`repro.core.parallel_adapters`) and the
activation cache stores (`repro.core.activation_cache`).

Decode runs one token against a per-layer-kind state cache (KV for
attention, (h, conv) for Mamba, (C, n, m) for mLSTM, (c, n, h, m) for
sLSTM), also scanned over periods.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.opset import get_opset
from repro.core.quantization import maybe_dequantize_tree
from repro.models import ssm
from repro.models.layers import (
    attention_decode,
    attention_decode_quant,
    attention_forward,
    init_attention,
    init_mlp,
    mlp_forward,
    rms_norm,
    softcap,
)
from repro.models.moe import init_moe, moe_forward, moe_forward_dense

# Every forward below dispatches its primitive ops (matmul, attention,
# embedding gather, tap emission) through an OpSet (core/opset.py) — the
# one seam kernel variants plug into. `ops=None` means the dense jnp
# oracle, bit-identical to the historical dequantize-then-dense code.
_REF_OPS = get_opset("ref")

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_block(rng, cfg, spec, dtype=jnp.float32) -> dict:
    """Parameters for one layer position."""
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {"ln1": jnp.zeros((d,), dtype)}
    if spec.kind == "attn":
        p["mixer"] = init_attention(k1, cfg, dtype)
    elif spec.kind == "mamba":
        p["mixer"] = ssm.init_mamba(k1, cfg, dtype)
    elif spec.kind == "mlstm":
        p["mixer"] = ssm.init_mlstm(k1, cfg, dtype)
    elif spec.kind == "slstm":
        p["mixer"] = ssm.init_slstm(k1, cfg, dtype)
    else:
        raise ValueError(f"unknown layer kind {spec.kind!r}")
    if spec.ffn and (cfg.d_ff or (spec.moe and cfg.moe)):
        p["ln2"] = jnp.zeros((d,), dtype)
        if spec.moe and cfg.moe is not None:
            p["ffn"] = init_moe(k2, d, cfg.moe, dtype)
        else:
            p["ffn"] = init_mlp(k3, d, cfg.d_ff, dtype)
    return p


def init_backbone(rng, cfg, dtype=jnp.float32) -> dict:
    """Full backbone parameter pytree; block leaves stacked over periods."""
    n_p = cfg.n_periods
    k_embed, k_head, k_blocks = jax.random.split(rng, 3)
    blocks = []
    for i, spec in enumerate(cfg.pattern):
        rngs = jax.random.split(jax.random.fold_in(k_blocks, i), n_p)
        blocks.append(jax.vmap(lambda r, s=spec: init_block(r, cfg, s, dtype))(rngs))
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model)) * cfg.d_model ** -0.5).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab)) * cfg.d_model ** -0.5
        ).astype(dtype)
    return params


def abstract_backbone(cfg, dtype=jnp.float32):
    """ShapeDtypeStruct pytree of the params — no allocation."""
    return jax.eval_shape(lambda: init_backbone(jax.random.PRNGKey(0), cfg, dtype))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def apply_block(p, x, cfg, spec, positions, ops=None, return_kv: bool = False):
    ops = ops if ops is not None else _REF_OPS
    # ref: dequantize the whole block; pallas: matmul weights stay
    # quantized and feed quant_matmul inside ops.matmul
    p = ops.prepare_block(p, spec)
    h = ops.rms_norm(x, p["ln1"], cfg.norm_eps)
    kv = None
    if spec.kind == "attn":
        if return_kv:
            mix, kv = attention_forward(
                p["mixer"], h, cfg, spec, positions, ops=ops, return_kv=True
            )
        else:
            mix = attention_forward(p["mixer"], h, cfg, spec, positions, ops=ops)
    elif spec.kind == "mamba":
        mix = ssm.mamba_forward(p["mixer"], h, cfg)
    elif spec.kind == "mlstm":
        mix = ssm.mlstm_forward(p["mixer"], h, cfg)
    elif spec.kind == "slstm":
        mix = ssm.slstm_forward(p["mixer"], h, cfg)
    x = x + mix
    if "ffn" in p:
        h = ops.rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.moe and cfg.moe is not None:
            x = x + moe_forward(p["ffn"], h, cfg.moe)
        else:
            x = x + mlp_forward(p["ffn"], h, ops=ops)
    if return_kv:
        # (k, v) post-rope for attention blocks, None otherwise — the
        # paged-serving prefill scatters these into the KV page pool
        return x, kv
    return x


def embed_inputs(params, cfg, batch: dict, ops=None):
    """Token embedding or stub-frontend embeddings.

    batch: {"tokens": (B,S) int32} and/or {"embeds": (B,S,d)};
    optional {"positions": (B,S) or (3,B,S)}.
    """
    ops = ops if ops is not None else _REF_OPS
    if "embeds" in batch:
        x = batch["embeds"]
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = ops.embed_lookup(params["embed"], tokens)
    if "positions" in batch:
        positions = batch["positions"]
    elif cfg.rope == "mrope":
        pos1 = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        positions = jnp.broadcast_to(pos1, (3, B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


def backbone_forward(params, cfg, batch: dict, collect_taps: bool = False,
                     return_inputs: bool = False, ops=None):
    """Returns (final_hidden (B,S,d), taps (n_periods,B,S,d) | None).

    With ``return_inputs=True`` the embedded input and positions are also
    returned — ``(final, taps, x0, positions)`` — so callers that need
    ``b0`` (the PAC+ steps) don't pay the embedding lookup twice.

    Taps pass through ``ops.emit_tap`` at the tap site: under the pallas
    OpSet with an int8/bf16 tap policy they leave the scan already in
    cache storage form (dict of int8 payload + scales / bf16) — no f32
    HBM round-trip on the way to the activation cache.
    """
    ops = ops if ops is not None else _REF_OPS
    x, positions = embed_inputs(params, cfg, batch, ops=ops)
    x0 = x

    def period_fn(carry, block_slice):
        h = carry
        for i, spec in enumerate(cfg.pattern):
            h = apply_block(block_slice[i], h, cfg, spec, positions, ops=ops)
        return h, (ops.emit_tap(h) if collect_taps else None)

    x, taps = jax.lax.scan(period_fn, x, tuple(params["blocks"]))
    if return_inputs:
        return x, taps, x0, positions
    return x, taps


def head_weight(params, cfg):
    """The (d, vocab) LM-head matrix: tied embedding transpose or the
    dedicated head, dequantized — the one definition shared by
    :func:`logits_from_hidden` and the fused cached-step CE kernel."""
    if cfg.tie_embeddings:
        return maybe_dequantize_tree(params["embed"]).T
    return maybe_dequantize_tree(params["lm_head"])


def logits_from_hidden(params, cfg, h):
    p_norm = maybe_dequantize_tree(params["final_norm"])
    h = rms_norm(h, p_norm, cfg.norm_eps)
    logits = h @ head_weight(params, cfg)
    return softcap(logits, cfg.logit_softcap)


def backbone_logits(params, cfg, batch: dict):
    h, _ = backbone_forward(params, cfg, batch)
    return logits_from_hidden(params, cfg, h)


def cross_entropy(logits: jax.Array, labels: jax.Array, ignore: int = -100):
    """Mean CE over non-ignored positions. logits (B,S,V), labels (B,S).

    Implemented as a one-hot contraction rather than take_along_axis: the
    label pick is then a product and a reduction over the vocab axis, and
    its gradient a dense (softmax − one-hot) with no scatter. It is the
    ``ref`` oracle the fused ``lmhead_ce`` path is tested against, so its
    numerics are pinned.
    """
    num, den = cross_entropy_parts(logits, labels, ignore)
    return num / jnp.maximum(den, 1)


def cross_entropy_parts(logits: jax.Array, labels: jax.Array, ignore: int = -100):
    """(summed NLL, valid-token count) — the pieces of the mean CE.

    Exposed so data-parallel callers can ``psum`` numerator and
    denominator separately and divide once: a pmean of per-shard means is
    only exact when every shard holds the same number of non-ignored
    tokens."""
    mask = labels != ignore
    labels = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logp.dtype)
    nll = -jnp.einsum("bsv,bsv->bs", logp, onehot)
    return jnp.sum(nll * mask), jnp.sum(mask)


# ---------------------------------------------------------------------------
# Decode (single token against a cache)
# ---------------------------------------------------------------------------


def init_cache(cfg, B: int, max_len: int, dtype=jnp.float32, kv_quant=None):
    """Cache pytree: one entry per pattern position, stacked over periods.

    kv_quant=8 stores attention K/V as INT8 with per-(token, kv-head)
    absmax scales (the paper's Eq. 1 applied to the KV cache — a
    beyond-paper serving feature; 4× less HBM read at decode)."""

    def one(spec):
        if spec.kind == "attn":
            if kv_quant == 8:
                return {
                    "k": jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.hd), jnp.int8),
                    "v": jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.hd), jnp.int8),
                    "k_scale": jnp.zeros((B, max_len, cfg.n_kv_heads), jnp.float32),
                    "v_scale": jnp.zeros((B, max_len, cfg.n_kv_heads), jnp.float32),
                }
            return {
                "k": jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.hd), dtype),
                "v": jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.hd), dtype),
            }
        if spec.kind == "mamba":
            return ssm.init_mamba_cache(cfg, B, dtype)
        if spec.kind == "mlstm":
            return ssm.init_mlstm_cache(cfg, B)
        if spec.kind == "slstm":
            return ssm.init_slstm_cache(cfg, B)
        raise ValueError(spec.kind)

    caches = []
    for spec in cfg.pattern:
        single = one(spec)
        caches.append(
            jax.tree.map(lambda t: jnp.broadcast_to(t[None], (cfg.n_periods,) + t.shape), single)
        )
    return caches


def abstract_cache(cfg, B: int, max_len: int, dtype=jnp.float32, kv_quant=None):
    return jax.eval_shape(lambda: init_cache(cfg, B, max_len, dtype, kv_quant=kv_quant))


def apply_block_decode(p, x, cfg, spec, cache, pos, ops=None):
    ops = ops if ops is not None else _REF_OPS
    p = ops.prepare_block(p, spec)
    h = ops.rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.kind == "attn":
        if "k_scale" in cache:  # INT8 KV cache (beyond-paper serving)
            mix, new_cache = attention_decode_quant(p["mixer"], h, cfg, spec, cache, pos, ops=ops)
        else:
            mix, ck, cv = attention_decode(p["mixer"], h, cfg, spec, cache["k"], cache["v"], pos, ops=ops)
            new_cache = {"k": ck, "v": cv}
    elif spec.kind == "mamba":
        mix, new_cache = ssm.mamba_decode(p["mixer"], h, cfg, cache)
    elif spec.kind == "mlstm":
        mix, new_cache = ssm.mlstm_decode(p["mixer"], h, cfg, cache)
    elif spec.kind == "slstm":
        mix, new_cache = ssm.slstm_decode(p["mixer"], h, cfg, cache)
    x = x + mix
    if "ffn" in p:
        h = ops.rms_norm(x, p["ln2"], cfg.norm_eps)
        if spec.moe and cfg.moe is not None:
            # decode: T = B tokens — widen capacity (cheap at decode T) to
            # make token drops rare; serving should not drop tokens.
            x = x + moe_forward(p["ffn"], h, cfg.moe, capacity_factor=2.0 * cfg.moe.capacity_factor)
        else:
            x = x + mlp_forward(p["ffn"], h, ops=ops)
    return x, new_cache


def backbone_decode(params, cfg, token_batch: dict, cache, pos, ops=None):
    """One decode step.

    token_batch: {"tokens": (B,1)} or {"embeds": (B,1,d)}; pos: () int32 —
    the index the new token is written at. Returns (logits (B,1,V), cache').
    """
    ops = ops if ops is not None else _REF_OPS
    if "embeds" in token_batch:
        x = token_batch["embeds"]
    else:
        x = ops.embed_lookup(params["embed"], token_batch["tokens"])

    def period_fn(carry, xs):
        block_slice, cache_slice = xs
        h = carry
        new_caches = []
        for i, spec in enumerate(cfg.pattern):
            h, nc = apply_block_decode(block_slice[i], h, cfg, spec, cache_slice[i], pos, ops=ops)
            new_caches.append(nc)
        return h, tuple(new_caches)

    x, new_cache = jax.lax.scan(period_fn, x, (tuple(params["blocks"]), tuple(cache)))
    return logits_from_hidden(params, cfg, x), list(new_cache)
