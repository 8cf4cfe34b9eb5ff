"""Weights and adapter state made from ``--seed``, on the device.

The benchmark, not the program, owns the weights: the harness hands
them to the program and the plain reference (``bench/reference.py``)
makes the same values again from the same seed, layer by layer, so the
reference takes nothing the program has made. Every leaf of layer ``l``
is ``normal(fold_in(fold_in(key, leaf_id), l)) * scale``, so a whole
stacked tree (one jitted call) and a single layer agree bit for bit.

Shapes come from a configuration file's ``arch`` and ``adapter``
sections (widths as published; ``adapter`` is the side network at
``d/r``). The backbone's layers belong to its architecture's module
(``bench/archs``), which owns their leaf ids; the ids here are the
shared pieces: embedding, final norm, head and side network. Nothing
here imports the program.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# stream ids: one independent key per purpose, drawn from the seed
STREAM_BACKBONE = 1
STREAM_ADAPTER = 2
STREAM_BANK = 1000  # + i: the i-th adapter of a serving bank

# one id per leaf name, so adding a leaf never shifts another's values;
# 10-18 are the side network's blocks (its own key, STREAM_ADAPTER)
_LEAF_IDS = {
    "embed": 1, "final_norm": 2, "lm_head": 3,
    "ln1": 10, "wq": 11, "wk": 12, "wv": 13, "wo": 14,
    "ln2": 15, "wi": 16, "wg": 17, "wo_ff": 18,
    "downs": 30, "lambda": 31, "up": 32, "out_norm": 33,
}
NORM_SCALE = 0.1  # norm gains are (1 + w): small random w exercises them
# a backbone leaf whose name holds one of these stays float32 whatever its
# size, as a deployment keeps a router's weights
F32_NAMES = ("router",)


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw uint32[2] key from any whole-number seed (more than 32
    bits are kept: ``PRNGKey`` would truncate them)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


class Stack(NamedTuple):
    """``count`` backbone layers alike: ``make(key, i)`` gives layer
    ``i``'s f32 leaves, flat by leaf name (``i`` may be traced)."""

    count: int
    make: Callable


def leaf(key, leaf_id: int, layer, shape, scale) -> jax.Array:
    """``normal(fold_in(fold_in(key, leaf_id), layer)) * scale`` in f32."""
    k = jax.random.fold_in(jax.random.fold_in(key, leaf_id), layer)
    return jax.random.normal(k, shape, jnp.float32) * scale


def _leaf(key, name: str, layer, shape, scale) -> jax.Array:
    return leaf(key, _LEAF_IDS[name], layer, shape, scale)


def layer_shapes(w: dict) -> dict:
    """name -> (shape, init scale) of one dense GQA decoder layer of
    widths ``w`` (an ``arch`` or ``adapter`` section)."""
    d, ff = w["d_model"], w["d_ff"]
    hq, hkv = w["n_heads"] * w["head_dim"], w["n_kv_heads"] * w["head_dim"]
    return {
        "ln1": ((d,), NORM_SCALE), "wq": ((d, hq), d ** -0.5),
        "wk": ((d, hkv), d ** -0.5), "wv": ((d, hkv), d ** -0.5),
        "wo": ((hq, d), hq ** -0.5), "ln2": ((d,), NORM_SCALE),
        "wi": ((d, ff), d ** -0.5), "wg": ((d, ff), d ** -0.5),
        "wo_ff": ((ff, d), ff ** -0.5),
    }


def causal_context(seq: int, window=None) -> float:
    """Mean number of positions a token of a causal row of ``seq`` tokens
    attends over, within ``window`` positions (None: the whole prefix)."""
    if window is None:
        return (seq + 1) / 2
    return sum(min(t, window) for t in range(1, seq + 1)) / seq


def layer_flops(w: dict, ctx: float) -> float:
    """Forward FLOPs of one token through one dense layer of widths
    ``w``: its matmuls, and attention (scores and values) over ``ctx``
    positions."""
    matmuls = sum(2 * s[0] * s[1] for s, _ in layer_shapes(w).values() if len(s) == 2)
    return matmuls + 4 * w["n_heads"] * w["head_dim"] * ctx


def make_layer(key, w: dict, layer, ids: dict = _LEAF_IDS) -> dict:
    """One dense layer's f32 weights, flat by leaf name (``layer`` may
    be traced), each leaf drawn under its id in ``ids``."""
    return {n: leaf(key, ids[n], layer, s, c) for n, (s, c) in layer_shapes(w).items()}


def make_embed(key, arch: dict) -> jax.Array:
    return _leaf(key, "embed", 0, (arch["vocab"], arch["d_model"]), arch["d_model"] ** -0.5)


def make_head(key, arch: dict) -> tuple:
    """(final_norm (d,), lm_head (d, vocab)) in f32."""
    d = arch["d_model"]
    return (_leaf(key, "final_norm", 0, (d,), NORM_SCALE),
            _leaf(key, "lm_head", 0, (d, arch["vocab"]), d ** -0.5))


def nest(flat: dict) -> dict:
    """Flat leaf names of a dense layer -> the program's block layout."""
    return {
        "ln1": flat["ln1"],
        "mixer": {k: flat[k] for k in ("wq", "wk", "wv", "wo")},
        "ln2": flat["ln2"],
        "ffn": {"wi": flat["wi"], "wg": flat["wg"], "wo": flat["wo_ff"]},
    }


def make_rest(key, arch: dict) -> dict:
    """The f32 leaves of the backbone outside its blocks, in the
    program's tree layout."""
    final_norm, head = make_head(key, arch)
    return {"embed": make_embed(key, arch), "final_norm": final_norm, "lm_head": head}


def make_adapter(key, arch: dict, adapter: dict, side: tuple) -> dict:
    """The side network's initial f32 state in the program's tree
    layout. ``side`` is (taps, windows): ``taps`` backbone taps, and per
    tap one dense block for each entry of ``windows`` (the j-th block
    after tap i is drawn as layer ``j * taps + i``). ``up`` is random,
    not zero, so every leaf has a gradient from the first step on."""
    (n, windows), d, da = side, arch["d_model"], adapter["d_model"]
    return {
        "downs": jax.vmap(lambda i: _leaf(key, "downs", i, (d, da), d ** -0.5))(
            jnp.arange(n + 1)),
        "lambda": jnp.full((n,), 0.5, jnp.float32),
        "blocks": [nest(jax.vmap(lambda i: make_layer(key, adapter, i))(j * n + jnp.arange(n)))
                   for j in range(len(windows))],
        "up": _leaf(key, "up", 0, (da, d), da ** -0.5),
        "out_norm": _leaf(key, "out_norm", 0, (da,), NORM_SCALE),
    }
