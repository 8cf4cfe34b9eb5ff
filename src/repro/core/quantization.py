"""Block-wise absmax quantization of the frozen LLM backbone (paper §IV-D).

Implements the paper's Eq. (1)/(2): weights are stored in a low-bit
integer format (INT8, or packed INT4) with one f32 scale per contiguous
block of ``block`` elements along the **last** axis — keeping the original
dimension structure, so code written against the f32 parameter's shape
(its sharding, the backbone scan's slicing) applies unchanged to the
quantized storage.

The storage/compute split follows the paper's Fig. 8 (and QLoRA): storage
dtype INT8/INT4, compute dtype f32/bf16 — ``dequantize`` happens at use,
layer-by-layer inside the backbone scan so at most one layer's worth of
f32 weights is live at a time. On TPU the fused Pallas kernel
(`repro.kernels.quant_matmul`) performs dequantisation in VMEM so HBM
traffic stays at the integer byte-width — the memory-roofline payoff of
the technique.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import compat


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Block-quantized tensor: int storage + per-block scales.

    q:      int8 array; for bits=4, two nibbles packed per byte along the
            last axis (shape[..., padded_last/2]).
    scale:  f32 array (..., n_blocks) — absmax-derived, one per block.
    """

    def __init__(self, q, scale, bits: int, block: int, orig_last: int):
        self.q = q
        self.scale = scale
        self.bits = bits
        self.block = block
        self.orig_last = orig_last

    # -- pytree protocol --
    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.block, self.orig_last)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        return self.q.shape[:-1] + (self.orig_last,)

    @property
    def dtype(self):  # storage dtype
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        return self.q.size * 1 + self.scale.size * 4

    def __repr__(self):
        return f"QTensor(int{self.bits}, shape={self.shape}, block={self.block})"


def _qmax(bits: int) -> int:
    return {8: 127, 4: 7}[bits]


def quantize(x: jax.Array, bits: int = 8, block: int = 128) -> QTensor:
    """Block-wise absmax quantization along the last axis (paper Eq. 1)."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    orig_last = x.shape[-1]
    block = min(block, orig_last)
    if bits == 4 and block % 2:
        block += 1  # nibble packing needs an even padded length
    nb = -(-orig_last // block)
    pad = nb * block - orig_last
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(x.shape[:-1] + (nb, block)).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xb), axis=-1)  # (..., nb)
    qmax = _qmax(bits)
    scale = absmax / qmax  # dequant multiplier; 0 where block is all-zero
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xb * inv[..., None]), -qmax, qmax).astype(jnp.int8)
    q = q.reshape(x.shape[:-1] + (nb * block,))
    if bits == 4:
        lo = q[..., 0::2] & 0xF
        hi = (q[..., 1::2] & 0xF) << 4
        q = (lo | hi).astype(jnp.int8)
    return QTensor(q, scale, bits, block, orig_last)


def dequantize(t: QTensor, dtype=jnp.float32) -> jax.Array:
    """Paper Eq. (2): elementwise q * scale, unpad, cast to compute dtype."""
    q = t.q
    if t.bits == 4:
        lo = (q.astype(jnp.int32) & 0xF)
        lo = jnp.where(lo >= 8, lo - 16, lo)  # sign-extend nibble
        hi = (q.astype(jnp.int32) >> 4) & 0xF
        hi = jnp.where(hi >= 8, hi - 16, hi)
        q = jnp.stack([lo, hi], axis=-1).reshape(q.shape[:-1] + (q.shape[-1] * 2,))
    padded_last = q.shape[-1]
    nb = padded_last // t.block
    xb = q.reshape(q.shape[:-1] + (nb, t.block)).astype(jnp.float32)
    x = (xb * t.scale[..., None]).reshape(q.shape[:-1] + (padded_last,))
    return x[..., : t.orig_last].astype(dtype)


# ---------------------------------------------------------------------------
# Tree helpers — quantize a whole backbone, dequantize lazily at use
# ---------------------------------------------------------------------------


def _is_qtensor(x: Any) -> bool:
    return isinstance(x, QTensor)


# leaves whose pytree path has a component containing one of these
# substrings stay full-precision no matter their size: MoE routers are
# quantization-sensitive — a few
# mis-rounded logits flip top-k expert assignments outright, a much larger
# error than any dense matmul suffers (cf. QLoRA keeping norms in f32)
QUANT_SKIP_NAMES = ("router",)


def _path_names(path) -> list:
    names = []
    for k in path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                names.append(str(getattr(k, attr)))
                break
    return names


def quantize_tree(
    tree, bits: int = 8, block: int = 128, min_size: int = 4096,
    skip_names=QUANT_SKIP_NAMES,
):
    """Quantize every large weight leaf; leave small/1-D leaves untouched.

    Leaves whose path has a component *containing* any ``skip_names``
    substring keep their dtype — by default anything router-like
    ("router", "moe_router", "router_w", ...; see QUANT_SKIP_NAMES)."""
    if isinstance(skip_names, str):  # a bare string is one name, not chars
        skip_names = (skip_names,)
    skip_names = tuple(skip_names)

    def f(path, x):
        if any(s in n for n in _path_names(path) for s in skip_names):
            return x
        if isinstance(x, jax.Array) and x.ndim >= 2 and x.size >= min_size:
            return quantize(x, bits, block)
        return x

    return compat.tree_map_with_path(f, tree)


def maybe_dequantize_tree(tree, dtype=jnp.float32):
    """Identity on plain arrays; dequantizes any QTensor leaves."""
    if _is_qtensor(tree):
        return dequantize(tree, dtype)
    return jax.tree.map(
        lambda x: dequantize(x, dtype) if _is_qtensor(x) else x, tree, is_leaf=_is_qtensor
    )


def tree_storage_bytes(tree) -> int:
    """Total storage bytes (int bytes for QTensors, array bytes otherwise)."""
    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=_is_qtensor):
        if _is_qtensor(leaf):
            total += leaf.nbytes
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total
