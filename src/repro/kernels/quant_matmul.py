"""Pallas TPU kernel: block-dequant INT8/INT4 matmul (paper §IV-D on TPU).

Computes ``y = x @ dequant(Wq)`` where ``Wq`` is stored INT8 (or packed
INT4) with per-(row, 128-col-block) absmax scales — the storage format of
`repro.core.quantization`. The weight tile is read at its integer
byte-width and converted **in VMEM**, so HBM traffic for the weights is
the integer byte-width; the MXU accumulates in f32. This is the
TPU-native rethink of the paper's (bitsandbytes-style)
dequant-then-GEMM: on a bandwidth-limited chip the fused version moves
4×/8× fewer weight bytes, which is exactly the term the memory roofline
charges.

Scale layout. A scale belongs to one contraction row ``k`` and one
128-column block ``c``. The kernel takes the scales transposed, as
``(N // 128, K)``, so that one grid step's scales are the block
``(bn // 128, bk)``: ``bk`` lanes and ``bn // 128`` sublanes, which
Mosaic accepts when ``bn // 128`` is a multiple of 8 or spans all of N.
Inside the tile each column block ``c`` folds its scale row into the
activations, ``(x * s[c]) @ q[:, c]`` — the same sum as
``x @ (q[:, c] * s[c]ᵀ)`` without a transpose in the kernel.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") with an f32 VMEM
accumulator scratch; block shapes default to (128, 1024, 256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QBLOCK = 128  # quantization block size along N (matches core.quantization)


def _nibbles(q):
    """Packed INT4 (bk, n) → sign-extended (low, high) nibbles as f32."""
    qi = q.astype(jnp.int32)
    lo = qi & 0xF
    hi = (qi >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return lo.astype(jnp.float32), hi.astype(jnp.float32)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, bits: int, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    s = s_ref[...]  # (bn // QBLOCK, bk) f32
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    nbj = s.shape[0]
    if bits == 8:
        q = q_ref[...]  # (bk, bn) int8
        for c in range(nbj):
            cols = slice(c * QBLOCK, (c + 1) * QBLOCK)
            xs = x * s[c : c + 1, :]
            acc_ref[:, cols] += dot(xs, q[:, cols].astype(jnp.float32))
    else:
        # packed (bk, bn // 2): column i holds unpacked columns 2i (low
        # nibble) and 2i + 1 (high). The tile's output is written split —
        # the low-nibble columns in its first half, the high in its second
        # — and the wrapper interleaves them back.
        lo, hi = _nibbles(q_ref[...])
        half, hb = lo.shape[1], QBLOCK // 2
        for c in range(nbj):
            cols = slice(c * hb, (c + 1) * hb)
            xs = x * s[c : c + 1, :]
            acc_ref[:, cols] += dot(xs, lo[:, cols])
            acc_ref[:, half + c * hb : half + (c + 1) * hb] += dot(xs, hi[:, cols])

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk", "interpret"))
def quant_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    bits: int = 8,
    bm: int = 128,
    bn: int = 1024,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """``x @ dequant(q, scale)`` with in-VMEM dequantisation → (M, N).

    x: (M, K) f32/bf16; q: (K, N) int8 or (K, N//2) packed int4 nibbles;
    scale: (K, N // QBLOCK) f32 per-(row, 128-col-block) absmax scales —
    the exact storage format of ``core.quantization.quantize(block=128)``.
    Returns (M, N) in x.dtype; MXU accumulation is f32.

    Block-size constraints (asserted, *not* padded — the weight shapes
    are static and callers align them): after clamping to the dims,
    ``bm | M``, ``bn | N``, ``bk | K``. A ``bn`` that would give a scale
    block of other than a multiple of 8 column blocks widens to N.
    ``interpret=True`` runs the Pallas interpreter off-TPU (the CPU test
    path).
    """
    M, K = x.shape
    N = scale.shape[1] * QBLOCK
    assert bn % QBLOCK == 0, "bn must cover whole quantization blocks"
    bm = min(bm, M)
    bn = min(bn, N)
    if bn != N and (N % bn or (bn // QBLOCK) % 8):
        bn = N
    bk = min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    pack = 2 if bits == 4 else 1

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn // pack), lambda i, j, k: (k, j)),
            pl.BlockSpec((bn // QBLOCK, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale.T)
    if bits == 4:  # undo the per-tile low/high split
        out = out.reshape(M, N // bn, 2, bn // 2).swapaxes(2, 3).reshape(M, N)
    return out
