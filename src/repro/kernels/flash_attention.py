"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Variants needed by the zoo: causal, sliding-window (gemma2 local layers,
the ``sw8k`` long-context serving mode), and attention-logit softcap
(gemma2/grok). Numerics mirror `repro.models.layers.flash_attention`
(the jnp oracle used as ``ref``).

Grid: (B·H, Sq/bq, Sk/bk) with K innermost; VMEM scratch carries the
online-softmax state (acc, m, l) across K steps; the final K step
normalises and writes the output tile. Causal/window masking is computed
from block-relative iota so out-of-range blocks contribute nothing (a
perf TODO in DESIGN.md notes block skipping via a restricted grid).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: Optional[int], cap: Optional[float],
    bq: int, bk: int, n_k: int,
):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    qpos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kstep * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kstep == n_k - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "attn_softcap", "bq", "bk", "interpret")
)
def flash_attention_tpu(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Blocked online-softmax attention → (BH, Sq, hd) in q.dtype.

    q, k, v: (BH, S, hd) f32/bf16 — batch·heads flattened, GQA repeat
    already applied. ``causal``/``window``/``attn_softcap`` select the
    masking/softcap variants (gemma2 local layers, grok softcap).

    Block sizes ``bq/bk`` tile (Sq, Sk); they are clamped to the dims
    and then **asserted** to divide them (no pad-and-slice here — the
    pallas OpSet pads S to a block multiple). Softmax state is carried in f32 VMEM
    scratch across K steps. ``interpret=True`` runs the Pallas
    interpreter off-TPU (bit-accurate, slow — the CI path).
    """
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    n_k = Sk // bk
    scale = hd ** -0.5

    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            cap=attn_softcap, bq=bq, bk=bk, n_k=n_k,
        ),
        grid=(BH, Sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
