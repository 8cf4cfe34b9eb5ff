"""Pallas TPU kernels: the cached-epoch (epoch ≥ 2) training hot path.

From epoch 2 on, the activation cache replaces every backbone forward
(paper §IV-B) and ``pac_cached_train_step`` becomes the dominant
per-step cost of a fine-tuning run. Its two heavy pieces are fused here:

* :func:`dq_adapter_mix` — the per-period tap consumption
  ``out = λ · (dequant(b) @ W_down) + (1 − λ) · a``
  where ``b`` is a cache entry in its *storage* form: f32, bf16, or the
  int8 block-absmax format of :mod:`repro.core.quantization`
  (``{"q": int8, "scale": f32}``). Dequantisation happens on the
  (bt, bk) tile **in VMEM**, so HBM (and host→device) traffic for the
  taps stays at the storage byte-width — the tap never materialises as
  an f32 (T, d) array. A custom VJP keeps that true in the backward
  pass too: ``dW_down = λ · dequant(b)ᵀ @ g`` re-dequantises tile-wise
  in a second kernel; the residual saved between the passes is the
  (T, d/r) down-projection, 1/r of the tap's size.

* :func:`lmhead_ce` — blockwise softmax-cross-entropy over the frozen
  LM head. The (T, vocab) logits tensor is never fully resident:
  an online-softmax sweep over vocab tiles tracks the running max /
  sum-exp / label logit (flash-attention style), and the backward pass
  recomputes each logits tile to form ``dh = (softmax − onehot) @ Wᵀ``.
  Only the (T,) per-token NLL and log-sum-exp are materialised.

:func:`cached_loss_parts` composes them into the full cached-epoch
PAC+ loss — ``impl="ref"`` is the pure-jnp numerics oracle (exactly the
pre-kernel math: upcast to f32, dense matmuls, full logits), and
``impl="pallas"`` the fused path. ``repro.core.steps.
pac_cached_train_step(kernel_impl=...)`` is the consumer.

Shape/dtype contract (every public op):

* Ragged shapes are zero-padded up to block multiples and sliced back
  (the PR 3 pad-and-slice idiom) — any (T, d, d_a, vocab) works.
* Block sizes are clamped to the array dims, so tiny CI shapes run the
  same code path as production shapes.
* ``interpret=None`` auto-selects: compiled on TPU, interpreter mode
  everywhere else (CPU/CI) — bit-accurate, not fast. Pass
  ``interpret=True``/``False`` to force.
* Compute is f32 on the MXU regardless of storage dtype
  (``preferred_element_type=jnp.float32``); outputs cast back to the
  carry/param dtype at the epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import QTensor, dequantize


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _pad_to(x, axis: int, target: int):
    if x.shape[axis] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pads)


def _named(call):
    """A ``pallas_call(..., name=...)`` under its own ``jit``: the jit
    starts a fresh name stack, so the compiled custom call is named
    ``<name>.N`` (``%lmhead_ce_bwd.2``) and not after the autodiff
    transform it runs under (``%transpose_jvp___.2``). The device trace
    shows ops by these instruction names."""
    return jax.jit(call)


# ---------------------------------------------------------------------------
# Cache-entry storage form
# ---------------------------------------------------------------------------
#
# A cached activation reaches the jitted step either as a plain array
# (f32 / bf16 policies) or, under the int8 policy, as a small dict
# {"q": int8 (..., d_pad), "scale": f32 (..., n_blocks)} — exactly the
# QTensor payload+scales of core.quantization, kept as a dict so the
# batch stays an ordinary pytree for jit/sharding. d_pad = n_blocks ·
# block ≥ d; the pad region quantises to zero so it contributes nothing
# to any contraction.


def is_quantized_entry(x) -> bool:
    """True for the int8 ``{"q", "scale"}`` storage form."""
    return isinstance(x, dict) and "q" in x


def entry_block(x) -> int:
    """Quantization block size of an int8 entry (from its shapes)."""
    return x["q"].shape[-1] // x["scale"].shape[-1]


def entry_to_f32(x, orig_last: int) -> jax.Array:
    """Storage form → f32 array (the eager/ref decompression)."""
    if is_quantized_entry(x):
        qt = QTensor(x["q"], x["scale"], 8, entry_block(x), orig_last)
        return dequantize(qt, jnp.float32)
    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Fused dequant × down-projection × λ-mix
# ---------------------------------------------------------------------------


def _scale_col(s, col):
    """Column ``col`` (traced) of a (rows, nb) scale block, as (rows, 1):
    a masked lane sum, exact because it selects a single lane."""
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.sum(jnp.where(lane == col, s, 0.0), axis=1, keepdims=True)


def _dequant_tile(q, s, first, qblock):
    """f32 tile of an int8 entry: q (rows, cols) int8 and s, the rows'
    whole (rows, nb) scale block; the tile's quantization blocks are
    ``first, first + 1, ...`` (``first`` may be traced)."""
    return jnp.concatenate([
        q[:, c * qblock:(c + 1) * qblock].astype(jnp.float32)
        * _scale_col(s, first + c)
        for c in range(q.shape[1] // qblock)
    ], axis=1)


def _mix_fwd_kernel(q_ref, s_ref, w_ref, a_ref, lam_ref, o_ref, bw_ref,
                    acc_ref, *, n_k: int, qblock: int):
    """One (bt, bj) output tile; K innermost. s_ref is None for float
    storage (the tile is just upcast); int8 tiles dequantise in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if s_ref is None:
        x = q_ref[...].astype(jnp.float32)
    else:
        bk_ = q_ref.shape[1]
        x = _dequant_tile(q_ref[...], s_ref[...], k * (bk_ // qblock), qblock)
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _done():
        bw = acc_ref[...]
        bw_ref[...] = bw
        lam = lam_ref[0]
        o_ref[...] = (
            lam * bw + (1.0 - lam) * a_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def _mix_fwd_impl(q, scale, w, a, lam, bt, bj, bk, interpret):
    """Returns (out (T, da) in a.dtype, bw (T, da) f32)."""
    T, d_store = q.shape
    da = w.shape[1]
    if scale is not None:
        qblock = d_store // scale.shape[1]
        bk = max(qblock, (min(bk, d_store) // qblock) * qblock)
    else:
        qblock = 0
        bk = min(bk, d_store)
    bt, bj = min(bt, T), min(bj, da)
    Tp = -(-T // bt) * bt
    dap = -(-da // bj) * bj
    Kp = -(-d_store // bk) * bk
    q = _pad_to(_pad_to(q, 0, Tp), 1, Kp)
    # w rows beyond its own d (int8 stores d_pad ≥ d) and up to Kp are
    # zero — matching the zero q/scale padding, they contribute nothing
    w = _pad_to(_pad_to(w, 0, Kp), 1, dap)
    a = _pad_to(_pad_to(a, 0, Tp), 1, dap)
    n_k = Kp // bk
    in_specs = [pl.BlockSpec((bt, bk), lambda i, j, k: (i, k))]
    args = [q]
    if scale is not None:
        scale = _pad_to(_pad_to(scale, 0, Tp), 1, Kp // qblock)
        # the rows' whole scale block: a (bt, bk // qblock) slice would
        # break Mosaic's (8, 128)-or-full-dim rule; the kernel picks the
        # tile's columns out of it
        in_specs.append(
            pl.BlockSpec((bt, Kp // qblock), lambda i, j, k: (i, 0))
        )
        args.append(scale)
    in_specs += [
        pl.BlockSpec((bk, bj), lambda i, j, k: (k, j)),
        pl.BlockSpec((bt, bj), lambda i, j, k: (i, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    args += [w, a, jnp.asarray(lam, jnp.float32).reshape(1)]

    kernel = functools.partial(_mix_fwd_kernel, n_k=n_k, qblock=qblock)
    if scale is None:  # drop the s_ref slot entirely
        kernel = functools.partial(
            lambda q_ref, w_ref, a_ref, lam_ref, o_ref, bw_ref, acc_ref, f:
            f(q_ref, None, w_ref, a_ref, lam_ref, o_ref, bw_ref, acc_ref),
            f=kernel,
        )
    out, bw = _named(pl.pallas_call(
        kernel,
        grid=(Tp // bt, dap // bj, n_k),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bt, bj), lambda i, j, k: (i, j)),
            pl.BlockSpec((bt, bj), lambda i, j, k: (i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Tp, dap), a.dtype),
            jax.ShapeDtypeStruct((Tp, dap), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((bt, bj), jnp.float32)],
        interpret=interpret,
        name="dq_adapter_mix_fwd",
    ))(*args)
    return out[:T, :da], bw[:T, :da]


def _mix_dw_kernel(q_ref, s_ref, g_ref, lam_ref, dw_ref, acc_ref,
                   *, n_k: int, qblock: int):
    """dW tile (bi, bj) = λ · Σ_T dequant(b)ᵀ @ g — T innermost."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if s_ref is None:
        x = q_ref[...].astype(jnp.float32)
    else:
        bi_ = q_ref.shape[1]
        x = _dequant_tile(
            q_ref[...], s_ref[...], pl.program_id(0) * (bi_ // qblock), qblock)
    acc_ref[...] += jax.lax.dot_general(
        x, g_ref[...].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _done():
        dw_ref[...] = (lam_ref[0] * acc_ref[...]).astype(dw_ref.dtype)


def _mix_dw_impl(q, scale, g, lam, d_out, out_dtype, bi, bj, bkt, interpret):
    """Backward weight grad: (d_out, da) = λ · dequant(b)[:, :d_out]ᵀ @ g."""
    T, d_store = q.shape
    da = g.shape[1]
    if scale is not None:
        qblock = d_store // scale.shape[1]
        bi = max(qblock, (min(bi, d_store) // qblock) * qblock)
    else:
        qblock = 0
        bi = min(bi, d_store)
    bj, bkt = min(bj, da), min(bkt, T)
    Dp = -(-d_store // bi) * bi
    dap = -(-da // bj) * bj
    Tp = -(-T // bkt) * bkt
    q = _pad_to(_pad_to(q, 0, Tp), 1, Dp)
    g = _pad_to(_pad_to(g, 0, Tp), 1, dap)
    n_k = Tp // bkt
    in_specs = [pl.BlockSpec((bkt, bi), lambda i, j, k: (k, i))]
    args = [q]
    if scale is not None:
        scale = _pad_to(_pad_to(scale, 0, Tp), 1, Dp // qblock)
        in_specs.append(  # whole scale rows, as in the forward
            pl.BlockSpec((bkt, Dp // qblock), lambda i, j, k: (k, 0))
        )
        args.append(scale)
    in_specs += [
        pl.BlockSpec((bkt, bj), lambda i, j, k: (k, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    args += [g, jnp.asarray(lam, jnp.float32).reshape(1)]

    kernel = functools.partial(_mix_dw_kernel, n_k=n_k, qblock=qblock)
    if scale is None:
        kernel = functools.partial(
            lambda q_ref, g_ref, lam_ref, dw_ref, acc_ref, f:
            f(q_ref, None, g_ref, lam_ref, dw_ref, acc_ref),
            f=kernel,
        )
    dw = _named(pl.pallas_call(
        kernel,
        grid=(Dp // bi, dap // bj, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Dp, dap), out_dtype),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
        name="dq_adapter_mix_dw",
    ))(*args)
    return dw[:d_out, :da]


def _zero_cotangent(x):
    """Zero (co)tangent matching a primal's tangent type: float0 for
    integer storage, a same-dtype zeros array (DCE'd by XLA) for floats."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _mix_op(bt: int, bj: int, bk: int, interpret: bool):
    """custom-VJP fused mix op, cached per static configuration.

    Differentiable in (w, a, lam) only — the cache entry (q, scale) is a
    frozen activation and receives a zero/float0 cotangent. Residuals:
    the storage-form entry itself plus the (T, da) f32 down-projection
    ``bw`` — never the dequantised (T, d) tap.
    """

    @jax.custom_vjp
    def op(q, scale, w, a, lam):
        out, _ = _mix_fwd_impl(q, scale, w, a, lam, bt, bj, bk, interpret)
        return out

    def fwd(q, scale, w, a, lam):
        out, bw = _mix_fwd_impl(q, scale, w, a, lam, bt, bj, bk, interpret)
        return out, (q, scale, bw, a, lam, w)

    def bwd(res, g):
        q, scale, bw, a, lam, w = res
        dw = _mix_dw_impl(
            q, scale, g, lam, w.shape[0], w.dtype, 256, bj, 256, interpret
        )
        g32 = g.astype(jnp.float32)
        lam32 = jnp.asarray(lam, jnp.float32)
        da_cot = ((1.0 - lam32) * g32).astype(a.dtype)
        dlam = jnp.sum(g32 * (bw - a.astype(jnp.float32)))
        dlam = dlam.astype(jnp.asarray(lam).dtype).reshape(jnp.shape(lam))
        dscale = None if scale is None else jnp.zeros_like(scale)
        return _zero_cotangent(q), dscale, dw, da_cot, dlam

    op.defvjp(fwd, bwd)
    return op


def dq_adapter_mix(b, w_down, a, lam, *, bt: int = 256, bj: int = 128,
                   bk: int = 512, interpret=None) -> jax.Array:
    """Fused ``λ · (dequant(b) @ w_down) + (1 − λ) · a``.

    b:      cache entry, (..., d)-shaped — an f32/bf16 array or the int8
            ``{"q": (..., d_pad) int8, "scale": (..., nb) f32}`` form.
            Dequantisation runs tile-wise in VMEM; b is treated as a
            constant (zero cotangent) — it is a frozen activation.
    w_down: (d, d_a) float. Rows are zero-extended to the entry's
            padded width, so d need not match d_pad.
    a:      (..., d_a) previous adapter state; out has a's dtype/shape
            (matching the reference's ``mixed.astype(carry.dtype)``).
    lam:    scalar λ (traced; differentiable).
    bt/bj/bk: block sizes over (tokens, d_a, contraction d) — clamped
            to the dims and (for int8) aligned down to the quantization
            block, then every dim is zero-padded to its block multiple
            and the result sliced back (ragged shapes welcome).
    interpret: None → compiled on TPU, interpreter elsewhere (CI).
    """
    interpret = _auto_interpret(interpret)
    if is_quantized_entry(b):
        q, scale = b["q"], b["scale"]
    else:
        q, scale = b, None
    lead = a.shape[:-1]
    q2 = q.reshape(-1, q.shape[-1])
    s2 = None if scale is None else scale.reshape(-1, scale.shape[-1])
    a2 = a.reshape(-1, a.shape[-1])
    out = _mix_op(bt, bj, bk, interpret)(q2, s2, w_down, a2, lam)
    return out.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# Blockwise softmax-cross-entropy over the LM head
# ---------------------------------------------------------------------------


_NEG = -1e30  # mask value for vocab padding


def _ce_fwd_kernel(h_ref, w_ref, lab_ref, nll_ref, lse_ref,
                   m_ref, l_ref, ll_ref, *, n_v: int, bv: int, V: int,
                   softcap):
    """Online softmax over vocab tiles: running max m, sum-exp l, and
    the label logit ll; the (bt, bv) logits tile lives only in VMEM."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    logits = jax.lax.dot_general(
        h_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    bt_ = logits.shape[0]
    col = k * bv + jax.lax.broadcasted_iota(jnp.int32, (bt_, bv), 1)
    logits = jnp.where(col < V, logits, _NEG)
    lab = lab_ref[...]  # (bt, 1) int32
    ll_ref[...] += jnp.sum(
        jnp.where(col == lab, logits, 0.0), axis=1, keepdims=True
    )
    bm = jnp.max(logits, axis=1, keepdims=True)
    new_m = jnp.maximum(m_ref[...], bm)
    l_ref[...] = l_ref[...] * jnp.exp(m_ref[...] - new_m) + jnp.sum(
        jnp.exp(logits - new_m), axis=1, keepdims=True
    )
    m_ref[...] = new_m

    @pl.when(k == n_v - 1)
    def _done():
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[...] = lse
        nll_ref[...] = lse - ll_ref[...]


def _ce_bwd_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dh_ref,
                   acc_ref, *, n_v: int, bv: int, V: int, softcap):
    """dh tile = dnll · Σ_vocab-tiles (softmax − onehot) @ Wᵀ, with each
    logits tile recomputed in VMEM (never materialised in HBM)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = jax.lax.dot_general(
        h_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    if softcap is not None:
        t = jnp.tanh(z / softcap)
        logits = softcap * t
        dfac = 1.0 - t * t  # d(softcap(z))/dz
    else:
        logits = z
        dfac = None
    bt_ = logits.shape[0]
    col = k * bv + jax.lax.broadcasted_iota(jnp.int32, (bt_, bv), 1)
    valid = col < V
    p = jnp.where(valid, jnp.exp(logits - lse_ref[...]), 0.0)
    p = p - jnp.where(col == lab_ref[...], 1.0, 0.0)
    if dfac is not None:
        p = p * dfac
    acc_ref[...] += jax.lax.dot_general(
        p, w_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_v - 1)
    def _done():
        dh_ref[...] = (acc_ref[...] * g_ref[...]).astype(dh_ref.dtype)


def _ce_pad(h, labels, bt):
    T, d = h.shape
    Tp = -(-T // bt) * bt
    return _pad_to(h, 0, Tp), _pad_to(labels.reshape(-1, 1), 0, Tp), Tp


def _ce_fwd_impl(h, w, labels, softcap, bt, bv, interpret):
    T, d = h.shape
    V = w.shape[1]
    bt, bv = min(bt, T), min(bv, V)
    hp, lab, Tp = _ce_pad(h, labels, bt)
    Vp = -(-V // bv) * bv
    wp = _pad_to(w, 1, Vp)
    n_v = Vp // bv
    nll, lse = _named(pl.pallas_call(
        functools.partial(
            _ce_fwd_kernel, n_v=n_v, bv=bv, V=V, softcap=softcap
        ),
        grid=(Tp // bt, n_v),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, k: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, k: (0, k)),
            pl.BlockSpec((bt, 1), lambda i, k: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bt, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, k: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=interpret,
        name="lmhead_ce_fwd",
    ))(hp, wp, lab)
    return nll[:T, 0], lse[:T, 0]


def _ce_bwd_impl(h, w, labels, lse, g, softcap, bt, bv, interpret):
    T, d = h.shape
    V = w.shape[1]
    bt, bv = min(bt, T), min(bv, V)
    hp, lab, Tp = _ce_pad(h, labels, bt)
    lsep = _pad_to(lse.reshape(-1, 1), 0, Tp)
    gp = _pad_to(g.astype(jnp.float32).reshape(-1, 1), 0, Tp)
    Vp = -(-V // bv) * bv
    wp = _pad_to(w, 1, Vp)
    n_v = Vp // bv
    dh = _named(pl.pallas_call(
        functools.partial(
            _ce_bwd_kernel, n_v=n_v, bv=bv, V=V, softcap=softcap
        ),
        grid=(Tp // bt, n_v),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, k: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, k: (0, k)),
            pl.BlockSpec((bt, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
        name="lmhead_ce_bwd",
    ))(hp, wp, lab, lsep, gp)
    return dh[:T]


@functools.lru_cache(maxsize=None)
def _ce_op(softcap, bt: int, bv: int, interpret: bool):
    """custom-VJP blockwise CE, cached per static configuration.
    Differentiable in h only (the head is frozen in PAC+)."""

    @jax.custom_vjp
    def op(h, w, labels):
        nll, _ = _ce_fwd_impl(h, w, labels, softcap, bt, bv, interpret)
        return nll

    def fwd(h, w, labels):
        nll, lse = _ce_fwd_impl(h, w, labels, softcap, bt, bv, interpret)
        return nll, (h, w, labels, lse)

    def bwd(res, g):
        h, w, labels, lse = res
        dh = _ce_bwd_impl(h, w, labels, lse, g, softcap, bt, bv, interpret)
        # the head is frozen — its zero cotangent is DCE'd by XLA
        return dh, jnp.zeros_like(w), _zero_cotangent(labels)

    op.defvjp(fwd, bwd)
    return op


def lmhead_ce(h, w, labels, *, softcap=None, bt: int = 128, bv: int = 512,
              interpret=None) -> jax.Array:
    """Per-token NLL of ``softmax(softcap(h @ w))`` without materialising
    the (T, vocab) logits.

    h:      (T, d) hidden states (post final-norm). Differentiable.
    w:      (d, V) frozen LM head (f32/bf16; dequantise QTensors first).
    labels: (T,) int32 target ids in [0, V) — clamp ignored positions to
            0 and mask their NLL outside (the masking is differentiable
            jnp, so ``d nll`` arrives pre-scaled by mask/denominator).
    softcap: optional tanh logit soft-cap (Gemma-style), applied inside
            the kernel in both passes.
    bt/bv:  token/vocab block sizes, clamped and zero-padded as needed;
            vocab padding columns are masked to −1e30 before the online
            max. Returns f32 (T,).
    """
    interpret = _auto_interpret(interpret)
    cap = None if softcap is None else float(softcap)
    return _ce_op(cap, bt, bv, interpret)(
        h, w, labels.astype(jnp.int32)
    )


# ---------------------------------------------------------------------------
# The composed cached-epoch loss (ref oracle + fused path)
# ---------------------------------------------------------------------------


def ref_cached_loss_parts(backbone_params, adapter_params, cfg, cached,
                          positions, r: int = 8):
    """Numerics oracle: eager f32 decompression + dense jnp math —
    bit-identical to the pre-kernel ``pac_cached_train_step`` body."""
    from repro.core.parallel_adapters import pac_logits
    from repro.models.backbone import cross_entropy_parts

    b0, taps, b_final = (
        entry_to_f32(cached[k], cfg.d_model)
        for k in ("b0", "taps", "b_final")
    )
    logits = pac_logits(
        backbone_params, adapter_params, cfg, b0, taps, b_final, positions, r
    )
    return cross_entropy_parts(logits, cached["labels"])


def fused_cached_loss_parts(backbone_params, adapter_params, cfg, cached,
                            positions, r: int = 8, interpret=None):
    """The Pallas fast path: storage-form entries feed
    :func:`dq_adapter_mix` per period (in-VMEM dequant, λ-mix fused) and
    the head runs through :func:`lmhead_ce` (blockwise CE). Everything
    else — the d/r-wide adapter blocks, norms, the up projection — is
    jnp at 1/r² the backbone's cost.
    """
    from repro.core.parallel_adapters import adapter_config
    from repro.core.quantization import maybe_dequantize_tree
    from repro.models.backbone import apply_block, head_weight
    from repro.models.layers import rms_norm

    labels = cached["labels"]
    B, S = labels.shape
    d = cfg.d_model
    acfg = adapter_config(cfg, r)
    da = acfg.d_model
    downs = adapter_params["downs"]
    lambdas = jnp.clip(adapter_params["lambda"], 0.0, 1.0)

    # b0 embedding-side projection: the same fused op with λ=1 (no mix)
    a = dq_adapter_mix(
        cached["b0"], downs[0], jnp.zeros((B, S, da), jnp.float32),
        jnp.float32(1.0), interpret=interpret,
    )

    def period_fn(carry, xs):
        a_prev = carry
        block_slice, down_i, lam_i, b_i = xs
        mixed = dq_adapter_mix(
            b_i, down_i, a_prev, lam_i, interpret=interpret
        )
        h = mixed.astype(a_prev.dtype)
        for j, spec in enumerate(acfg.pattern):
            h = apply_block(block_slice[j], h, acfg, spec, positions)
        return h, None

    a, _ = jax.lax.scan(
        period_fn,
        a,
        (tuple(adapter_params["blocks"]), downs[1:], lambdas,
         cached["taps"]),
    )
    a = rms_norm(a, adapter_params["out_norm"], acfg.norm_eps)
    side = a @ adapter_params["up"]

    # b_final is one (B, S, d) plane consumed elementwise — its
    # decompression is the storage-width H2D transfer plus one cheap
    # on-device dequant (no matmul to fuse into)
    h = entry_to_f32(cached["b_final"], d) + side
    p_norm = maybe_dequantize_tree(backbone_params["final_norm"])
    h = rms_norm(h, p_norm, cfg.norm_eps)
    w_head = head_weight(backbone_params, cfg)

    mask = labels != -100
    lab = jnp.where(mask, labels, 0)
    nll = lmhead_ce(
        h.reshape(B * S, d), w_head, lab.reshape(B * S),
        softcap=cfg.logit_softcap, interpret=interpret,
    ).reshape(B, S)
    return jnp.sum(nll * mask), jnp.sum(mask)


def cached_loss_parts(backbone_params, adapter_params, cfg, cached,
                      positions, r: int = 8, *, impl: str = "ref",
                      interpret=None):
    """(summed NLL, valid-token count) of the cached-epoch PAC+ loss.

    ``cached``: {"b0", "taps", "b_final"} in storage form (arrays or
    int8 {"q","scale"} dicts) + "labels". ``impl="ref"`` is the jnp
    oracle, ``impl="pallas"`` the fused kernels; both accept all three
    storage forms, so the oracle also validates the compressed handoff.
    """
    if impl == "ref":
        return ref_cached_loss_parts(
            backbone_params, adapter_params, cfg, cached, positions, r
        )
    if impl == "pallas":
        return fused_cached_loss_parts(
            backbone_params, adapter_params, cfg, cached, positions, r,
            interpret=interpret,
        )
    raise ValueError(f"kernel_impl must be 'ref' or 'pallas', got {impl!r}")
