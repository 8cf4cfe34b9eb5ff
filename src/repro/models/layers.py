"""Core transformer layers: norms, rotary embeddings, GQA attention.

Attention is implemented as a *blocked online-softmax* ("flash") function
with a custom VJP so that neither forward nor backward ever materialises
the (S×S) score matrix — the backward pass recomputes per-KV-block scores,
exactly like the TPU Pallas kernel in ``repro.kernels.flash_attention``
(this function doubles as its reference oracle at block granularity).

Supports: causal masking, sliding windows (gemma2/serving variants),
attention logit soft-capping (gemma2/grok), and GQA/MQA head layouts.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float = 1_000_000.0, sections=(2, 3, 3)
) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, hd); positions: (3, B, S) — temporal/height/width ids.
    The hd/2 frequency slots are split across the three position streams in
    the ratio ``sections`` (t:h:w), per arXiv:2409.12191.
    """
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(hd, theta)  # (half,)
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections:
        acc += s
        bounds.append(half * acc // total)
    slot = jnp.arange(half)
    # stream index per frequency slot: 0,1,2
    stream = jnp.select(
        [slot < bounds[0], slot < bounds[1]], [0, 1], default=2
    )  # (half,)
    pos = positions.astype(jnp.float32)  # (3, B, S)
    # pick the position stream per frequency slot: (B, S, half)
    pos_per_slot = jnp.moveaxis(pos, 0, -1)[:, :, stream]
    angles = pos_per_slot * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked ("flash") attention with custom VJP
# ---------------------------------------------------------------------------

_NEG_INF = -1e30
_PAD_POS = 2 ** 30  # sentinel position for padded KV slots (never attended)


def _block_mask(
    q_pos: jax.Array, k_pos: jax.Array, causal: bool, window: Optional[int]
) -> jax.Array:
    """(Sq, blk) boolean mask: True = attend."""
    m = k_pos[None, :] < _PAD_POS  # padded slots are masked everywhere
    d = q_pos[:, None] - k_pos[None, :]
    if causal:
        m &= d >= 0
    if window is not None:
        m &= d < window
    return m


def _scores(q, k, scale, cap):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    return softcap(s, cap)


def _dscores(q, k, scale, cap, ds_post):
    """VJP of _scores wrt the pre-cap logits -> propagate to q,k later."""
    if cap is None:
        return ds_post * scale
    s_pre = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    t = jnp.tanh(s_pre / cap)
    return ds_post * (1.0 - jnp.square(t)) * scale


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    block_k: int = 512,
):
    """Memory-bounded attention.

    q: (B, H, Sq, hd); k, v: (B, H, Sk, hd) — GQA repeat must already be
    applied (or use grouped heads upstream). q_pos: (Sq,), k_pos: (Sk,).
    Returns (B, H, Sq, hd) in q.dtype.
    """
    o, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window, attn_softcap, block_k)
    return o


def _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window, cap, block_k):
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    scale = 1.0 / (hd ** 0.5)
    nb = max(1, -(-Sk // block_k))
    pad = nb * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=_PAD_POS)
    # scan over block *indices* and dynamic-slice K/V in the body: a
    # pre-stacked (nb,B,H,blk,hd) transpose would hold two more copies
    # of K/V per layer.
    pb = k_pos.reshape(nb, block_k)

    def body(carry, xs):
        o, m, l = carry
        j, pj = xs
        kj = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k, axis=2)
        s = _scores(q, kj, scale, cap)  # (B,H,Sq,blk) f32
        mask = _block_mask(q_pos, pj, causal, window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vj.astype(jnp.float32)
        )
        return (o, m_new, l), None

    o0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    m0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), (jnp.arange(nb), pb))
    l = jnp.maximum(l, 1e-30)
    o = (o / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)
    return o, lse


def _flash_fwd(q, k, v, q_pos, k_pos, causal, window, cap, block_k):
    o, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window, cap, block_k)
    return o, (q, k, v, q_pos, k_pos, o, lse)


def _flash_bwd(causal, window, cap, block_k, res, do):
    q, k, v, q_pos, k_pos, o, lse = res
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    scale = 1.0 / (hd ** 0.5)
    do_f = do.astype(jnp.float32)
    o_f = o.astype(jnp.float32)
    delta = jnp.sum(do_f * o_f, axis=-1)  # (B,H,Sq)

    nb = max(1, -(-Sk // block_k))
    pad = nb * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=_PAD_POS)
    pb = k_pos.reshape(nb, block_k)

    def body(dq, xs):
        j, pj = xs
        kj = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k, axis=2)
        s = _scores(q, kj, scale, cap)
        mask = _block_mask(q_pos, pj, causal, window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B,H,Sq,blk)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, do_f)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_f, vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None])  # d wrt post-cap logits
        ds = _dscores(q, kj, scale, cap, ds)  # includes scale & cap chain
        ds = jnp.where(mask[None, None], ds, 0.0)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kj.astype(jnp.float32))
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(body, dq0, (jnp.arange(nb), pb))
    dk = dkb.transpose(1, 2, 0, 3, 4).reshape(B, H, nb * block_k, hd)[:, :, :Sk]
    dv = dvb.transpose(1, 2, 0, 3, 4).reshape(B, H, nb * block_k, hd)[:, :, :Sk]
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        jnp.zeros_like(q_pos),
        jnp.zeros_like(k_pos),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Full GQA attention layer (projections + rope + flash / decode paths)
# ---------------------------------------------------------------------------


def init_attention(rng, cfg, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.hd
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(k1, (d, cfg.n_heads * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (cfg.n_heads * hd, d)) * s).astype(dtype),
    }


def _project_qkv(p, x, cfg, positions, ops=None):
    B, S, _ = x.shape
    hd = cfg.hd
    mm = ops.matmul if ops is not None else (lambda a, w: a @ w)
    q = mm(x, p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = mm(x, p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = mm(x, p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope == "rope":
        pos = positions if positions.ndim == 2 else positions[0]
        rope = ops.apply_rope if ops is not None else apply_rope
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    elif cfg.rope == "mrope":
        mrope = ops.apply_mrope if ops is not None else apply_mrope
        q = mrope(q, positions, cfg.rope_theta)
        k = mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    B, S, Hkv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, Hkv, n_rep, hd)).reshape(
        B, S, Hkv * n_rep, hd
    )


def ref_attention_core(q, k, v, cfg, spec, block_k: int = 1024) -> jax.Array:
    """The jnp attention core on projected/rope'd q,k,v — the `ref`
    OpSet's attention. q: (B,S,H,hd); k,v: (B,S,Hkv,hd) -> (B,S,H·hd).

    Grouped-head flash: the n_rep query heads sharing a KV head are
    folded into the query-row axis, so K/V are read at n_kv_heads and
    the repeated KV (n_rep× their size) is never materialized.
    """
    B, S, _, _ = q.shape
    n_rep = cfg.n_heads // cfg.n_kv_heads
    hkv, hd = cfg.n_kv_heads, cfg.hd
    pos1d = jnp.arange(S)
    # q-head g*n_rep+r shares kv head g (matches _repeat_kv layout);
    # row index inside a kv head = r*S + s.
    q = q.reshape(B, S, hkv, n_rep, hd).transpose(0, 2, 3, 1, 4)
    q = q.reshape(B, hkv, n_rep * S, hd)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # (B,Hkv,S,hd)
    o = flash_attention(
        q, k, v, jnp.tile(pos1d, n_rep), pos1d, True, spec.window,
        cfg.attn_softcap, min(block_k, S),
    )
    o = o.reshape(B, hkv, n_rep, S, hd).transpose(0, 3, 1, 2, 4)
    return o.reshape(B, S, cfg.n_heads * hd)


def attention_forward(
    p,
    x: jax.Array,
    cfg,
    spec,
    positions: jax.Array,
    block_k: int = 1024,
    ops=None,
    return_kv: bool = False,
):
    """Full-sequence (train/prefill) attention. x: (B,S,d); positions: (B,S) or (3,B,S).

    ``return_kv=True`` additionally returns the post-rope ``(k, v)``
    pair ((B,S,Hkv,hd) each) — the prefill path of the paged serving
    engine captures them into the page pool instead of re-projecting
    the prompt token by token."""
    q, k, v = _project_qkv(p, x, cfg, positions, ops)
    if ops is not None:
        o = ops.attention(q, k, v, cfg, spec, block_k)
        out = ops.matmul(o, p["wo"])
    else:
        o = ref_attention_core(q, k, v, cfg, spec, block_k)
        out = o @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def quantize_kv_token(t: jax.Array):
    """Per-(B,1,Hkv) absmax INT8 quantization of one K/V token.

    t: (B, 1, Hkv, hd) f32 -> (int8 same shape, f32 scale (B, 1, Hkv)).
    """
    absmax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)  # (B,1,Hkv)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def attention_decode_quant(p, x, cfg, spec, cache, pos, ops=None):
    """Single-token decode against an INT8 KV cache (beyond-paper serving
    feature — the paper's Eq. 1 absmax quantization applied to the KV
    cache, per (token, kv-head) scales).

    Dequantization is folded *after* the score/value einsums so the HBM
    read is the INT8 payload + scales, never a materialized f32 cache.
    cache: {"k": int8 (B,Smax,Hkv,hd), "k_scale": f32 (B,Smax,Hkv), v...}.
    """
    B, _, _ = x.shape
    Smax = cache["k"].shape[1]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(pos, (3, B, 1)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, ops)
    kq, ks = quantize_kv_token(k)
    vq, vs = quantize_kv_token(v)
    new_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, pos, axis=1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, pos, axis=1),
        "k_scale": jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, pos, axis=1),
        "v_scale": jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, pos, axis=1),
    }
    n_rep = cfg.n_heads // cfg.n_kv_heads
    hd = cfg.hd
    qh = q.reshape(B, cfg.n_kv_heads, n_rep, hd)
    s = jnp.einsum(
        "bgrd,bsgd->bgrs", qh.astype(jnp.float32), new_cache["k"].astype(jnp.float32)
    ) * (hd ** -0.5)
    s = s * jnp.swapaxes(new_cache["k_scale"], 1, 2)[:, :, None, :]  # fold K scales
    s = softcap(s, cfg.attn_softcap)
    kpos = jnp.arange(Smax)
    valid = kpos <= pos
    if spec.window is not None:
        valid &= kpos > pos - spec.window
    s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    w = w * jnp.swapaxes(new_cache["v_scale"], 1, 2)[:, :, None, :]  # fold V scales
    o = jnp.einsum("bgrs,bsgd->bgrd", w, new_cache["v"].astype(jnp.float32))
    o = o.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    out = ops.matmul(o, p["wo"]) if ops is not None else o @ p["wo"]
    return out, new_cache


def attention_decode(
    p,
    x: jax.Array,
    cfg,
    spec,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    positions_full=None,
    ops=None,
):
    """Single-token decode. x: (B,1,d); cache_[kv]: (B,Smax,Hkv,hd); pos: () int32.

    Returns (out (B,1,d), new_cache_k, new_cache_v).
    """
    B, _, _ = x.shape
    Smax = cache_k.shape[1]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(pos, (3, B, 1)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, ops)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    hd = cfg.hd
    kk = cache_k  # (B,Smax,Hkv,hd)
    vv = cache_v
    qh = q.reshape(B, cfg.n_kv_heads, n_rep, hd)  # query per kv-group
    s = jnp.einsum(
        "bgrd,bsgd->bgrs", qh.astype(jnp.float32), kk.astype(jnp.float32)
    ) * (hd ** -0.5)
    s = softcap(s, cfg.attn_softcap)
    kpos = jnp.arange(Smax)
    valid = kpos <= pos
    if spec.window is not None:
        valid &= kpos > pos - spec.window
    s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", w, vv.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    out = ops.matmul(o, p["wo"]) if ops is not None else o @ p["wo"]
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# Gated MLP (llama-style)
# ---------------------------------------------------------------------------


def init_mlp(rng, d: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "wi": (jax.random.normal(k1, (d, d_ff)) * d ** -0.5).astype(dtype),
        "wg": (jax.random.normal(k2, (d, d_ff)) * d ** -0.5).astype(dtype),
        "wo": (jax.random.normal(k3, (d_ff, d)) * d_ff ** -0.5).astype(dtype),
    }


def mlp_forward(p, x: jax.Array, ops=None) -> jax.Array:
    if ops is not None:
        mm = ops.matmul
        return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wi"]), p["wo"])
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
