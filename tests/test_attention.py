"""Attention layer tests: flash vs naive, gradients, RoPE/M-RoPE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import apply_mrope, apply_rope, flash_attention, softcap
from repro.kernels.ref import flash_attention_ref


def _qkv(key, B=2, H=3, S=37, hd=16, Sk=None):
    ks = jax.random.split(key, 3)
    Sk = Sk or S
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, Sk, hd))
    v = jax.random.normal(ks[2], (B, H, Sk, hd))
    return q, k, v


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("cap", [None, 20.0])
@pytest.mark.parametrize("block_k", [7, 16, 64])
def test_flash_matches_naive(window, cap, block_k):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    S = q.shape[2]
    pos = jnp.arange(S)
    out = flash_attention(q, k, v, pos, pos, True, window, cap, block_k)
    ref = flash_attention_ref(
        q.reshape(-1, S, 16), k.reshape(-1, S, 16), v.reshape(-1, S, 16),
        causal=True, window=window, attn_softcap=cap,
    ).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("window,cap", [(None, None), (8, None), (None, 20.0)])
def test_flash_gradients_match_naive(window, cap):
    q, k, v = _qkv(jax.random.PRNGKey(1), B=1, H=2, S=24, hd=8)
    S = q.shape[2]
    pos = jnp.arange(S)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, pos, pos, True, window, cap, 8)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        hd = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * hd ** -0.5
        s = softcap(s, cap)
        qp, kp = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        m = qp >= kp
        if window is not None:
            m &= (qp - kp) < window
        s = jnp.where(m[None, None], s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return jnp.sum(o * jnp.cos(o))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_flash_uneven_kv_padding():
    """Sk not divisible by block_k exercises the padded tail."""
    q, k, v = _qkv(jax.random.PRNGKey(2), S=11, Sk=29)
    posq = jnp.arange(11) + 18  # decode-ish offset: queries after keys
    posk = jnp.arange(29)
    out = flash_attention(q, k, v, posq, posk, True, None, None, 8)
    hd = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * hd ** -0.5
    m = (posq[:, None] - posk[None, :]) >= 0
    s = jnp.where(m[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_rope_relative_property():
    """RoPE: q·k depends only on relative offset."""
    hd = 32
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, hd))
    def dot_at(pq, pk):
        qr = apply_rope(q, jnp.array([[pq]]))
        kr = apply_rope(k, jnp.array([[pk]]))
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(5, 3) - dot_at(105, 103)) < 1e-3
    assert abs(dot_at(5, 3) - dot_at(5, 4)) > 1e-5  # actually varies


def test_mrope_matches_rope_for_text():
    """With t==h==w position ids, M-RoPE must reduce to plain RoPE."""
    B, S, H, hd = 2, 6, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, hd))
    pos1 = jnp.broadcast_to(jnp.arange(S), (B, S))
    pos3 = jnp.broadcast_to(pos1, (3, B, S))
    a = apply_rope(x, pos1, theta=1e6)
    b = apply_mrope(x, pos3, theta=1e6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_softcap_bounds():
    x = jnp.linspace(-1000, 1000, 101)
    y = softcap(x, 30.0)
    assert jnp.all(jnp.abs(y) <= 30.0)
    np.testing.assert_allclose(np.asarray(softcap(x, None)), np.asarray(x))


def test_grouped_head_gqa_equals_repeated_kv():
    """Grouped-head GQA (``ref_attention_core``): folding the n_rep q-heads
    sharing a KV head into the query-row axis must equal explicit KV
    repetition."""
    B, Hkv, n_rep, S, hd = 2, 2, 4, 32, 8
    H = Hkv * n_rep
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    pos = jnp.arange(S)

    # reference: repeat KV to H heads (q head g*n_rep+r <- kv head g)
    kr = jnp.repeat(k, n_rep, axis=1)
    vr = jnp.repeat(v, n_rep, axis=1)
    ref = flash_attention(q, kr, vr, pos, pos, True, None, None, 16)

    # grouped: (B,H,S,hd) -> (B,Hkv,n_rep*S,hd), row r*S+s
    qg = q.reshape(B, Hkv, n_rep, S, hd).reshape(B, Hkv, n_rep * S, hd)
    og = flash_attention(qg, k, v, jnp.tile(pos, n_rep), pos, True, None, None, 16)
    og = og.reshape(B, Hkv, n_rep, S, hd).reshape(B, H, S, hd)
    np.testing.assert_allclose(np.asarray(og), np.asarray(ref), atol=2e-5)


def test_flash_block_index_slicing_matches_across_block_sizes():
    """iter 6 (dynamic-slice KV in the scan body): results must be
    invariant to block_k, including non-divisible sizes."""
    q, k, v = _qkv(jax.random.PRNGKey(8), B=1, H=2, S=29, hd=8)
    pos = jnp.arange(29)
    outs = [
        flash_attention(q, k, v, pos, pos, True, None, None, bk)
        for bk in (4, 8, 29, 64)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=2e-5)
