"""MoE dispatch tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.configs import get_arch
from repro.configs.base import MoESpec
from repro.models.moe import init_moe, moe_forward, moe_forward_dense


def _spec(E=4, K=2, cf=8.0):
    return MoESpec(n_experts=E, top_k=K, d_expert=32, capacity_factor=cf)


def test_capacity_matches_dense_when_no_drop():
    spec = _spec(cf=8.0)  # capacity ≥ T ⇒ nothing dropped
    p = init_moe(jax.random.PRNGKey(0), 16, spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    a = moe_forward(p, x, spec)
    b = moe_forward_dense(p, x, spec)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_capacity_drops_bounded():
    spec = _spec(cf=1.0)
    p = init_moe(jax.random.PRNGKey(2), 16, spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16, 16))
    out, aux = moe_forward(p, x, spec, return_aux=True)
    assert 0.0 <= float(aux["dropped_frac"]) < 0.7
    assert float(aux["load_balance"]) >= 0.99  # ≥1 by Cauchy-Schwarz-ish


def test_aux_losses_finite_and_balanced_router_is_optimal():
    spec = _spec(E=4, K=1, cf=8.0)
    p = init_moe(jax.random.PRNGKey(4), 16, spec)
    # uniform router ⇒ load_balance == 1 exactly
    p = dict(p, router=jnp.zeros_like(p["router"]))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 16))
    _, aux = moe_forward(p, x, spec, return_aux=True)
    np.testing.assert_allclose(float(aux["load_balance"]), 1.0, atol=0.15)


@settings(max_examples=10, deadline=None)
@given(E=st.sampled_from([2, 4, 8]), K=st.integers(1, 3), seed=st.integers(0, 100))
def test_moe_output_finite_property(E, K, seed):
    K = min(K, E)
    spec = MoESpec(n_experts=E, top_k=K, d_expert=16, capacity_factor=2.0)
    p = init_moe(jax.random.PRNGKey(seed), 8, spec)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 8, 8))
    out = moe_forward(p, x, spec)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()


def test_moe_grads_flow_to_router_and_experts():
    spec = _spec()
    p = init_moe(jax.random.PRNGKey(6), 16, spec)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 16))

    def loss(p):
        return jnp.sum(jnp.square(moe_forward(p, x, spec)))

    g = jax.grad(loss)(p)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0
    assert float(jnp.max(jnp.abs(g["wi"]))) > 0


def test_capacity_rounding_sublane_and_cap():
    from repro.models.moe import _capacity

    spec = _spec(E=4, K=2, cf=1.0)
    # rounds up to a multiple of 8 ...
    assert _capacity(100, spec) % 8 == 0
    # ... but never exceeds the token count (top_k constraint)
    assert _capacity(2, spec) <= 2
    assert _capacity(1, spec) == 1


MOE_ARCHS = ["grok-1-314b", "jamba-1.5-large-398b", "kimi-k2-1t-a32b",
             "mixtral-8x7b", "moonshot-v1-16b-a3b"]


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_moe_matches_per_token_reference(arch):
    """At a capacity where nothing drops, the dispatched layer equals a
    plain loop over tokens, each summing its top-k experts' gated MLPs
    weighted by their router probabilities (float64, no dispatch)."""
    spec = get_arch(arch).reduced().moe
    d = get_arch(arch).reduced().d_model
    p = init_moe(jax.random.PRNGKey(30), d, spec)
    x = jax.random.normal(jax.random.PRNGKey(31), (2, 8, d))
    out, aux = moe_forward(p, x, spec, return_aux=True,
                           capacity_factor=float(spec.n_experts))
    assert float(aux["dropped_frac"]) == 0.0

    P = {k: np.asarray(v, np.float64) for k, v in p.items()}
    silu = lambda z: z / (1.0 + np.exp(-z))
    want = np.zeros((2 * 8, d))
    for t, xt in enumerate(np.asarray(x, np.float64).reshape(-1, d)):
        logits = xt @ P["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        for e in np.argsort(-probs)[:spec.top_k]:
            h = silu(xt @ P["wg"][e]) * (xt @ P["wi"][e])
            want[t] += probs[e] * (h @ P["wo"][e])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, d), want,
                               rtol=1e-4, atol=1e-5)
