"""Mixture-of-Experts with capacity-based token dispatch.

Token-choice top-k routing with a static per-expert capacity
``C = ceil(top_k * T / E * capacity_factor)``: each expert gathers its
highest-priority assigned tokens (priority = router probability), computes
a gated-MLP, and the results are scatter-combined with the routing
weights. Dropped tokens (over capacity) fall back to the residual stream,
the standard GShard/Switch behaviour. Routing is global: every token of
the (B, S) batch competes for the same E × C expert slots.

FLOPs are ``E × C × expert_mlp`` ≈ ``top_k × T × expert_mlp ×
capacity_factor`` — i.e. the *active* parameter count, which is what the
roofline's ``6·N_active·D`` model expects.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_moe(rng, d: int, spec, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    E, de = spec.n_experts, spec.d_expert
    return {
        "router": (jax.random.normal(k1, (d, E)) * d ** -0.5).astype(jnp.float32),
        "wi": (jax.random.normal(k2, (E, d, de)) * d ** -0.5).astype(dtype),
        "wg": (jax.random.normal(k3, (E, d, de)) * d ** -0.5).astype(dtype),
        "wo": (jax.random.normal(k4, (E, de, d)) * de ** -0.5).astype(dtype),
    }


def _capacity(T: int, spec, capacity_factor=None) -> int:
    cf = spec.capacity_factor if capacity_factor is None else capacity_factor
    c = int(spec.top_k * T * cf / spec.n_experts)
    c = -(-max(1, c) // 8) * 8  # round up to the TPU sublane
    return min(T, c)  # top_k needs k <= size along the token axis


def moe_forward(p, x: jax.Array, spec, return_aux: bool = False,
                capacity_factor=None):
    """x: (B, S, d) -> (B, S, d) [+ aux losses dict]."""
    B, S, d = x.shape
    E, K = spec.n_experts, spec.top_k
    T = B * S
    C = _capacity(T, spec, capacity_factor)
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32)) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)  # (T, K)

    # assignment matrix with router-prob priorities, zero where unassigned
    assign = jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], top_e].set(top_p)
    prio = assign.T  # (E, T)

    # per-expert top-C tokens by priority
    gate, idx = jax.lax.top_k(prio, C)  # (E, C)
    valid = gate > 0.0

    # dispatch: gather each expert's tokens
    xe = jnp.take(xt, idx.reshape(-1), axis=0).reshape(E, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["wi"]
    )
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])  # (E, C, d)

    # combine: scatter-add gate-weighted expert outputs back to tokens
    w = jnp.where(valid, gate, 0.0).astype(ye.dtype)  # (E, C)
    out = jnp.zeros((T, d), ye.dtype).at[idx.reshape(-1)].add(
        (ye * w[..., None]).reshape(E * C, d))
    out = out.reshape(B, S, d).astype(x.dtype)

    if not return_aux:
        return out
    # Switch-style load-balance loss (means over all tokens)
    me = jnp.mean(probs, axis=0)  # (E,)
    fe = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E), axis=1), axis=0
    )  # fraction of tokens to each expert
    aux = {
        "load_balance": E * jnp.sum(me * fe),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped_frac": 1.0 - jnp.sum(valid) / (T * K),
    }
    return out, aux


def moe_forward_dense(p, x: jax.Array, spec):
    """Dense (all-experts) reference for small-scale correctness checks."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = xt.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, spec.top_k)
    w = jnp.zeros((T, spec.n_experts), jnp.float32)
    w = w.at[jnp.arange(T)[:, None], top_e].set(top_p)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, p["wg"])) * jnp.einsum(
        "td,edf->etf", xt, p["wi"]
    )
    ye = jnp.einsum("etf,efd->etd", h, p["wo"])  # (E, T, d)
    out = jnp.einsum("te,etd->td", w.astype(ye.dtype), ye)
    return out.reshape(B, S, d).astype(x.dtype)
