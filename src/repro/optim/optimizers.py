"""Optimizers from scratch (no optax in this environment).

AdamW and SGD+momentum over arbitrary pytrees, with global-norm clipping
and the usual schedules. State layouts are plain pytrees shaped like
their parameters, so they take the same shardings (replicated, on the
edge mesh).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def _zeros_like_tree(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


def adamw_init(params):
    return {"mu": _zeros_like_tree(params), "nu": _zeros_like_tree(params), "count": jnp.zeros((), jnp.int32)}


def adamw_update(
    params,
    grads,
    state,
    lr=1e-3,
    b1=0.9,
    b2=0.999,
    eps=1e-8,
    weight_decay=0.01,
):
    count = state["count"] + 1
    c = count.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32), state["mu"], grads)
    nu = jax.tree.map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)), state["nu"], grads
    )
    mu_hat_scale = 1.0 / (1 - b1 ** c)
    nu_hat_scale = 1.0 / (1 - b2 ** c)

    def upd(p, m, v):
        step = m * mu_hat_scale / (jnp.sqrt(v * nu_hat_scale) + eps)
        return (p.astype(jnp.float32) - lr * (step + weight_decay * p.astype(jnp.float32))).astype(
            p.dtype
        )

    new_params = jax.tree.map(upd, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu, "count": count}


# ---------------------------------------------------------------------------
# SGD + momentum
# ---------------------------------------------------------------------------


def sgdm_init(params):
    return {"m": _zeros_like_tree(params)}


def sgdm_update(params, grads, state, lr=1e-2, momentum=0.9):
    m = jax.tree.map(lambda m_, g: momentum * m_ + g.astype(jnp.float32), state["m"], grads)
    new_params = jax.tree.map(
        lambda p, m_: (p.astype(jnp.float32) - lr * m_).astype(p.dtype), params, m
    )
    return new_params, {"m": m}


# ---------------------------------------------------------------------------
# Utilities
# ---------------------------------------------------------------------------


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), norm


def linear_warmup(step, warmup_steps: int, peak_lr: float):
    return peak_lr * jnp.minimum(1.0, (step + 1) / warmup_steps)


def cosine_schedule(step, total_steps: int, peak_lr: float, warmup_steps: int = 0, final_frac=0.1):
    warm = jnp.minimum(1.0, (step + 1) / jnp.maximum(warmup_steps, 1))
    prog = jnp.clip((step - warmup_steps) / jnp.maximum(total_steps - warmup_steps, 1), 0.0, 1.0)
    cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return peak_lr * warm * cos
