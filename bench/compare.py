"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own (``bench/limits``):

* ``loss_gap`` — the largest |program − reference| over the first
  steps' losses (nats);
* ``grad_gap`` — the worst leaf's gap between the norms of the first
  gradient as the optimizer got it (program: AdamW's first moment after
  one step over ``1 − b1``; reference: its clipped gradient), over the
  larger of that leaf's reference norm and the median leaf's;
* ``update_gap`` — the same measure of the parameters' change over the
  first steps. Leaves whose first reference gradient is under a
  thousandth of the median leaf's are left out: Adam moves them by
  round-off alone.
"""

from __future__ import annotations

import math

import jax
import numpy as np

SMALL_GRAD = 1e-3  # of the median leaf's reference gradient norm


def leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x, np.float64)))
            for p, x in flat}


def norm_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """(worst relative gap, its leaf) over the leaves in ``keep``."""
    keys = [k for k in ref if keep is None or k in keep]
    floor = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def training_numbers(prog: dict, ref: dict, b1: float) -> dict:
    """``prog``/``ref``: {"losses", "g1" (ref) or "mu1" (prog),
    "start", "end"} trees on the host."""
    g_prog = leaf_norms(jax.tree.map(lambda m: np.asarray(m) / (1.0 - b1), prog["mu1"]))
    g_ref = leaf_norms(ref["g1"])
    median = float(np.median(list(g_ref.values())))
    keep = {k for k, v in g_ref.items() if v >= SMALL_GRAD * median}
    delta = lambda t: jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                                   t["end"], t["start"])
    u_gap, u_leaf = norm_gap(leaf_norms(delta(prog)), leaf_norms(delta(ref)), keep)
    g_gap, g_leaf = norm_gap(g_prog, g_ref)
    loss_gap = max(abs(a - b) if math.isfinite(a) else math.inf
                   for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss_gap, "grad_gap": g_gap, "update_gap": u_gap,
            "_where": {"grad_gap": g_leaf, "update_gap": u_leaf,
                       "left_out": sorted(set(g_ref) - keep),
                       "losses_program": prog["losses"], "losses_reference": ref["losses"]}}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}). A number without a limit,
    or not finite, is not correct."""
    checks, ok = {}, True
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        limit = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
        ok &= limit is not None and math.isfinite(value) and value <= limit
    return ok, checks
