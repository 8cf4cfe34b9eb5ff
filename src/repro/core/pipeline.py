"""Hybrid data+pipeline parallel runtime (paper §V-A) in JAX.

Three layers:

1. **Schedule** — ``build_1f1b_schedule`` emits the paper's
   one-forward-one-backward micro-batch order (Fig. 10b); validated for
   legality (dependencies, at-most-one-in-flight-per-device) in tests.
2. **Simulator** — ``simulate_plan`` replays a
   :class:`~repro.core.planner.Plan` through a discrete-event model
   (compute, inter-stage links, AllReduce) and returns the per-minibatch
   timeline (the modelled side of Figs. 12/16).
3. **Runtime** — ``pipeline_apply`` runs a *real* SPMD pipeline over a
   ``stage`` mesh axis with ``shard_map`` + ``ppermute`` (GPipe-style
   rotation, autodiff straight through the collective). Since PR 2 this
   is the **trainer's execution path**, not a test-only artifact:
   ``repro.launch.train --dp N --stages S`` runs epoch-1 PAC+ through it
   on a 2-D ``(dp, stage)`` mesh (``repro.core.steps
   .pipeline_pac_train_step``), with each stage emitting its periods'
   taps for the activation cache and the adapter, then drops to pure
   data parallelism from epoch 2 (paper Fig. 10/11). Micro-batch
   gradient accumulation ≡ the paper's per-stage gradient aggregation;
   AllReduce of adapter grads over ``dp`` is the (tiny) trailing
   collective.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map

# ---------------------------------------------------------------------------
# 1F1B schedule
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Op:
    stage: int
    micro: int
    kind: str  # "F" | "B"


def build_1f1b_schedule(n_stages: int, n_micro: int) -> List[List[Op]]:
    """Per-stage op order for 1F1B (PipeDream-flush). Returns ops[stage] lists."""
    out: List[List[Op]] = []
    for s in range(n_stages):
        warmup = min(n_stages - s - 1, n_micro)
        ops: List[Op] = [Op(s, m, "F") for m in range(warmup)]
        f, b = warmup, 0
        while b < n_micro:
            if f < n_micro:
                ops.append(Op(s, f, "F"))
                f += 1
            ops.append(Op(s, b, "B"))
            b += 1
        # dedupe while preserving order (warmup overlap)
        seen = set()
        ops = [o for o in ops if not ((o.kind, o.micro) in seen or seen.add((o.kind, o.micro)))]
        out.append(ops)
    return out


def validate_schedule(sched: List[List[Op]], n_micro: int) -> None:
    """Raises if the schedule violates pipeline dependencies."""
    n_stages = len(sched)
    for s, ops in enumerate(sched):
        fs = [o.micro for o in ops if o.kind == "F"]
        bs = [o.micro for o in ops if o.kind == "B"]
        assert fs == sorted(fs) and len(fs) == n_micro, f"stage {s}: bad F order"
        assert bs == sorted(bs) and len(bs) == n_micro, f"stage {s}: bad B order"
        # 1F1B memory bound: in-flight microbatches ≤ n_stages - s
        inflight = 0
        for o in ops:
            inflight += 1 if o.kind == "F" else -1
            assert inflight <= n_stages - s, f"stage {s}: {inflight} in flight"


# ---------------------------------------------------------------------------
# Discrete-event simulator
# ---------------------------------------------------------------------------


def simulate_plan(plan, comm_bytes_per_stage: Optional[Sequence[float]] = None) -> dict:
    """Replay 1F1B through the plan's stage times; returns timing breakdown."""
    S, M = plan.n_stages, plan.micro_batches
    sched = build_1f1b_schedule(S, M)
    # per-stage fwd/bwd split as recorded from LayerCost by the planner's
    # _phase_latencies; hand-built stages without recorded times fall back
    # to the historical tf:tb = 1:2 approximation
    tf, tb = [], []
    for st in plan.stages:
        if getattr(st, "fwd_time", 0.0) or getattr(st, "bwd_time", 0.0):
            tf.append(st.fwd_time)
            tb.append(st.bwd_time)
        else:
            tf.append(st.stage_time / 3.0)
            tb.append(2.0 * st.stage_time / 3.0)
    if comm_bytes_per_stage is None:
        comm = [0.0] * S
    else:
        comm = [
            b / min(d.bandwidth for d in st.devices)
            for b, st in zip(comm_bytes_per_stage, plan.stages)
        ]
    f_done = {}
    b_done = {}
    dev_free = [0.0] * S
    idx = [0] * S
    remaining = sum(len(x) for x in sched)
    while remaining:
        progressed = False
        for s in range(S):
            if idx[s] >= len(sched[s]):
                continue
            op = sched[s][idx[s]]
            if op.kind == "F":
                ready = 0.0 if s == 0 else f_done.get((s - 1, op.micro), None)
                if ready is None:
                    continue
                start = max(dev_free[s], ready + (comm[s - 1] if s else 0.0))
                f_done[(s, op.micro)] = start + tf[s]
                dev_free[s] = start + tf[s]
            else:
                ready = f_done.get((s, op.micro))
                up = 0.0 if s == S - 1 else b_done.get((s + 1, op.micro), None)
                if up is None or ready is None:
                    continue
                start = max(dev_free[s], ready, up + (comm[s] if s < S - 1 else 0.0))
                b_done[(s, op.micro)] = start + tb[s]
                dev_free[s] = start + tb[s]
            idx[s] += 1
            remaining -= 1
            progressed = True
        assert progressed, "schedule deadlock"
    total = max(b_done.values())
    busy = sum(M * (tf[s] + tb[s]) for s in range(S))
    return {
        "minibatch_time": total,
        "bubble_fraction": 1.0 - busy / (total * S),
        "per_stage_busy": [M * (tf[s] + tb[s]) for s in range(S)],
    }


# ---------------------------------------------------------------------------
# Real SPMD pipeline over a `stage` mesh axis
# ---------------------------------------------------------------------------


def stack_stages(blocks, n_stages: int):
    """Re-chunk period-stacked block params (n_p, ...) → (n_stages, n_p/s, ...)."""

    def f(x):
        n_p = x.shape[0]
        assert n_p % n_stages == 0, f"{n_p} periods not divisible by {n_stages} stages"
        return x.reshape((n_stages, n_p // n_stages) + x.shape[1:])

    return jax.tree.map(f, blocks)


def stack_stages_ragged(blocks, boundaries: Sequence[int]):
    """Uneven re-chunk: stage ``s`` owns periods ``[boundaries[s],
    boundaries[s+1])``; every stage's slab is zero-padded to the max
    periods-per-stage so the leaves stay rectangular —
    (n_stages, max_pp, ...). Padded slots must be masked to identity by
    the stage function (see the partition's ``masks()``)."""
    counts = [b - a for a, b in zip(boundaries, boundaries[1:])]
    assert counts and min(counts) >= 1, f"bad boundaries {boundaries}"
    max_pp = max(counts)

    def f(x):
        n_p = x.shape[0]
        assert n_p == boundaries[-1], (
            f"{n_p} periods but boundaries end at {boundaries[-1]}"
        )
        slabs = []
        for a, b in zip(boundaries, boundaries[1:]):
            s = x[a:b]
            if b - a < max_pp:
                pad = jnp.zeros((max_pp - (b - a),) + x.shape[1:], x.dtype)
                s = jnp.concatenate([s, pad], axis=0)
            slabs.append(s)
        return jnp.stack(slabs)

    return jax.tree.map(f, blocks)


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,
    mesh: Mesh,
    axis: str = "stage",
    batch_axis: Optional[str] = None,
    collect_taps: bool = False,
    periods_per_stage: Optional[Sequence[int]] = None,
):
    """GPipe-style rotation: run ``stage_fn`` over pipelined micro-batches.

    stage_fn(params_slice, h) -> h' — one stage's compute (same shape
    in/out). With ``collect_taps=True`` it must instead return
    ``(h', taps)`` where ``taps`` is an array — or any pytree of arrays,
    e.g. the int8 ``{"q", "scale"}`` storage form a pallas OpSet emits —
    whose every leaf has shape (periods_per_stage, mb, ...): the stage's
    intermediate activations, e.g. the post-period hidden states PAC+'s
    adapter consumes.

    stage_params: leaves with leading dim n_stages (sharded over ``axis``).
    x_micro: (n_micro, mb, ...) micro-batched input. When ``batch_axis``
    names a second mesh axis, dim 1 (the micro-batch) is sharded over it
    — hybrid data×pipeline parallelism on a 2-D ``(dp, stage)`` mesh.

    Returns the (n_micro, mb, ...) outputs of the LAST stage, or with
    ``collect_taps`` a pair ``(outs, taps)`` where ``taps`` mirrors the
    stage-tap pytree with every leaf (n_micro, n_periods_total, mb, ...)
    assembled across stages in layer order (stage s owns periods
    [s·pp, (s+1)·pp)).

    ``periods_per_stage`` declares a *ragged* partition (a planner
    :class:`~repro.core.planner.StagePartition` executed for real): every
    stage's tap buffer is padded to max(periods_per_stage) — build the
    params with :func:`stack_stages_ragged` and mask padded periods to
    identity inside ``stage_fn`` — and the taps are assembled in true
    layer order from the uneven boundaries, dropping the padding slots.

    Differentiable: ``ppermute``'s transpose is the reverse permutation, so
    ``jax.grad`` through this function implements the backward pipeline.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    T = n_micro + n_stages - 1

    def spmd(params, xs):
        idx = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        local_params = jax.tree.map(lambda p: p[0], params)
        taps_buf = None

        def step(carry, t):
            state, outs, taps_buf = carry
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(idx == 0, xs[inject], state)
            if collect_taps:
                y, taps = stage_fn(local_params, x_in)
            else:
                y = stage_fn(local_params, x_in)
            # this stage processes micro-batch m = t - idx at time t
            m = t - idx
            if collect_taps:
                slot_m = jnp.clip(m, 0, n_micro - 1)
                valid = jnp.logical_and(m >= 0, m < n_micro)
                taps_buf = jax.tree.map(
                    lambda buf, tp: jnp.where(
                        valid, jax.lax.dynamic_update_index_in_dim(buf, tp, slot_m, 0), buf
                    ),
                    taps_buf, taps,
                )
            # collect finished micro-batches on the last stage
            out_t = t - (n_stages - 1)
            slot = jnp.clip(out_t, 0, n_micro - 1)
            write = jnp.logical_and(idx == n_stages - 1, out_t >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(outs, y, slot, 0)
            outs = jnp.where(write, updated, outs)
            # rotate activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs, taps_buf), None

        if collect_taps:
            # probe the per-stage tap shape without committing compute
            # (a pytree of ShapeDtypeStructs — storage-form taps are dicts)
            tap_shape = jax.eval_shape(stage_fn, local_params, xs[0])[1]
            taps_buf = jax.tree.map(
                lambda t: jnp.zeros((n_micro,) + t.shape, t.dtype), tap_shape
            )
        (state, outs, taps_buf), _ = jax.lax.scan(
            step, (state, outs, taps_buf), jnp.arange(T)
        )
        # replicate the last stage's buffer everywhere (psum of masked copies —
        # a broadcast; ppermute cannot fan out one source to all)
        outs = jax.lax.psum(jnp.where(idx == n_stages - 1, outs, 0.0), axis)
        if collect_taps:
            # (1, n_micro, pp, mb, ...) sharded over `axis` on the new
            # leading dim → global (n_stages, n_micro, pp, mb, ...)
            return outs, jax.tree.map(lambda t: t[None], taps_buf)
        return outs

    b = batch_axis
    x_spec = P(None, b) if b else P()
    if collect_taps:
        # the tap *structure* (not shapes) decides the out_specs pytree:
        # every leaf — bare array or {"q","scale"} storage form — carries
        # (stage, micro, pp, mb, ...), so one spec shape fits all leaves
        tap_struct = jax.eval_shape(
            stage_fn, jax.tree.map(lambda p: p[0], stage_params), x_micro[0]
        )[1]
        leaf_spec = P(axis, None, None, b) if b else P(axis)
        out_specs = (x_spec, jax.tree.map(lambda _: leaf_spec, tap_struct))
    else:
        out_specs = x_spec
    fn = shard_map(
        spmd,
        mesh=mesh,
        in_specs=(P(axis), x_spec),
        out_specs=out_specs,
        check_rep=False,
    )
    if not collect_taps:
        return fn(stage_params, x_micro)
    outs, taps = fn(stage_params, x_micro)
    # (n_stages, n_micro, pp, mb, ...) → (n_micro, n_periods, mb, ...);
    # stage-major period order == layer order (stack_stages is contiguous)
    taps = jax.tree.map(lambda t: jnp.moveaxis(t, 0, 1), taps)
    if periods_per_stage is not None and len(set(periods_per_stage)) > 1:
        # ragged partition: keep each stage's first pp_s (active) periods,
        # concatenated in stage order == true layer order
        assert len(periods_per_stage) == n_stages, (periods_per_stage, n_stages)
        taps = jax.tree.map(
            lambda t: jnp.concatenate(
                [t[:, s, :pp] for s, pp in enumerate(periods_per_stage)], axis=1
            ),
            taps,
        )
    else:
        taps = jax.tree.map(
            lambda t: t.reshape((t.shape[0], t.shape[1] * t.shape[2]) + t.shape[3:]),
            taps,
        )
    return outs, taps


def pipeline_grads(
    loss_fn: Callable,
    trainable,
    frozen,
    batch_micro,
    mesh: Mesh,
    axis: str = "stage",
):
    """value_and_grad of a micro-batched pipelined loss.

    loss_fn(trainable, frozen, batch_micro, mesh) -> scalar mean loss.
    Provided for symmetry; gradient accumulation across micro-batches is
    what AllReduce-per-minibatch in the paper amounts to.
    """
    return jax.value_and_grad(lambda tp: loss_fn(tp, frozen, batch_micro, mesh))(trainable)
