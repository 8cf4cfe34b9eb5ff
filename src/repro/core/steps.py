"""Training / serving step functions.

These are the units that get ``jax.jit``-ed with mesh shardings — one per
fine-tuning technique (the paper's comparison set) plus the serving paths:

* ``pac_train_step``          — PAC+ epoch-1: frozen (possibly quantized)
                                 backbone forward + side-network update.
* ``pipeline_pac_train_step`` — PAC+ epoch-1 on a 2-D (dp, stage) mesh:
                                 staged backbone forward (1F1B) + dp
                                 AllReduce of adapter grads.
* ``pac_cached_train_step``   — PAC+ epoch≥2: adapter-only, from cache.
* ``full_train_step``         — full fine-tuning baseline.
* ``lora_train_step``         — LoRA baseline (backprop through backbone).
* ``houlsby_train_step``      — serial Adapters baseline.
* ``prefill_step``            — forward over a full prompt (inference).
* ``decode_step``             — one token against a KV/state cache.
* ``pac_decode_step``         — decode through backbone + fine-tuned side
                                 network (serving a personalised model).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core import peft
from repro.core.opset import get_opset
from repro.core.parallel_adapters import (
    adapter_decode,
    adapter_forward,
    init_adapter_cache,
    pac_logits,
)
from repro.models.backbone import (
    backbone_decode,
    backbone_forward,
    backbone_logits,
    cross_entropy,
    embed_inputs,
    logits_from_hidden,
)
from repro.optim import adamw_update, clip_by_global_norm

# ---------------------------------------------------------------------------
# PAC+ steps
# ---------------------------------------------------------------------------


def pac_loss_fn(adapter_params, backbone_params, cfg, batch, r: int = 8):
    b_final, taps, x, positions = backbone_forward(
        backbone_params, cfg, batch, collect_taps=True, return_inputs=True
    )
    # the gradient "highway": nothing upstream of the taps is differentiated
    x, b_final, taps = jax.lax.stop_gradient((x, b_final, taps))
    logits = pac_logits(backbone_params, adapter_params, cfg, x, taps, b_final, positions, r)
    return cross_entropy(logits, batch["labels"])


def pac_train_step(
    backbone_params, adapter_params, opt_state, batch, *, cfg, r: int = 8, lr=1e-3,
    clip=1.0, kernel_impl: str = "ref", tap_policy: str = "f32", interpret=None,
):
    """Epoch-1 PAC+ step.

    ``kernel_impl`` selects the frozen-path OpSet: ``"ref"`` (default) is
    the dense jnp oracle, bit-identical to the historical step;
    ``"pallas"`` runs the frozen forward on still-quantized weights
    (quant_matmul / Pallas flash attention) and emits the activation
    triple through ``emit_tap`` — with ``tap_policy`` = the cache's
    compress policy it leaves the step already in storage form, and the
    adapter loss consumes it via the fused cached-step kernels (the
    frozen path is stop-gradient'd, so no VJP is needed through Pallas).

    Returns (loss, adapter_params', opt_state', (b0, taps, b_final))."""
    if kernel_impl == "ref":
        b_final, taps, x, positions = backbone_forward(
            backbone_params, cfg, batch, collect_taps=True, return_inputs=True
        )
        x, b_final, taps = jax.lax.stop_gradient((x, b_final, taps))

        def loss_fn(ap):
            logits = pac_logits(backbone_params, ap, cfg, x, taps, b_final, positions, r)
            return cross_entropy(logits, batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(adapter_params)
        grads, _ = clip_by_global_norm(grads, clip)
        adapter_params, opt_state = adamw_update(adapter_params, grads, opt_state, lr=lr)
        return loss, adapter_params, opt_state, (x, taps, b_final)

    from repro.kernels.cached_step import cached_loss_parts

    ops = get_opset(kernel_impl, tap_policy, interpret)
    b_final, taps, x, positions = backbone_forward(
        backbone_params, cfg, batch, collect_taps=True, return_inputs=True, ops=ops
    )
    b0_s, bf_s = ops.emit_tap(x), ops.emit_tap(b_final)
    b0_s, taps, bf_s = jax.lax.stop_gradient((b0_s, taps, bf_s))
    cached = {"b0": b0_s, "taps": taps, "b_final": bf_s, "labels": batch["labels"]}

    def loss_fn(ap):
        num, den = cached_loss_parts(
            backbone_params, ap, cfg, cached, positions, r,
            impl=kernel_impl, interpret=interpret,
        )
        return num / jnp.maximum(den, 1)

    loss, grads = jax.value_and_grad(loss_fn)(adapter_params)
    grads, _ = clip_by_global_norm(grads, clip)
    adapter_params, opt_state = adamw_update(adapter_params, grads, opt_state, lr=lr)
    return loss, adapter_params, opt_state, (b0_s, taps, bf_s)


def _cached_positions(cached_batch, cfg):
    if "positions" in cached_batch:
        return cached_batch["positions"]
    B, S = cached_batch["labels"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(positions, (3, B, S))
    return positions


def pac_cached_train_step(
    backbone_params, adapter_params, opt_state, cached_batch, *, cfg, r: int = 8,
    lr=1e-3, clip=1.0, kernel_impl: str = "ref", interpret=None,
):
    """Epoch≥2 PAC+ step: backbone forward replaced by the activation cache.

    cached_batch: {"b0": (B,S,d), "taps": (n_p,B,S,d), "b_final": (B,S,d),
                   "labels": (B,S), optional "positions"}. Each activation
    may arrive in its *storage* form — an f32/bf16 array, or the int8
    ``{"q", "scale"}`` payload the cache hands out with
    ``get_batch(compressed=True)`` — and is decompressed inside the step
    (on-device), never eagerly on the host.

    ``kernel_impl`` selects the compute path (`repro.kernels.cached_step`):
    ``"ref"`` (default) is the dense jnp oracle — upcast to f32, full
    (B,S,vocab) logits; ``"pallas"`` fuses the per-period dequant ×
    down-projection × λ-mix in VMEM and streams the LM-head cross-entropy
    blockwise, so neither the f32 taps nor the logits tensor are ever
    fully resident. ``interpret=None`` auto-selects the Pallas
    interpreter off-TPU (CI). Both paths produce matching losses/grads to
    f32 tolerance (tests/test_cached_step.py).

    Only the LM head / final norm of ``backbone_params`` is read — the rest
    of the backbone can be released from memory (paper §IV-B memory win).
    Jit with ``donate_argnums=(1, 2)`` to reuse the adapter/optimizer
    buffers in place (they are returned updated).
    """
    from repro.kernels.cached_step import cached_loss_parts

    positions = _cached_positions(cached_batch, cfg)

    def loss_fn(ap):
        num, den = cached_loss_parts(
            backbone_params, ap, cfg, cached_batch, positions, r,
            impl=kernel_impl, interpret=interpret,
        )
        return num / jnp.maximum(den, 1)

    loss, grads = jax.value_and_grad(loss_fn)(adapter_params)
    grads, _ = clip_by_global_norm(grads, clip)
    adapter_params, opt_state = adamw_update(adapter_params, grads, opt_state, lr=lr)
    return loss, adapter_params, opt_state


def dp_cached_train_step(
    backbone_params, adapter_params, opt_state, cached_batch, *, cfg, mesh,
    batch_axes, r: int = 8, lr=1e-3, clip=1.0, kernel_impl: str = "pallas",
    interpret=None,
):
    """Epoch≥2 cached step, data-parallel over ``batch_axes`` of ``mesh``
    via an explicit shard_map — the DP twin of :func:`pac_cached_train_step`
    for the Pallas path (whose ``pallas_call``s GSPMD cannot repartition;
    the ref path can instead be jitted with
    ``core.mesh.cached_step_shardings``).

    Per shard: local (num, den) CE parts from the fused loss, psum'd over
    ``batch_axes`` before the division (exact global mean); adapter grads
    pmean'd (the psum's transpose re-sums the replicated cotangent, so the
    mean removes the axes× factor — same argument as the pipeline step).
    The update is replicated. ``batch_axes`` must shard the batch dim of
    every cached entry (use ``core.mesh.cached_batch_axes``).
    """
    from repro.kernels.cached_step import cached_loss_parts
    from repro.core.mesh import batch_specs

    axes = tuple(batch_axes)

    def spmd(ap, bp, cached):
        positions = _cached_positions(cached, cfg)

        def loss_fn(a):
            num, den = cached_loss_parts(
                bp, a, cfg, cached, positions, r,
                impl=kernel_impl, interpret=interpret,
            )
            num = jax.lax.psum(num, axes)
            den = jax.lax.psum(den, axes)
            return num / jnp.maximum(den, 1)

        loss, grads = jax.value_and_grad(loss_fn)(ap)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads)
        return loss, grads

    cspecs = batch_specs(cached_batch, axes)
    fn = shard_map(
        spmd,
        mesh=mesh,
        in_specs=(P(), P(), cspecs),
        out_specs=(P(), P()),
        check_rep=False,
    )
    loss, grads = fn(adapter_params, backbone_params, cached_batch)
    grads, _ = clip_by_global_norm(grads, clip)
    adapter_params, opt_state = adamw_update(adapter_params, grads, opt_state, lr=lr)
    return loss, adapter_params, opt_state


# ---------------------------------------------------------------------------
# Hybrid DP×PP PAC+ step (paper Fig. 10/11 — the epoch-1 edge-pool regime)
# ---------------------------------------------------------------------------


def _backbone_stage_fn(cfg, masked: bool = False, ops=None):
    """One pipeline stage of the frozen backbone: scan this stage's periods,
    emitting every period's hidden state (the PAC+ taps) through
    ``ops.emit_tap`` (identity when no OpSet is given — the ref path).

    ``masked=True`` is the ragged-partition variant: the stage params are
    ``{"blocks": padded_slab, "mask": (max_pp,)}`` (see
    ``stack_stages_ragged``) and periods whose mask is False run as
    identity — they are the zero-padding that equalizes slab shapes across
    uneven stages, and both their carry and their tap slot are discarded.
    """
    from repro.models.backbone import apply_block

    emit = ops.emit_tap if ops is not None else (lambda h: h)

    def run_period(bs, hh, positions):
        for i, spec in enumerate(cfg.pattern):
            hh = apply_block(bs[i], hh, cfg, spec, positions, ops=ops)
        return hh

    def _positions(h):
        lead = (3,) if cfg.rope == "mrope" else ()
        return jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32), lead + h.shape[:2]
        )

    if masked:

        def stage_fn(local, h):
            positions = _positions(h)

            def period_fn(carry, xs):
                bs, m = xs
                hh = jnp.where(m, run_period(bs, carry, positions), carry)
                return hh, emit(hh)

            return jax.lax.scan(
                period_fn, h, (tuple(local["blocks"]), local["mask"])
            )

        return stage_fn

    def stage_fn(block_slice, h):
        positions = _positions(h)

        def period_fn(carry, bs):
            hh = run_period(bs, carry, positions)
            return hh, emit(hh)

        return jax.lax.scan(period_fn, h, tuple(block_slice))

    return stage_fn


def pipeline_pac_loss_and_grads(
    backbone_params, adapter_params, batch, *, cfg, mesh, n_micro,
    r: int = 8, dp_axis: str = "dp", stage_axis: str = "stage",
    partition=None, kernel_impl: str = "ref", tap_policy: str = "f32",
    interpret=None,
):
    """Distributed epoch-1 forward+grads: staged backbone forward over the
    ``stage`` mesh axis (1F1B micro-batching via :func:`pipeline_apply`),
    adapter loss/grads data-parallel over ``dp`` with an explicit psum
    (the paper's per-minibatch AllReduce of the *trainable* params only).

    ``partition`` (a :class:`~repro.core.planner.StagePartition`) makes the
    planner's Plan the execution contract: its period boundaries choose
    what each stage runs. A *uniform* partition reduces to exactly the
    even-split path (bit-for-bit — same stage function, same stacking); a
    *ragged* one pads each stage's parameter slab to the max
    periods-per-stage, runs the padding as masked identity periods, and
    re-assembles the taps in true layer order from the uneven boundaries.

    ``kernel_impl="pallas"`` runs every stage's frozen forward on the
    pallas OpSet (still-quantized weights in quant_matmul, Pallas flash
    attention) and emits taps in ``tap_policy`` storage form — each stage's
    tap leaves ``pipeline_apply`` as a pytree (int8 payload + scales under
    the int8 policy), and the adapter loss consumes it through the fused
    cached-step kernels.

    Returns (loss, adapter_grads, (b0, taps, b_final)) — the activation
    triple is what the cache captures; all are global (dp-sharded) arrays
    (or storage-form pytrees under a pallas tap policy).
    """
    from repro.core.pipeline import pipeline_apply, stack_stages, stack_stages_ragged
    from repro.models.backbone import cross_entropy_parts

    from repro.data import DataPipeline

    n_stages = mesh.shape[stage_axis]
    dp = mesh.shape[dp_axis] if dp_axis in mesh.axis_names else 1
    if partition is not None:
        if partition.n_stages != n_stages:
            raise ValueError(
                f"plan has {partition.n_stages} stages but the mesh's "
                f"{stage_axis!r} axis has {n_stages}"
            )
        if partition.n_periods != cfg.n_periods:
            raise ValueError(
                f"plan partitions {partition.n_periods} periods but "
                f"{cfg.name} has {cfg.n_periods}"
            )
        if partition.is_uniform:
            partition = None  # identical to the even split — take that path
    if partition is None and cfg.n_periods % n_stages:
        raise ValueError(
            f"{cfg.n_periods} periods not divisible by {n_stages} pipeline stages"
        )
    if "positions" in batch:
        # _backbone_stage_fn rebuilds implicit arange positions per stage;
        # silently running custom positions through it would cache wrong
        # activations for every later epoch
        raise NotImplementedError(
            "pipeline_pac_train_step supports implicit (arange) positions only"
        )

    ops = None if kernel_impl == "ref" else get_opset(kernel_impl, tap_policy, interpret)
    x, positions = embed_inputs(backbone_params, cfg, batch, ops=ops)
    B = x.shape[0]
    # staged backbone forward: (B,S,d) → micro-batched → 1F1B pipeline
    # (dp_microbatches owns the layout contract + divisibility checks)
    x_micro = DataPipeline.dp_microbatches({"x": x}, n_micro, dp)["x"]
    if partition is None:
        stage_params = stack_stages(backbone_params["blocks"], n_stages)
        stage_fn = _backbone_stage_fn(cfg, ops=ops)
        pps = None
    else:  # ragged plan: padded slabs + per-stage active-period masks
        stage_params = {
            "blocks": stack_stages_ragged(
                backbone_params["blocks"], partition.boundaries
            ),
            "mask": jnp.asarray(partition.masks(), dtype=bool),
        }
        stage_fn = _backbone_stage_fn(cfg, masked=True, ops=ops)
        pps = partition.periods_per_stage
    b_final_micro, taps_micro = pipeline_apply(
        stage_fn, stage_params, x_micro, mesh,
        axis=stage_axis, batch_axis=dp_axis if dp > 1 else None,
        collect_taps=True, periods_per_stage=pps,
    )
    b_final = b_final_micro.reshape((B,) + b_final_micro.shape[2:])
    # (n_micro, n_p, mb, S, ·) → (n_p, B, S, ·) — micro-major sample order
    # (tree-mapped: a storage-form tap is a pytree of payload + scales)
    taps = jax.tree.map(lambda t: jnp.moveaxis(t, 1, 0), taps_micro)
    taps = jax.tree.map(lambda t: t.reshape(t.shape[:1] + (B,) + t.shape[3:]), taps)
    b0 = x if ops is None else ops.emit_tap(x)
    b_final = b_final if ops is None else ops.emit_tap(b_final)
    b0, taps, b_final = jax.lax.stop_gradient((b0, taps, b_final))

    # adapter loss + grads, dp-sharded batch, explicit AllReduce
    def spmd_grads(ap, head, b0_l, taps_l, bf_l, labels_l, pos_l):
        def loss_fn(a):
            if ops is None:
                logits = pac_logits(head, a, cfg, b0_l, taps_l, bf_l, pos_l, r)
                num, den = cross_entropy_parts(logits, labels_l)
            else:
                from repro.kernels.cached_step import cached_loss_parts

                cached = {"b0": b0_l, "taps": taps_l, "b_final": bf_l,
                          "labels": labels_l}
                num, den = cached_loss_parts(
                    head, a, cfg, cached, pos_l, r,
                    impl=kernel_impl, interpret=interpret,
                )
            if dp > 1:  # global mean: psum parts, not pmean of local means
                num = jax.lax.psum(num, dp_axis)
                den = jax.lax.psum(den, dp_axis)
            return num / jnp.maximum(den, 1)

        loss, grads = jax.value_and_grad(loss_fn)(ap)
        if dp > 1:
            # AllReduce completes the global gradient (trainable params
            # only — tiny). pmean, not psum: the transpose of the psum in
            # loss_fn already re-sums the replicated cotangent over dp, so
            # each shard's grad carries a dp× factor that the mean removes.
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp_axis), grads)
        return loss, grads

    bspec = P(dp_axis) if dp > 1 else P()
    tspec = P(None, dp_axis) if dp > 1 else P()
    pspec = (P(None, dp_axis) if positions.ndim == 3 else P(dp_axis)) if dp > 1 else P()
    fn = shard_map(
        spmd_grads,
        mesh=mesh,
        in_specs=(P(), P(), bspec, tspec, bspec, bspec, pspec),
        out_specs=(P(), P()),
        check_rep=False,
    )
    loss, grads = fn(
        adapter_params, backbone_params, b0, taps, b_final, batch["labels"], positions
    )
    return loss, grads, (b0, taps, b_final)


def pipeline_pac_train_step(
    backbone_params, adapter_params, opt_state, batch, *, cfg, mesh, n_micro,
    r: int = 8, lr=1e-3, clip=1.0, dp_axis: str = "dp", stage_axis: str = "stage",
    partition=None, kernel_impl: str = "ref", tap_policy: str = "f32",
    interpret=None,
):
    """Epoch-1 PAC+ step on a 2-D ``(dp, stage)`` mesh — the distributed
    twin of :func:`pac_train_step` (same signature plus mesh/n_micro).

    Backbone forward runs staged over ``stage`` with 1F1B micro-batching
    (optionally along a planner ``partition`` — see
    :func:`pipeline_pac_loss_and_grads`); adapter grads are AllReduced
    across ``dp``; the update itself is replicated (identical on every
    device after the AllReduce). Returns
    (loss, adapter_params', opt_state', (b0, taps, b_final)).
    """
    loss, grads, acts = pipeline_pac_loss_and_grads(
        backbone_params, adapter_params, batch, cfg=cfg, mesh=mesh,
        n_micro=n_micro, r=r, dp_axis=dp_axis, stage_axis=stage_axis,
        partition=partition, kernel_impl=kernel_impl, tap_policy=tap_policy,
        interpret=interpret,
    )
    grads, _ = clip_by_global_norm(grads, clip)
    adapter_params, opt_state = adamw_update(adapter_params, grads, opt_state, lr=lr)
    return loss, adapter_params, opt_state, acts


# ---------------------------------------------------------------------------
# Baseline fine-tuning steps
# ---------------------------------------------------------------------------


def full_train_step(params, opt_state, batch, *, cfg, lr=1e-4, clip=1.0):
    def loss_fn(p):
        return cross_entropy(backbone_logits(p, cfg, batch), batch["labels"])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    grads, _ = clip_by_global_norm(grads, clip)
    params, opt_state = adamw_update(params, grads, opt_state, lr=lr)
    return loss, params, opt_state


def lora_train_step(backbone_params, lora_params, opt_state, batch, *, cfg, lr=1e-3, clip=1.0):
    def loss_fn(lp):
        return cross_entropy(peft.lora_logits(backbone_params, lp, cfg, batch), batch["labels"])

    loss, grads = jax.value_and_grad(loss_fn)(lora_params)
    grads, _ = clip_by_global_norm(grads, clip)
    lora_params, opt_state = adamw_update(lora_params, grads, opt_state, lr=lr)
    return loss, lora_params, opt_state


def houlsby_train_step(backbone_params, ad_params, opt_state, batch, *, cfg, lr=1e-3, clip=1.0):
    def loss_fn(ap):
        return cross_entropy(peft.houlsby_logits(backbone_params, ap, cfg, batch), batch["labels"])

    loss, grads = jax.value_and_grad(loss_fn)(ad_params)
    grads, _ = clip_by_global_norm(grads, clip)
    ad_params, opt_state = adamw_update(ad_params, grads, opt_state, lr=lr)
    return loss, ad_params, opt_state


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def prefill_step(params, batch, *, cfg, kernel_impl: str = "ref", interpret=None):
    """Full-prompt forward (inference-prefill). Returns last-position logits."""
    ops = None if kernel_impl == "ref" else get_opset(kernel_impl, interpret=interpret)
    h, _ = backbone_forward(params, cfg, batch, ops=ops)
    return logits_from_hidden(params, cfg, h)[:, -1:, :]


def decode_step(params, token_batch, cache, pos, *, cfg, kernel_impl: str = "ref",
                interpret=None):
    """One-token decode against the cache. Returns (logits, cache')."""
    ops = None if kernel_impl == "ref" else get_opset(kernel_impl, interpret=interpret)
    return backbone_decode(params, cfg, token_batch, cache, pos, ops=ops)


def pac_decode_step(
    backbone_params, adapter_params, token_batch, cache, adapter_cache, pos, *, cfg,
    r: int = 8, kernel_impl: str = "ref", interpret=None,
):
    """Serve the personalised model: backbone decode + side-network decode.

    The frozen backbone decode dispatches through the ``kernel_impl``
    OpSet (quantized projections under ``"pallas"``); the side network
    and LM head stay on the ref ops — they are the trainable/fp math."""
    from repro.models.backbone import _REF_OPS, apply_block_decode

    ops = _REF_OPS if kernel_impl == "ref" else get_opset(kernel_impl, interpret=interpret)
    if "embeds" in token_batch:
        x = token_batch["embeds"]
    else:
        x = ops.embed_lookup(backbone_params["embed"], token_batch["tokens"])

    def period_fn(carry, xs):
        block_slice, cache_slice = xs
        h = carry
        new_caches = []
        for i, spec in enumerate(cfg.pattern):
            h, nc = apply_block_decode(block_slice[i], h, cfg, spec, cache_slice[i], pos, ops=ops)
            new_caches.append(nc)
        return h, (tuple(new_caches), h)

    b_final, (new_cache, taps_t) = jax.lax.scan(
        period_fn, x, (tuple(backbone_params["blocks"]), tuple(cache))
    )
    side, new_acache = adapter_decode(
        adapter_params, cfg, x, taps_t, adapter_cache, pos, r
    )
    logits = logits_from_hidden(backbone_params, cfg, b_final + side)
    return logits, list(new_cache), new_acache
