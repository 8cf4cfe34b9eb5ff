"""The edge trainer's mesh and shardings.

One mesh shape serves every multi-device run: a 2-D ``(dp, stage)``
mesh. Epoch 1 runs the frozen backbone pipelined over ``stage`` with
the batch split over ``dp``; the cached epochs data-parallel over the
whole pool. Parameters and optimizer state are replicated (the paper
AllReduces adapter gradients); only the batch is sharded.

Meshes are built by functions, never at import, so importing this
module touches no device state.
"""

from __future__ import annotations

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro import compat


def make_edge_mesh(dp: int, stages: int, devices=None):
    """2-D ``(dp, stage)`` mesh for the hybrid DP×PP edge trainer.

    ``devices`` defaults to the first dp·stages of ``jax.devices()`` (on
    CPU, fake host devices from ``compat.force_host_device_count``)."""
    import jax

    total = dp * stages
    if devices is None:
        devices = jax.devices()
    if len(devices) < total:
        raise RuntimeError(
            f"need {total} devices for a {dp}×{stages} (dp, stage) mesh, "
            f"have {len(devices)}; on CPU call "
            f"compat.force_host_device_count({total}) before any JAX use"
        )
    return compat.make_mesh((dp, stages), ("dp", "stage"), devices=devices[:total])


def make_plan_mesh(partition, devices=None, dp: int = None):
    """2-D ``(dp, stage)`` mesh shaped by an executable
    :class:`~repro.core.planner.StagePartition`: the plan's stage count
    becomes the ``stage`` axis; ``dp`` defaults to the widest replica
    count the device pool supports (pool // stages — the uniform-mesh
    rendering of the plan's per-stage device groups)."""
    import jax

    stages = partition.n_stages
    if devices is None:
        devices = jax.devices()
    if dp is None:
        dp = max(1, len(devices) // stages)
    return make_edge_mesh(dp, stages, devices)


def data_axes(mesh) -> tuple:
    """Mesh axes that shard the batch: ``("dp",)`` on an edge mesh."""
    return ("dp",) if "dp" in mesh.axis_names else ()


def path_names(path) -> list:
    out = []
    for k in path:
        if isinstance(k, DictKey):
            out.append(str(k.key))
        elif isinstance(k, SequenceKey):
            out.append(f"[{k.idx}]")
        else:
            out.append(str(k))
    return out


def batch_specs(batch, batch_axes) -> dict:
    """PartitionSpecs for a batch dict: each leaf's batch dim over
    ``batch_axes``, every other dim replicated. The batch dim leads,
    except in ``taps`` (n_p, B, S, ·) and mrope ``positions`` (3, B, S)."""
    axes = tuple(batch_axes)
    B = axes if len(axes) > 1 else (axes[0] if axes else None)

    def spec_for(path, leaf):
        name = path_names(path)[0]
        bdim = 1 if name == "taps" or (name == "positions" and leaf.ndim == 3) else 0
        return P(*[B if i == bdim else None for i in range(leaf.ndim)])

    return compat.tree_map_with_path(spec_for, batch)


def replicated(tree, mesh: Mesh):
    """NamedSharding pytree replicating every leaf of ``tree`` over ``mesh``.

    The edge trainer's adapter/optimizer state is tiny (1/r² of the
    backbone) — the paper keeps it replicated on every device and
    AllReduces grads, rather than sharding it."""
    s = NamedSharding(mesh, P())
    return compat.tree_map(lambda _: s, tree)


def cached_batch_axes(cached_batch, mesh: Mesh) -> tuple:
    """Mesh axes the epoch≥2 cached batch shards over: the data axes,
    *plus* the pipeline ``stage`` axis when the batch divides — the
    backbone no longer runs from epoch 2, so the whole pool
    data-parallels instead of the stage devices duplicating work. The
    shared contract behind :func:`cached_step_shardings` and the
    shard_map-based ``steps.dp_cached_train_step``."""
    axes = list(data_axes(mesh))
    if "stage" in mesh.axis_names:
        B = cached_batch["labels"].shape[0]
        pool = int(np.prod([mesh.shape[a] for a in axes + ["stage"]]))
        if B % pool == 0:
            axes.append("stage")
    return tuple(axes)


def cached_step_shardings(backbone, adapter, opt_state, cached_batch, mesh: Mesh):
    """in_shardings for the epoch≥2 pure-DP cached step
    (``pac_cached_train_step(backbone, adapter, opt, cached_batch)``):
    params/optimizer replicated, the cached activation batch sharded over
    :func:`cached_batch_axes`. Handles compressed entries — an int8
    ``{"q", "scale"}`` leaf pair inherits the batch layout of the tensor
    it stores."""
    axes = cached_batch_axes(cached_batch, mesh)
    return (
        replicated(backbone, mesh),
        replicated(adapter, mesh),
        replicated(opt_state, mesh),
        to_named(batch_specs(cached_batch, axes), mesh),
    )


def to_named(tree_specs, mesh: Mesh):
    """PartitionSpec pytree → NamedSharding pytree."""
    return compat.tree_map(lambda s: NamedSharding(mesh, s), tree_specs,
                           is_leaf=lambda x: isinstance(x, P))
