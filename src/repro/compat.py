"""The JAX API surface this repo builds on, in one place.

The repo runs on one installation: JAX 0.9.0 (jaxlib 0.9.0, libtpu
0.0.34), pinned in pyproject.toml. APIs whose spelling has moved between
JAX releases are called here and nowhere else, so a future upgrade
touches one file. Nothing outside this module may import ``AxisType``,
pass ``axis_types=`` / ``check_vma=``, or import ``shard_map``.

* ``make_mesh(shape, axes)`` — ``jax.make_mesh`` with ``Auto`` axes.
* ``abstract_mesh(shape, axes)`` — :class:`jax.sharding.AbstractMesh`.
* ``shard_map(f, mesh, in_specs, out_specs, check_rep=...)`` —
  ``jax.shard_map``; ``check_rep`` is its ``check_vma``.
* ``tree_map`` / ``tree_map_with_path`` — the pytree helpers.
* ``force_host_device_count(n)`` — the ``XLA_FLAGS`` knob for faked
  host devices (must run before the first backend initialisation).
* ``default_cache_dir()`` / ``enable_compilation_cache()`` — JAX's
  persistent compilation cache.
"""

from __future__ import annotations

import os
import re
from typing import Optional

import jax

__all__ = [
    "make_mesh",
    "abstract_mesh",
    "shard_map",
    "tree_map",
    "tree_map_with_path",
    "force_host_device_count",
    "default_cache_dir",
    "enable_compilation_cache",
]


# ---------------------------------------------------------------------------
# Meshes
# ---------------------------------------------------------------------------


def make_mesh(shape, axes, *, devices=None):
    """Device mesh whose axes all have ``Auto`` sharding semantics."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def abstract_mesh(shape, axes):
    """:class:`jax.sharding.AbstractMesh` of the given shape."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def shard_map(f, mesh, in_specs, out_specs, check_rep: Optional[bool] = None):
    """``jax.shard_map``; ``check_rep`` sets its replication check
    (``check_vma``), ``None`` keeps JAX's default."""
    kwargs = {} if check_rep is None else {"check_vma": check_rep}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs)


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------

tree_map = jax.tree.map
tree_map_with_path = jax.tree_util.tree_map_with_path


# ---------------------------------------------------------------------------
# Process-level knobs
# ---------------------------------------------------------------------------


def force_host_device_count(n: int) -> None:
    """Fake ``n`` host-platform devices (multi-device runs on the CPU).

    Must be called before the first JAX backend initialisation — the
    device count locks when the backend comes up, not at ``import jax``.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}"
    ).strip()


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def default_cache_dir() -> str:
    """Where the persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else
    ``<repo>/.jax_cache`` (git-ignored). A fixed path matters: the
    cache key includes it, so a moving directory never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache in
    :func:`default_cache_dir` and return that directory.

    Thresholds are dropped to zero so even the tiny CPU-test programs
    cache (the default min-compile-time gate skips them). Raises if the
    directory cannot be created.
    """
    cache_dir = default_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
