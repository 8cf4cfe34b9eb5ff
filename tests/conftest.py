"""Shared test harness.

* Pins tests to the CPU (``JAX_PLATFORMS=cpu``): Pallas kernels run in
  interpret mode. ``test_tpu_compile.py`` compiles the main-path kernels
  for a described TPU v5e without running them.
* Enables JAX's persistent compilation cache in
  ``compat.default_cache_dir()`` — ``JAX_COMPILATION_CACHE_DIR`` when
  set, else ``<repo>/.jax_cache`` — so reruns skip recompiling identical
  tiny programs. The directory and thresholds travel as env vars too,
  so subprocess tests (test_pipeline) cache in the same place.
* Session-scoped tiny-config/params/batch fixtures shared across
  modules, so each module stops re-initialising the same reduced model.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(_HERE, "..", "src"))
sys.path.insert(0, _HERE)  # `import _propcheck` from test modules

import jax  # noqa: E402
import pytest  # noqa: E402

from repro import compat  # noqa: E402

# env (as well as jax.config) so test subprocesses use the same cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compat.default_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

jax.config.update("jax_enable_x64", False)
compat.enable_compilation_cache()


# ---------------------------------------------------------------------------
# Shared tiny-model fixtures (session-scoped: JAX arrays are immutable and
# every consumer treats params/batches as read-only inputs)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="session")
def tiny_cfg():
    """The reduced dense transformer used by most correctness tests."""
    from repro.configs import get_arch

    return get_arch("internlm2-1.8b").reduced()


@pytest.fixture(scope="session")
def tiny_backbone(tiny_cfg):
    from repro.models import backbone as bb

    return bb.init_backbone(jax.random.PRNGKey(0), tiny_cfg)


@pytest.fixture(scope="session")
def tiny_adapter(tiny_cfg):
    from repro.core.parallel_adapters import init_adapter

    return init_adapter(jax.random.PRNGKey(1), tiny_cfg, r=4)


@pytest.fixture(scope="session")
def tiny_batch(tiny_cfg):
    B, S = 2, 12
    return {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(2), (B, S), 0, tiny_cfg.vocab
        ),
        "labels": jax.random.randint(
            jax.random.PRNGKey(3), (B, S), 0, tiny_cfg.vocab
        ),
    }
