"""Architecture config registry.

Each assigned architecture lives in its own module and registers an
:class:`~repro.configs.base.ArchConfig` with the exact published
hyper-parameters (source cited in the config).
"""

import importlib

from repro.configs.base import (  # noqa: F401
    ArchConfig,
    LayerSpec,
    MoESpec,
    get_arch,
    list_archs,
    register,
)

_MODULES = [
    "musicgen_large",
    "grok_1_314b",
    "moonshot_v1_16b_a3b",
    "kimi_k2_1t_a32b",
    "qwen2_vl_7b",
    "xlstm_125m",
    "gemma2_2b",
    "jamba_1_5_large_398b",
    "internlm2_1_8b",
    "granite_20b",
    "mixtral_8x7b",
    "paper_models",
]

_loaded = False


def load_all() -> None:
    global _loaded
    if _loaded:
        return
    for m in _MODULES:
        importlib.import_module(f"repro.configs.{m}")
    _loaded = True
