# Importing this package registers every rule with the engine registry.
from tools.palint.rules import (  # noqa: F401
    axis_name,
    bytecode,
    compat_surface,
    jit_purity,
    layering,
    pallas_blockspec,
    storage_form,
)
