"""Rule: layer boundaries the architecture depends on.

* ``src/repro/models/`` must not import ``repro.kernels`` — the model
  layer reaches kernels only through the OpSet seam (``core/opset.py``),
  which is what lets ``--kernels ref|pallas`` swap implementations
  without touching model code.
* nothing under ``src/repro/`` outside ``launch/`` may import
  ``repro.launch`` — ``launch/`` holds the command-line entry points
  only, the top layer; the library never reaches up into it.
* ``examples/`` must not touch ``repro.launch.train`` privates
  (``train._foo``) — they are thin clients of the runtime session API;
  private trainer internals are free to change under them.
"""

from __future__ import annotations

import ast

from tools.palint.engine import Context, Finding, PyModule, Rule, register

_TRAIN = "repro.launch.train"


def _imports(node, pkg: str) -> bool:
    """True when ``node`` imports the ``repro.<pkg>`` package or below."""
    full = f"repro.{pkg}"

    def under(name: str) -> bool:
        return name == full or name.startswith(full + ".")

    if isinstance(node, ast.ImportFrom):
        if node.module and under(node.module):
            return True
        if node.module == "repro" and any(a.name == pkg for a in node.names):
            return True
        if node.level and node.module and node.module.split(".")[0] == pkg:
            # relative spelling inside src/repro — `from ..kernels import x`
            return True
    if isinstance(node, ast.Import):
        return any(under(a.name) for a in node.names)
    return False


@register
class LayeringRule(Rule):
    name = "layering"
    summary = ("models/ must not import repro.kernels (OpSet is the seam); "
               "only launch/ may import repro.launch; "
               "examples must not use repro.launch.train privates")

    def check(self, module: PyModule, ctx: Context):
        if module.rel.startswith("src/repro/models/"):
            for node in ast.walk(module.tree):
                if _imports(node, "kernels"):
                    yield Finding(
                        self.name, module.rel, node.lineno,
                        "model layer imports repro.kernels — route through "
                        "the OpSet (core/opset.py), the only sanctioned seam",
                    )

        if (module.rel.startswith("src/repro/")
                and not module.rel.startswith("src/repro/launch/")):
            for node in ast.walk(module.tree):
                if _imports(node, "launch"):
                    yield Finding(
                        self.name, module.rel, node.lineno,
                        "library module imports repro.launch — launch/ holds "
                        "the entry points only; import from the layer below",
                    )

        if module.rel.startswith("examples/"):
            for node in ast.walk(module.tree):
                if isinstance(node, ast.ImportFrom) and node.module == _TRAIN:
                    private = [a.name for a in node.names if a.name.startswith("_")]
                    if private:
                        yield Finding(
                            self.name, module.rel, node.lineno,
                            f"imports trainer privates {private} from "
                            f"{_TRAIN} — use the runtime session API "
                            "(repro.runtime) instead",
                        )
                elif isinstance(node, ast.Attribute) and node.attr.startswith("_"):
                    base = module.imports.resolve(node.value)
                    if base == _TRAIN:
                        yield Finding(
                            self.name, module.rel, node.lineno,
                            f"touches {_TRAIN}.{node.attr} — trainer privates "
                            "are not a stable surface for examples",
                            col=node.col_offset,
                        )
