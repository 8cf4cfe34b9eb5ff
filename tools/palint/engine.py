"""palint core: rule registry, file loading, suppressions, the runner.

A rule is a class with a unique ``name``, a one-line ``summary``, and a
``check`` method yielding :class:`Finding` (a violation — fails the run)
and/or :class:`Report` (informational data surfaced in ``--json``, e.g.
per-``pallas_call`` VMEM estimates). Python rules get a parsed
:class:`PyModule`; project rules run once against the repo root.

Per-line suppression::

    something_flagged()  # palint: disable=rule-name  -- why it is OK

suppresses findings of the named rule(s) on that physical line
(comma-separate several; ``disable=all`` silences every rule).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional

if __package__ in (None, ""):  # pragma: no cover - direct script use
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    )

from tools.fsutil import repo_root, walk_files
from tools.palint.astutil import ImportMap

_SUPPRESS_RE = re.compile(
    r"#\s*palint:\s*disable=([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
)


@dataclasses.dataclass
class Finding:
    """One violation. ``path`` is repo-root-relative (posix separators)."""

    rule: str
    path: str
    line: int
    message: str
    col: int = 0
    extra: Optional[dict] = None

    def to_json(self) -> dict:
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "col": self.col, "message": self.message}
        if self.extra:
            d["extra"] = self.extra
        return d

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class Report:
    """Informational per-site data (never fails the run)."""

    rule: str
    path: str
    line: int
    data: dict

    def to_json(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "data": self.data}


class PyModule:
    """One parsed python file plus its suppression table."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.imports = ImportMap(self.tree)
        self.suppressions: Dict[int, set] = {}
        for lineno, line in enumerate(source.splitlines(), 1):
            m = _SUPPRESS_RE.search(line)
            if m:
                self.suppressions[lineno] = {
                    r.strip() for r in m.group(1).split(",") if r.strip()
                }

    def is_suppressed(self, rule: str, line: int) -> bool:
        rules = self.suppressions.get(line, ())
        return rule in rules or "all" in rules


@dataclasses.dataclass
class Context:
    """Run-wide configuration handed to every rule."""

    root: str
    vmem_budget_bytes: int = 16 * 1024 * 1024
    assume_dim: int = 128


class Rule:
    """Base: AST rule over one python module."""

    name: str = ""
    summary: str = ""
    kind: str = "python"  # "python" | "project"

    def check(self, module: PyModule, ctx: Context) -> Iterable:
        raise NotImplementedError

    def check_project(self, ctx: Context) -> Iterable:
        raise NotImplementedError


_REGISTRY: Dict[str, Rule] = {}


def register(cls):
    """Class decorator adding a rule (by its ``name``) to the registry."""
    rule = cls()
    if not rule.name:
        raise ValueError(f"rule {cls.__name__} has no name")
    if rule.name in _REGISTRY:
        raise ValueError(f"duplicate rule name {rule.name!r}")
    _REGISTRY[rule.name] = rule
    return cls


def all_rules() -> List[Rule]:
    import tools.palint.rules  # noqa: F401  (registers on import)

    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


DEFAULT_PATHS = ("src", "tests", "examples", "tools")


def _rel(path: str, root: str) -> str:
    try:
        rel = os.path.relpath(path, root)
    except ValueError:  # different drive (windows)
        rel = path
    if rel.startswith(".."):
        rel = os.path.basename(path)
    return rel.replace(os.sep, "/")


@dataclasses.dataclass
class Result:
    findings: List[Finding]
    reports: List[Report]
    n_files: int

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict:
        return {
            "version": 1,
            "ok": self.ok,
            "files_scanned": self.n_files,
            "findings": [f.to_json() for f in self.findings],
            "reports": [r.to_json() for r in self.reports],
        }


def run(
    paths: Optional[Iterable[str]] = None,
    *,
    root: Optional[str] = None,
    ctx: Optional[Context] = None,
) -> Result:
    """Run every registered rule over ``paths`` (files or directories,
    relative to ``root``). Default: :data:`DEFAULT_PATHS`."""
    root = os.path.abspath(root or repo_root())
    ctx = ctx or Context(root=root)
    ctx.root = root

    explicit = list(paths) if paths else None
    scan = explicit if explicit is not None else [
        p for p in DEFAULT_PATHS if os.path.exists(os.path.join(root, p))
    ]
    py_files = walk_files(scan, root=root, suffixes=(".py",))

    rules = all_rules()
    findings: List[Finding] = []
    reports: List[Report] = []

    def emit(items, module: Optional[PyModule] = None):
        for item in items:
            if isinstance(item, Report):
                reports.append(item)
            elif module is not None and module.is_suppressed(item.rule, item.line):
                continue
            else:
                findings.append(item)

    for path in py_files:
        rel = _rel(path, root)
        try:
            source = open(path, encoding="utf-8").read()
            module = PyModule(path, rel, source)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            lineno = getattr(e, "lineno", None) or 0
            findings.append(Finding(
                rule="parse-error", path=rel, line=lineno,
                message=f"cannot analyze: {e.__class__.__name__}: {e}",
            ))
            continue
        for rule in rules:
            if rule.kind == "python":
                emit(rule.check(module, ctx), module)

    for rule in rules:
        if rule.kind == "project":
            emit(rule.check_project(ctx))

    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    reports.sort(key=lambda r: (r.path, r.line, r.rule))
    return Result(findings, reports, n_files=len(py_files))
