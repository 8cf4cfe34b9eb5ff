"""Shared filesystem helpers for the repo's ``tools/`` scripts.

One definition of "where is the repo root" and "which files does a tool
walk", used by both :mod:`tools.palint` and ``tools/check_docs.py`` so
the two gates can never disagree about what they cover.
"""

from __future__ import annotations

import os
from typing import Callable, Iterable, Optional

# Directories no tool ever wants to descend into.
SKIP_DIRS = frozenset(
    {"__pycache__", ".git", ".pytest_cache", ".mypy_cache", ".ruff_cache",
     "node_modules", ".venv", "venv", ".eggs"}
)


def repo_root() -> str:
    """Absolute path of the repository root (the parent of ``tools/``)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def walk_files(
    paths: Iterable[str],
    *,
    root: Optional[str] = None,
    suffixes: Optional[tuple] = None,
    keep: Optional[Callable[[str], bool]] = None,
) -> list:
    """Expand files/directories into a sorted, deduplicated file list.

    ``paths`` entries are taken relative to ``root`` (default:
    :func:`repo_root`) unless absolute; directories are walked
    recursively with :data:`SKIP_DIRS` pruned. A file is kept when it
    ends with one of ``suffixes`` (or unconditionally without them) and,
    if supplied, ``keep(path)`` returns True.
    """
    base = root or repo_root()

    def _wanted(path: str) -> bool:
        if suffixes and not os.path.basename(path).endswith(tuple(suffixes)):
            return False
        return keep(path) if keep else True

    out = []
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(base, p)
        if os.path.isfile(full):
            # explicitly named files bypass the suffix filter:
            # the caller asked for exactly this one
            if keep is None or keep(full):
                out.append(os.path.abspath(full))
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d not in SKIP_DIRS and not d.startswith(".")
                )
                for f in sorted(filenames):
                    fp = os.path.join(dirpath, f)
                    if _wanted(fp):
                        out.append(os.path.abspath(fp))
    return sorted(dict.fromkeys(out))


def doc_files(root: Optional[str] = None) -> list:
    """README.md + docs/*.md — the markdown set the docs gate covers."""
    base = root or repo_root()
    files = [os.path.join(base, "README.md")]
    docs = os.path.join(base, "docs")
    if os.path.isdir(docs):
        files += sorted(
            os.path.join(docs, f) for f in os.listdir(docs) if f.endswith(".md")
        )
    return [f for f in files if os.path.exists(f)]
