"""palint command line.

Usage::

    python -m tools.palint                      # default paths
    python -m tools.palint src tests            # explicit targets
    python -m tools.palint --json               # machine-readable output
    python -m tools.palint --list-rules         # rule catalog

Exit status: 0 clean, 1 findings, 2 bad invocation.
"""

from __future__ import annotations

import argparse
import json
import sys

from tools.palint.engine import DEFAULT_PATHS, Context, all_rules, run


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m tools.palint",
        description="Project-invariant static analyzer for the PAC "
                    "jax_pallas stack (see docs/LINTING.md).",
    )
    ap.add_argument(
        "paths", nargs="*",
        help=f"files/directories to scan (default: {' '.join(DEFAULT_PATHS)})",
    )
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings + per-site reports as JSON")
    ap.add_argument("--root", default=None,
                    help="repo root override (used by the test suite)")
    ap.add_argument("--vmem-budget-mib", type=float, default=16.0,
                    help="per-core VMEM budget for pallas-blockspec "
                         "(default: 16 MiB)")
    ap.add_argument("--assume-dim", type=int, default=128,
                    help="value charged for block dims that stay dynamic "
                         "(default: 128)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="also print per-site reports in text mode")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.list_rules:
        for rule in all_rules():
            print(f"{rule.name:18s} [{rule.kind}] {rule.summary}")
        return 0

    ctx = Context(
        root="",  # filled by run()
        vmem_budget_bytes=int(args.vmem_budget_mib * 1024 * 1024),
        assume_dim=args.assume_dim,
    )
    result = run(args.paths or None, root=args.root, ctx=ctx)

    if args.as_json:
        print(json.dumps(result.to_json(), indent=1, sort_keys=True))
        return 0 if result.ok else 1

    for f in result.findings:
        print(f.render())
    if args.verbose:
        for r in result.reports:
            print(f"note: {r.path}:{r.line}: [{r.rule}] "
                  + json.dumps(r.data, sort_keys=True))
    status = "clean" if result.ok else f"{len(result.findings)} finding(s)"
    print(f"palint: {result.n_files} files, "
          f"{len(result.reports)} report(s), {status}")
    return 0 if result.ok else 1


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
