"""``python -m repro.analysis`` — lint-only entry with zero jax dependency.

The full analyzer lives behind ``python -m repro check`` (which needs jax
for the trace layer).  This entry runs just the QFT AST rules, so the CI
lint job — which installs only ruff/mypy, not the jax stack — can gate
the custom rules on the same checkout.
"""
from __future__ import annotations

import argparse
import sys

from .lint import DEFAULT_LINT_ROOTS, iter_py_files
from .runner import find_repo_root, run_check


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="QFT lint rules (AST layer only; no jax required)")
    ap.add_argument("--paths", nargs="*", default=None,
                    help="repo-relative files/dirs (default: src/repro "
                         "paper)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    root = find_repo_root()
    # zero matched files means the path spec (or cwd) rotted, not that the
    # tree is clean — fail loudly instead of passing vacuously
    if not iter_py_files(root, args.paths or DEFAULT_LINT_ROOTS):
        print(f"repro.analysis: no .py files under {root} for "
              f"{args.paths or list(DEFAULT_LINT_ROOTS)}", file=sys.stderr)
        return 2

    report = run_check(lint_paths_arg=args.paths, trace=False, lint=True,
                       root=root)
    print(report.format(verbose=args.verbose))
    return 0 if report.ok() else 1


if __name__ == "__main__":
    sys.exit(main())
