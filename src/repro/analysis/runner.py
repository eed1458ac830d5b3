"""Compose the two analyzer layers into one Report (the `repro check` body).

Kept separate from pipeline/cli.py so tests and CI helpers can run checks
programmatically without argparse, and separate from jaxpr_checks so the
lint layer stays importable without jax tracing costs.
"""
from __future__ import annotations

from pathlib import Path

from .lint import DEFAULT_LINT_ROOTS, lint_paths
from .report import Report


def find_repo_root(start: Path | None = None) -> Path:
    """Nearest ancestor holding the repo's anchor files.  The lint layer
    needs repo-relative paths for its rule filters, so `repro check` must
    work from any cwd inside the repo."""
    p = (start or Path.cwd()).resolve()
    for cand in (p, *p.parents):
        if (cand / "src" / "repro").is_dir():
            return cand
    return p


def run_check(configs: list[str] | None = None,
              lint_paths_arg: list[str] | None = None,
              trace: bool = True, lint: bool = True,
              prefill_budget: int | None = None,
              root: Path | None = None) -> Report:
    """Run the requested layers and return the combined Report.

    ``configs=None`` means every registry arch; ``lint_paths_arg=None``
    means the default roots (src/repro + paper).  ``trace=False``
    skips the jaxpr layer (lint-only mode — fast, no jax import cost in
    the hot path of pre-commit usage).
    """
    report = Report()
    if lint:
        report.extend(lint_paths(find_repo_root(root),
                                 lint_paths_arg or DEFAULT_LINT_ROOTS))
    if trace:
        # deferred: importing jaxpr_checks pulls in jax + the model zoo,
        # which lint-only callers never need
        from .jaxpr_checks import analyze
        report.extend(analyze(configs, prefill_budget=prefill_budget))
    return report
