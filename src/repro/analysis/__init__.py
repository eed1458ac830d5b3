"""Static analysis for the QFT reproduction (`python -m repro check`).

Two layers over one report schema:

- **jaxpr_checks** — trace-time invariant analyzer: traces the real step
  constructors for every registry config with ``jax.make_jaxpr`` /
  ``eval_shape`` and proves the serve/train structural invariants (one
  host-transfer surface per decode step, integer-operand dots with no f32
  dequant materialization, prefill recompile surface, plan coverage,
  kernel routing) without allocating or running anything.
- **lint** — repo-specific AST rules QFT001..QFT006 with
  ``# qft: noqa[RULE]`` suppression.

``runner.run_check`` composes both into a :class:`report.Report`.
"""
from .lint import RULES, lint_paths, lint_source           # noqa: F401
from .report import SCHEMA_VERSION, Diagnostic, Report     # noqa: F401
from .runner import run_check                              # noqa: F401
