"""byteps-lint: project-native static analysis (docs/static-analysis.md).

Run with ``python -m byteps_tpu.tools.lint``; programmatic entry is
``run_lint(root) -> List[Finding]``. Six rules, each encoding an
invariant a past PR enforced only by memory: ``wire-layout``,
``guarded-by``, ``device-thread``, ``env-sync``, ``metrics-schema``,
``doc-paths``.
Per-line suppression: ``# bps-lint: disable=<rule>``.
"""

from .base import Finding, Project, Rule, all_rules, run_lint

__all__ = ["Finding", "Project", "Rule", "all_rules", "run_lint"]
