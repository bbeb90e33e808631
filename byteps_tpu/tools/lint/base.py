"""byteps-lint core: project model, findings, rule registry, suppression.

The framework is deliberately dependency-free (ast + re + pathlib): it
must run in CI boxes and pre-commit hooks without the training stack.
Each rule is one class with a ``name``, a one-line ``doc`` and a
``check(project)`` returning structured findings; ``run_lint`` filters
per-line suppressions (``# bps-lint: disable=<rule>`` on the flagged
line or the line directly above; ``//`` comments work in C++ sources,
``<!-- ... -->`` in Markdown).

The rules encode invariants that previously lived only in reviewers'
heads — see docs/static-analysis.md for the catalog and the historical
bug each rule pins down.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence

# Directories never scanned: the linter itself (its sources quote rule
# names, env vars and metric names as DATA), caches, VCS internals.
_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache", "lint"}

_SUPPRESS_RE = re.compile(
    r"(?:#|//|<!--)\s*bps-lint:\s*disable=([\w,\-\s]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation: rule slug, repo-relative path, 1-based line."""

    rule: str
    path: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Project:
    """Lazily-cached view of the tree being linted.

    ``root`` is either the real repo root (``byteps_tpu/`` package plus
    ``docs/``) or a fixture tree mimicking the same shape; every lookup
    degrades gracefully when a piece is absent so single-rule fixtures
    stay tiny.
    """

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        pkg = os.path.join(self.root, "byteps_tpu")
        self.pkg_root = pkg if os.path.isdir(pkg) else self.root
        self.docs_root = os.path.join(self.root, "docs")
        self._text: Dict[str, Optional[str]] = {}
        self._ast: Dict[str, Optional[ast.AST]] = {}

    # -- file discovery ------------------------------------------------ #

    def _walk(self, top: str, suffix: str) -> List[str]:
        out: List[str] = []
        if not os.path.isdir(top):
            return out
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_DIRS)
            for f in sorted(filenames):
                if f.endswith(suffix):
                    out.append(os.path.join(dirpath, f))
        return out

    def py_files(self) -> List[str]:
        """Package Python sources (the system under lint — excludes the
        linter itself and anything outside the package)."""
        return self._walk(self.pkg_root, ".py")

    def cc_files(self) -> List[str]:
        return self._walk(self.pkg_root, ".cc")

    def native_source(self) -> Optional[str]:
        """The wire-protocol ground truth (``native/ps.cc``), or the
        first .cc file for fixture trees."""
        ccs = self.cc_files()
        for c in ccs:
            if os.path.basename(c) == "ps.cc":
                return c
        return ccs[0] if ccs else None

    def doc(self, name: str) -> Optional[str]:
        p = os.path.join(self.docs_root, name)
        return p if os.path.exists(p) else None

    def documents(self) -> List[str]:
        """``README.md`` and the Markdown files under ``docs/``."""
        readme = os.path.join(self.root, "README.md")
        return ([readme] if os.path.isfile(readme) else []) \
            + self._walk(self.docs_root, ".md")

    def env_scan_files(self) -> List[str]:
        """Sources scanned for BYTEPS_*/DMLC_* env reads: the package
        (.py and .cc) plus the examples, which read documented knobs."""
        return (self.py_files() + self.cc_files()
                + self._walk(os.path.join(self.root, "examples"), ".py"))

    # -- content caches ------------------------------------------------ #

    def text(self, path: str) -> Optional[str]:
        if path not in self._text:
            try:
                with open(path, encoding="utf-8", errors="replace") as f:
                    self._text[path] = f.read()
            except OSError:
                self._text[path] = None
        return self._text[path]

    def lines(self, path: str) -> List[str]:
        t = self.text(path)
        return t.splitlines() if t is not None else []

    def tree(self, path: str) -> Optional[ast.AST]:
        if path not in self._ast:
            t = self.text(path)
            try:
                self._ast[path] = ast.parse(t) if t is not None else None
            except SyntaxError:
                self._ast[path] = None
        return self._ast[path]

    def rel(self, path: str) -> str:
        return os.path.relpath(path, self.root)

    # -- suppression --------------------------------------------------- #

    def suppressed(self, path: str, line: int, rule: str) -> bool:
        """True when the flagged line (or the one directly above, for
        statements too long to carry a trailing comment) disables the
        rule. ``disable=all`` silences every rule on that line — use
        sparingly; the named form documents WHICH invariant is waived."""
        lines = self.lines(path)
        for ln in (line, line - 1):
            if 1 <= ln <= len(lines):
                m = _SUPPRESS_RE.search(lines[ln - 1])
                if m:
                    # " -": the tail of a Markdown comment's "-->"
                    rules = {r.strip(" -") for r in m.group(1).split(",")}
                    if rule in rules or "all" in rules:
                        return True
        return False


class Rule:
    """Base class: subclasses set ``name``/``doc`` and implement
    ``check``. Findings come back unfiltered; ``run_lint`` applies
    suppressions so every rule gets them for free."""

    name = "abstract"
    doc = ""

    def check(self, project: Project) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


def all_rules() -> List[Rule]:
    """The registered rule set, import-cycle-free (rules import base,
    never each other)."""
    from .device_thread import DeviceThreadRule
    from .doc_paths import DocPathsRule
    from .env_sync import EnvSyncRule
    from .locks import GuardedByRule
    from .metrics_schema import MetricsSchemaRule
    from .wire_layout import WireLayoutRule

    return [WireLayoutRule(), GuardedByRule(), DeviceThreadRule(),
            EnvSyncRule(), MetricsSchemaRule(), DocPathsRule()]


def run_lint(root: str,
             rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the suite over ``root``; returns suppression-filtered
    findings sorted by (path, line, rule). ``rules``: optional subset
    of rule names."""
    project = Project(root)
    selected = all_rules()
    if rules:
        wanted = set(rules)
        unknown = wanted - {r.name for r in selected}
        if unknown:
            raise ValueError(
                f"unknown rule(s): {sorted(unknown)}; available: "
                f"{sorted(r.name for r in selected)}")
        selected = [r for r in selected if r.name in wanted]
    findings: List[Finding] = []
    for rule in selected:
        for f in rule.check(project):
            abs_path = os.path.join(project.root, f.path)
            if not project.suppressed(abs_path, f.line, f.rule):
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
