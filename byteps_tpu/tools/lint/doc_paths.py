"""Rule ``doc-paths``: a file cited in backticks in ``README.md`` or a
document under ``docs/`` exists in the tree.

Historical bug class: the documents outlive what they describe. From
PR 23 on every PR was judged by ``benchmark/run.py``, yet ``README.md``
and twelve files under ``docs/`` went on sending the reader to a
CPU-era benchmark script, its phases and its regression gate, and
justified design choices by figures nothing recorded (PR 47 deleted
the script and found the citations by hand). A path is checked where
it can be resolved without guessing:

- one with a directory part, where its first component is a top-level
  directory of the repository or a directory of the package
  (``jax/train.py`` resolves under ``byteps_tpu/``), with or without
  a ``:line`` behind it;
- a bare file name, where NO file of that name exists anywhere in the
  tree (a deleted script fails; ``ps.cc`` and a user's ``train.py`` do
  not).

Paths of the reference's tree (``byteps/...``, ``example/...``), paths
with a placeholder (``<dir>/0/comm.json``) and directories that
``.gitignore`` lists are out of scope: the rule reads the same on a
fresh checkout as on a builder's disk.
"""

from __future__ import annotations

import os
import re
from typing import List, Optional, Set

from .base import Finding, Project, Rule

_SPAN_RE = re.compile(r"`([^`\n]+)`")
_PATH_RE = re.compile(
    r"(?<![\w./<>*{}$~-])"
    r"((?:[\w.-]+/)*[\w-][\w.-]*\.(?:py|md|json|sh|cc|h))"
    r"(?::\d+)?(?![\w/<>*{}-])")


def _ignored_dirs(project: Project) -> Set[str]:
    """Directories ``.gitignore`` lists by name (``name/`` rows)."""
    lines = project.lines(os.path.join(project.root, ".gitignore"))
    return {ln.strip().strip("/") for ln in lines
            if ln.strip().endswith("/") and "*" not in ln}


class _Tree:
    """What of the tree a citation may name: every file's name, and the
    directories a scoped path may start with."""

    def __init__(self, project: Project):
        self.project = project
        ignored = _ignored_dirs(project) | {".git"}
        self.names: Set[str] = set()
        for _dirpath, dirnames, filenames in os.walk(project.root):
            dirnames[:] = [d for d in dirnames if d not in ignored]
            self.names.update(filenames)
        self.top = _subdirs(project.root) - ignored
        self.pkg = _subdirs(project.pkg_root) - ignored

    def stale(self, cited: str) -> Optional[str]:
        """Why a citation is stale; None where it holds or is out of
        the rule's scope."""
        if "/" not in cited:
            if cited in self.names:
                return None
            return (f"cites `{cited}` but no file of that name is in "
                    f"the tree")
        first = cited.split("/", 1)[0]
        roots = [root for root, dirs in ((self.project.root, self.top),
                                         (self.project.pkg_root, self.pkg))
                 if first in dirs]
        if not roots or any(os.path.isfile(os.path.join(root, cited))
                            for root in roots):
            return None
        return f"cites `{cited}` but the tree has no such file"


def _subdirs(root: str) -> Set[str]:
    return {d for d in os.listdir(root)
            if os.path.isdir(os.path.join(root, d))}


class DocPathsRule(Rule):
    name = "doc-paths"
    doc = ("a file cited in backticks in README.md or docs/*.md must "
           "exist (scoped paths resolve, bare names exist somewhere)")

    def check(self, project: Project) -> List[Finding]:
        documents = project.documents()
        if not documents:
            return []  # fixture without documents
        tree = _Tree(project)
        findings: List[Finding] = []
        for doc in documents:
            rel = project.rel(doc)
            for lineno, text in enumerate(project.lines(doc), start=1):
                for span in _SPAN_RE.finditer(text):
                    for m in _PATH_RE.finditer(span.group(1)):
                        why = tree.stale(m.group(1))
                        if why:
                            findings.append(Finding(
                                self.name, rel, lineno, why))
        return findings
