"""Parsed-source cache, findings, and the pragma contract.

Every tpulint pass walks the same repository snapshot: :class:`Tree`
reads and ``ast.parse``\\ s each file once, and all passes share the
cache — the "shared AST walk" that lets obs_lint become pass 4 without
a second tree traversal.

Suppression: a finding is silenced by an inline pragma

    # tpulint: disable=<rule>[,<rule>] -- <justification>

on the offending line, or in the comment block immediately above the
offending statement. The justification text after ``--`` is REQUIRED:
a pragma without one is itself a finding (``pragma-justification``).
The lint exists to keep hand-maintained invariants honest; an
unexplained exemption is exactly the kind of silent drift it hunts.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re

# What `tools/tpulint.py` scans by default, relative to the repo root.
# tests/ are walked too but individual rules scope themselves (e.g. the
# raw-env-read ban exempts tests, the undeclared-name rule does not —
# a typo'd monkeypatch.setenv would otherwise test nothing).
DEFAULT_SCAN = ("tpuflow", "tools", "flows", "tests")

_PRAGMA_RE = re.compile(
    r"#\s*tpulint:\s*disable=([a-z0-9_,\- ]+?)\s*(?:--\s*(.*\S))?\s*$"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Tree:
    """One repository snapshot: file discovery + source/AST caches."""

    def __init__(self, root: str, scan: tuple[str, ...] = DEFAULT_SCAN):
        self.root = os.path.abspath(root)
        self.scan = scan
        self._files: list[str] | None = None
        self._src: dict[str, str] = {}
        self._ast: dict[str, ast.Module | None] = {}
        self._pragmas: dict[str, dict[int, tuple[set, bool, int]]] = {}
        self.parse_errors: list[Finding] = []

    # ------------------------------------------------------------ files
    def files(self) -> list[str]:
        """Repo-relative paths of every scanned ``.py`` file."""
        if self._files is not None:
            return self._files
        out = []
        for entry in self.scan:
            full = os.path.join(self.root, entry)
            if os.path.isfile(full):
                out.append(entry)
                continue
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fname in sorted(filenames):
                    if fname.endswith(".py"):
                        out.append(
                            os.path.relpath(
                                os.path.join(dirpath, fname), self.root
                            )
                        )
        self._files = sorted(set(out))
        return self._files

    def source(self, rel: str) -> str:
        if rel not in self._src:
            with open(os.path.join(self.root, rel)) as f:
                self._src[rel] = f.read()
        return self._src[rel]

    def tree(self, rel: str) -> ast.Module | None:
        """Parsed module, or None (with a recorded finding) on a syntax
        error — a file the passes can't see must not pass silently."""
        if rel not in self._ast:
            try:
                self._ast[rel] = ast.parse(self.source(rel))
            except SyntaxError as e:
                self._ast[rel] = None
                self.parse_errors.append(
                    Finding("syntax-error", rel, e.lineno or 0, str(e.msg))
                )
        return self._ast[rel]

    # ---------------------------------------------------------- pragmas
    def _pragma_map(self, rel: str) -> dict[int, tuple[set, bool, int]]:
        """line -> (rules, justified, pragma_line). A pragma covers its
        own line; a comment-line pragma also covers the comment block it
        opens and the first code line after it."""
        if rel in self._pragmas:
            return self._pragmas[rel]
        mapping: dict[int, tuple[set, bool, int]] = {}
        try:
            lines = self.source(rel).split("\n")
        except OSError:
            # Synthetic finding paths ("tpuflow", a missing README) have
            # no source to carry pragmas.
            self._pragmas[rel] = mapping
            return mapping
        i = 0
        while i < len(lines):
            m = _PRAGMA_RE.search(lines[i])
            if not m:
                i += 1
                continue
            rules = {
                r.strip() for r in m.group(1).split(",") if r.strip()
            }
            justified = bool(m.group(2))
            entry = (rules, justified, i + 1)
            mapping[i + 1] = entry
            if lines[i].lstrip().startswith("#"):
                # Comment-block pragma: extend through the rest of the
                # block onto the first code line.
                j = i + 1
                while j < len(lines) and (
                    not lines[j].strip()
                    or lines[j].lstrip().startswith("#")
                ):
                    mapping[j + 1] = entry
                    j += 1
                if j < len(lines):
                    mapping[j + 1] = entry
            i += 1
        self._pragmas[rel] = mapping
        return mapping

    def suppression(self, rel: str, line: int, rule: str):
        """(suppressed, pragma_finding_or_None) for a finding at
        rel:line of ``rule``."""
        entry = self._pragma_map(rel).get(line)
        if entry is None:
            return False, None
        rules, justified, pragma_line = entry
        if rule not in rules:
            return False, None
        if not justified:
            return True, Finding(
                "pragma-justification", rel, pragma_line,
                f"pragma disables {rule!r} without a justification — "
                "append `-- <why this finding is safe to silence>`",
            )
        return True, None


class Sink:
    """Finding collector that applies the pragma contract once."""

    def __init__(self, tree: Tree):
        self.tree = tree
        self.findings: list[Finding] = []
        self._pragma_findings: dict[tuple, Finding] = {}

    def emit(self, rel: str, line: int, rule: str, message: str) -> None:
        suppressed, pragma_finding = self.tree.suppression(rel, line, rule)
        if pragma_finding is not None:
            key = (pragma_finding.path, pragma_finding.line)
            self._pragma_findings[key] = pragma_finding
        if not suppressed:
            self.findings.append(Finding(rule, rel, line, message))

    def result(self) -> list[Finding]:
        return sorted(
            self.findings + list(self._pragma_findings.values()),
            key=lambda f: (f.path, f.line, f.rule),
        )


# ------------------------------------------------------------- helpers
def dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None
