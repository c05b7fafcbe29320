"""graftlint core: findings, source-tree loading, baselines, suppression.

Pure stdlib ON PURPOSE (the same contract as tools/supervise.py): the
linter's job includes proving that parts of the repo never import jax,
so it must itself run on a host where jax is broken or absent.  The
jax-free rule in imports.py covers this package too — a jax import
sneaking in here fails the lint it implements.

A :class:`Finding` carries a line number for humans but identifies
itself to the BASELINE by a line-free key (rule + path + message): an
unrelated edit above a baselined violation must not resurrect it, and a
new violation must not hide behind a stale line number.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

# Directories never scanned: tests exercise the rules with deliberate
# positive fixtures, chip_work/ holds unpacked copies of other commits for
# chip runs (.gitignore), and csrc/ is not python.
EXCLUDE_DIRS = {"tests", "__pycache__", "chip_work", ".git", ".claude",
                "csrc", "related", "node_modules"}

_SUPPRESS = re.compile(r"#\s*graftlint:\s*ignore(?:\[([a-z0-9_,\- ]+)\])?")


def repo_root() -> str:
    """The checkout root (this file lives at tools/graftlint/base.py)."""
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


@dataclass
class Finding:
    rule: str
    path: str          # repo-relative, forward slashes
    line: int          # 1-based; 0 = file-level
    message: str
    baselined: bool = False

    @property
    def identity(self) -> str:
        """Line-free baseline key."""
        return f"{self.rule}::{self.path}::{self.message}"

    def render(self) -> str:
        mark = "  (baselined)" if self.baselined else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}{mark}"

    def as_json(self) -> Dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message, "baselined": self.baselined}


@dataclass
class SourceFile:
    """One parsed python file.  ``tree`` is None when the file does not
    parse — the parse error itself becomes a finding, and every other
    rule skips the file."""

    path: str
    text: str
    tree: Optional[ast.AST] = None
    parse_error: Optional[str] = None
    lines: List[str] = field(default_factory=list)

    @classmethod
    def from_text(cls, path: str, text: str) -> "SourceFile":
        sf = cls(path=path, text=text, lines=text.splitlines())
        try:
            sf.tree = ast.parse(text, filename=path)
        except SyntaxError as e:
            sf.parse_error = f"{e.msg} (line {e.lineno})"
        return sf

    def suppressed(self, rule: str, line: int) -> bool:
        """``# graftlint: ignore`` (any rule) or ``# graftlint:
        ignore[rule-a, rule-b]`` on the finding's line suppresses it —
        the per-site escape hatch for a sanctioned violation; the
        baseline is the bulk one."""
        if 1 <= line <= len(self.lines):
            m = _SUPPRESS.search(self.lines[line - 1])
            if m:
                rules = m.group(1)
                if not rules:
                    return True
                return rule in [r.strip() for r in rules.split(",")]
        return False


class Tree:
    """The loaded source tree rules run over.

    ``files`` maps repo-relative posix paths to SourceFiles.  Tests
    build synthetic trees from string dicts (:func:`tree_from_sources`);
    the CLI loads the real checkout (:func:`load_tree`).
    """

    def __init__(self, files: Dict[str, SourceFile], root: str = ""):
        self.files = files
        self.root = root

    def exists(self, relpath: str) -> bool:
        if relpath in self.files:
            return True
        # Resolution must see repo files the scan skipped (nothing
        # currently — but a future exclude must not break import edges).
        return bool(self.root) and os.path.isfile(
            os.path.join(self.root, relpath))

    def parse_findings(self) -> List[Finding]:
        return [Finding("parse-error", sf.path, 0, sf.parse_error)
                for sf in self.files.values() if sf.parse_error]


def tree_from_sources(sources: Dict[str, str]) -> Tree:
    return Tree({p: SourceFile.from_text(p, s) for p, s in sources.items()})


def load_tree(root: Optional[str] = None) -> Tree:
    root = root or repo_root()
    files: Dict[str, SourceFile] = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d not in EXCLUDE_DIRS)
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            full = os.path.join(dirpath, name)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            try:
                with open(full, encoding="utf-8") as fh:
                    text = fh.read()
            except OSError as e:          # unreadable file: surface it
                files[rel] = SourceFile(path=rel, text="",
                                        parse_error=str(e))
                continue
            files[rel] = SourceFile.from_text(rel, text)
    return Tree(files, root=root)


# ------------------------------------------------------------- baseline

def load_baseline(path: str) -> List[str]:
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    if isinstance(data, dict):
        data = data.get("findings", [])
    if not isinstance(data, list) \
            or not all(isinstance(x, str) for x in data):
        raise ValueError(f"{path}: baseline must be a JSON list of "
                         "finding identities (or {'findings': [...]})")
    return data


def write_baseline(path: str, findings: List[Finding]) -> None:
    ids = sorted({f.identity for f in findings})
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"comment": "graftlint suppression baseline: known "
                              "pre-existing violations, keyed line-free "
                              "(rule::path::message).  Regenerate with "
                              "--write-baseline; shrink it, never grow "
                              "it.",
                   "findings": ids}, fh, indent=2)
        fh.write("\n")


def apply_baseline(findings: List[Finding], baseline: List[str]
                   ) -> List[Finding]:
    """Mark (not drop) baselined findings; callers decide whether
    baselined ones fail the run (--fail-on-new does not)."""
    known = set(baseline)
    for f in findings:
        f.baselined = f.identity in known
    return findings


# ------------------------------------------------ shared AST utilities

def walk_with_parents(tree: ast.AST):
    """Yield (node, ancestors) pairs, ancestors outermost-first."""
    stack: List[ast.AST] = []

    def rec(node):
        yield node, tuple(stack)
        stack.append(node)
        for child in ast.iter_child_nodes(node):
            yield from rec(child)
        stack.pop()

    yield from rec(tree)


def dotted_name(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute(Name('jax'), 'jit'); None for anything
    that is not a plain dotted name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None
