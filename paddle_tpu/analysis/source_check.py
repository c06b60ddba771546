"""Repo-source AST check: every ``shard_map``/``pcast`` call site must
route through the :mod:`paddle_tpu.framework.jax_compat` seam.

The seam keeps the package's manual-SPMD defaults (``check_vma=False``)
and its one private jax accessor in a single file, so the next jax
surface change is a one-file edit.  A direct ``jax.shard_map`` call
anywhere else silently takes jax's own defaults instead — an invariant
this machine check holds as part of the tier-1 ``analysis`` suite.

Flags, per file (excluding ``framework/jax_compat.py`` itself):

- ``from jax.experimental.shard_map import ...`` / ``import
  jax.experimental.shard_map``;
- ``from jax.experimental import shard_map``;
- attribute access ``jax.shard_map`` / ``jax.experimental.shard_map``;
- attribute access ``jax.lax.pcast`` (or ``lax.pcast`` off a
  ``from jax import lax`` binding).
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional

from .findings import Finding, Severity

__all__ = ["check_jax_compat_seam", "check_source_text"]

_SEAM_FILE = os.path.join("framework", "jax_compat.py")

_FIX = ("route through paddle_tpu.framework.jax_compat "
        "(shard_map / pcast) so the manual-SPMD defaults stay "
        "single-homed")


def _attr_chain(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _SeamVisitor(ast.NodeVisitor):
    def __init__(self, relpath: str):
        self.relpath = relpath
        self.hits: List[Finding] = []

    def _hit(self, node: ast.AST, what: str) -> None:
        self.hits.append(Finding(
            rule="jax-compat-seam",
            severity=Severity.ERROR,
            subject=what,
            message=(f"direct {what} bypasses the framework/jax_compat "
                     "seam"),
            fix=_FIX,
            source=f"{self.relpath}:{getattr(node, 'lineno', 0)}",
        ))

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        if mod.startswith("jax.experimental.shard_map"):
            self._hit(node, f"from {mod} import")
        elif mod == "jax.experimental" and \
                any(a.name == "shard_map" for a in node.names):
            self._hit(node, "from jax.experimental import shard_map")
        elif mod == "jax" and any(a.name == "shard_map"
                                  for a in node.names):
            self._hit(node, "from jax import shard_map")
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name.startswith("jax.experimental.shard_map"):
                self._hit(node, f"import {a.name}")
        self.generic_visit(node)

    _CHAIN_TARGETS = ("jax.shard_map", "jax.experimental.shard_map",
                      "experimental.shard_map", "jax.lax.pcast",
                      "lax.pcast")

    def visit_Attribute(self, node: ast.Attribute) -> None:
        chain = _attr_chain(node)
        # prefix match: the qualified spelling
        # jax.experimental.shard_map.shard_map(...) must hit too, not
        # just the bare module attribute
        for target in self._CHAIN_TARGETS:
            if chain == target or chain.startswith(target + "."):
                self._hit(node, chain)
                break
        # don't generic_visit: the chain's inner Attributes would re-match
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, ast.Attribute):
                self.visit(child)


def check_source_text(source: str, relpath: str = "<string>"
                      ) -> List[Finding]:
    """Seam-check one source string (unit-testable core)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(
            rule="jax-compat-seam", severity=Severity.WARNING,
            subject="unparseable source",
            message=f"could not parse {relpath}: {e}",
            source=relpath)]
    v = _SeamVisitor(relpath)
    v.visit(tree)
    return v.hits


def check_jax_compat_seam(root: Optional[str] = None) -> List[Finding]:
    """Walk every ``.py`` under ``root`` (default: the installed
    ``paddle_tpu`` package) and seam-check it; the seam module itself is
    the one allowed call site."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            if rel.replace(os.sep, "/") == _SEAM_FILE.replace(os.sep, "/"):
                continue
            try:
                with open(path, encoding="utf-8", errors="replace") as f:
                    src = f.read()
            except OSError:
                continue
            findings.extend(check_source_text(src, rel))
    return findings
