"""Rule ``host-sync``: implicit device→host transfers inside step
functions — each one stalls the device queue for a full round trip, and
inside a train step turns an async dispatch loop into lock-step.

Two detection layers:

- **source walk** (AST over the python source of the step/loss functions
  the linter was handed): ``float(x)`` / ``int(x)`` / ``bool(x)`` on a
  non-literal, ``np.asarray`` / ``np.array`` on anything, ``.numpy()`` /
  ``.item()`` / ``.tolist()`` method calls, and ``jax.device_get``.
  Under ``jit`` these either crash at trace time (concretization) or —
  worse — silently sync per step on the eager path; the AST sees them
  before any trace does.  When a function's source is unavailable
  (builtins, C callables) it is skipped.
- **jaxpr walk**: host-callback primitives (``pure_callback``,
  ``io_callback``, ``debug_callback``) and infeed/outfeed ops recorded in
  the traced program — transfers that survived into the compiled step.

Severity: warning (a deliberate ``debug_callback`` during bring-up is
legitimate; the baseline pins accepted ones).

Scoped exemption: some host syncs are the DESIGN — the snapshot capture
path (:mod:`paddle_tpu.distributed.checkpoint.snapshot`) device-gets
shards into host RAM every ``PADDLE_TPU_SNAP_EVERY`` steps on purpose.
Functions decorated ``@host_sync_ok`` (:mod:`..annotations`) are skipped,
both when handed to the linter directly (object attribute) and when they
appear as decorated inner defs inside a linted function's source (AST
decorator match) — while undecorated strays in step functions keep
flagging.  The exemption is per-function and carries its justification on
the object; it is narrower than a baseline entry, which pins one emitted
finding rather than blessing a code path.
"""

from __future__ import annotations

import ast
import inspect
import textwrap
from typing import List, Optional

from ..annotations import host_sync_ok, is_host_sync_ok  # noqa: F401
from ..findings import Finding, Severity
from ..program import ProgramArtifacts
from . import rule

_CAST_BUILTINS = {"float", "int", "bool"}
_SYNC_METHODS = {"numpy", "item", "tolist"}
_SYNC_NP_FUNCS = {"asarray", "array"}
# jax 0.9 lowers jax.debug.print to its own ``debug_print`` primitive
_CALLBACK_PRIMS = ("callback", "debug_print", "infeed", "outfeed",
                   "device_get")


def _source_of(fn) -> Optional[str]:
    try:
        return textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return None


def _parse(src: str) -> Optional[ast.AST]:
    for candidate in (src, f"({src.strip().rstrip(',')})"):
        try:
            return ast.parse(candidate)
        except SyntaxError:
            continue
    return None


def _attr_chain(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _ast_marked_ok(node: ast.AST) -> bool:
    """FunctionDef carrying a ``@host_sync_ok`` decorator (bare or
    called)?  Matches the terminal name so both ``@host_sync_ok`` and
    ``@annotations.host_sync_ok(reason=...)`` spellings count."""
    for dec in getattr(node, "decorator_list", ()):
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = target.attr if isinstance(target, ast.Attribute) else \
            getattr(target, "id", None)
        if name == "host_sync_ok":
            return True
    return False


class _HostSyncVisitor(ast.NodeVisitor):
    def __init__(self, fn_name: str, filename: str):
        self.fn_name = fn_name
        self.filename = filename
        self.hits: List[Finding] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if _ast_marked_ok(node):
            return  # scoped exemption: skip the whole decorated subtree
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def _hit(self, node: ast.AST, what: str, detail: str) -> None:
        line = getattr(node, "lineno", 0)
        self.hits.append(Finding(
            rule="host-sync",
            severity=Severity.WARNING,
            subject=f"{what} in {self.fn_name}",
            message=(f"{detail} forces a device->host transfer inside a "
                     "step function — one queue stall per call"),
            fix="keep the value on device (jnp ops) or move the read "
                "outside the step; for diagnostics use the fused probe "
                "pattern (HealthGuard) that resolves lagged",
            source=f"{self.filename}:{line}" if self.filename else None,
        ))

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Name) and func.id in _CAST_BUILTINS:
            if node.args and not isinstance(node.args[0], ast.Constant):
                self._hit(node, f"{func.id}()",
                          f"builtin {func.id}() on a computed value")
        elif isinstance(func, ast.Attribute):
            chain = _attr_chain(func)
            if func.attr in _SYNC_METHODS and not node.args:
                self._hit(node, f".{func.attr}()",
                          f"method .{func.attr}()")
            elif chain in ("jax.device_get",):
                self._hit(node, "jax.device_get", "jax.device_get")
            elif func.attr in _SYNC_NP_FUNCS and chain.split(".")[0] in (
                    "np", "numpy"):
                self._hit(node, chain, f"{chain} on a traced value")
        self.generic_visit(node)


@rule("host-sync")
def check_host_sync(art: ProgramArtifacts, config: dict) -> List[Finding]:
    findings: List[Finding] = []
    for fn in art.source_fns:
        if is_host_sync_ok(fn):
            continue  # scoped exemption carried on the object
        src = _source_of(fn)
        if src is None:
            continue
        tree = _parse(src)
        if tree is None:
            continue
        name = getattr(fn, "__name__", "step_fn")
        filename = ""
        try:
            filename = inspect.getsourcefile(fn) or ""
            for anchor in ("paddle_tpu/", "tests/"):
                i = filename.find(anchor)
                if i >= 0:
                    filename = filename[i:]
                    break
        except TypeError:
            pass
        v = _HostSyncVisitor(name, filename)
        v.visit(tree)
        findings.extend(v.hits)

    for prim_name, params in art.jaxpr_prims:
        if any(k in prim_name for k in _CALLBACK_PRIMS):
            cb = params.get("callback")
            detail = getattr(cb, "__name__", prim_name) if cb else prim_name
            findings.append(Finding(
                rule="host-sync",
                severity=Severity.WARNING,
                subject=f"host callback primitive {prim_name}",
                message=(f"traced program contains host callback "
                         f"{detail!r} — a device->host round trip baked "
                         "into the compiled step"),
                fix="remove the callback from the hot path or gate it "
                    "behind a debug flag",
                context={"primitive": prim_name},
            ))
    return findings
