"""The one home for the jax manual-SPMD surface this codebase uses
(jax 0.9: ``jax.shard_map`` with ``check_vma``/``axis_names``,
``jax.lax.pcast``, the tracing axis environment).

Every shard_map/pcast call site in the package routes through here
(``analysis/source_check.py`` enforces it), so the package's defaults —
``check_vma=False``: the bodies here use collectives the checker cannot
type — and its single use of a private jax accessor live in one file.
"""

from __future__ import annotations

from typing import Optional, Set

import jax

__all__ = ["shard_map", "pcast", "bound_axis_names"]


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names: Optional[Set[str]] = None):
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def pcast(x, axes, to: str = "varying"):
    return jax.lax.pcast(x, axes, to=to)


def bound_axis_names() -> Set[str]:
    """Mesh axis names currently bound as MANUAL by an enclosing shard_map
    (empty when tracing/running outside one). The overlap layer uses this to
    refuse a nested shard_map — e.g. a TP layer invoked inside the compiled
    pipeline engine's manual "pipe" region, where opening a second manual
    region would fail at trace time."""
    from jax._src.core import get_axis_env  # no public accessor in jax 0.9

    return set(get_axis_env().axis_sizes)
