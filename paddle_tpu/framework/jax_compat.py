"""The one home for the jax manual-SPMD surface this codebase uses
(jax 0.9: ``jax.shard_map`` with ``check_vma``/``axis_names``,
``jax.lax.pcast``, the tracing axis environment).

Every shard_map/pcast call site in the package routes through here
(``analysis/source_check.py`` enforces it), so the package's defaults —
``check_vma=False``: the bodies here use collectives the checker cannot
type — and its uses of private jax accessors live in one file.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Set

import jax

__all__ = ["shard_map", "pcast", "bound_axis_names", "default_layout",
           "persistent_cache_off"]


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


def default_layout(x: jax.Array) -> bool:
    """Whether ``x`` lies in the layout its backend gives an array of its
    shape and type by default (not in one a compiled program chose)."""
    from jax._src.interpreters.pxla import is_default_layout  # no public
    # test in jax 0.9

    return is_default_layout(x.format.layout, x.sharding, x.aval)


@contextlib.contextmanager
def persistent_cache_off():
    """Compile with JAX's persistent compilation cache off.  jax 0.9 hands
    back the results of an executable it loaded from that cache in the
    default layout, whatever layout the program was compiled to give them:
    a program whose work is its result's layout (``jax.device_put`` to a
    ``Format``) has to be compiled afresh."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # it decides once whether it is on
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
