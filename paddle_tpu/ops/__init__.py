"""Low-level op implementations: XLA reference paths + Pallas TPU kernels."""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # a backend that fails to initialise raises here: it must not read as
    # "not a TPU, take the XLA path"
    return jax.devices()[0].platform == "tpu"


def pallas_interpret_mode() -> bool:
    """True when the ``pallas_interpret`` flag forces the kernels through the
    Pallas interpreter (CPU testing of the TPU kernel paths)."""
    from ..framework.flags import get_flags

    return bool(get_flags("pallas_interpret")["pallas_interpret"])


_gspmd_trace: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_gspmd_trace", default=False)


@contextlib.contextmanager
def gspmd_program():
    """Trace scope for a program GSPMD will partition over a mesh that is
    NOT the hybrid mesh (``ServingEngine``'s TP / CP meshes).  GSPMD cannot
    partition a Mosaic custom call and no shard_map wrapper knows that
    mesh, so inside the scope every kernel takes the partitionable XLA path
    and says so with a ``kernel_fallback(<flag>, "no_mesh")`` event."""
    token = _gspmd_trace.set(True)
    try:
        yield
    finally:
        _gspmd_trace.reset(token)


def pallas_eligible(flag_name: str) -> bool:
    """True when the Pallas path should be used: the flag is on and either
    the backend is a TPU or interpreter mode is forced.

    With a live hybrid mesh the kernels compose through the shard_map
    wrappers in ``ops/sharded.py``; with none, the program being traced is
    a one-device program (plain ``TrainStep`` / ``generate()`` /
    ``ServingEngine``) however many chips the host holds, and it gets the
    local kernel — the same program on a one-chip and a four-chip host.
    A partitioned program built outside the hybrid mesh declares itself
    with :func:`gspmd_program`; one that does not is refused by jax at
    lowering ("Mosaic kernels cannot be automatically partitioned"), never
    run quietly without its kernels."""
    from ..framework.flags import get_flags

    interpret = pallas_interpret_mode()
    if not interpret and not _on_tpu():
        return False
    if not get_flags(flag_name)[flag_name]:
        return False
    if _gspmd_trace.get() and not interpret:  # interpreted kernels are jnp
        from ..telemetry import kernel_fallback

        kernel_fallback(flag_name, "no_mesh")
        return False
    return True


def pallas_mode(flag_name: str):
    """Kernel dispatch resolution shared by the functional wrappers:
    ``None`` (XLA path) | ``("mesh", mesh, interpret)`` (shard_map wrapper)
    | ``("local", None, interpret)`` (direct kernel)."""
    if not pallas_eligible(flag_name):
        return None
    from .sharded import active_mesh

    interp = pallas_interpret_mode()
    mesh = active_mesh()
    if mesh is not None:
        return ("mesh", mesh, interp)
    return ("local", None, interp)
