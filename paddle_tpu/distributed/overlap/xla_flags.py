"""Single entry point for the XLA latency-hiding-scheduler flags.

The ring decomposition and the bucketed grad comm only pay off when XLA
actually schedules the collectives asynchronously under compute. On TPU
that is the latency-hiding scheduler + async collective fusion, enabled
by ``XLA_FLAGS`` that must be set BEFORE the PJRT backend initializes —
scattering them across launch scripts is how configs silently lose them,
so they live here and every launcher calls one function.

CPU safety: the ``--xla_tpu_*`` flags are unknown to the CPU backend
(XLA aborts the process on unknown flags), so on any non-TPU target this
module applies NOTHING. ``PADDLE_TPU_XLA_OVERLAP_FLAGS=0`` is the kill
switch (the test suite pins it so tier-1 stays deterministic); the
applied set feeds the AOT compile fingerprint so toggling flags can
never hit a stale cached executable.
"""

from __future__ import annotations

import os
import sys
from typing import Optional, Tuple

__all__ = ["overlap_xla_flags", "apply_overlap_xla_flags",
           "applied_overlap_flags", "effective_overlap_flags",
           "OVERLAP_TPU_FLAGS"]

# conservative, public latency-hiding set (jax/XLA TPU guidance; the
# paper's collective-matmul pass rides the same scheduler machinery)
OVERLAP_TPU_FLAGS: Tuple[str, ...] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_collective_permute=true",
)

_applied: Tuple[str, ...] = ()


def _flags_enabled() -> bool:
    return os.environ.get("PADDLE_TPU_XLA_OVERLAP_FLAGS", "1") not in (
        "0", "false")


def _target_platform(platform: Optional[str] = None) -> str:
    """Best available answer for which backend will initialize. Explicit
    argument > initialized backend > JAX_PLATFORMS env > "cpu" (the safe
    default: applying nothing is always correct, applying TPU flags to a
    CPU backend is an abort)."""
    if platform:
        return platform.lower()
    if "jax" in sys.modules:
        try:
            import jax
            from jax._src import xla_bridge

            if getattr(xla_bridge, "_backends", None):
                return jax.default_backend()
        except Exception:
            pass
    env = os.environ.get("JAX_PLATFORMS", "") or os.environ.get(
        "JAX_PLATFORM_NAME", "")
    return (env.split(",")[0].strip() or "cpu").lower()


def _backend_initialized() -> bool:
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:
        return False


def overlap_xla_flags(platform: Optional[str] = None) -> Tuple[str, ...]:
    """The flag set for ``platform`` (no mutation): TPU gets the
    latency-hiding set, everything else gets nothing."""
    if not _flags_enabled():
        return ()
    return OVERLAP_TPU_FLAGS if _target_platform(platform) == "tpu" else ()


def _env_flag_keys() -> set:
    """Keys already set in ``XLA_FLAGS`` (exact token keys, so a key that
    is a prefix of another key — e.g. ``…async_collective_fusion`` vs
    ``…async_collective_fusion_fuse_all_gather`` — never false-positives
    the way substring matching does)."""
    return {tok.split("=", 1)[0]
            for tok in os.environ.get("XLA_FLAGS", "").split() if tok}


def apply_overlap_xla_flags(platform: Optional[str] = None) -> Tuple[str, ...]:
    """Fold the overlap flags into ``XLA_FLAGS`` (idempotent; flags whose
    key is already present — user override — are left untouched and NOT
    counted as applied). Returns the tuple actually added. Call BEFORE
    the first jax device access; once the backend is up this warns and
    applies nothing, because PJRT has already parsed the env."""
    global _applied
    flags = overlap_xla_flags(platform)
    if not flags:
        return ()
    present = _env_flag_keys()
    if _backend_initialized():
        missing = [f for f in flags if f.split("=", 1)[0] not in present]
        if missing:
            import logging

            logging.getLogger("paddle_tpu.distributed").warning(
                "apply_overlap_xla_flags() called after jax backend init — "
                "%d flag(s) NOT applied (set XLA_FLAGS before importing "
                "jax, or call this earlier): %s", len(missing), missing)
        _applied = ()
        return _applied
    add = [f for f in flags if f.split("=", 1)[0] not in present]
    if add:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + " ".join(add)).strip()
    _applied = tuple(add)
    try:
        from ... import telemetry

        telemetry.record_event("overlap", "xla_flags_applied",
                               flags=list(add), already_present=len(flags)
                               - len(add))
    except Exception:
        pass
    return _applied


def applied_overlap_flags() -> Tuple[str, ...]:
    """What :func:`apply_overlap_xla_flags` actually put into the
    environment this process (bench detail). NOT the fingerprint input —
    fingerprints use :func:`effective_overlap_flags`, which also sees
    flags inherited through the environment."""
    return _applied


def effective_overlap_flags() -> Tuple[str, ...]:
    """The overlap-relevant flag TOKENS effective for this process, read
    from ``XLA_FLAGS`` itself — the fingerprint input. Env-derived (not
    the process-local ``_applied``) so a supervisor-relaunched child that
    inherits the parent's XLA_FLAGS fingerprints identically to the
    parent, and a user override (same key, different value) fingerprints
    differently from the stock set."""
    keys = {f.split("=", 1)[0] for f in OVERLAP_TPU_FLAGS}
    return tuple(sorted(
        tok for tok in os.environ.get("XLA_FLAGS", "").split()
        if tok.split("=", 1)[0] in keys))
