"""Measured compile telemetry: flight-recorder events + runtime gauges for
every AOT compile, and the ``cost_analysis()`` FLOP count.

Event protocol (the flight recorder narrates compile time the same way it
narrates checkpoints):

- ``compile_begin``  — fingerprint known, wall-clock starts; covers both
  the XLA compile and a persistent-cache deserialize.
- ``compile_end``    — ``mode`` ∈ ``cold`` (XLA compiled) | ``warm``
  (deserialized from the :class:`~paddle_tpu.compile.cache.ExecutableCache`),
  seconds, fingerprint, cost-analysis FLOPs, and whether the cold result
  was persisted.

Gauges/counters exported through ``telemetry.prometheus_text()``:
``compile_cold_total`` / ``compile_warm_total``, ``compile_seconds_last``,
``compile_seconds_total`` (the recoverable wall-clock the cache exists to
amortize), ``compile_cost_flops_last``.

:func:`flops_of` pulls XLA's own executed-FLOP estimate off a compiled
executable.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["flops_of", "compile_begin", "compile_end", "bump_counter",
           "cache_event", "remat_diagnostics"]


def flops_of(compiled) -> Optional[float]:
    """XLA ``cost_analysis()`` FLOPs of a compiled executable (one call =
    one train step for TrainStep programs); None when the backend has no
    cost model. Works on deserialized executables too."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = ca.get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:
        return None


def _telemetry():
    from .. import telemetry

    return telemetry


def bump_counter(name: str, value: float = 1.0) -> None:
    """Swallow-all counter bump — the one shared 'telemetry never breaks
    the compile path' seam for the whole package."""
    try:
        _telemetry().bump(name, value)
    except Exception:
        pass


def cache_event(name: str, **data) -> None:
    """Swallow-all ``compile_cache`` flight-recorder event (drops,
    evictions, orphan sweeps, serialize-unsupported, unsafe-topology)."""
    try:
        _telemetry().record_event("compile_cache", name, **data)
    except Exception:
        pass


def compile_begin(name: str, fingerprint: str) -> None:
    try:
        _telemetry().record_event("compile_begin", name,
                                  fingerprint=fingerprint)
    except Exception:
        pass


def compile_end(name: str, fingerprint: str, mode: str, seconds: float,
                flops: Optional[float] = None,
                persisted: Optional[bool] = None) -> None:
    """Record one finished compile (``mode`` = ``cold`` | ``warm``)."""
    try:
        t = _telemetry()
        t.record_event("compile_end", name, fingerprint=fingerprint,
                       mode=mode, seconds=round(seconds, 4), flops=flops,
                       persisted=persisted)
        t.bump(f"compile_{mode}_total")
        t.bump("compile_seconds_total", seconds)
        t.set_gauge("compile_seconds_last", seconds)
        if flops:
            t.set_gauge("compile_cost_flops_last", flops)
    except Exception:
        pass


def remat_diagnostics(name: str, fingerprint: str, count: int) -> None:
    """Record the SPMD partitioner's involuntary-remat warning count for
    one cold compile (captured by the AOT service, priced fully by the
    shardlint ``involuntary-remat`` rule): a nonzero
    ``compile_partitioner_remats_last`` gauge is the cheap always-on
    tripwire; ``paddle_tpu.analysis.lint`` is the detailed follow-up."""
    try:
        t = _telemetry()
        t.record_event("compile_diagnostics", name,
                       fingerprint=fingerprint, partitioner_remats=count)
        t.bump("compile_partitioner_remats_total", count)
        t.set_gauge("compile_partitioner_remats_last", count)
    except Exception:
        pass
