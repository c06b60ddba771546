"""Measured (not guessed) comm/compute overlap.

Two measurement paths, in order of fidelity:

- :func:`overlap_fraction_from_trace` — the ground truth on real
  hardware: walk a chrome trace (the profiler's artifact), intersect the
  collective intervals with the compute intervals, and report the
  fraction of collective wall-time that ran UNDER compute. This is the
  literal "collective time ∧ compute time" estimator.
- :func:`hidden_comm_seconds` — the analytic bound for when only HLO
  byte counts and a measured step time exist (CPU virtual
  meshes can't produce a truthful device trace): ring-decomposed bytes
  are overlappable by construction, hidden up to the compute time
  actually available.

Whichever path produced the number, it lands on the step's
:class:`~paddle_tpu.telemetry.TracedProgram` via
``set_overlap_fraction`` so StepMeter/prometheus export it.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = ["overlap_fraction_from_trace", "hidden_comm_seconds",
           "COLLECTIVE_EVENT_RE"]

# names XLA / the profiler give collective work on a device track
COLLECTIVE_EVENT_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|all_gather|all_reduce|reduce_scatter|ppermute|psum", re.IGNORECASE)


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _intersection(span: Tuple[float, float],
                  merged: List[Tuple[float, float]]) -> float:
    s, e = span
    covered = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        covered += min(e, me) - max(s, ms)
    return covered


def overlap_fraction_from_trace(events: Iterable[Dict]) -> Optional[float]:
    """Fraction of collective wall-time hidden under concurrent compute,
    from chrome-trace ``"ph": "X"`` events (``ts``/``dur`` in us).

    Collective events match :data:`COLLECTIVE_EVENT_RE` by name; every
    other duration event on a non-telemetry track counts as compute.
    Returns None when the trace has no collective events (nothing to
    hide)."""
    collectives: List[Tuple[float, float]] = []
    compute: List[Tuple[float, float]] = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        dur = float(ev.get("dur", 0) or 0)
        if dur <= 0:
            continue
        ts = float(ev.get("ts", 0) or 0)
        span = (ts, ts + dur)
        if COLLECTIVE_EVENT_RE.search(str(ev.get("name", ""))):
            collectives.append(span)
        elif ev.get("cat") != "telemetry":
            compute.append(span)
    if not collectives:
        return None
    merged = _merge(compute)
    total = sum(e - s for s, e in collectives)
    hidden = sum(_intersection(c, merged) for c in collectives)
    return min(1.0, hidden / total) if total > 0 else None


def hidden_comm_seconds(overlappable_s: float, exposed_s: float,
                        compute_s: float) -> Dict[str, float]:
    """Analytic overlap accounting for a step whose collectives split into
    ring-decomposed (overlappable-by-construction) and boundary (exposed)
    time, against ``compute_s`` of schedulable compute.

    Returns ``{hidden_s, exposed_s, overlap_fraction}`` where
    ``hidden_s = min(overlappable_s, compute_s)`` — a transfer can only
    hide under compute that exists — and ``overlap_fraction`` is hidden
    time over TOTAL collective time (the same ∧-estimator the trace path
    computes)."""
    overlappable_s = max(0.0, float(overlappable_s))
    exposed_s = max(0.0, float(exposed_s))
    compute_s = max(0.0, float(compute_s))
    hidden = min(overlappable_s, compute_s)
    total = overlappable_s + exposed_s
    frac = (hidden / total) if total > 0 else None
    return {"hidden_s": hidden,
            "exposed_s": exposed_s + (overlappable_s - hidden),
            "overlap_fraction": frac}
