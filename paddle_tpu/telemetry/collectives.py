"""Collective tracing + analytic ICI cost model.

Two recording modes, matching how collectives actually reach the hardware:

- **eager**: ``distributed.all_reduce(x)`` & friends each execute one jitted
  shard_map program — ``record_collective`` is called per execution from
  ``communication._run`` with the payload shape in hand.
- **trace-time**: a collective issued while tracing someone else's jit
  (tensor is a ``jax.core.Tracer``) executes whenever the enclosing program
  runs — the record is tagged ``trace_time: True`` and counted once per
  trace. Compiled engines (1F1B pipeline, DistributedTrainStep's implicit
  grad psum) instead register a :class:`TracedProgram` — the analytic
  per-step collective profile — and bump its execution counter per call, so
  executed bytes stay accurate without re-tracing.

Wire cost uses the standard ring formulas: all-reduce moves ``2(n-1)/n * S`` bytes per chip,
gather/scatter ``(n-1)/n * S``, permute ``S``; the time estimate prices
those bytes at the chip's public one-way ICI bandwidth.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

from . import runtime
from .recorder import record_event

__all__ = ["record_collective", "collective_stats", "ici_cost_estimate",
           "ring_wire_bytes", "TracedProgram", "register_traced_program",
           "PEAK_TFLOPS", "ICI_GBPS_ONEWAY", "PEAK_HBM_GBPS", "chip_lookup"]

# ---------------------------------------------------------------------------
# chip tables (single home — chip_smoke.py and StepMeter price against these)
#
# Keyed by substrings of ``device.device_kind`` ("TPU v5 lite", "TPU v5e",
# "TPU v5p", "TPU v4", "TPU v6 lite" / "TPU v6e").  Sources:
# - bf16 peak and HBM bandwidth: Google Cloud TPU documentation, the
#   per-generation system-architecture pages ("TPU v5e": 197 TFLOP/s bf16,
#   819 GB/s; "TPU v5p": 459, 2765; "TPU v4": 275, 1228; "TPU v6e": 918,
#   1640).
# - ICI: jax-ml.github.io/scaling-book, TPU chapter — one-directional
#   bandwidth PER LINK (v5e 4.5e10 B/s; a v5e chip has four such links,
#   which is the documentation's 1,600 Gbit/s per chip both ways).
# - the "cpu" rows are nominal placeholders so a CPU test run can price a
#   step at all; they describe no machine, and :func:`chip_lookup` reaches
#   them only for ``device.platform == "cpu"``.

# chip kind → peak bf16 TFLOP/s
PEAK_TFLOPS = {
    "v5 lite": 197.0, "v5e": 197.0, "v5litepod": 197.0,
    "v5p": 459.0, "v4": 275.0, "v6e": 918.0, "v6": 918.0,
    "cpu": 0.5,
}

# chip kind → per-link one-directional ICI bandwidth, GB/s
ICI_GBPS_ONEWAY = {
    "v5 lite": 45.0, "v5e": 45.0, "v5litepod": 45.0,
    "v5p": 90.0, "v4": 45.0, "v6e": 90.0, "v6": 90.0,
    "cpu": 10.0,
}

# chip kind → peak HBM bandwidth GB/s
PEAK_HBM_GBPS = {
    "v5 lite": 819.0, "v5e": 819.0, "v5litepod": 819.0,
    "v5p": 2765.0, "v4": 1228.0, "v6e": 1640.0, "v6": 1640.0,
    "cpu": 50.0,
}


def chip_lookup(device, table: dict) -> float:
    """Match device_kind substrings against a chip table ('v5 lite' vs
    'v5e' naming quirks live HERE, once).  A device the table does not
    know is an error, never a default: a number priced against the wrong
    peak is worse than no number."""
    if device.platform == "cpu":
        return table["cpu"]
    kind = device.device_kind.lower()
    for key, val in table.items():
        if key != "cpu" and key in kind:
            return val
    raise KeyError(
        f"device_kind {device.device_kind!r} (platform "
        f"{device.platform!r}) is not in the chip table; add its peak, "
        "with the source, to paddle_tpu/telemetry/collectives.py")


# ring-cost wire factor per participant count n
_RING_FACTORS = {
    "all_reduce": lambda n: 2.0 * (n - 1) / n,
    "reduce": lambda n: 2.0 * (n - 1) / n,          # lowered to all_reduce
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "broadcast": lambda n: (n - 1) / n,
    "scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "psum": lambda n: 2.0 * (n - 1) / n,
}


def ring_wire_bytes(kind: str, nbytes: int, group_size: int) -> float:
    """Per-chip wire bytes for one collective over a ring of group_size.
    A single-participant group moves nothing over the wire."""
    n = int(group_size)
    if n <= 1:
        return 0.0
    factor = _RING_FACTORS.get(kind, lambda n: 1.0)(n)
    return factor * float(nbytes)


_ici_gbps_cache: Optional[float] = None


def _ici_gbps() -> float:
    # the chip is fixed for the process lifetime: resolve jax.devices()
    # once, not per eager collective (stays lazy — resolving at import
    # would force backend init)
    global _ici_gbps_cache
    if _ici_gbps_cache is None:
        import jax

        _ici_gbps_cache = chip_lookup(jax.devices()[0], ICI_GBPS_ONEWAY)
    return _ici_gbps_cache


def ici_cost_estimate(kind: str, nbytes: int, group_size: int,
                      ici_gbps: Optional[float] = None) -> dict:
    """Analytic {wire_bytes, est_s} for one collective call."""
    wire = ring_wire_bytes(kind, nbytes, group_size)
    bw = (ici_gbps if ici_gbps is not None else _ici_gbps()) * 1e9
    return {"wire_bytes": wire, "est_s": wire / bw if bw > 0 else 0.0}


# ---------------------------------------------------------------------------
# aggregate registry

class _Agg:
    __slots__ = ("calls", "trace_records", "bytes", "wire_bytes", "est_s")

    def __init__(self):
        self.calls = 0          # executed collectives (eager + program execs)
        self.trace_records = 0  # trace-time records (once per trace)
        self.bytes = 0.0        # payload bytes of executed collectives
        self.wire_bytes = 0.0
        self.est_s = 0.0


_aggs: Dict[str, _Agg] = {}
_agg_lock = threading.Lock()


def _agg(kind: str) -> _Agg:
    # caller holds _agg_lock
    a = _aggs.get(kind)
    if a is None:
        a = _aggs[kind] = _Agg()
    return a


def record_collective(kind: str, nbytes: int, axes: Sequence[str] = (),
                      group_size: int = 1, trace_time: bool = False,
                      source: str = "eager") -> None:
    """Record one collective call (see module docstring for modes)."""
    if not runtime.enabled():
        return
    cost = ici_cost_estimate(kind, nbytes, group_size)
    with _agg_lock:
        a = _agg(kind)
        if trace_time:
            a.trace_records += 1
        else:
            a.calls += 1
            a.bytes += nbytes
            a.wire_bytes += cost["wire_bytes"]
            a.est_s += cost["est_s"]
    record_event("collective", kind, nbytes=int(nbytes),
                 axes=list(axes), group_size=int(group_size),
                 wire_bytes=int(cost["wire_bytes"]),
                 ici_est_s=round(cost["est_s"], 9),
                 trace_time=bool(trace_time), source=source)


def collective_stats() -> Dict[str, dict]:
    """Aggregate per-kind stats: executed calls, payload/wire bytes, the
    analytic ICI seconds, and trace-time record counts."""
    with _agg_lock:
        return {k: {"calls": a.calls, "trace_records": a.trace_records,
                    "bytes": int(a.bytes), "wire_bytes": int(a.wire_bytes),
                    "ici_est_s": a.est_s}
                for k, a in _aggs.items()}


def total_collective_bytes() -> float:
    with _agg_lock:
        return sum(a.bytes for a in _aggs.values())


# ---------------------------------------------------------------------------
# compiled programs with known collective profiles

class TracedProgram:
    """Analytic per-execution collective profile of one compiled program
    (e.g. the 1F1B pipeline step: 2 ppermutes x T ticks + 1 scalar psum).
    ``record_execution()`` folds the profile into the global aggregates and
    bumps the execution counter — the 'counter of executions' for
    collectives that only exist inside a jit."""

    def __init__(self, tag: str,
                 collectives: Sequence[dict]):  # {kind, nbytes, group_size, count}
        self.tag = tag
        self.collectives = [dict(c) for c in collectives]
        self.executions = 0
        # measured comm/compute overlap: the fraction of this program's
        # collective wall-time hidden under concurrent compute. None until
        # someone MEASURES it (chrome-trace interval intersection or the
        # HLO-bytes analytic bound — distributed/overlap/measure.py);
        # never guessed here.
        self.overlap_fraction: Optional[float] = None
        self._overlap_source: Optional[str] = None
        # profile is static: price it once, not per step (and never under
        # the aggregate lock — ici_cost_estimate may resolve jax.devices())
        self._per_exec = []
        for c in self.collectives:
            n = int(c.get("count", 1))
            cost = ici_cost_estimate(c["kind"], int(c["nbytes"]),
                                     int(c.get("group_size", 1)))
            self._per_exec.append(
                (c["kind"], n, int(c["nbytes"]) * n,
                 cost["wire_bytes"] * n, cost["est_s"] * n))

    def set_overlap_fraction(self, fraction: float,
                             source: str = "measured") -> None:
        """Attach a MEASURED comm/compute overlap fraction (collective
        time ∧ compute time over collective time) to this program —
        exported through StepMeter summaries, the prometheus gauge, and
        bench detail. ``source`` names the measurement path
        ("chrome_trace" | "hlo_bytes" | custom)."""
        self.overlap_fraction = max(0.0, min(1.0, float(fraction)))
        self._overlap_source = source
        runtime.set_gauge("overlap_fraction_last", self.overlap_fraction)
        record_event("overlap", self.tag,
                     overlap_fraction=round(self.overlap_fraction, 4),
                     source=source)

    def wire_bytes_per_execution(self) -> float:
        return sum(w for _, _, _, w, _ in self._per_exec)

    def record_execution(self) -> None:
        if not runtime.enabled():
            return
        self.executions += 1
        with _agg_lock:
            for kind, n, nbytes, wire, est in self._per_exec:
                a = _agg(kind)
                a.calls += n
                a.bytes += nbytes
                a.wire_bytes += wire
                a.est_s += est
        runtime.bump(f"traced_program_executions_total:{self.tag}")
        record_event("collective_program", self.tag,
                     executions=self.executions,
                     collectives=self.collectives, trace_time=True,
                     source="compiled")


_programs: Dict[str, TracedProgram] = {}


def register_traced_program(tag: str, collectives: Sequence[dict]) -> TracedProgram:
    """Register (or replace) a compiled program's analytic collective
    profile; the registration itself is recorded as a trace-time event."""
    prog = TracedProgram(tag, collectives)
    _programs[tag] = prog
    if runtime.enabled():
        with _agg_lock:
            for c in prog.collectives:
                _agg(c["kind"]).trace_records += 1
        record_event("collective_trace", tag, collectives=prog.collectives,
                     trace_time=True, source="compiled")
    return prog


def traced_programs() -> Dict[str, TracedProgram]:
    return dict(_programs)


def _reset() -> None:
    with _agg_lock:
        _aggs.clear()
    _programs.clear()


runtime.on_reset(_reset)
