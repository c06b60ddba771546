"""The program's own spans, read from the run's profiler trace.

``paddle_tpu.profiler.span`` enters a ``jax.profiler.TraceAnnotation`` around
each phase of ``ServingEngine.step()`` (``serve.*``) and of
``TrainStep.__call__`` (``train.*``); PERF.md has the table.  In a traced run
they lie on plane ``/host:CPU`` of the same ``.xplane.pb`` the device lines
are in, on the same nanosecond clock, with their facts as the event's stats.
``lib/trace.py`` keeps the benchmark's ``bench.*`` spans only, and a reader's
``ReadCtx`` holds no path, so this module finds the run's file itself: the
newest one under ``<root>/.bench_out/trace/*``.

Against a program that records no such span (a parent commit), ``of_run``
returns an empty list and every reader built on it returns None."""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import registry, trace

PREFIXES = ("serve.", "train.")
REST = ""       # the owner of idle time that no listed span covers


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float            # ns, the profile's clock
    end: float              # ns
    facts: Tuple[Tuple[str, object], ...] = ()

    @property
    def dur(self) -> float:
        return self.end - self.start


def from_profile(profile) -> List[Span]:
    """The ``serve.*`` / ``train.*`` events of the host plane, every thread's,
    by start."""
    out: List[Span] = []
    for plane in profile.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    start = float(e.start_ns)
                    out.append(Span(e.name, start,
                                    start + float(e.duration_ns),
                                    tuple((k, v) for k, v in e.stats)))
    return sorted(out, key=lambda s: (s.start, -s.end))


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> List[Span]:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def load(path: str) -> List[Span]:
    """Parsed once per file: nine metrics read one run's trace."""
    return _load(path, os.path.getmtime(path))


def newest_xplane(root: Optional[str] = None) -> Optional[str]:
    """The newest trace any cell of this checkout wrote: the run's own, since
    a run is one process and its tracer empties its directory first."""
    base = os.path.join(root or registry.ROOT, ".bench_out", "trace")
    found = []
    for d in glob.glob(os.path.join(base, "*")):
        try:
            found.append(trace.newest_xplane(d))
        except FileNotFoundError:
            pass
    return max(found, key=os.path.getmtime) if found else None


def of_run(root: Optional[str] = None) -> List[Span]:
    path = newest_xplane(root)
    return load(path) if path else []


# -- reductions -----------------------------------------------------------
def owners(spans: Iterable[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces covering every instant that some
    span covers, each given to the covering span that started LAST: where
    spans nest, as one thread's do, that is the deepest one."""
    spans = [s for s in spans if s.end > s.start]
    points = sorted({s.start for s in spans} | {s.end for s in spans})
    order = sorted(spans, key=lambda s: s.start)
    active: List[Span] = []
    out: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i].start <= a:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > a]
        if active:
            top = max(active, key=lambda s: (s.start, -s.end))
            if out and out[-1][1] == a and out[-1][2] == top.name:
                out[-1] = (out[-1][0], b, top.name)
            else:
                out.append((a, b, top.name))
    return out


def idle_by_span(tr: trace.Trace, spans: Sequence[Span],
                 listed: Iterable[str]) -> Dict[str, float]:
    """Idle nanoseconds of the first chip inside the traced window, by the
    deepest covering span whose name is in ``listed``; under ``REST`` what no
    listed span covers.  The values sum to the chip's idle time."""
    listed = set(listed)
    gaps = trace.subtract([tr.window], trace.busy(tr, min(tr.devices)))
    pieces = owners(s for s in spans if s.name in listed)
    acc: Dict[str, float] = {REST: 0.0}
    j = 0
    for g in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= g[0]:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g[1]:
            t = trace.overlap(g, pieces[k][:2])
            acc[pieces[k][2]] = acc.get(pieces[k][2], 0.0) + t
            covered += t
            k += 1
        acc[REST] += (g[1] - g[0]) - covered
    return acc


def listed_spans(reg: Optional[registry.Registry] = None) -> List[str]:
    """Every span name that some ``idle_under_span`` metric of
    ``BENCHMARK.json`` lists: the partition is over these."""
    reg = reg or registry.Registry()
    names: List[str] = []
    for m in reg.benchmark["per_layer"]:
        spec = reg.layer_metric(m["name"])
        if spec["reader"] == "idle_under_span":
            names += spec["args"]["spans"]
    return names


def lengths_ms(tr: trace.Trace, spans: Sequence[Span],
               name: str) -> List[float]:
    """Lengths of the spans of that name that lie inside the window, ms."""
    lo, hi = tr.window
    return [s.dur / 1e6 for s in spans
            if s.name == name and s.start >= lo and s.end <= hi]
