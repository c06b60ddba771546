"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, per-operation time by name,
executions of a compiled program, collective time, and idle gaps matched to
the host span that covers them.

What the trace looks like on the v5e (jax 0.9.0, libtpu 0.0.34; read off the
first traced runs of PR 23): one plane per chip named ``/device:TPU:<n>``
whose line ``XLA Ops`` holds one event per executed HLO operation, named by
the whole instruction (``%jvp_flash_fwd_.12 = (bf16[...]) custom-call(...)``:
a Pallas kernel carries its ``name`` there), and whose line ``XLA Modules``
holds one event per execution of a compiled program
(``jit_serve_decode_fn(<id>)``, ``jit_serve_prefill_fn``,
``jit_train_step_guarded``: the program names its own since PR 24; at PR 23
they read ``jit__decode_fn`` and ``jit__unknown``).  Lines ``Steps`` and
``Async XLA Ops`` (copies in flight) are not read.  The host's threads are lines of the plane
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear under their
own names.  All times are nanoseconds from the start of the profile, on one
clock for host and device.

Only ``jax.profiler.ProfileData`` is needed to read the file."""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns
    end: float              # ns
    text: str = ""          # the head of the HLO instruction, for a label

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """``devices``: per chip, the op events and the program executions.
    ``spans``: the benchmark's own host spans.  ``window``: the traced
    interval the shares are taken over."""
    devices: Dict[int, Dict[str, List[Event]]]
    spans: List[Event]
    window: Interval


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _short(name: str) -> str:
    """On the device lines an event's name is the whole HLO instruction,
    ``%fusion.4 = bf16[...] fusion(...)``: the operation's own name is what
    stands before `` = `` (its operands' names must not match a pattern)."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _events(line, device: bool) -> List[Event]:
    out, seen = [], {}
    for e in line.events:
        full = e.name
        if full not in seen:
            seen[full] = (_short(full), full[:240]) if device else (full, full)
        name, text = seen[full]
        start = float(e.start_ns)
        out.append(Event(name, start, start + float(e.duration_ns), text))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> Trace:
    devices: Dict[int, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln, True) for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            devices[int(m.group(1))] = {
                "ops": lines.get(OPS_LINE, []),
                "modules": lines.get(MODULES_LINE, [])}
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                spans += [e for e in _events(ln, False)
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if windows:
        window = (min(e.start for e in windows), max(e.end for e in windows))
    else:
        every = [e for d in devices.values() for e in d["ops"]]
        window = (min((e.start for e in every), default=0.0),
                  max((e.end for e in every), default=0.0))
    spans = [e for e in spans if e.name != WINDOW_SPAN]
    return Trace(devices, sorted(spans, key=lambda e: e.start), window)


# -- interval arithmetic ------------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def within(merged: Sequence[Interval], window: Interval) -> float:
    """Total length of the merged, sorted intervals inside ``window``
    (a bisection, not a scan: a trace holds 10^5 busy intervals and is
    asked once per host span)."""
    lo = bisect.bisect_right(merged, (window[0], float("inf"))) - 1
    hi = bisect.bisect_left(merged, (window[1], float("-inf")))
    return total(clip(merged[max(lo, 0):hi], window))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- reductions ------------------------------------------------------------
def _iv(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def busy(trace: Trace, dev: int) -> List[Interval]:
    """Merged intervals in which some operation ran on chip ``dev``."""
    return union(clip(_iv(trace.devices[dev]["ops"]), trace.window))


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def busy_s(trace: Trace) -> float:
    """Seconds an operation ran on the device, mean over the chips."""
    if not trace.devices:
        return 0.0
    return sum(total(busy(trace, d)) for d in trace.devices) \
        / len(trace.devices) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    w = window_s(trace)
    return None if w <= 0 or not trace.devices else 1.0 - busy_s(trace) / w


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own time: its duration less what the events
    nested inside it cover (a ``while`` spans the operations of its body)."""
    out: List[List] = []
    stack: List[int] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and out[stack[-1]][0].end <= e.start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(e.end, out[stack[-1]][0].end) - e.start
        out.append([e, e.dur])
        stack.append(len(out) - 1)
    return [(e, max(t, 0.0)) for e, t in out]


def op_seconds(trace: Trace, pattern: str) -> float:
    """Own time of the operations whose name matches ``pattern``, in
    seconds, mean over the chips."""
    if not trace.devices:
        return 0.0
    rx = re.compile(pattern)
    t = 0.0
    for d in trace.devices.values():
        t += sum(own for e, own in self_times(d["ops"])
                 if rx.search(e.name) and overlap((e.start, e.end),
                                                  trace.window) > 0)
    return t / len(trace.devices) / 1e9


def op_events(trace: Trace, pattern: str) -> List[Event]:
    """The matching operations of the first chip, inside the window."""
    if not trace.devices:
        return []
    rx = re.compile(pattern)
    d = trace.devices[min(trace.devices)]
    return [e for e in d["ops"] if rx.search(e.name)
            and trace.window[0] <= e.start and e.end <= trace.window[1]]


def program_runs(trace: Trace, pattern: str) -> List[Event]:
    """Executions of the compiled programs whose name matches, on the first
    chip, that lie wholly inside the window."""
    if not trace.devices:
        return []
    rx = re.compile(pattern)
    d = trace.devices[min(trace.devices)]
    return [e for e in d["modules"] if rx.search(e.name)
            and trace.window[0] <= e.start and e.end <= trace.window[1]]


def kind_of(e: Event) -> str:
    """A label that is the same for the same operation in every layer: the
    instruction's name without its number, then its result type and the
    head of its operands, layouts left out."""
    label = re.sub(r"^%?([\w-]+?)[.\d]*(?= = |$)", r"\1", e.text)
    return re.sub(r"\{[^}]*(\}|$)| ?%[\w.-]+", "", label)[:96]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The kinds of operation that took most device time in the window: own
    time summed over every execution of every instruction of one kind, in
    seconds, mean over the chips."""
    acc: Dict[str, float] = {}
    for d in trace.devices.values():
        for e, own in self_times(d["ops"]):
            if overlap((e.start, e.end), trace.window) > 0:
                k = kind_of(e)
                acc[k] = acc.get(k, 0.0) + own
    k = max(len(trace.devices), 1)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[label, t / k / 1e9] for label, t in rows]


def exposed_collective_s(trace: Trace) -> float:
    """Seconds in which a collective ran on a chip and no other operation
    did, mean over the chips."""
    if not trace.devices:
        return 0.0
    t = 0.0
    for dev, d in trace.devices.items():
        coll = [e for e in d["ops"] if COLLECTIVE.match(e.name)]
        # what runs nested inside a collective is the collective's own work
        inner = union(_iv(coll))
        rest = [e for e in d["ops"] if not COLLECTIVE.match(e.name)
                and not _inside((e.start, e.end), inner)
                and not _contains_any(e, inner)]
        t += total(clip(subtract(inner, union(_iv(rest))), trace.window))
    return t / len(trace.devices) / 1e9


def _inside(iv: Interval, merged: Sequence[Interval]) -> bool:
    return any(s <= iv[0] and iv[1] <= e for s, e in merged)


def _contains_any(e: Event, merged: Sequence[Interval]) -> bool:
    """A control-flow operation (``while``, ``conditional``) that spans a
    collective is not compute that hides it."""
    return any(e.start <= s and t <= e.end for s, t in merged)


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle time of the first chip, by the benchmark's host span that covers
    most of each gap, in seconds: the longest totals first."""
    if not trace.devices:
        return []
    dev = min(trace.devices)
    gaps = subtract([trace.window], busy(trace, dev))
    acc: Dict[str, float] = {}
    j = 0
    spans = trace.spans
    for g in gaps:
        while j < len(spans) and spans[j].end <= g[0]:
            j += 1
        best, best_t = "unattributed", 0.0
        k = j
        while k < len(spans) and spans[k].start < g[1]:
            t = overlap(g, (spans[k].start, spans[k].end))
            if t > best_t:
                best, best_t = spans[k].name[len(SPAN_PREFIX):], t
            k += 1
        acc[best] = acc.get(best, 0.0) + (g[1] - g[0])
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9] for name, t in rows]


def span_host_ms(trace: Trace, name: str) -> List[float]:
    """For each host span of that name inside the window: its length less
    the time the first chip was busy inside it, in milliseconds."""
    if not trace.devices:
        return []
    b = busy(trace, min(trace.devices))
    out = []
    for s in trace.spans:
        if s.name == SPAN_PREFIX + name and s.start >= trace.window[0] \
                and s.end <= trace.window[1]:
            out.append((s.dur - within(b, (s.start, s.end))) / 1e6)
    return out
