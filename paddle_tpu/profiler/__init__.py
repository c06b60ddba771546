"""paddle_tpu.profiler — profiling facade over jax.profiler + a host timeline.

Parity target: ``paddle.profiler`` (reference
``python/paddle/profiler/profiler.py:346`` Profiler, ``:215``
export_chrome_tracing, ``utils.py`` RecordEvent, benchmark timer). The
reference drives CUPTI through a C++ tracer; on TPU the device-side story is
XLA's own profiler (``jax.profiler.start_trace`` → TensorBoard/XPlane), so
this facade:

- keeps paddle's scheduler-window state machine (CLOSED/READY/RECORD/
  RECORD_AND_RETURN) and ``Profiler.step()`` protocol;
- records *host* events (``RecordEvent`` scopes, step spans, dataloader
  spans) in-process and exports them as a chrome trace JSON you can open in
  ``chrome://tracing`` / Perfetto — same artifact the reference's
  ``export_chrome_tracing`` produces;
- enters ``jax.profiler.TraceAnnotation`` for every ``RecordEvent`` /
  ``span`` scope, always: whoever holds a profiler session (this facade,
  ``jax.profiler.start_trace``, a TensorBoard capture) sees the program's
  own phases (``serve.*``, ``train.*``; PERF.md has the table) beside the
  device lines, and with no session live a scope costs about two microseconds;
- captures the XLA device trace per RECORD window when ``targets`` include
  ``ProfilerTarget.TPU`` (written under ``<log_dir>/xplane`` for
  TensorBoard).

The benchmark half (``timer_only=True``) reproduces the reference's
``benchmark().step_info()`` throughput readout ("reader_cost/batch_cost/ips").
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from enum import Enum
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple, Union

from jax.profiler import TraceAnnotation as _TraceAnnotation

__all__ = [
    "ProfilerState", "ProfilerTarget", "Profiler", "RecordEvent", "span",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
    "SortedKeys", "benchmark",
]


class ProfilerState(Enum):
    """Scheduler states, matching reference `profiler.py:73`."""

    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # record, and emit the collected window at this step


class ProfilerTarget(Enum):
    """Profiled hardware. TPU replaces the reference's GPU/CUPTI target.

    ``ProfilerTarget.GPU`` is an ALIAS of ``ProfilerTarget.TPU`` (same enum
    value, ``GPU is TPU``): scripts written against the reference's
    ``targets=[ProfilerTarget.GPU]`` select the device (XLA/xplane) trace
    here, exactly as ``TPU`` does — there is no separate CUDA path."""

    CPU = 0
    TPU = 1
    GPU = 1  # alias of TPU (see class docstring)
    CUSTOM_DEVICE = 2


class SortedKeys(Enum):
    """Summary-table sort orders (reference `profiler.py:259`).

    ``TPUTotal``/``TPUAvg``/``TPUMax``/``TPUMin`` are this port's native
    names; the reference's ``GPU*`` spellings are kept as aliases (same
    values) so reference-written scripts keep working. Both sort the host
    timeline — device-side timing lives in the xplane trace."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7
    TPUTotal = 4  # alias of GPUTotal
    TPUAvg = 5    # alias of GPUAvg
    TPUMax = 6    # alias of GPUMax
    TPUMin = 7    # alias of GPUMin


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Cyclic window scheduler, matching reference `profiler.py:121`.

    Each cycle is ``closed`` steps off, ``ready`` steps warming, ``record``
    steps tracing (last one RECORD_AND_RETURN); ``repeat=0`` repeats forever;
    the first ``skip_first`` steps are forced CLOSED."""
    if closed < 0 or ready < 0 or record < 1 or repeat < 0 or skip_first < 0:
        raise ValueError("make_scheduler: closed/ready>=0, record>=1, repeat/skip_first>=0")
    period = closed + ready + record

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat > 0 and step >= repeat * period:
            return ProfilerState.CLOSED
        pos = step % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def _default_scheduler(step: int) -> ProfilerState:
    # no scheduler: record everything; the final window is emitted on stop()
    return ProfilerState.RECORD


def _range_scheduler(start: int, end: int) -> Callable[[int], ProfilerState]:
    def fn(step: int) -> ProfilerState:
        if step < start - 1 or step >= end:
            return ProfilerState.CLOSED
        if step == start - 1:
            return ProfilerState.READY
        if step == end - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable[["Profiler"], None]:
    """Return an ``on_trace_ready`` callback writing chrome-trace JSON files
    into ``dir_name`` (reference `profiler.py:215`)."""
    os.makedirs(dir_name, exist_ok=True)

    def handle_fn(prof: "Profiler") -> None:
        name = worker_name or f"host_{socket.gethostname()}pid_{os.getpid()}"
        stamp = time.strftime("%Y_%m_%d_%H_%M_%S") + f"_{int(time.time_ns() % 1e6):06d}"
        path = os.path.join(dir_name, f"{name}_time_{stamp}.paddle_trace.json")
        prof.export(path, format="json")

    return handle_fn


def load_profiler_result(filename: str) -> Dict[str, Any]:
    """Load a chrome trace JSON previously written by :func:`Profiler.export`."""
    with open(filename) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# host event timeline

class _Event:
    __slots__ = ("name", "start_ns", "end_ns", "tid", "event_type", "args")

    def __init__(self, name, start_ns, end_ns, tid, event_type, args=None):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.tid = tid
        self.event_type = event_type
        self.args = args or {}


class _Timeline:
    """Thread-safe in-process event buffer for one RECORD window."""

    def __init__(self):
        self._events: List[_Event] = []
        self._lock = threading.Lock()

    def add(self, ev: _Event) -> None:
        with self._lock:
            self._events.append(ev)

    def events(self) -> List[_Event]:
        with self._lock:
            return list(self._events)


_active_profiler: Optional["Profiler"] = None


class RecordEvent:
    """A named scope on the profiler's clock: THE span primitive of this
    package (reference ``python/paddle/profiler/utils.py`` RecordEvent).

    It always enters ``jax.profiler.TraceAnnotation(name, **facts)``.  A
    TraceMe is inert unless a profiler session is live — a paddle
    :class:`Profiler` with a device target, a plain
    ``jax.profiler.start_trace``, a TensorBoard capture — so "tracing on"
    means "a session is live" and nothing else: there is no switch.  In a
    live session the scope lands on plane ``/host:CPU`` of the xplane, on
    the same nanosecond clock as the device lines, with ``facts`` as its
    stats.  In addition it is appended to the paddle ``Profiler``'s host
    timeline (the chrome-trace export) while one is recording.

    Usable as a context manager or via explicit ``begin()``/``end()``;
    :func:`span` is the short spelling the program's own phases use."""

    __slots__ = ("name", "event_type", "facts", "_start_ns", "_annotation")

    def __init__(self, name: str, event_type: str = "UserDefined", **facts):
        self.name = name
        self.event_type = event_type
        self.facts = facts
        self._start_ns: Optional[int] = None
        self._annotation = None

    def begin(self) -> None:
        self._annotation = _TraceAnnotation(self.name, **self.facts)
        self._annotation.__enter__()
        prof = _active_profiler
        if prof is not None and prof._recording and not prof._timer_only:
            self._start_ns = time.perf_counter_ns()

    def note(self, **facts) -> None:
        """Facts known only once the work is done (how many were admitted,
        how many bytes came back): attached to the open scope."""
        self.facts.update(facts)
        if self._annotation is not None:
            self._annotation.set_metadata(**facts)

    def end(self) -> None:
        annotation, self._annotation = self._annotation, None
        if annotation is None:
            return
        # the annotation closes whatever became of the paddle Profiler
        # between begin() and end()
        annotation.__exit__(None, None, None)
        start_ns, self._start_ns = self._start_ns, None
        prof = _active_profiler
        if start_ns is not None and prof is not None and prof._recording:
            prof._timeline.add(_Event(self.name, start_ns,
                                      time.perf_counter_ns(),
                                      threading.get_ident(), self.event_type,
                                      dict(self.facts)))

    def __enter__(self) -> "RecordEvent":
        self.begin()
        return self

    def __exit__(self, *exc) -> None:
        self.end()


def span(name: str, **facts) -> RecordEvent:
    """``with span("serve.decode", rows=5):`` — a :class:`RecordEvent`
    whose keyword arguments are the scope's facts."""
    return RecordEvent(name, "UserDefined", **facts)


class Profiler:
    """Profiling session manager (reference `profiler.py:346`).

    Drives the scheduler window state machine via :meth:`step`, collects
    host events + optional XLA device traces during RECORD windows, and
    invokes ``on_trace_ready(self)`` at each RECORD_AND_RETURN boundary.

    ``scheduler`` may be a callable ``step -> ProfilerState``, a
    ``(start, end)`` tuple meaning "record steps [start, end)", or None
    (record everything until stop)."""

    def __init__(self, *, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler: Union[Callable[[int], ProfilerState], Tuple[int, int], None] = None,
                 on_trace_ready: Optional[Callable[["Profiler"], None]] = None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, with_flops: bool = False,
                 custom_device_types: Optional[list] = None):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)):
            self._scheduler = _range_scheduler(int(scheduler[0]), int(scheduler[1]))
        else:
            self._scheduler = _default_scheduler
        self._targets = list(targets) if targets is not None else [ProfilerTarget.CPU,
                                                                   ProfilerTarget.TPU]
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._record_shapes = record_shapes
        self._profile_memory = profile_memory
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._timeline = _Timeline()
        self._windows: List[List[_Event]] = []
        self._recording = False
        self._device_trace_dir: Optional[str] = None
        self._device_tracing = False
        self._step_start_ns: Optional[int] = None
        self._session_start_ns: Optional[int] = None
        self._window_start_ns: Optional[int] = None
        self._emitted_window_start_ns: Optional[int] = None
        self._bench = benchmark()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        global _active_profiler
        _active_profiler = self
        self._session_start_ns = time.perf_counter_ns()
        self._bench.begin()
        self.current_state = self._scheduler(self.step_num)
        self._apply_state(self.current_state)
        self._step_start_ns = time.perf_counter_ns()

    def stop(self) -> None:
        global _active_profiler
        self._close_step_span()
        if self._recording:
            # final window: clear the flag FIRST so _emit_window does not
            # re-arm a fresh buffer (which would also advance the telemetry
            # window cutoff past the events being exported)
            self._recording = False
            self._emit_window()
        self._stop_device_trace()
        self.current_state = ProfilerState.CLOSED
        if _active_profiler is self:
            _active_profiler = None

    def __enter__(self) -> "Profiler":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def step(self, num_samples: Optional[int] = None) -> None:
        """Advance the step counter; drives window transitions."""
        self._close_step_span()
        self._bench.step(num_samples)
        prev = self.current_state
        if prev == ProfilerState.RECORD_AND_RETURN:
            self._emit_window()
        self.step_num += 1
        self.current_state = self._scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN and \
                self.current_state not in (ProfilerState.RECORD,
                                           ProfilerState.RECORD_AND_RETURN):
            self._apply_state(ProfilerState.CLOSED)
        else:
            self._apply_state(self.current_state)
        self._step_start_ns = time.perf_counter_ns()

    def step_info(self, unit: str = "samples") -> str:
        """Benchmark readout for the last step (reference `timer.py` step_info)."""
        return self._bench.step_info(unit)

    # -- internals ---------------------------------------------------------

    def _apply_state(self, state: ProfilerState) -> None:
        want_record = state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if want_record and not self._recording:
            self._timeline = _Timeline()
            self._window_start_ns = time.perf_counter_ns()
            self._recording = True
            self._start_device_trace()
        elif not want_record and self._recording:
            self._recording = False
            self._stop_device_trace()

    def _close_step_span(self) -> None:
        if self._recording and self._step_start_ns is not None and not self._timer_only:
            self._timeline.add(_Event(f"ProfileStep#{self.step_num}",
                                      self._step_start_ns, time.perf_counter_ns(),
                                      threading.get_ident(), "ProfileStep"))

    def _start_device_trace(self) -> None:
        if ProfilerTarget.TPU not in self._targets or self._timer_only:
            return
        try:
            import jax
            self._device_trace_dir = os.path.join(
                os.environ.get("PADDLE_TPU_PROFILE_DIR", "profiler_log"), "xplane")
            os.makedirs(self._device_trace_dir, exist_ok=True)
            jax.profiler.start_trace(self._device_trace_dir)
            self._device_tracing = True
        except Exception:
            self._device_tracing = False

    def _stop_device_trace(self) -> None:
        if self._device_tracing:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    def _emit_window(self) -> None:
        self._windows.append(self._timeline.events())
        # export() may run long after this window rotates: remember ITS
        # start so the telemetry merge matches _last_window()'s host events
        self._emitted_window_start_ns = self._window_start_ns
        self._stop_device_trace()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        if self._recording:  # next window gets a fresh buffer
            self._timeline = _Timeline()
            self._window_start_ns = time.perf_counter_ns()
            self._start_device_trace()

    # -- results -----------------------------------------------------------

    def _last_window(self) -> List[_Event]:
        if self._windows:
            return self._windows[-1]
        return self._timeline.events()

    def export(self, path: str, format: str = "json") -> None:
        """Write the most recent window as chrome-trace JSON, with telemetry
        flight-recorder events (collectives, steps, checkpoints, watchdog
        arms) recorded since :meth:`start` merged onto the timeline under
        the ``telemetry`` category."""
        if format not in ("json", "chrome"):
            raise ValueError("paddle_tpu profiler exports chrome-trace json "
                             "(device traces go to TensorBoard via xplane dir)")
        pid = os.getpid()
        trace = {"traceEvents": [], "displayTimeUnit": "ms"}
        for ev in self._last_window():
            trace["traceEvents"].append({
                "name": ev.name, "ph": "X", "pid": pid, "tid": ev.tid,
                "ts": ev.start_ns / 1e3, "dur": (ev.end_ns - ev.start_ns) / 1e3,
                "cat": ev.event_type, "args": ev.args,
            })
        trace["traceEvents"].extend(self._telemetry_events(pid))
        with open(path, "w") as f:
            json.dump(trace, f)

    def _telemetry_events(self, pid: int) -> List[dict]:
        """Flight-recorder events since the exported window began (falling
        back to session start) as chrome-trace entries: collectives with an
        ICI estimate become duration ('X') slices on a dedicated track,
        everything else instant ('i') marks — all under cat 'telemetry' so
        merged events are distinguishable. The window cutoff keeps repeat-
        scheduler exports from re-shipping earlier windows' events."""
        try:
            from .. import telemetry

            # cutoff must match _last_window(): the last EMITTED window's
            # start when windows exist, else the live window's
            start = self._emitted_window_start_ns if self._windows \
                else self._window_start_ns
            events = telemetry.get_flight_recorder().events(
                since_mono_ns=start or self._session_start_ns or 0)
        except Exception:
            return []
        out = []
        for ev in events:
            mono = ev.get("mono_ns")
            if mono is None:
                continue
            args = {k: v for k, v in ev.items()
                    if k not in ("kind", "name", "mono_ns", "ts")}
            entry = {"name": f"{ev['kind']}:{ev['name']}", "pid": pid,
                     "tid": "telemetry", "ts": mono / 1e3,
                     "cat": "telemetry", "args": args}
            est = ev.get("ici_est_s")
            if ev["kind"] == "collective" and est:
                entry["ph"] = "X"
                entry["dur"] = max(est * 1e6, 0.001)  # µs
            else:
                entry["ph"] = "i"
                entry["s"] = "t"
            out.append(entry)
        return out

    def summary(self, sorted_by: SortedKeys = SortedKeys.CPUTotal,
                op_detail: bool = True, thread_sep: bool = False,
                time_unit: str = "ms") -> str:
        """Aggregate the last window per event name and print a table."""
        scale = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}[time_unit]
        agg: Dict[str, List[float]] = {}
        for ev in self._last_window():
            d = (ev.end_ns - ev.start_ns) / scale
            agg.setdefault(ev.name, []).append(d)
        rows = [(name, len(ds), sum(ds), sum(ds) / len(ds), max(ds), min(ds))
                for name, ds in agg.items()]
        key = {SortedKeys.CPUTotal: 2, SortedKeys.CPUAvg: 3, SortedKeys.CPUMax: 4,
               SortedKeys.CPUMin: 5, SortedKeys.TPUTotal: 2, SortedKeys.TPUAvg: 3,
               SortedKeys.TPUMax: 4, SortedKeys.TPUMin: 5}.get(sorted_by, 2)
        rows.sort(key=lambda r: r[key],
                  reverse=sorted_by not in (SortedKeys.CPUMin, SortedKeys.TPUMin))
        w = max([len(r[0]) for r in rows] + [10])
        lines = [f"{'Name':<{w}}  {'Calls':>6} {'Total(' + time_unit + ')':>12} "
                 f"{'Avg':>10} {'Max':>10} {'Min':>10}"]
        lines.append("-" * len(lines[0]))
        for name, n, tot, avg, mx, mn in rows:
            lines.append(f"{name:<{w}}  {n:>6} {tot:>12.3f} {avg:>10.3f} "
                         f"{mx:>10.3f} {mn:>10.3f}")
        try:  # HBM watermarks (PJRT memory stats; absent on CPU backends)
            from .. import telemetry

            wm = telemetry.hbm_watermarks()
            if wm["devices"]:
                lines.append(f"HBM ({wm['devices']} device(s)): live "
                             f"{wm['live_gb']:.3f} GB, peak "
                             f"{wm['peak_gb']:.3f} GB, limit "
                             f"{wm['limit_gb']:.3f} GB")
        except Exception:
            pass
        table = "\n".join(lines)
        print(table)
        return table


class benchmark:
    """Throughput timer (reference ``python/paddle/profiler/timer.py``):
    tracks reader (dataloader) cost vs batch cost and instantaneous /
    average ips. ``paddle_tpu.io.DataLoader`` reports reader spans via
    :meth:`before_reader`/:meth:`after_reader`."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._step_start = None
        self._reader_start = None
        self.reader_cost = 0.0
        self.batch_cost = 0.0
        self.total_samples = 0
        self.total_time = 0.0
        self.steps = 0
        self._last_info = ""

    def begin(self) -> None:
        self._step_start = time.perf_counter()

    def before_reader(self) -> None:
        self._reader_start = time.perf_counter()

    def after_reader(self) -> None:
        if self._reader_start is not None:
            self.reader_cost += time.perf_counter() - self._reader_start
            self._reader_start = None

    def step(self, num_samples: Optional[int] = None) -> None:
        if self._step_start is None:
            self._step_start = time.perf_counter()
            return
        now = time.perf_counter()
        self.batch_cost = now - self._step_start
        self.total_time += self.batch_cost
        self.steps += 1
        if num_samples:
            self.total_samples += num_samples
        self._step_start = now

    def step_info(self, unit: str = "samples") -> str:
        """Readout for the last step. ``reader_cost`` is the PER-STEP
        AVERAGE of accumulated reader time (the reference timer's
        semantics), not the raw cumulative sum. Every rate guards a zero
        denominator (a zero-duration first step — e.g. step() straight
        after begin(), or a sub-tick clock — reads 0.0 instead of
        raising)."""
        avg_reader = self.reader_cost / self.steps if self.steps > 0 \
            else self.reader_cost
        if self.total_samples and self.total_time > 0:
            ips, u = self.total_samples / self.total_time, unit
        elif self.total_time > 0:
            ips, u = self.steps / self.total_time, "steps"
        else:
            ips, u = 0.0, unit if self.total_samples else "steps"
        self._last_info = (f"reader_cost: {avg_reader:.5f} s, "
                           f"batch_cost: {self.batch_cost:.5f} s, ips: {ips:.3f} {u}/s")
        return self._last_info
