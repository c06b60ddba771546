"""paddle_tpu.telemetry — unified training telemetry.

The metrics + tracing subsystem the rest of the stack reports into
(reference: the CUPTI tracer + ``paddle.profiler`` summary tables; here the
host side is first-class because XLA owns the device):

- **collective tracing** — ``distributed.communication`` records every eager
  collective (kind, payload bytes, mesh axes, analytic ICI cost); compiled
  engines register :class:`TracedProgram` profiles with execution counters;
  collectives traced inside someone else's jit are tagged ``trace_time``.
- **step metrics** — :class:`StepMeter`: tokens/s, achieved MFU/MBU from a
  FLOP/byte model, loss/grad-norm, skipped-step counters (health guard /
  AMP found-inf), JSONL emission, Prometheus text export via
  :func:`prometheus_text`.
- **memory watermarks** — :func:`hbm_watermarks` / :func:`hbm_stats`:
  per-device live/peak/limit HBM from PJRT memory stats (CPU: graceful
  zeros).
- **flight recorder** — :class:`FlightRecorder`: bounded ring of recent
  events (collectives, steps, checkpoints, elastic transitions, watchdog
  arms), dumped to JSON on demand / unhandled exception / watchdog hang.
  The profiler merges these events onto its chrome-trace timeline under the
  ``telemetry`` category.  The serving engine's ``serve_submit`` …
  ``serve_finish`` events are INSTANTS on ``perf_counter_ns`` for crash
  dumps and ``trace_coverage``: a request's lifecycle (a handful of events
  a request; none a token or a flush, so a loaded engine's ring holds the
  recent requests' whole stories), and they never reach the device trace.
  They are not the source of per-layer time — that is
  :func:`paddle_tpu.profiler.span`, whose ``serve.*`` / ``train.*`` scopes
  lie on the profiler's clock beside the device lines and write nothing
  here.  WHEN tokens became visible is on the ``serve.deliver`` span, and
  what the slowest deliveries waited for is in
  ``SLOMeter.summary()["longest_cycles"]``.

Env vars: ``PADDLE_TPU_TELEMETRY=0`` disables recording;
``PADDLE_TPU_TELEMETRY_DIR`` makes StepMeters write JSONL there by default;
``PADDLE_TPU_FLIGHT_RECORDER_DIR`` / ``_SIZE`` control the crash dump
location and ring size; ``PADDLE_TPU_FLIGHT_RECORDER=0`` opts out of the
unhandled-exception dump hook.
"""

from .runtime import (bump, counters, disable, enable, enabled,  # noqa: F401
                      reset, set_gauge)
from .recorder import (FlightRecorder, dump_flight_recorder,  # noqa: F401
                       get_flight_recorder, kernel_fallback, record_event)
from .collectives import (ICI_GBPS_ONEWAY, PEAK_HBM_GBPS,  # noqa: F401
                          PEAK_TFLOPS, TracedProgram, chip_lookup,
                          collective_stats, ici_cost_estimate,
                          record_collective, register_traced_program,
                          ring_wire_bytes, total_collective_bytes,
                          traced_programs)
from .memory import hbm_peak_gb, hbm_stats, hbm_watermarks  # noqa: F401
from .stepmeter import StepMeter  # noqa: F401
from .prometheus import prometheus_text, render_histogram  # noqa: F401
from .tracing import (TRACE_KEY, chrome_trace_events, mint,  # noqa: F401
                      trace_coverage, trace_ids)
from .tracing import spans as trace_spans  # noqa: F401
from .aggregator import (Histogram, MemoryDepot, MetricsPusher,  # noqa: F401
                         local_snapshot, prometheus_rollup_text, rollup,
                         start_metrics_pusher)
from . import blackbox  # noqa: F401

__all__ = [
    "enable", "disable", "enabled", "reset", "bump", "set_gauge", "counters",
    "FlightRecorder", "get_flight_recorder", "record_event",
    "dump_flight_recorder", "kernel_fallback",
    "record_collective", "collective_stats", "total_collective_bytes",
    "ici_cost_estimate", "ring_wire_bytes", "TracedProgram",
    "register_traced_program", "traced_programs",
    "PEAK_TFLOPS", "ICI_GBPS_ONEWAY", "PEAK_HBM_GBPS", "chip_lookup",
    "hbm_stats", "hbm_watermarks", "hbm_peak_gb",
    "StepMeter", "prometheus_text", "render_histogram",
    "TRACE_KEY", "mint", "trace_spans", "trace_ids", "trace_coverage",
    "chrome_trace_events",
    "Histogram", "MemoryDepot", "MetricsPusher", "local_snapshot",
    "rollup", "prometheus_rollup_text", "start_metrics_pusher",
    "blackbox",
]
