"""The benchmark's one command.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one new process: it finds the cell, its configuration, traffic,
builder and runner by the names in ``BENCHMARK.json`` (``lib/registry.py``),
builds the system with weights made on the device from ``--seed``, warms up
the shapes this cell uses (set-up), measures for ``--seconds``, checks the
outputs against the plain reference outside the window, and prints ONE JSON
object as the last line of its standard output.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, taken by the host's clock with
the profiler off; with ``--trace 1`` the profiler runs over the last seconds
of the window and the metrics are the cell's per-layer metrics.

Off a TPU, with fewer chips than the cell asks for, or on a ``device_kind``
that ``lib/peaks.py`` does not know, it exits non-zero and prints no result.
``execute(..., rehearsal=True)`` is for the tests' Python call only: a tiny
configuration on the CPU, which returns counts and no metric."""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
from typing import Optional

from benchmark.lib import clock, device, program, registry
from benchmark.lib import trace as trace_lib


@dataclasses.dataclass
class Ctx:
    """What a runner is given besides the system and the traffic."""
    seed: int
    seconds: float
    rehearsal: bool
    tracer: program.Tracer
    devices: list
    reach_chip_s: float = 0.0
    setup_s: Optional[float] = None
    memory_peak_bytes: int = 0

    def window_opens_at(self, t: float) -> None:
        """Set-up ends where the measured window opens (``t`` may lie a
        ramp ahead): process start to here, less the time the machine took
        to hand over its chips (``device.probe``), is ``setup_s``."""
        clock.mark("warm_up")
        self.setup_s = clock.process_age_s() + (t - clock.now()) \
            - self.reach_chip_s
        # no full collection inside the window: after tracing, a JAX process
        # holds millions of objects and one gen-2 pass stops the host loop
        # for seconds (two of 26 runs of PR 23 stalled 1.8 s and 3.5 s)
        gc.collect()
        gc.freeze()
        gc.disable()

    def window_closed(self) -> None:
        gc.enable()
        # before the reference runs: the peak is the system's, not the
        # yardstick's
        self.memory_peak_bytes = device.memory_peak_bytes(self.devices)


@dataclasses.dataclass
class ReadCtx:
    """What a per-layer metric's reader is given."""
    trace: Optional[trace_lib.Trace]
    facts: dict
    end_to_end: dict
    config: dict
    traffic: dict
    peaks: Optional[dict]
    chips: int
    fallbacks: dict
    cache: dict


def execute(workload: str, seed: int, seconds: float, trace: bool,
            root: Optional[str] = None, rehearsal: bool = False) -> dict:
    reg = registry.Registry(root)
    cell = reg.workload(workload)
    config = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    clock.mark("interpreter")
    import jax                                      # noqa: F401
    clock.mark("import_jax")
    record, devices, peaks = device.probe(cell["chips"], rehearsal)
    clock.mark("reach_chip")

    cache = program.enable_compile_cache() if not rehearsal else None
    program.use_kernels(rehearsal)
    clock.mark("import_program")
    tracer = program.Tracer(
        os.path.join(reg.root, ".bench_out", "trace", workload), bool(trace))
    ctx = Ctx(int(seed), float(seconds), rehearsal, tracer, devices,
              reach_chip_s=record.get("reach_chip_s", 0.0))

    system = reg.module("builders", config["builder"]).build(
        config, traffic, int(seed), devices)
    clock.mark("build")
    outcome = reg.module("runners", traffic["runner"]).run(
        system, traffic, ctx)
    fallbacks = program.fallbacks()
    cache_stats = cache.as_dict() if cache is not None else {}

    if "check_sample" in outcome:
        # serving: the engine and its pool go before the reference comes
        outcome.pop("release", None)
        verdict = system.verify(outcome.pop("check_sample"))
        outcome["facts"]["check"] = verdict
        outcome["correct"] = bool(verdict["ok"])

    result = {
        "workload": workload, "seed": int(seed), "seconds": float(seconds),
        "correct": bool(outcome["correct"]),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": {},
        "device": dict(record, memory_peak_bytes=ctx.memory_peak_bytes),
        "facts": _plain(outcome["facts"]),
        "samples": outcome["end_to_end"].get("samples", {}),
        "compile_cache": cache_stats,
    }
    if rehearsal:
        result["rehearsal"] = True      # counts only: no metric, no time
        return result

    e2e = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
    result["measured"] = _plain(e2e)    # every statistic, by name
    result["setup_phases"] = clock.phases()     # where set-up went
    if not trace:
        for m in reg.metrics_of(workload, "end_to_end"):
            result["metrics"][m["name"]] = _metric(e2e[m["name"]], m)
        return result

    reduced = trace_lib.load(trace_lib.newest_xplane(tracer.dir))
    rctx = ReadCtx(reduced, outcome["facts"], e2e, config, traffic, peaks,
                   cell["chips"], fallbacks, cache_stats)
    for m in reg.metrics_of(workload, "per_layer"):
        spec = reg.layer_metric(m["name"])
        value = reg.module("readers", spec["reader"]).read(
            rctx, **spec.get("args", {}))
        if value is not None:       # nothing to read: left out of the line
            result["metrics"][m["name"]] = _metric(value, m)
    result["device"]["busy_s"] = trace_lib.busy_s(reduced)
    result["device"]["window_s"] = trace_lib.window_s(reduced)
    result["breakdown"] = {"device_ops": trace_lib.top_ops(reduced, 10),
                           "idle_gaps": trace_lib.idle_gaps(reduced, 10)}
    return result


def _metric(value, entry: dict) -> dict:
    value = float(value)
    if not math.isfinite(value):
        raise ValueError(f"metric {entry['name']} is {value}")
    return {"value": value, "unit": entry["unit"]}


def _plain(x):
    """Facts for the result line: numbers and short lists, no samples."""
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()
                if not (isinstance(v, (list, tuple)) and len(v) > 16)}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if hasattr(x, "item") and getattr(x, "ndim", 1) == 0:
        return x.item()
    return x if isinstance(x, (int, float, str, bool, type(None))) else str(x)


def compared(result: dict) -> dict:
    """Each number the run's verdict compared, beside its limit."""
    check = result["facts"].get("check", {})
    return {name: {"value": check.get(name), "limit": limit}
            for name, limit in check.get("limits", {}).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run", description=__doc__,
                                 formatter_class=argparse.
                                 RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args(argv)
    result = execute(a.workload, a.seed, a.seconds, bool(a.trace))
    # the line's last key and the last lines on standard error: what a
    # record of a run that was not correct keeps
    result["compared"] = compared(result)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct {result['correct']} failed {result['failed']} of "
          f"{result['attempted']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
