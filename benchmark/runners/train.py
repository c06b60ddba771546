"""Training: fresh seeded batches made on the host each step, fed through
the step the builder made.  Every step is timed by the sync rule (host clock
around work that ends in ``block_until_ready``); the rate is all the tokens
of the window over all its time, the last step's overrun included."""

from __future__ import annotations

import math

from benchmark.lib import clock, generators
from benchmark.lib.program import span


def run(system, traffic: dict, ctx) -> dict:
    rng = generators.rng_for(ctx.seed, 0)
    check = system.check(generators.rng_for(ctx.seed, 2))
    clock.mark("check")
    x0, y0 = system.batch(rng)
    # warm-up: the first call compiles (or loads) the step, the second
    # proves that a fresh batch does not compile again
    warm = [float(system.step(x0, y0)), float(system.step(*system.batch(rng)))]
    seconds = float(ctx.seconds)
    t0 = clock.now()
    ctx.window_opens_at(t0)
    trace_from = t0 + seconds - min(float(traffic.get("trace_s", 6.0)),
                                    seconds)
    losses, step_s = [], []
    while True:
        t = clock.now()
        if t - t0 >= seconds:
            break
        if ctx.tracer.on and ctx.tracer.started_at is None and t >= trace_from:
            ctx.tracer.start(t)
        with span("batch_prep"):
            x, y = system.batch(rng)
        with span("step"):
            loss = system.step(x, y)
            loss.value.block_until_ready()
        step_s.append(clock.now() - t)
        losses.append(float(loss))
    elapsed = clock.now() - t0
    if ctx.tracer.running:
        ctx.tracer.stop(clock.now())
    ctx.window_closed()
    tokens = len(losses) * system.tokens_per_step
    # one repeated batch: the second pass must have learnt from the first
    again = [float(system.step(x0, y0)), float(system.step(x0, y0))]
    finish = system.finish()
    bad = [v for v in warm + losses + again if not math.isfinite(v)]
    facts = {"steps": len(losses), "tokens": tokens, "elapsed_s": elapsed,
             "step_ms_median": (clock.median(step_s) or 0.0) * 1e3,
             "loss_first": warm[0], "loss_last": losses[-1] if losses else None,
             "repeated_batch": again, "check": check, "finish": finish}
    return {"end_to_end": {"train_tok_s_chip":
                           tokens / elapsed / system.chips},
            "facts": facts, "attempted": len(losses), "failed": len(bad),
            "correct": bool(check["ok"] and finish["ok"] and not bad
                            and again[1] < again[0])}
