"""Open loop: independent users.  Requests are due at seeded Poisson (or
burstier) times at the rate FIXED in the traffic file, whether or not
earlier ones have finished.  One thread drives the generator and
``eng.step()``.

ramp (set-up) -> window of ``--seconds`` -> drain.  The sample is every
request DUE in the window.  When the window ends the generator stops and
what is in flight drains for at most ``drain_s``; a sampled request that has
not finished by then is failed."""

from __future__ import annotations

import time

from benchmark.lib import clock, generators, serving
from benchmark.lib.program import span


def run(system, traffic: dict, ctx) -> dict:
    client = serving.Client(system.engine)
    eng = client.eng

    def step():
        with span("step"):
            eng.step()

    sample_check = serving.warm_up_sample(client, traffic, ctx.seed,
                                          system.vocab, step)
    clock.mark("engine_and_sample")
    ramp, seconds = float(traffic["ramp_s"]), float(ctx.seconds)
    rate = float(traffic["arrivals"]["rate_per_s"])
    n = max(int(round(rate * (ramp + seconds))), 1)
    reqs = generators.requests(traffic, ctx.seed, n, system.vocab,
                               span_s=ramp + seconds)
    t0 = clock.now()
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    ctx.window_opens_at(w0)
    trace_from = w1 - min(float(traffic.get("trace_s", 6.0)), seconds)
    sent, late, i = [], [], 0
    while True:
        t = clock.now()
        if t >= w1:
            break
        if ctx.tracer.on and ctx.tracer.started_at is None and t >= trace_from:
            ctx.tracer.start(t)
        if i < n and t0 + reqs[i].due_s <= t:
            with span("submit"):
                while i < n and t0 + reqs[i].due_s <= t:
                    due = t0 + reqs[i].due_s
                    sent.append(client.send(reqs[i], due))
                    late.append(sent[-1].sent_t - due)
                    i += 1
        if client.live:
            step()
        else:
            nxt = t0 + reqs[i].due_s if i < n else w1
            with span("generator_sleep"):
                time.sleep(max(min(nxt, w1) - clock.now(), 0.0))
    if ctx.tracer.running:
        ctx.tracer.stop(clock.now())
    summary_at_close = eng.meter.summary()
    ctx.window_closed()
    sample = [s for s in sent if w0 <= s.due_t < w1]
    drain_until = clock.now() + float(traffic["drain_s"])
    while any(not s.done and not s.error for s in sample) \
            and clock.now() < drain_until:
        eng.step()
    lat = serving.latency_metrics(sample, from_due=True)
    done = [s for s in sample if s.done]
    facts = {
        "requests_offered": n, "requests_sampled": len(sample),
        "requests_completed": len(done),
        "tokens_completed": sum(len(s.req.prompt) + s.req.want for s in done),
        "generator_late_ms_max": max(late, default=0.0) * 1e3,
        "generator_late_ms_p95": (clock.percentile(late, 95) or 0.0) * 1e3,
        "queue_wait_ms": [(s.admit_t - s.due_t) * 1e3 for s in sample
                          if s.admit_t is not None],
        "meter": summary_at_close,
        "prefilled_tokens_traced": serving.prefilled_tokens(sent, ctx.tracer),
        "engine_steps": eng.steps_total,
    }
    return {"end_to_end": serving.end_to_end(
                lat, facts["tokens_completed"], seconds),
            "facts": facts,
            "attempted": len(sample),
            "failed": serving.count_failed(sample),
            "check_sample": sample_check, "release": client}

