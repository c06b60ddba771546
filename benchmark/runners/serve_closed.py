"""Closed loop: ``clients`` callers that each wait for the reply and send the
next request at once (no think time) — a batch pipeline, not independent
users.  One thread drives the clients and ``eng.step()``.

ramp (set-up, so that the clients' first burst has spread out) -> window of
``--seconds``.  The sample is every request COMPLETED in the window; what is
in flight when it closes is abandoned and counts nowhere."""

from __future__ import annotations

import itertools

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
    # the fixed population, cycled in this seed's order
    reqs = itertools.cycle(generators.requests(
        traffic, ctx.seed, int(traffic["population"]), system.vocab))
    sent = []

    def send_next():
        with span("submit"):
            sent.append(client.send(next(reqs), clock.now()))

    t0 = clock.now()
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    ctx.window_opens_at(w0)
    trace_from = w1 - min(float(traffic.get("trace_s", 6.0)), seconds)
    for _ in range(int(traffic["clients"])):
        send_next()
    while True:
        t = clock.now()
        if t >= w1:
            break
        if ctx.tracer.on and ctx.tracer.started_at is None and t >= trace_from:
            ctx.tracer.start(t)
        step()
        for _ in client.take_done():
            send_next()
    if ctx.tracer.running:
        ctx.tracer.stop(clock.now())
    ctx.window_closed()
    sample = [s for s in sent
              if (s.done and w0 <= s.finished_t < w1)
              or (s.error and w0 <= s.sent_t < w1)]
    done = [s for s in sample if s.done]
    lat = serving.latency_metrics(done, from_due=False)
    tokens = sum(len(s.req.prompt) + s.req.want for s in done)
    facts = {
        "requests_sampled": len(sample), "requests_completed": len(done),
        "tokens_completed": tokens,
        "queue_wait_ms": [(s.admit_t - s.sent_t) * 1e3 for s in done
                          if s.admit_t is not None],
        "meter": eng.meter.summary(),
        "prefilled_tokens_traced": serving.prefilled_tokens(sent, ctx.tracer),
        "engine_steps": eng.steps_total,
    }
    return {"end_to_end": serving.end_to_end(lat, tokens, seconds),
            "facts": facts,
            "attempted": len(sample),
            "failed": serving.count_failed(sample),
            "check_sample": sample_check, "release": client}

