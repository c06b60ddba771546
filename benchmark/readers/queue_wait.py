"""From when a request was due (open loop) or sent (closed loop) to the
engine's own ``RequestClock.admit_t``: the q-th percentile, ms."""

from benchmark.lib import clock


def read(ctx, q):
    return clock.percentile(ctx.facts.get("queue_wait_ms", []), q)
