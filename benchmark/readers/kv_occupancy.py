"""``SLOMeter.summary()["kv_pool_occupancy_peak"]`` in percent; evictions
and defers are printed beside it under ``facts.meter``."""


def read(ctx):
    peak = ctx.facts.get("meter", {}).get("kv_pool_occupancy_peak")
    return None if peak is None else 100.0 * float(peak)
