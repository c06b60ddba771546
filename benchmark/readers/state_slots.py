"""``SLOMeter.summary()["state_slots_peak"]`` in percent: the largest share
of the row-state slots (one per decode row) in use.  None where the model
keeps no per-row state."""


def read(ctx):
    peak = ctx.facts.get("meter", {}).get("state_slots_peak")
    return None if peak is None else 100.0 * float(peak)
