"""Which machine this is.  A measurement needs the chip: off a TPU, with
fewer chips than the cell asks for, or on a ``device_kind`` the peaks table
does not know, the run ends with a non-zero exit code and prints no result.
There is no CPU lane; the tests' rehearsal passes ``rehearsal=True`` from
Python and gets counts, never a time under a metric's name."""

from __future__ import annotations

from . import clock, peaks


class NoChip(SystemExit):
    def __init__(self, why: str):
        super().__init__(f"benchmark: {why}")


def probe(chips: int, rehearsal: bool = False):
    """-> (device record for the result line, the devices the cell uses,
    the peaks of their kind or ``None`` in a rehearsal).

    ``reach_chip_s`` is how long the machine took to hand its chips to this
    process: the first ``jax.devices()``, with JAX already imported and
    nothing of the program loaded.  It is the platform's time, not set-up
    work (8.3-11.9 s on the v5e machines of PR 23, the CPU idle for most of
    it, and it moves between two levels 2.6-3.6 s apart from one run of the
    same code to the next), so ``setup_s`` leaves it out and the result line
    carries it here."""
    import jax

    t = clock.now()
    devices = jax.devices()
    reach_chip_s = clock.now() - t
    dev = devices[0]
    record = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    if rehearsal:
        return record, devices[:chips], None     # counts only, no time
    record["reach_chip_s"] = reach_chip_s
    if dev.platform != "tpu":
        raise NoChip(f"JAX found no accelerator (platform {dev.platform!r})")
    try:
        peak = peaks.lookup(dev.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return record, devices[:chips], peak


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as the CPU does not)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
