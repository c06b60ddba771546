"""The benchmark's clock and its percentile arithmetic.

``now`` is ``time.monotonic``: the serving engine's own ``RequestClock``
stamps (``admit_t``) default to the same clock, so a queue wait can be
taken from a due time of ours to an admit time of theirs."""

from __future__ import annotations

import math
import os
import time
from typing import Optional, Sequence

now = time.monotonic


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default method).  ``None`` for an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def tail_mean(values: Sequence[float], lo: float = 90.0,
              hi: float = 99.0) -> Optional[float]:
    """The mean over the ranks ``lo``..``hi`` (percent) of the sorted
    sample: value ``i`` of ``n`` owns the rank interval [i/n, (i+1)/n) and
    counts by the part of it that lies inside [lo, hi).  Where a sample is a
    mixture of two populations, a percentile steps by the whole distance
    between them as the upper one's weight crosses it; this mean moves by
    that distance times the change of weight over the width of the band, so
    it is continuous in the weight and in ``n``.  ``None`` for an empty
    sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= lo < hi <= 100.0:
        raise ValueError(f"ranks {lo}..{hi} outside 0..100 or empty")
    a, b = len(xs) * lo / 100.0, len(xs) * hi / 100.0
    total = sum(xs[i] * (min(i + 1, b) - max(i, a))
                for i in range(math.floor(a), min(math.ceil(b), len(xs))))
    return total / (b - a)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the q-th percentile: a tail is
    worth reporting where this is ten or more."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def process_age_s() -> float:
    """Seconds since this process was started by the kernel (not since
    Python got as far as this module): ``setup_s`` counts the interpreter's
    start-up and the imports as well."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])           # field 22: starttime
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


_IMPORTED_AT = time.perf_counter()

# -- where set-up goes ---------------------------------------------------
_MARKS = [("start", 0.0, 0.0)]


def mark(name: str) -> None:
    """End of one phase of set-up: its name, the process's age and the CPU
    seconds (all threads) it has used so far."""
    _MARKS.append((name, process_age_s(), time.process_time()))


def phases() -> dict:
    """{phase: [wall s, CPU s]} between consecutive marks, in order.  CPU
    far under wall is waiting (the chip, the disk, a host that is shared);
    CPU over wall is work on several threads."""
    return {name: [age - _MARKS[i][1], cpu - _MARKS[i][2]]
            for i, (name, age, cpu) in enumerate(_MARKS[1:])}
