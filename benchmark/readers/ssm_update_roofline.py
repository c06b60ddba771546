"""The decode state-update kernel's share of its roofline.  It is bound by
memory: per live row and state layer it must read the row's float32 state
and write it back, and does a handful of operations per byte.  The least
time for the traced window is therefore the state bytes its decode steps
had to move (``state_rows`` of each ``serve.decode`` span, times the bytes a
row costs from ``lib/ssm_bytes.py``) over the chip's peak HBM bytes/s; the
share is that over the kernel's own device time in the window.  Only spans
whose decode program ran inside the window are counted, as only those
kernel events are.  None where the program has no such kernel or span."""

from benchmark.lib import ssm_bytes, trace
from benchmark.readers.state_rows_per_step import rows_in_window


def read(ctx, pattern):
    if ctx.peaks is None or "layer_types" not in ctx.config:
        return None
    took = trace.op_seconds(ctx.trace, pattern)
    rows = sum(rows_in_window(ctx))
    if took <= 0 or rows <= 0:
        return None
    least = rows * ssm_bytes.update_bytes_per_row(ctx.config) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
