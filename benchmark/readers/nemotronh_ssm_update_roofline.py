"""``ssm_state_update``'s share of its roofline for a ``nemotron_h``
configuration: the construction of ``readers/ssm_update_roofline.py`` with
the bytes counted by ``lib/nemotron_h_cost.py`` (that reader's cost function
reads Granite-4.0-H's key names).  The least time for the traced window is
the state bytes its decode steps had to move (``state_rows`` of each
``serve.decode`` span inside it, times the bytes a row costs over every
``M`` block) over the chip's peak HBM bytes/s; the share is that over the
kernel's own device time in the window.  None where the configuration is of
another family, or the program has no such kernel or span."""

from benchmark.lib import nemotron_h_cost, trace
from benchmark.readers.state_rows_per_step import rows_in_window


def read(ctx, pattern):
    if ctx.peaks is None or "hybrid_override_pattern" not in ctx.config:
        return None
    took = trace.op_seconds(ctx.trace, pattern)
    rows = sum(rows_in_window(ctx))
    if took <= 0 or rows <= 0:
        return None
    least = rows * nemotron_h_cost.update_bytes_per_row(ctx.config) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
