"""Rows whose recurrent state a decode step updated: mean of the
``state_rows`` fact over the ``serve.decode`` spans inside the traced
window.  None where the program records no such fact."""

from benchmark.lib import program_spans


def rows_in_window(ctx):
    """``state_rows`` of every ``serve.decode`` span that lies inside the
    traced window and carries the fact."""
    lo, hi = ctx.trace.window
    return [int(dict(s.facts)["state_rows"])
            for s in program_spans.of_run()
            if s.name == "serve.decode" and s.start >= lo and s.end <= hi
            and "state_rows" in dict(s.facts)]


def read(ctx):
    rows = rows_in_window(ctx)
    return sum(rows) / len(rows) if rows else None
