"""Share of the rows that went through a state-space layer's state update
by the CHUNKED SCAN (the rows of lanes of more than one row: prompt chunks)
rather than by the one-step kernel (a lane's one row), over the window, in
percent: ``ssm_rows_total{path="scan"}`` over both paths.  Where it is low
the one-step kernel carries the cell, and the scan's cost is a sequential
step's alone.  Nothing to read where the program has no ``ssm_rows_total``
or no row went through either path."""

from benchmarks.layer_metrics._counters import delta

NAME = "ssm_rows_total"


def read(ctx):
    scan = delta(ctx, NAME, path="scan")
    step = delta(ctx, NAME, path="step")
    if scan is None or step is None or scan + step <= 0:
        return None
    return 100.0 * scan / (scan + step)
