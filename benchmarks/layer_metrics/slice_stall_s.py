"""Seconds of stalled step cycles that closed inside the traced slice:
0.0 in a sound slice, so that a line whose slice stood still says so by
itself (``step_stall_s`` is the untraced window's).  None: no slice, or a
program whose window marks no clock."""

from benchmarks.layer_metrics._slice import clock


def read(ctx):
    sliced = clock(ctx)
    return None if sliced is None else float(sliced["stall_s"])
