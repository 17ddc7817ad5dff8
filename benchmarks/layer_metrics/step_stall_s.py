"""Seconds of stalled step cycles in the untraced window, wherever the
time stood (``dispatch``, ``wait``, ``host``, ``compile``): 0.0 in a sound
run, so that a line says by itself whether its run stood still."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    return delta(ctx, "step_stall_seconds_total")
