"""Share of the step cycles in which the device provably had nothing
queued, in percent: the step clock's ``starved`` leg over all three legs of
every kind, over the untraced window (the program's counter; a program
without the step clock has nothing to read)."""

from benchmarks.layer_metrics._counters import delta


def read(ctx):
    starved = delta(ctx, "step_leg_seconds_total", leg="starved")
    legs = delta(ctx, "step_leg_seconds_total")
    if starved is None or not legs:
        return None
    return 100.0 * starved / legs
