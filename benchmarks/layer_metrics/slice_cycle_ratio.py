"""What the open profiler window costs the pod, as a ratio: the seconds of
the step cycles that closed inside the traced slice over what the SAME
cycles cost in the untraced window,

    sum_k cycle_s_k / sum_k (cycles_k x window_mean_k),

``window_mean_k`` = Δ``step_cycle_seconds_sum{kind=k}`` /
Δ``step_cycle_seconds_count{kind=k}`` over the window.  Weighted by the
slice's own mix of kinds, so a slice that happens to hold more chunk steps
does not read as slower; a kind the window did not run is left out of both
sums.  1.00: the window costs the pod nothing.  None: no slice, a program
whose window marks no clock, or no kind that both ran."""

from benchmarks.layer_metrics._counters import delta
from benchmarks.layer_metrics._slice import clock


def read(ctx):
    sliced = clock(ctx)
    if sliced is None:
        return None
    seconds = same = 0.0
    for kind, k in sliced["kinds"].items():
        total = delta(ctx, "step_cycle_seconds_sum", kind=kind)
        cycles = delta(ctx, "step_cycle_seconds_count", kind=kind)
        if not k["cycles"] or total is None or not cycles:
            continue
        seconds += k["cycle_s"]
        same += k["cycles"] * total / cycles
    return seconds / same if same else None
