"""Mean cycle of the whole-budget sequential step over the untraced
window, in ms: from that dispatch call's return to the next call's return
(the step clock's three legs of kind ``seq``; stalled cycles are in
``step_stall_s``, not here)."""

from benchmarks.layer_metrics._counters import delta


def read(ctx, kind="seq"):
    seconds = delta(ctx, "step_cycle_seconds_sum", kind=kind)
    cycles = delta(ctx, "step_cycle_seconds_count", kind=kind)
    if seconds is None or not cycles:
        return None
    return 1e3 * seconds / cycles
