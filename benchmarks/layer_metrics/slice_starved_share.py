"""``device_starved_share`` over the traced slice, in percent: the step
clock's ``starved`` seconds over all three legs of every kind, of the
cycles that closed inside the slice: the same 3 s that ``device_idle_share``
reads, so their difference in one line is launch and fetch latency and the
idle inside a pipelined ``overlap``.  None: no slice, a program whose
window marks no clock, or a slice in which no cycle closed."""

from benchmarks.layer_metrics._slice import clock


def read(ctx):
    sliced = clock(ctx)
    if sliced is None:
        return None
    kinds = sliced["kinds"].values()
    legs = sum(k["wait_s"] + k["starved_s"] + k["overlap_s"] for k in kinds)
    if not legs:
        return None
    return 100.0 * sum(k["starved_s"] for k in kinds) / legs
