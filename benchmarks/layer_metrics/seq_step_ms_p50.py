"""Median device time of one execution of the slowest step program: the
sequential mixed step, which carries a prefill chunk beside the decode
rows and has the most rows.

A step program is any program (an ``XLA Modules`` event name) that ran
three times or more inside the traced slice and took 2 % or more of all
program time.  The programs carry no name of their own in the trace today
(``jit__unknown(<hash>)``), so the sequential one is known by its time
alone; the run's ``step_programs`` line lists each with count and median."""

from benchmarks import trace_reduce
from benchmarks.client_metrics import percentile


def read(ctx):
    dev = ctx["device"]
    if not dev:
        return None
    progs = trace_reduce.step_programs(dev["modules"])
    if not progs:
        return None
    return max(percentile(v, 50) for v in progs.values()) * 1e3
