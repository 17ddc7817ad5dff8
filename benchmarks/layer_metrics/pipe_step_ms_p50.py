"""Median device time of one execution of the step programs other than
the slowest: the pipelined decode steps (slots rows, no prefill chunk).
Nothing to read where every step of the slice ran the sequential program
(see ``seq_step_ms_p50`` for how the programs are told apart)."""

from benchmarks import trace_reduce
from benchmarks.client_metrics import percentile


def read(ctx):
    dev = ctx["device"]
    if not dev:
        return None
    progs = sorted(trace_reduce.step_programs(dev["modules"]).values(),
                   key=lambda v: percentile(v, 50))
    durs = [d for v in progs[:-1] for d in v]
    return percentile(durs, 50) * 1e3 if durs else None
