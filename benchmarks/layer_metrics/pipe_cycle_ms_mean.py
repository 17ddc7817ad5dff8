"""Mean cycle of the pipelined decode step over the untraced window, in
ms: ``seq_cycle_ms_mean``'s reader on the step clock's kind ``pipe``."""

from benchmarks import manifest

_cycle = manifest.load_reader("seq_cycle_ms_mean")


def read(ctx):
    return _cycle(ctx, kind="pipe")
