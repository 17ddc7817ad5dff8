"""Bytes of recurrent state over all the bytes the live sequences hold
(state + the GQA layers' pages), in percent, averaged over the window's
dispatches: the deltas of ``kv_held_byte_steps_total{kind="state"}`` and
``{kind="pages"}`` (the engine adds, at every dispatch, the state bytes of
the slots with a live sequence and the bytes of the pages in use).  The
state's share is what sets how many lanes the chip can carry where it is
high: it does not shrink with a short context.  Nothing to read where the
program has no such counter."""

from benchmarks.layer_metrics import _counters

NAME = "kv_held_byte_steps_total"


def read(ctx):
    state = _counters.delta(ctx, NAME, kind="state")
    pages = _counters.delta(ctx, NAME, kind="pages")
    if state is None or pages is None or state + pages <= 0:
        return None
    return 100.0 * state / (state + pages)
