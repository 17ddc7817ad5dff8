"""Window-layer pages in use over what the same sequences would hold had
no page been released behind the window, in percent, averaged over the
window's mixed dispatches: the deltas of
``kv_window_page_steps_total{state="held"}`` and ``{state="unreleased"}``
(the engine adds the pool's pages in use, and the pages its live slots
have ever been given, at every mixed dispatch).  100 would be a window
layer that keeps every page, as a full layer does.  Nothing to read where
the program has no such counter."""

from benchmarks.layer_metrics import _counters

NAME = "kv_window_page_steps_total"


def read(ctx):
    held = _counters.delta(ctx, NAME, state="held")
    whole = _counters.delta(ctx, NAME, state="unreleased")
    if held is None or not whole:
        return None
    return 100.0 * held / whole
