"""The step clock over the traced slice: what the program's profiler
window hands back under ``"clock"`` (``ProfilerWindows.stop()``, kept at
``engine.profiler.last_window``): the cycles that closed inside the slice,
by kind and leg, and the stalled seconds (shared by the ``slice_*``
readers)."""


def clock(ctx):
    """``{"kinds": {kind: {cycles, cycle_s, wait_s, starved_s, overlap_s,
    call_s}}, "stall_s", "stalls"}``, or None: no slice was traced (a
    ``--trace 0`` run), or the program's window marks no clock."""
    profiler = getattr(ctx.get("engine"), "profiler", None)
    window = getattr(profiler, "last_window", None) or {}
    return window.get("clock")
