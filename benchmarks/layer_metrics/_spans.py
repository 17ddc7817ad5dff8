"""The engine-scope spans of the last closed profiler window (shared by the
readers that take a program span).

``ProfilerWindows.stop()`` returns them and keeps them as
``engine.profiler.last_window`` (``{"t0_monotonic", "t1_monotonic",
"spans"}``, spans on ``time.monotonic``); a program without that attribute
(before PR 24) has nothing to read and every reader here returns None."""


def window_spans(ctx):
    """The spans that ended inside the traced slice, oldest first, or None
    where there is no slice or the program kept no window."""
    dev = ctx.get("device")
    engine = ctx.get("engine")
    last = getattr(getattr(engine, "profiler", None), "last_window", None)
    if not dev or not last or not last.get("spans"):
        return None
    t0, t1 = dev["slice_monotonic"]
    return sorted((s for s in last["spans"]
                   if s.get("end") is not None and t0 <= s["end"] <= t1),
                  key=lambda s: s["end"])
