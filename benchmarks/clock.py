"""Joining the two clocks of a traced slice.

The program's spans are stamped with ``time.monotonic``; the profiler's
events (device operations and host annotations alike) carry the trace's own
clock.  ``obs/profiler.py::ProfilerWindows`` writes two anchors into every
window it opens, host annotations named ``arks_clock[<time.monotonic_ns()>]``:
one right after ``start_trace`` returns and one right before ``stop_trace``.
An anchor's name says what ``time.monotonic`` read as it was emitted, its
start says where that moment lies on the trace's clock, so

    offset = anchor start (trace clock) - monotonic in the name

is what has to be added to a span's times to lay it on the trace.  Two
anchors also show how far the clocks drift over the slice.  (A ``--trace 1``
run still takes ``-time.monotonic()`` read after ``profiler.start()``
returned, which is late by however long the first events took to appear.)

What else the trace carries: the plane ``Task Environment`` has
``profile_start_time`` / ``profile_stop_time`` in wall-clock nanoseconds
(seen on a v5e, PR 23's recorded trace).  That is an absolute clock, but of
another kind (``time.time``, which is stepped and slewed) and nothing says
that the events' zero is that moment; the anchors need neither assumption.
"""

from __future__ import annotations

import re

ANCHOR = re.compile(r"^arks_clock\[(\d+)\]$")


def anchors(xplane_path: str) -> list[tuple[float, float]]:
    """(start on the trace's clock, ``time.monotonic`` in the name), in
    seconds, of every anchor on a host plane, oldest first."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                m = ANCHOR.match(e.name)
                if m:
                    out.append((e.start_ns * 1e-9, int(m.group(1)) * 1e-9))
    return sorted(out)


def offset(xplane_path: str | None) -> dict:
    """``{"offset_s", "drift_s", "anchors"}``: trace clock minus
    ``time.monotonic`` at the first anchor (None without one: the spans are
    then left off the trace), and how much the last anchor's differs."""
    found = anchors(xplane_path) if xplane_path else []
    if not found:
        return {"offset_s": None, "drift_s": None, "anchors": 0}
    first = found[0][0] - found[0][1]
    last = found[-1][0] - found[-1][1]
    return {"offset_s": first, "drift_s": last - first,
            "anchors": len(found)}
