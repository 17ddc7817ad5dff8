"""The step loop's own clock: every dispatch's cycle by leg and by kind,
where the end-to-end numbers are read.

The step-section spans (``phase.<phase>.<section>``) name WHAT the host
did, but only inside a profiler window.  This clock is on like the request
spans are, in every window: it says HOW LONG each leg of a cycle was, from
``time.monotonic`` readings the step loop takes at its dispatch and wait
sites.  It is written by the engine thread alone and has no switch.  A
profiler window reads it at its two ends (:meth:`StepClock.snapshot`,
:func:`window`), so the cycles of a traced slice can be held against the
same cycles of the untraced run.

**A cycle** runs from the return of one model dispatch call to the return
of the next, and carries the ``kind`` of the dispatch that OPENED it, the
program the device runs while the cycle lasts (``seq``, ``seq_tail``,
``pipe``, ``spec``, ``spec_pipe``, ``decode``): a ``seq`` cycle is that
step's wait and the host work up to the next dispatch's return, whatever
program comes next.  Three legs sum to it:

- ``wait``     the engine thread blocked fetching results (``np.asarray``
               in a resolve): the device's leg;
- ``starved``  from the end of a wait that left NOTHING in flight, as far
               as the host knows, to the return of the next dispatch call:
               the device provably had nothing queued (a sequential step's
               host gap; the time ``_pipe_inflight`` stood empty);
- ``overlap``  all other host time (delivery, admission, the step's tail
               and the loop, waits for the GIL) while the device had work.

The dispatch call's own duration lies inside ``starved`` or ``overlap``
and is summed beside them under the kind of the program called.  A pod
that goes idle ABANDONS the open cycle (:meth:`StepClock.idle`): idle time
belongs to no leg.

**A stall** is a cycle over :data:`STALL_X` times the trailing median of
its kind and over :data:`STALL_MIN_S`: it stays out of the leg sums and
the histogram, so that a window's means are the steady state's, and is
counted by ``where`` the time stood (the ``dispatch`` call, a ``wait``,
the ``host``, or ``compile`` when the process compiled in it), kept as a
record (:attr:`StepClock.stalls`) and written once into the tracer's ring
as the engine-scope event ``stall``, which the collector lays on every
request trace that lived through it (docs/monitoring.md, "Reading a stall
record").  The record says what held the process: ``cpu_s``, the process's
own CPU seconds across the cycle, and ``gc_s``, the seconds of it the
garbage collector ran.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import time
import weakref

LEGS = ("wait", "starved", "overlap")
WHERE = ("dispatch", "wait", "host", "compile")

# Two constants, not knobs: a stall is a cycle over STALL_X times the
# trailing median of its kind AND over STALL_MIN_S.  The first keeps a
# 65 ms step's 2x jitter out, the second a 10 ms step's 10x one.
STALL_X = 8.0
STALL_MIN_S = 0.25
# The rule is silent until a kind has this many cycles behind it (its
# first uses compile, ROADMAP D12), and the median trails this many.
WARM_CYCLES = 32
TRAIL = 128
# The median is taken again every so many cycles: sorting 128 floats a
# dispatch would be a third of the clock's cost.
_MEDIAN_EVERY = 8
RECORDS = 64

# 5 ms - 2 s: a pipelined decode step of 10 ms to a chunk step of 120 ms
# with room on both sides; what lies over 2 s is a stall or a first use.
CYCLE_BUCKETS = [0.005, 0.0075, 0.01, 0.015, 0.02, 0.03, 0.04, 0.05, 0.065,
                 0.08, 0.1, 0.125, 0.15, 0.2, 0.3, 0.5, 0.75, 1.0, 2.0]
# The handlers' lag behind the door (stream_deliver_lag_seconds,
# stream_defer_lag_seconds) and the collector's late wakes
# (host_wake_late_seconds): 0.5 ms - 5 s.
LAG_BUCKETS = [0.0005, 0.001, 0.002, 0.003, 0.005, 0.0075, 0.01, 0.015,
               0.02, 0.03, 0.05, 0.075, 0.1, 0.15, 0.25, 0.5, 1.0, 2.5, 5.0]

# One kind's cumulative totals, in this order (``StepClock.snapshot``).
KIND_TOTALS = ("cycles", "cycle_s", "wait_s", "starved_s", "overlap_s",
               "call_s")
_NO_TOTALS = (0, 0.0, 0.0, 0.0, 0.0, 0.0)


def window(before: tuple, after: tuple) -> dict:
    """What the clock accounted between two :meth:`StepClock.snapshot`
    readings: ``{"kinds": {kind: {cycles, cycle_s, wait_s, starved_s,
    overlap_s, call_s}}, "stall_s"}``.  Whole cycles only, by when they
    CLOSED: for every kind the three legs sum to ``cycle_s``; a kind that
    closed no cycle and made no call between the two is left out."""
    kinds = {}
    for kind, b in after[0].items():
        a = before[0].get(kind, _NO_TOTALS)
        if b != a:
            kinds[kind] = {f: y - x for f, x, y in zip(KIND_TOTALS, a, b)}
    return {"kinds": kinds, "stall_s": after[1] - before[1]}


def _watch_gc(clock: "StepClock") -> None:
    """Feed ``clock`` the collector's start and stop (``gc.callbacks``).
    The hook holds the clock weakly and leaves the list with it: an engine
    that is dropped leaves nothing behind.  (Never from inside the hook:
    the collector walks the list by index while it calls.)"""
    ref = weakref.ref(clock)

    def hook(phase: str, info: dict) -> None:
        c = ref()
        if c is None:
            return
        gen = info["generation"]
        if phase == "start":
            c._gc_t0[gen] = time.monotonic()
        elif c._gc_t0[gen] is not None:     # (a stop whose start it saw)
            c._gc_s += time.monotonic() - c._gc_t0[gen]
            c._gc_t0[gen] = None

    def unhook() -> None:
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(hook)

    gc.callbacks.append(hook)
    weakref.finalize(clock, unhook).atexit = False


class _Trail:
    """The trailing median of one kind's cycles (None until it is warm)."""

    __slots__ = ("times", "n", "median")

    def __init__(self) -> None:
        self.times: collections.deque = collections.deque(maxlen=TRAIL)
        self.n = 0
        self.median: float | None = None

    def add(self, s: float) -> None:
        self.times.append(s)
        self.n += 1
        if self.n >= WARM_CYCLES and (self.n % _MEDIAN_EVERY == 0
                                      or self.median is None):
            self.median = sorted(self.times)[len(self.times) // 2]


class StepClock:
    """One engine's step clock.  ``metrics`` holds the families
    (``EngineMetrics``), ``tracer`` takes the ``stall`` event, ``state``
    returns (streams live, queue depth) and is called for a stall record
    only."""

    def __init__(self, metrics, tracer=None, state=None) -> None:
        self._legs = metrics.step_leg_seconds_total
        self._calls = metrics.step_call_seconds_total
        self._cycles = metrics.step_cycle_seconds
        self._stalls_n = metrics.step_stalls_total
        self._stall_s = metrics.step_stall_seconds_total
        self._compiles = metrics.xla_compilations_total
        self._tracer = tracer
        self._state = state
        self._trails: dict[str, _Trail] = {}
        # The open cycle: the kind that opened it (None: no cycle open),
        # when, its dispatch's rows, the waits so far, and since when
        # nothing was in flight (None: the device has work).
        self._kind: str | None = None
        self._t_open = 0.0
        self._rows = 0
        self._wait = 0.0
        self._starved_from: float | None = None
        self._compiled = 0.0
        # The trailing median the last closed cycle was judged by (None
        # until its kind is warm): ProfilerWindows.on_step reads this one.
        self.last_median: float | None = None
        self.stalls: collections.deque = collections.deque(maxlen=RECORDS)
        # Cycles a dispatch opened and idle() dropped: with the
        # histogram's counts and the stalls, every dispatch.
        self.abandoned = 0
        # Plain cumulative totals beside the families: ({kind: KIND_TOTALS
        # values}, stalled seconds, stalls).  Replaced WHOLE at a dispatch's
        # return and never written into afterwards, so a thread that reads
        # the attribute (a profiler window's two ends) holds one consistent
        # object: whole cycles, every kind's legs summing to its cycle_s.
        self._totals: tuple = ({}, 0.0, 0)
        # What a stall record says of the process: its own CPU seconds at
        # the open cycle's beginning, and the collector's seconds so far
        # (summed by the gc.callbacks hook, start stamps by generation).
        self._cpu_open = 0.0
        self._gc_t0: list[float | None] = [None, None, None]
        self._gc_s = 0.0
        self._gc_open = 0.0
        _watch_gc(self)

    def snapshot(self) -> tuple:
        """The cumulative totals as they stood at the last dispatch's
        return: ``({kind: (cycles, cycle_s, wait_s, starved_s, overlap_s,
        call_s)}, stall_s, stalls)``.  Safe from any thread; the difference
        of two is :func:`window`'s."""
        return self._totals

    def waited(self, t0: float, t1: float, inflight: int) -> None:
        """A resolve's blocking fetch ran from ``t0`` to ``t1`` and left
        ``inflight`` dispatches on the device."""
        if self._kind is None:
            return
        self._wait += t1 - t0
        if not inflight:
            # (Nothing can be waited for again before the next dispatch.)
            self._starved_from = t1

    def dispatched(self, kind: str, t_call: float, t_ret: float,
                   rows: int = 0) -> None:
        """A model dispatch call of ``kind`` ran from ``t_call`` to
        ``t_ret``: closes the open cycle there and opens the next."""
        call = t_ret - t_call
        compiled = self._compiles.total()
        cpu = time.process_time()
        kinds, stall_s, stalls = self._totals
        kinds = dict(kinds)
        if self._kind is not None and self._close(
                t_ret, call, compiled != self._compiled, cpu, kinds):
            stall_s, stalls = stall_s + (t_ret - self._t_open), stalls + 1
        else:
            self._calls.inc(call, kind=kind)
            t = kinds.get(kind, _NO_TOTALS)
            kinds[kind] = t[:5] + (t[5] + call,)
        self._totals = (kinds, stall_s, stalls)
        self._kind = kind
        self._t_open = t_ret
        self._rows = rows
        self._wait = 0.0
        self._starved_from = None
        self._compiled = compiled
        self._cpu_open = cpu
        self._gc_open = self._gc_s

    def idle(self) -> None:
        """The pod has nothing to run: the open cycle belongs to no leg."""
        if self._kind is not None:
            self._kind = None
            self.abandoned += 1

    def _close(self, t_ret: float, call: float, compiled: bool, cpu: float,
               kinds: dict) -> bool:
        """Account the open cycle (``kinds``: the totals being built for
        this dispatch's return); True if it was a stall."""
        kind = self._kind
        cycle = t_ret - self._t_open
        wait = self._wait
        starved = (0.0 if self._starved_from is None
                   else t_ret - self._starved_from)
        trail = self._trails.get(kind)
        if trail is None:
            trail = self._trails[kind] = _Trail()
        median = self.last_median = trail.median
        trail.add(cycle)
        if (median is not None and cycle > STALL_MIN_S
                and cycle > STALL_X * median):
            self._stalled(t_ret, kind, cycle, median, call, wait, compiled,
                          cpu)
            return True
        overlap = max(cycle - wait - starved, 0.0)
        legs = self._legs
        legs.inc(wait, kind=kind, leg="wait")
        legs.inc(starved, kind=kind, leg="starved")
        legs.inc(overlap, kind=kind, leg="overlap")
        self._cycles.observe(cycle, kind=kind)
        n, cycle_s, wait_s, starved_s, overlap_s, call_s = kinds.get(
            kind, _NO_TOTALS)
        kinds[kind] = (n + 1, cycle_s + cycle, wait_s + wait,
                       starved_s + starved, overlap_s + overlap, call_s)
        return False

    def _stalled(self, t_ret, kind, cycle, median, call, wait, compiled,
                 cpu) -> None:
        # The leg that held the excess: a stalled cycle is over eight
        # medians long, so its longest part is over two and no sound leg.
        host = cycle - call - wait
        if compiled:
            where = "compile"
        elif call >= wait and call >= host:
            where = "dispatch"
        elif wait >= host:
            where = "wait"
        else:
            where = "host"
        self._stalls_n.inc(1, where=where)
        self._stall_s.inc(cycle, where=where)
        streams, queued = self._state() if self._state else (0, 0)
        # wake_late_s: the latest wake of the tracer's collector inside
        # the stall, filled in by the collector when it folds the event
        # (obs/trace.py): about as late as the stall is long = every
        # Python thread stood; punctual = only this thread's call blocked.
        # cpu_s: the process's own CPU seconds across the cycle (all its
        # threads): near 0 = it was not scheduled, near ``seconds`` or over
        # = something computed, holding the GIL or beside it.  gc_s: the
        # collector's part of the cycle.
        record = {"t_monotonic": t_ret, "kind": kind, "where": where,
                  "seconds": cycle, "median_s": median, "call_s": call,
                  "wait_s": wait, "rows": self._rows, "streams": streams,
                  "queued": queued, "wake_late_s": None,
                  "cpu_s": cpu - self._cpu_open,
                  "gc_s": self._gc_s - self._gc_open}
        self.stalls.append(record)
        if self._tracer is not None:
            self._tracer.evt("", "stall", "I", record)
