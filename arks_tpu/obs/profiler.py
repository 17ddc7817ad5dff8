"""On-demand ``jax.profiler`` windows.

Armed two ways:

- **HTTP**: ``POST /v1/profiler/start`` / ``POST /v1/profiler/stop`` on
  the serving port — writes a profiler trace dir an operator can open in
  TensorBoard / Perfetto.
- **Auto-arm**: when a step's wall time jumps past
  ``ARKS_PROF_AUTO_ARM`` × the trailing median of the step cycles (default
  0 = off; the median is the step clock's, ``obs/stepclock.py``, the one
  its stall rule reads), a window of ``ARKS_PROF_WINDOW_S`` seconds opens
  by itself — the profile of the anomaly, captured while it is still
  happening.

While a window is active the engine run loop wraps each step in a
``jax.profiler.TraceAnnotation`` carrying the live request/trace ids, so
device timelines correlate back to the span timelines in the TraceStore.
All hooks are called from the run loop (not the guarded hot-path
functions) and early-return to a couple of float compares when idle.

**A window runs without the profiler's Python tracer unless it is asked
for** (``start(python=True)``): that tracer hooks every Python call of
every thread while the window lasts, which slows the host the window is
there to time, and no reader of this repository takes its events.  Who
asks: ``POST /v1/profiler/start`` with ``{"python": true}`` and the
auto-armed window, both read by a person looking for where the host stood.
The host tracer (TraceMe: the annotations below, the runtime's own) stays
as JAX sets it.

A window is also the ONE switch of the step-section spans
(``phase.<phase>.<section>``, docs/monitoring.md): ``sections`` is True
between ``start()`` and ``stop()``, the step loop tests that attribute at
each section site and records nothing otherwise, and ``stop()`` hands back
the engine-scope spans of the window.  The two clocks are joined by
anchors: a ``TraceAnnotation`` named ``arks_clock[<time.monotonic_ns()>]``
right after ``start_trace`` returns and another right before
``stop_trace``, so a reduction reads (trace clock - ``time.monotonic``)
from the anchors' own timestamps instead of guessing when the profiler
began to collect.

A window also MARKS THE STEP CLOCK (``obs/stepclock.py``): it snapshots the
clock's totals after the opening anchor and before the closing one, and
``stop()`` hands back their difference under ``"clock"``: the cycles that
closed inside the window, by kind and leg, and the stalls.  Held against
the same families' deltas over an untraced stretch, that says what the open
window itself cost the pod.  And ``stop()`` leaves the window's engine-scope
spans beside the profile as a Chrome trace file (:data:`SPANS_FILE`), on
the profile's clock: the host's sections over the device's ops, where the
Python frames used to be the only host detail.
"""

from __future__ import annotations

import contextlib
import glob
import json
import logging
import os
import threading
import time

from arks_tpu.obs import perfetto, stepclock
from arks_tpu.utils import knobs
from arks_tpu.utils.swallow import swallowed

log = logging.getLogger("arks_tpu.profiler")

CLOCK_ANCHOR = "arks_clock"
SPANS_FILE = "arks_spans.trace.json"


def anchor_name(monotonic_ns: int) -> str:
    return f"{CLOCK_ANCHOR}[{monotonic_ns}]"


def anchor_offset_s(name: str, trace_start_s: float) -> float | None:
    """Trace clock minus ``time.monotonic``, from one anchor event: its
    name carries the monotonic reading taken as it was emitted, the trace
    gives its start on the trace's clock.  None: not an anchor."""
    if not (name.startswith(CLOCK_ANCHOR + "[") and name.endswith("]")):
        return None
    try:
        ns = int(name[len(CLOCK_ANCHOR) + 1:-1])
    except ValueError:
        return None
    return trace_start_s - ns * 1e-9


def _trace_options(python: bool):
    """``jax.profiler.ProfileOptions`` with the Python tracer on or off and
    everything else as JAX sets it; None on a JAX without them (the trace
    then starts as that JAX starts it)."""
    import jax
    if not hasattr(jax.profiler, "ProfileOptions"):
        return None
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python else 0
    return options


def _find_anchor(profile_dir: str, name: str) -> float | None:
    """Where the anchor called ``name`` starts on the clock of the profile
    under ``profile_dir``, in seconds; None: no profile, or no such event."""
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns * 1e-9
    return None


class ProfilerWindows:
    def __init__(self, base_dir: str | None = None, tracer=None,
                 clock=None) -> None:
        self.base_dir = base_dir or knobs.get_str("ARKS_PROF_DIR")
        self.auto_mult = knobs.get_float("ARKS_PROF_AUTO_ARM",
                                         fallback=0.0)
        self.window_s = knobs.get_float("ARKS_PROF_WINDOW_S")
        self.active = False
        # True while a window is open: the step loop's section sites test
        # this one attribute (no environment variable, no second switch).
        self.sections = False
        self.tracer = tracer
        # The engine's step clock (None: a window returns no "clock").
        self.clock = clock
        # The last closed window: {"dir", "python", "t0_monotonic",
        # "t1_monotonic", "spans", "clock"} — what stop() returned, kept
        # for in-process readers.
        self.last_window: dict | None = None
        self._t0: float | None = None
        self._python = False
        self._anchor0 = ""
        self._clock0: tuple | None = None
        self.dir: str | None = None
        self.auto_armed_total = 0
        self._lock = threading.Lock()
        self._auto_end: float | None = None

    def _anchor(self) -> str:
        import jax
        name = anchor_name(time.monotonic_ns())
        with jax.profiler.TraceAnnotation(name):
            pass
        return name

    def start(self, logdir: str | None = None, python: bool = False) -> dict:
        """Open a profiler window and switch the step-section spans on;
        ``python``: with the profiler's Python tracer (every Python call of
        every thread hooked while the window lasts).  Returns {"ok", "dir",
        "python", "t0_monotonic"} or an error."""
        with self._lock:
            if self.active:
                return {"ok": False, "error": "already_active",
                        "dir": self.dir}
            d = logdir or os.path.join(
                self.base_dir, time.strftime("%Y%m%d-%H%M%S"))
            try:
                os.makedirs(d, exist_ok=True)
                import jax
                options = _trace_options(python)
                if options is None:
                    jax.profiler.start_trace(d)
                else:
                    jax.profiler.start_trace(d, profiler_options=options)
                self._anchor0 = self._anchor()
            except Exception as e:
                log.debug("profiler start failed", exc_info=True)
                return {"ok": False, "error": f"{type(e).__name__}: {e}"}
            self.dir = d
            self._python = bool(python) or options is None
            self._clock0 = (self.clock.snapshot()
                            if self.clock is not None else None)
            self._t0 = time.monotonic()
            if self.tracer is not None:
                self.tracer.open_window()
            self.active = self.sections = True
            return {"ok": True, "dir": d, "python": self._python,
                    "t0_monotonic": self._t0}

    def stop(self) -> dict:
        """Close the window.  Returns {"ok", "dir", "python",
        "t0_monotonic", "t1_monotonic", "spans", "clock"}: the engine-scope
        spans (scheduler phases and their sections, ``pipe``, ``compile``
        ...) recorded since ``start()``, on ``time.monotonic``, and what
        the step clock accounted meanwhile (:meth:`_clock_window`)."""
        with self._lock:
            if not self.active:
                return {"ok": False, "error": "not_active"}
            self.active = self.sections = False
            self._auto_end = None
            d, self.dir = self.dir, None
            t1 = time.monotonic()
            spans = (self.tracer.close_window()
                     if self.tracer is not None else [])
            out = {"dir": d, "python": self._python,
                   "t0_monotonic": self._t0, "t1_monotonic": t1,
                   "spans": spans}
            if self._clock0 is not None:
                out["clock"] = self._clock_window(t1)
            self.last_window = out
            try:
                import jax
                self._anchor()
                jax.profiler.stop_trace()
            except Exception as e:
                log.debug("profiler stop failed", exc_info=True)
                return {"ok": False, "error": f"{type(e).__name__}: {e}",
                        **out}
            # A quiet window's host detail.  A Python window has its frames
            # for that, a profile of hundreds of MB to read the anchor back
            # from, and may be closing on the engine thread (auto-armed).
            if not self._python:
                try:
                    self._write_spans(d, spans)
                except Exception as e:
                    # The profile stands without its spans file.
                    swallowed("profiler.spans_file", e, warn=True)
            return {"ok": True, **out}

    def _clock_window(self, t1: float) -> dict:
        """The step clock between the window's two ends: ``{"kinds": {kind:
        {cycles, cycle_s, wait_s, starved_s, overlap_s, call_s}},
        "stall_s", "stalls"}``: whole cycles that CLOSED inside the window,
        so a kind's three legs sum to its ``cycle_s``; ``stalls`` the
        records whose ``t_monotonic`` lies in it."""
        out = stepclock.window(self._clock0, self.clock.snapshot())
        out["stalls"] = [dict(r) for r in list(self.clock.stalls)
                         if self._t0 <= r["t_monotonic"] <= t1]
        return out

    def _write_spans(self, d: str, spans: list[dict]) -> None:
        """The window's spans as a Chrome trace file beside the profile,
        moved onto the profile's clock by the opening anchor's offset (no
        profile or no anchor in it: no file)."""
        at = _find_anchor(d, self._anchor0)
        offset = (None if at is None
                  else anchor_offset_s(self._anchor0, at))
        if offset is None:
            return
        moved = [dict(s, start=s["start"] + offset,
                      end=None if s.get("end") is None
                      else s["end"] + offset)
                 for s in spans if s.get("start") is not None]
        with open(os.path.join(d, SPANS_FILE), "w") as f:
            json.dump(perfetto.chrome_trace([], moved), f)

    def on_step(self, dur_s: float, median_s: float | None = None) -> None:
        """Run-loop hook: one step's wall time and the trailing median of
        the step cycles (``StepClock.last_median``; None while it is not
        warm, or for a step that only waited for a request).  Closes an
        expired auto window; opens one when the step time spikes past
        ``auto_mult`` × the median."""
        if self.active:
            if self._auto_end is not None and time.monotonic() > self._auto_end:
                self.stop()
            return
        if self.auto_mult <= 0 or not median_s:
            return
        if dur_s > self.auto_mult * median_s:
            # Its reader is a person looking for where the host stood: the
            # one window that wants the Python frames.
            r = self.start(python=True)
            if r.get("ok"):
                self._auto_end = time.monotonic() + self.window_s
                self.auto_armed_total += 1

    def annotate(self, name: str, ids: str = ""):
        """A ``jax.profiler.TraceAnnotation`` stamping the live span ids
        into the device timeline; a null context if jax is unavailable."""
        try:
            import jax
            label = f"{name}[{ids}]" if ids else name
            return jax.profiler.TraceAnnotation(label)
        except Exception as e:
            # No jax (pure-I/O process) → annotations are a no-op.
            swallowed("profiler.annotate", e)
            return contextlib.nullcontext()
