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
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time

from arks_tpu.utils import knobs
from arks_tpu.utils.swallow import swallowed

log = logging.getLogger("arks_tpu.profiler")

CLOCK_ANCHOR = "arks_clock"


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


class ProfilerWindows:
    def __init__(self, base_dir: str | None = None, tracer=None) -> None:
        self.base_dir = base_dir or knobs.get_str("ARKS_PROF_DIR")
        self.auto_mult = knobs.get_float("ARKS_PROF_AUTO_ARM",
                                         fallback=0.0)
        self.window_s = knobs.get_float("ARKS_PROF_WINDOW_S")
        self.active = False
        # True while a window is open: the step loop's section sites test
        # this one attribute (no environment variable, no second switch).
        self.sections = False
        self.tracer = tracer
        # The last closed window: {"dir", "t0_monotonic", "t1_monotonic",
        # "spans"} — what stop() returned, kept for in-process readers.
        self.last_window: dict | None = None
        self._t0: float | None = None
        self.dir: str | None = None
        self.auto_armed_total = 0
        self._lock = threading.Lock()
        self._auto_end: float | None = None

    def _anchor(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(anchor_name(time.monotonic_ns())):
            pass

    def start(self, logdir: str | None = None) -> dict:
        """Open a profiler window and switch the step-section spans on.
        Returns {"ok", "dir", "t0_monotonic"} or an error."""
        with self._lock:
            if self.active:
                return {"ok": False, "error": "already_active",
                        "dir": self.dir}
            d = logdir or os.path.join(
                self.base_dir, time.strftime("%Y%m%d-%H%M%S"))
            try:
                os.makedirs(d, exist_ok=True)
                import jax
                jax.profiler.start_trace(d)
                self._anchor()
            except Exception as e:
                log.debug("profiler start failed", exc_info=True)
                return {"ok": False, "error": f"{type(e).__name__}: {e}"}
            self.dir = d
            self._t0 = time.monotonic()
            if self.tracer is not None:
                self.tracer.open_window()
            self.active = self.sections = True
            return {"ok": True, "dir": d, "t0_monotonic": self._t0}

    def stop(self) -> dict:
        """Close the window.  Returns {"ok", "dir", "t0_monotonic",
        "t1_monotonic", "spans"}: the engine-scope spans (scheduler phases
        and their sections, ``pipe``, ``compile`` ...) recorded since
        ``start()``, on ``time.monotonic``."""
        with self._lock:
            if not self.active:
                return {"ok": False, "error": "not_active"}
            self.active = self.sections = False
            self._auto_end = None
            d, self.dir = self.dir, None
            t1 = time.monotonic()
            spans = (self.tracer.close_window()
                     if self.tracer is not None else [])
            out = {"dir": d, "t0_monotonic": self._t0, "t1_monotonic": t1,
                   "spans": spans}
            self.last_window = out
            try:
                import jax
                self._anchor()
                jax.profiler.stop_trace()
            except Exception as e:
                log.debug("profiler stop failed", exc_info=True)
                return {"ok": False, "error": f"{type(e).__name__}: {e}",
                        **out}
            return {"ok": True, **out}

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
            r = self.start()
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
