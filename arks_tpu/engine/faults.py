"""Fault taxonomy, injection, and escalation for the serving engine.

DeepServe (PAPERS.md, arxiv 2501.14417) treats fast request-preserving
recovery as a first-class serving requirement; this module is the engine's
vocabulary for it:

- **StepFault**: the typed wrapper every scheduler-phase fault is raised
  as.  It carries *blast-radius attribution* — which request(s) the
  failing operation was doing work for (``culprits``) — plus any request
  state that would otherwise be stranded in locals when the stack unwinds
  (``survivors``).  The engine's recovery loop quarantines only the
  culprits (bounded per-request retry budget, ``ARKS_FAULT_RETRIES``) and
  token-replays everyone else.
- **FaultInjector**: the ``ARKS_FAULT_INJECT`` chaos hook.  Spec:
  comma-separated ``phase:nth:kind`` entries (``decode:3:runtime`` = raise
  a RuntimeError at the 3rd decode-dispatch issue).  Threaded through
  every dispatch/resolve/alloc point so the chaos suite can kill any
  scheduler phase deterministically.  Phases: ``decode`` (any
  decode-carrying model dispatch issue, incl. pipelined and mixed),
  ``resolve`` (their host-sync tails), ``admit`` / ``admit_resolve``
  (fused admissions), ``chunk`` (chunked-prefill dispatch), ``replay``
  (recovery re-admission), ``pages`` (page-table growth/alloc),
  ``guide`` (guide-table upload), ``spec`` (speculative dispatch),
  ``preempt`` (preemptive-swap spill issue/harvest and victim resume —
  culprit is the preempted/resuming request only), ``disk_spill``
  (tier-2 disk spill issue — serves no request, so nobody's retry
  budget burns), ``peer_fetch`` (disk/peer prefix-block fetch resolve —
  culprit is the fetching request only), ``residency`` (windowed-
  residency span step: engage/spill/prefetch/forward — culprits are
  the window-engaged requests only), ``resize`` (elastic topology
  resize seams: drain / reshard / resume — fired at a fully drained
  boundary after every stream was preempted to the host, so NOBODY is
  quarantined; drain/reshard faults recover at the old shape, a
  resume fault at the new one).
  Kinds: ``runtime``, ``value``, ``oom`` (RESOURCE_EXHAUSTED-shaped
  RuntimeError), ``hang`` (sleeps ``ARKS_FAULT_HANG_S``, default 3600 —
  the watchdog-escalation fixture).
- **Watchdog**: detects a wedged device dispatch — a ``step()`` that has
  not returned within ``ARKS_DISPATCH_DEADLINE_S`` — flips the engine
  state to ``wedged`` (readiness then 503s), dumps the in-flight
  diagnostics, and escalates to ``os._exit(70)`` so the pod supervisor
  restarts the process (the same shared-fate policy as a broken gang
  dispatch channel, engine._emit).  Disabled at 0 (the default): the
  deadline must be set ABOVE the worst first-dispatch jit compile, which
  also runs inside step().
- **swallowed()**: the sanctioned route for the few handlers that
  intentionally swallow an exception (platform capability probes, debug
  introspection).  tests/test_fault_guard.py statically REQUIRES every
  ``except Exception`` under arks_tpu/engine/ to re-raise or call into
  this module — a silent swallow cannot merge.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from arks_tpu.utils import knobs

log = logging.getLogger("arks_tpu.faults")

# Engine state codes surfaced by the engine_state gauge (docs/monitoring.md).
STATE_SERVING = 0
STATE_RECOVERING = 1
STATE_WEDGED = 2
STATE_CODES = {"serving": STATE_SERVING, "recovering": STATE_RECOVERING,
               "wedged": STATE_WEDGED}


class StepFault(Exception):
    """A scheduler-phase fault with blast-radius attribution.

    ``phase``     the scheduler phase that faulted (metric label).
    ``kind``      coarse failure class (metric label; see classify()).
    ``culprits``  request ids the failing operation was doing work FOR —
                  the quarantine set (retry-budget accounting).
    ``survivors`` request-state descriptors (engine._Survivor) that only
                  lived in the failing frame's locals: un-registered
                  admissions, not-yet-replayed recovery snapshots.  The
                  recovery loop re-admits them; without this they would be
                  stranded (client blocks forever).
    """

    def __init__(self, phase: str, kind: str, culprits=(), survivors=(),
                 message: str = ""):
        super().__init__(message or f"engine fault in phase {phase!r} ({kind})")
        self.phase = phase
        self.kind = kind
        self.culprits = tuple(culprits)
        self.survivors = list(survivors)


class InjectedFault(RuntimeError):
    """Raised by FaultInjector.fire(); distinguishable in logs/tests."""


def classify(exc: BaseException) -> str:
    """Coarse fault kind for the engine_faults_total metric label.
    Deliberately low-cardinality: dashboards alert on (phase, kind), and
    one label value per exception class would explode the family."""
    if isinstance(exc, StepFault):
        return exc.kind
    msg = f"{type(exc).__name__}: {exc}"
    if "RESOURCE_EXHAUSTED" in msg or isinstance(exc, MemoryError):
        return "oom"
    if isinstance(exc, InjectedFault):
        return "injected"
    if isinstance(exc, (ValueError, TypeError, KeyError, IndexError)):
        return "value"
    if isinstance(exc, OSError):
        return "io"
    return "runtime"


_KINDS = ("runtime", "value", "oom", "hang")


class FaultInjector:
    """ARKS_FAULT_INJECT chaos hook: ``phase:nth:kind[,phase:nth:kind...]``.

    ``nth`` is the 1-based occurrence of ``fire(phase)`` calls for that
    phase; each spec entry fires at most once.  Engine-thread only (the
    counters are unsynchronized on purpose — all fire sites run on the
    scheduler thread)."""

    def __init__(self, spec: str | None = None):
        self._specs: list[list] = []   # [phase, nth, kind, armed]
        self._counts: dict[str, int] = {}
        spec = (knobs.get_str("ARKS_FAULT_INJECT", fallback="") or ""
                ) if spec is None else spec
        if spec:
            for entry in spec.split(","):
                self.arm(entry)

    def arm(self, entry: str) -> None:
        """Add one ``phase:nth:kind`` spec (env parsing and the
        chaos harness's programmatic injection)."""
        entry = entry.strip()
        if not entry:
            return
        parts = entry.split(":")
        if len(parts) != 3:
            raise ValueError(
                f"ARKS_FAULT_INJECT entry {entry!r}: expected phase:nth:kind")
        phase, nth_s, kind = parts
        try:
            nth = int(nth_s)
        except ValueError:
            raise ValueError(
                f"ARKS_FAULT_INJECT entry {entry!r}: nth must be an integer")
        if nth < 1:
            raise ValueError(
                f"ARKS_FAULT_INJECT entry {entry!r}: nth must be >= 1")
        if kind not in _KINDS:
            raise ValueError(
                f"ARKS_FAULT_INJECT entry {entry!r}: kind must be one of "
                f"{_KINDS}")
        self._specs.append([phase, nth, kind, True])

    @property
    def active(self) -> bool:
        return bool(self._specs)

    def fire(self, phase: str) -> None:
        """Count one occurrence of ``phase``; raise if a spec matches."""
        if not self._specs:
            return
        n = self._counts.get(phase, 0) + 1
        self._counts[phase] = n
        for spec in self._specs:
            if spec[3] and spec[0] == phase and spec[1] == n:
                spec[3] = False
                kind = spec[2]
                log.warning("fault injection: phase=%s nth=%d kind=%s",
                            phase, n, kind)
                if kind == "hang":
                    time.sleep(knobs.get_float("ARKS_FAULT_HANG_S"))
                    return
                if kind == "oom":
                    raise InjectedFault(
                        f"RESOURCE_EXHAUSTED (injected at {phase}:{n})")
                if kind == "value":
                    raise ValueError(f"injected fault at {phase}:{n}")
                raise InjectedFault(f"injected fault at {phase}:{n}")


def swallowed(site: str, exc: BaseException | None = None) -> None:
    """Record an INTENTIONALLY swallowed exception (capability probes,
    best-effort introspection).  The one sanctioned alternative to
    re-raising under arks_tpu/engine/ (tests/test_fault_guard.py): the
    debug log keeps the swallow observable without turning a benign probe
    failure into a serving fault."""
    log.debug("swallowed exception at %s: %s", site, exc, exc_info=exc)


class Watchdog:
    """Wedged-dispatch detector: ``heartbeat()`` returns (phase, t0) of
    the in-flight scheduler step (None when idle); if a step overruns the
    deadline the watchdog calls ``on_wedged()`` (flip state/readiness,
    dump diagnostics) and escalates through ``exit_fn(70)`` so the pod
    supervisor restarts the process.  ``exit_fn`` is injectable for
    tests; production uses os._exit — a wedged device call cannot be
    cancelled from Python, so a clean shutdown is not on the table."""

    def __init__(self, deadline_s: float, heartbeat, on_wedged,
                 exit_fn=os._exit):
        self.deadline_s = deadline_s
        self._heartbeat = heartbeat
        self._on_wedged = on_wedged
        self._exit_fn = exit_fn
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name="watchdog",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        poll = max(min(self.deadline_s / 4.0, 1.0), 0.02)
        while not self._stop.wait(poll):
            hb = self._heartbeat()
            if hb is None:
                continue
            phase, t0 = hb
            age = time.monotonic() - t0
            if age <= self.deadline_s:
                continue
            log.critical(
                "engine step wedged for %.1fs (> ARKS_DISPATCH_DEADLINE_S="
                "%.1fs) in phase %r; flipping readiness and exiting 70 so "
                "the supervisor restarts the pod", age, self.deadline_s,
                phase)
            try:
                self._on_wedged(phase, age)
            except Exception as e:  # the escalation must not be derailed
                swallowed("watchdog.on_wedged", e)
            self._exit_fn(70)
            return
