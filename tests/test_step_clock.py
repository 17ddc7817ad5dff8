"""The step loop's own clock (PR 38, ``arks_tpu/obs/stepclock.py``).

The clock takes every reading as an argument, so the unit cases below hand
it made-up times: nothing here is judged by the CPU's wall clock.  The
engine cases assert counts, sums against the run's own wall time as an
upper bound, and which families stand on ``/metrics``.
"""

import gc
import json
import sys
import threading
import time
import types
import urllib.request

import pytest

from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                             SamplingParams)
from arks_tpu.engine import engine as engine_mod
from arks_tpu.engine.engine import EngineMetrics
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config
from arks_tpu.obs import stepclock
from arks_tpu.obs import trace as trace_mod
from arks_tpu.obs.stepclock import StepClock

import harness

LEGS = stepclock.LEGS


def _legs(m, kind):
    return {leg: m.step_leg_seconds_total.get(kind=kind, leg=leg)
            for leg in LEGS}


def _cycles(m, kind=None):
    """(count, sum) of ``step_cycle_seconds`` of one kind, or of all."""
    n = s = 0.0
    for key, (_, total, count) in m.step_cycle_seconds._data.items():
        if kind is None or dict(key)["kind"] == kind:
            n, s = n + count, s + total
    return n, s


def _stalls(m):
    return {w: (m.step_stalls_total.get(where=w),
                m.step_stall_seconds_total.get(where=w))
            for w in stepclock.WHERE}


class _Seq:
    """Drives a clock through sequential steps on a made-up time line: a
    dispatch call of ``call`` s, ``overlap`` s of host work beside the
    device, a wait of ``wait`` s that leaves nothing in flight, ``gap`` s
    of host work before the next call."""

    def __init__(self, clock, kind="seq"):
        self.clock, self.kind, self.t = clock, kind, 100.0

    def step(self, call=0.001, overlap=0.002, wait=0.050, gap=0.004,
             kind=None):
        c = self.clock
        c.dispatched(kind or self.kind, self.t, self.t + call, rows=7)
        self.t += call + overlap
        c.waited(self.t, self.t + wait, 0)
        self.t += wait + gap


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["seq", "seq_tail", "pipe"])
def test_the_three_legs_sum_to_the_cycle(kind):
    m = EngineMetrics()
    s = _Seq(StepClock(m), kind)
    for _ in range(11):
        s.step()
    legs = _legs(m, kind)
    n, total = _cycles(m, kind)
    assert n == 10                      # the eleventh cycle is still open
    assert sum(legs.values()) == pytest.approx(total)
    assert total == pytest.approx(10 * 0.057)
    assert legs["wait"] == pytest.approx(10 * 0.050)
    # From the wait's end to the next call's RETURN: gap + call.
    assert legs["starved"] == pytest.approx(10 * 0.005)
    assert legs["overlap"] == pytest.approx(10 * 0.002)
    # The calls are summed beside the legs, the first one (which closed
    # nothing) included.
    assert m.step_call_seconds_total.get(kind=kind) == pytest.approx(0.011)
    assert _cycles(m) == (n, total)     # no other kind was written


def test_a_cycle_has_the_kind_of_the_dispatch_that_opened_it():
    """seq, seq_tail, pipe in turn: each cycle is the opening dispatch's
    wait and the host work up to the next call's return, so a kind's mean
    cycle is its own step plus what the host added, whatever came next."""
    m = EngineMetrics()
    s = _Seq(StepClock(m))
    for _ in range(5):
        s.step(kind="seq", wait=0.060)
        s.step(kind="seq_tail", wait=0.020)
        s.step(kind="pipe", wait=0.010)
    s.step(kind="seq")
    for kind, wait in (("seq", 0.060), ("seq_tail", 0.020), ("pipe", 0.010)):
        n, total = _cycles(m, kind)
        assert n == 5
        assert _legs(m, kind)["wait"] == pytest.approx(5 * wait)
        assert total == pytest.approx(5 * (wait + 0.007))


def test_a_pipeline_that_always_has_a_dispatch_in_flight_is_never_starved():
    m = EngineMetrics()
    c = StepClock(m)
    t = 10.0
    c.dispatched("pipe", t, t + 0.001)
    for _ in range(20):                 # depth 1 behind the one in flight:
        t += 0.002                      # issue the next, then wait for the
        c.dispatched("pipe", t, t + 0.001)     # older one
        c.waited(t + 0.001, t + 0.011, 1)
        t += 0.011
    legs = _legs(m, "pipe")
    assert legs["starved"] == 0.0
    assert legs["wait"] == pytest.approx(19 * 0.010 + 0.0)
    n, total = _cycles(m, "pipe")
    assert n == 20 and sum(legs.values()) == pytest.approx(total)


def test_a_wait_that_leaves_nothing_in_flight_starts_the_starved_leg():
    """A pipeline's drain: two waits, the second leaves nothing; what
    follows it up to the next call's return is starved, the first wait's
    tail is not."""
    m = EngineMetrics()
    c = StepClock(m)
    c.dispatched("pipe", 0.0, 0.001)
    c.dispatched("pipe", 0.002, 0.003)
    c.waited(0.003, 0.010, 1)           # the older one: one still runs
    c.waited(0.012, 0.020, 0)           # the last one: the device is empty
    c.dispatched("seq", 0.030, 0.031)
    legs = _legs(m, "pipe")
    assert legs["starved"] == pytest.approx(0.011)
    assert legs["wait"] == pytest.approx(0.015)
    # The first cycle whole (the second call beside a running step) and
    # the host work between the two waits.
    assert legs["overlap"] == pytest.approx(0.002 + 0.002)
    assert _cycles(m, "pipe") == (2, pytest.approx(0.030))


def test_an_idle_pod_abandons_the_open_cycle():
    m = EngineMetrics()
    s = _Seq(StepClock(m))
    s.step()
    s.step()
    s.clock.idle()
    s.clock.idle()                      # nothing open: nothing to drop
    s.t += 500.0                        # nobody asked for anything
    s.step()
    s.step()
    assert s.clock.abandoned == 1
    n, total = _cycles(m, "seq")
    assert n == 2 and total == pytest.approx(2 * 0.057)
    assert all(v == (0.0, 0.0) for v in _stalls(m).values())


# ---------------------------------------------------------------------------
# Stalls
# ---------------------------------------------------------------------------


def _warm(kind="seq", tracer=None, state=None, n=40):
    m = EngineMetrics()
    s = _Seq(StepClock(m, tracer, state), kind)
    for _ in range(n + 1):
        s.step()
    return m, s


_SOUND = dict(call=0.001, overlap=0.002, wait=0.050, gap=0.004)


@pytest.mark.parametrize("where, part, slow", [
    ("dispatch", "call", 0.6), ("wait", "wait", 0.65),
    ("host", "gap", 0.6), ("host", "overlap", 0.6)])
def test_a_stalled_cycle_is_counted_where_the_time_stood(where, part, slow):
    events = []

    class Ring:
        def evt(self, *rec):
            events.append(rec)

    m, s = _warm(tracer=Ring(), state=lambda: (5, 3))
    calls = m.step_call_seconds_total.get(kind="seq")
    # Over ten medians and over 0.25 s.  A slow call lies in the cycle its
    # own return closes; any other part in the one the NEXT return closes.
    s.step(**{part: slow})
    if part != "call":
        s.step()
    cycle = 0.057 - _SOUND[part] + slow
    got = _stalls(m)
    assert got[where] == (1.0, pytest.approx(cycle))
    assert sum(n for n, _ in got.values()) == 1
    # It is in no mean: every cycle in the sums is a sound one.
    n, total = _cycles(m, "seq")
    assert n in (40, 41) and total == pytest.approx(n * 0.057)
    assert sum(_legs(m, "seq").values()) == pytest.approx(total)
    assert m.step_call_seconds_total.get(kind="seq") == pytest.approx(
        calls + (0.0 if part == "call" else 0.001))
    (rec,) = s.clock.stalls
    assert [e[1:3] for e in events] == [("stall", "I")]
    assert events[0][0] == "" and events[0][3] is rec
    assert rec["where"] == where and rec["kind"] == "seq"
    assert rec["seconds"] == pytest.approx(cycle)
    assert rec["median_s"] == pytest.approx(0.057)
    assert (rec["rows"], rec["streams"], rec["queued"]) == (7, 5, 3)
    assert rec["wake_late_s"] is None   # the collector fills it in
    s.step()
    s.step()                            # sound cycles count again
    assert _cycles(m, "seq")[0] == n + 2 and len(s.clock.stalls) == 1


def test_a_cycle_that_compiled_is_never_a_stall_of_the_machine():
    m, s = _warm()
    s.step()
    m.xla_compilations_total.inc()      # a first use compiles (ROADMAP D12)
    s.step(call=3.0)
    assert _stalls(m)["compile"] == (1.0, pytest.approx(3.056))
    assert s.clock.stalls[-1]["where"] == "compile"


def test_the_rule_is_silent_until_a_kind_has_thirty_two_cycles():
    m, s = _warm(n=stepclock.WARM_CYCLES - 2)
    s.step(gap=5.0)                     # the 31st and 32nd cycle
    s.step()
    assert s.clock.last_median is None
    assert all(v == (0.0, 0.0) for v in _stalls(m).values())
    assert _cycles(m, "seq")[0] == stepclock.WARM_CYCLES
    s.step(gap=5.0)                     # now the kind is warm
    s.step()
    assert _stalls(m)["host"][0] == 1
    # Another kind starts cold beside it.
    s.step(kind="seq_tail", gap=5.0)
    s.step(kind="seq_tail")
    assert _stalls(m)["host"][0] == 1


def test_a_long_cycle_under_a_quarter_second_is_no_stall():
    m = EngineMetrics()
    s = _Seq(StepClock(m), "pipe")
    for _ in range(41):
        s.step(wait=0.008, overlap=0.001, gap=0.0)
    s.step(wait=0.200, overlap=0.001, gap=0.0)      # 20 medians, 0.2 s
    s.step(wait=0.008, overlap=0.001, gap=0.0)
    assert all(v == (0.0, 0.0) for v in _stalls(m).values())
    assert _cycles(m, "pipe")[0] == 42


def test_requests_that_lived_through_a_stall_keep_their_trace_with_it(
        monkeypatch):
    """The record goes through the tracer's ring once; the collector lays
    it, as a span over the stalled cycle, on every request trace that
    overlaps it and flags the trace, so sampling cannot drop it; a request
    that ended before the stall is sampled as ever."""
    monkeypatch.setenv("ARKS_TRACE", "1")
    monkeypatch.setenv("ARKS_TRACE_SAMPLE", "0.0")
    tr = trace_mod.Tracer()
    m, s = _warm(tracer=tr)
    # The ring stamps events itself: put its clock on the made-up line.
    monkeypatch.setattr(trace_mod, "time",
                        types.SimpleNamespace(monotonic=lambda: s.t))
    tr.evt("early", "queue", "B")
    tr.evt("early", "finish", "I", "length")
    s.step()
    tr.evt("live", "queue", "B")
    s.step(gap=3.0)
    s.step()
    tr.evt("live", "finish", "I", "length")
    tr.flush()
    assert tr.store.get("early") is None
    trace = tr.store.get("live")
    assert trace["flags"] == ["stalled"]
    (span,) = [sp for sp in trace["spans"] if sp["name"] == "stall"]
    assert span["end"] - span["start"] == pytest.approx(3.053)
    assert span["arg"]["where"] == "host"
    assert span["arg"]["seconds"] == pytest.approx(3.053)
    json.dumps(trace)                   # the record is JSON-plain there


def test_a_late_collector_wake_reaches_the_stall_record(monkeypatch):
    """``wake_late_s`` needs no thread of its own: the collector notes how
    late each of its timed waits returned, and a stall is given the latest
    wake inside it.  A wake about as late as the stall is long: every
    Python thread stood.  Punctual wakes: only the engine's call blocked."""
    monkeypatch.setenv("ARKS_TRACE", "1")
    tr = trace_mod.Tracer()
    m = EngineMetrics()
    tr.wake_hist = m.host_wake_late_seconds
    _, s = _warm(tracer=tr)
    monkeypatch.setattr(trace_mod, "time",
                        types.SimpleNamespace(monotonic=lambda: s.t))
    s.step()
    t0 = s.t
    tr._note_wake(t0 - 0.3, t0 - 0.3)          # punctual, before the stall
    s.step(gap=3.0)
    tr._note_wake(s.t - 0.01, s.t - 0.01 - 2.9)     # 2.9 s late, inside it
    s.step()
    tr._note_wake(s.t, s.t - 0.001)
    tr.flush()
    (rec,) = s.clock.stalls
    assert rec["wake_late_s"] == pytest.approx(2.9)
    assert m.host_wake_late_seconds._data[()][2] == 3    # one a wake
    # A blocked call beside punctual wakes reads small, not None.
    s.step(wait=4.0)
    tr._note_wake(s.t - 2.0, s.t - 2.0 - 0.002)
    s.step()
    tr.flush()
    assert s.clock.stalls[-1]["where"] == "wait"
    assert s.clock.stalls[-1]["wake_late_s"] == pytest.approx(0.002)


def test_the_profilers_auto_arm_reads_the_clocks_median(monkeypatch,
                                                        tmp_path):
    """One trailing median: ``ProfilerWindows.on_step`` keeps none of its
    own and is handed the step clock's."""
    from arks_tpu.obs import profiler as prof_mod
    monkeypatch.setenv("ARKS_PROF_DIR", str(tmp_path / "prof"))
    monkeypatch.setenv("ARKS_PROF_AUTO_ARM", "4.0")
    pw = prof_mod.ProfilerWindows()
    assert not hasattr(pw, "_steps")
    _, s = _warm(n=10)
    pw.on_step(5.0, s.clock.last_median)        # not warm: judged by nothing
    assert s.clock.last_median is None and not pw.active
    _, s = _warm()
    assert s.clock.last_median == pytest.approx(0.057)
    pw.on_step(0.1, s.clock.last_median)        # under 4 medians
    pw.on_step(5.0, None)                       # a step that waited idle
    assert not pw.active
    pw.on_step(0.3, s.clock.last_median)
    assert pw.active
    pw.stop()


# ---------------------------------------------------------------------------
# The plain totals, and the profiler window that marks them (PR 52)
# ---------------------------------------------------------------------------


def _kinds(clock):
    return {k: dict(zip(stepclock.KIND_TOTALS, v))
            for k, v in clock.snapshot()[0].items()}


def test_the_totals_are_the_families_own_numbers():
    """``snapshot()`` carries what the families carry, as plain numbers:
    a stalled cycle is in neither's legs and in both's stalled seconds."""
    m, s = _warm()
    for _ in range(3):
        s.step(kind="seq_tail", wait=0.020)
    s.step(kind="pipe", gap=0.9)                # closes a sound seq_tail
    s.step()                                    # closes pipe's first: cold
    kinds, stall_s, stalls = s.clock.snapshot()
    assert set(kinds) == {"seq", "seq_tail", "pipe"}
    for kind, tot in _kinds(s.clock).items():
        n, total = _cycles(m, kind)
        legs = _legs(m, kind)
        assert tot["cycles"] == n and tot["cycle_s"] == pytest.approx(total)
        assert (tot["wait_s"], tot["starved_s"], tot["overlap_s"]) == (
            pytest.approx(legs["wait"]), pytest.approx(legs["starved"]),
            pytest.approx(legs["overlap"]))
        assert tot["call_s"] == pytest.approx(
            m.step_call_seconds_total.get(kind=kind))
        assert tot["wait_s"] + tot["starved_s"] + tot["overlap_s"] \
            == pytest.approx(tot["cycle_s"])
    assert (stall_s, stalls) == (0.0, 0)
    s.step(gap=3.0)
    s.step()
    kinds, stall_s, stalls = s.clock.snapshot()
    assert stalls == 1 and stall_s == pytest.approx(3.053)
    assert stall_s == pytest.approx(sum(v for _, v in _stalls(m).values()))
    assert kinds["seq"][0] == _cycles(m, "seq")[0]
    # The object a reader holds is never written into afterwards.
    held = s.clock.snapshot()
    frozen = (dict(held[0]), held[1], held[2])
    for _ in range(5):
        s.step()
    assert (dict(held[0]), held[1], held[2]) == frozen
    assert s.clock.snapshot()[0]["seq"][0] == frozen[0]["seq"][0] + 5


def test_a_reader_thread_never_sees_a_half_written_total():
    """The engine thread is the only writer and replaces the totals whole:
    whatever instant another thread reads them, every kind's legs sum to
    its cycle seconds and its cycles are what the legs account for."""
    m = EngineMetrics()
    s = _Seq(StepClock(m))
    stop = threading.Event()
    seen, bad = [0], []

    def reader():
        last = 0
        while not stop.is_set():
            kinds, stall_s, stalls = s.clock.snapshot()
            n = 0
            for kind, t in kinds.items():
                tot = dict(zip(stepclock.KIND_TOTALS, t))
                legs = tot["wait_s"] + tot["starved_s"] + tot["overlap_s"]
                # Every cycle here is 57 ms, 50 of them waited.
                if (abs(legs - tot["cycle_s"]) > 1e-6
                        or abs(tot["cycle_s"] - 0.057 * tot["cycles"]) > 1e-6
                        or abs(tot["wait_s"] - 0.050 * tot["cycles"]) > 1e-6):
                    bad.append((kind, tot))
                n += tot["cycles"]
            if n < last:
                bad.append(("went back", n, last))
            last = n
            seen[0] += 1

    readers = [threading.Thread(target=reader, daemon=True) for _ in range(6)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for r in readers:
            r.start()
        deadline = time.monotonic() + 20
        for i in range(20000):
            s.step(kind=("seq", "seq_tail", "pipe")[i % 3])
            if time.monotonic() > deadline:
                break
    finally:
        stop.set()
        for r in readers:
            r.join(10)
        sys.setswitchinterval(interval)
    assert not any(r.is_alive() for r in readers)
    assert seen[0] > 100 and not bad, bad[:3]


class _MadeUpTime:
    """``time`` for the clock and the profiler: both read what the test
    says it is."""

    def __init__(self, seq):
        self.seq, self.cpu = seq, 50.0

    def monotonic(self):
        return self.seq.t

    def monotonic_ns(self):
        return int(self.seq.t * 1e9)

    def process_time(self):
        return self.cpu

    strftime = staticmethod(time.strftime)


@pytest.fixture
def window(monkeypatch, tmp_path):
    """A warm clock on a made-up time line and a profiler window over it
    whose ``jax.profiler`` calls are recorded, not made."""
    import jax
    from arks_tpu.obs import profiler as prof_mod
    m, s = _warm()
    fake = _MadeUpTime(s)
    monkeypatch.setattr(prof_mod, "time", fake)
    monkeypatch.setattr(stepclock, "time", fake)
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    prof = prof_mod.ProfilerWindows(str(tmp_path), clock=s.clock)
    return types.SimpleNamespace(m=m, s=s, prof=prof, time=fake,
                                 calls=calls, dir=str(tmp_path / "w"))


def test_a_window_returns_the_whole_cycles_that_closed_inside_it(window):
    s, prof = window.s, window.prof
    for _ in range(4):
        s.step()                        # closed before start(): outside
    before = _kinds(s.clock)["seq"]
    assert prof.start(window.dir)["ok"]
    for _ in range(5):
        s.step(kind="seq", wait=0.060)
        s.step(kind="pipe", wait=0.010, overlap=0.004, gap=0.0)
    out = prof.stop()
    assert out["ok"] and out["python"] is False
    for _ in range(3):
        s.step()                        # closed after stop(): outside
    clock = out["clock"]
    assert set(clock) == {"kinds", "stall_s", "stalls"}
    assert set(clock["kinds"]) == {"seq", "pipe"}
    for k in clock["kinds"].values():
        assert set(k) == set(stepclock.KIND_TOTALS)
        assert k["wait_s"] + k["starved_s"] + k["overlap_s"] \
            == pytest.approx(k["cycle_s"])
    # The cycle open at start() closed inside (a sound 57 ms one), the
    # fifth pipe cycle was still open at stop().
    seq, pipe = clock["kinds"]["seq"], clock["kinds"]["pipe"]
    assert (seq["cycles"], pipe["cycles"]) == (6, 4)
    assert seq["cycle_s"] == pytest.approx(0.057 + 5 * 0.067)
    assert pipe["cycle_s"] == pytest.approx(4 * 0.015)
    assert pipe["starved_s"] == pytest.approx(4 * 0.001)   # the next call
    assert seq["call_s"] == pytest.approx(0.005)    # every call made inside
    assert pipe["call_s"] == pytest.approx(0.005)
    assert (clock["stall_s"], clock["stalls"]) == (0.0, [])
    assert _kinds(s.clock)["seq"]["cycles"] == before["cycles"] + 6 + 2
    # Kept for in-process readers, the same content.
    assert prof.last_window["clock"] == clock
    assert prof.last_window["python"] is False
    json.dumps(out)                     # the HTTP response carries it
    # A second window starts from its own beginning.
    assert prof.start(window.dir)["ok"]
    s.step()
    assert prof.stop()["clock"]["kinds"]["seq"]["cycles"] == 1
    # Without a clock a window returns no such key.
    from arks_tpu.obs import profiler as prof_mod
    bare = prof_mod.ProfilerWindows(window.dir)
    assert bare.start(window.dir)["ok"] and "clock" not in bare.stop()


def test_a_stall_inside_the_window_is_in_its_clock_and_one_before_is_not(
        window):
    s, prof = window.s, window.prof
    s.step(gap=2.0)
    s.step()                            # a stall before the window
    assert len(s.clock.stalls) == 1
    assert prof.start(window.dir)["ok"]
    s.step()
    s.step(wait=1.5)
    s.step()
    s.step()
    clock = prof.stop()["clock"]
    s.step(gap=2.0)
    s.step()                            # and one after it
    assert len(s.clock.stalls) == 3
    (rec,) = clock["stalls"]
    assert rec["where"] == "wait" and rec["seconds"] == pytest.approx(1.507)
    assert clock["stall_s"] == pytest.approx(1.507)
    assert rec == s.clock.stalls[1] and rec is not s.clock.stalls[1]
    # The stalled cycle is in no kind's sums: of the four cycles that
    # closed inside, three were sound.
    assert clock["kinds"]["seq"]["cycles"] == 3
    assert clock["kinds"]["seq"]["cycle_s"] == pytest.approx(3 * 0.057)
    assert prof.last_window["clock"]["stalls"] == [rec]


def test_a_stall_record_says_what_held_the_process(window):
    """``cpu_s``: the process's own CPU seconds across the stalled cycle;
    ``gc_s``: the collector's seconds inside it (one ``gc.callbacks`` hook
    of the clock's).  A process that was not scheduled reads both near 0,
    a long collection reads ``gc_s`` near ``seconds``."""
    s, fake = window.s, window.time

    def collection_takes(phase, info, seconds=[0.0]):
        if phase == "start":
            s.t += seconds[0]           # after the clock's own hook ran
            fake.cpu += seconds[0]

    gc.callbacks.append(collection_takes)
    try:
        s.step()
        fake.cpu += 0.004
        s.step(gap=3.0)                 # not scheduled: no CPU, no gc
        s.step()
        rec = s.clock.stalls[-1]
        assert rec["seconds"] == pytest.approx(3.053)
        assert rec["cpu_s"] == pytest.approx(0.0) and rec["gc_s"] == 0.0
        # A collection of 0.8 s inside the next stalled cycle.
        s.step()
        collection_takes.__defaults__[0][0] = 0.8
        gc.collect()
        collection_takes.__defaults__[0][0] = 0.0
        s.step()
        rec = s.clock.stalls[-1]
        assert rec["where"] == "host"
        assert rec["seconds"] == pytest.approx(0.857)
        assert rec["gc_s"] == pytest.approx(0.8)
        assert rec["cpu_s"] == pytest.approx(0.8)
        # It is the cycle's own: the next stall starts from nothing.
        s.step(gap=2.0)
        fake.cpu += 1.9                 # something computed all along
        s.step()
        rec = s.clock.stalls[-1]
        assert rec["gc_s"] == 0.0 and rec["cpu_s"] == pytest.approx(1.9)
        json.dumps(rec)
    finally:
        gc.callbacks.remove(collection_takes)


def test_a_dropped_clock_takes_its_gc_hook_along():
    gc.collect()
    n = len(gc.callbacks)
    clock = StepClock(EngineMetrics())
    assert len(gc.callbacks) == n + 1
    del clock
    gc.collect()                        # the hook sees its clock gone
    gc.collect()
    assert len(gc.callbacks) <= n


@pytest.mark.parametrize("how", ["default", "python", "http-default",
                                 "http-python", "http-truthy-string",
                                 "auto-armed"])
def test_who_gets_the_profilers_python_tracer(how, window, monkeypatch,
                                              request):
    """A window runs WITHOUT the Python tracer unless it is asked for:
    ``start(python=True)``, the HTTP body's ``{"python": true}``, and the
    auto-armed window.  The host tracer stays as JAX sets it."""
    import jax
    prof, calls = window.prof, window.calls
    if how.startswith("http"):
        srv = request.getfixturevalue("server")
        prof = srv.engine.profiler
        body = {"http-default": {"logdir": window.dir},
                "http-python": {"logdir": window.dir, "python": True},
                "http-truthy-string": {"logdir": window.dir,
                                       "python": "no"}}[how]
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/profiler/start",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            started = json.load(r)
    elif how == "auto-armed":
        prof.auto_mult = 4.0
        prof.on_step(0.3, 0.057)
        started = {"ok": prof.active, "python": prof._python}
    else:
        started = prof.start(window.dir, python=how == "python")
    want = how in ("python", "http-python", "auto-armed")
    try:
        assert started["ok"] and started["python"] is want
        ((d, kw),) = calls
        options = kw["profiler_options"]
        assert isinstance(options, jax.profiler.ProfileOptions)
        assert options.python_tracer_level == (1 if want else 0)
        assert options.host_tracer_level \
            == jax.profiler.ProfileOptions().host_tracer_level
    finally:
        stopped = prof.stop()
    assert stopped["ok"] and stopped["python"] is want
    assert prof.last_window["python"] is want


def test_a_jax_without_profile_options_starts_the_trace_as_it_does(
        window, monkeypatch):
    import jax
    monkeypatch.delattr(jax.profiler, "ProfileOptions")
    started = window.prof.start(window.dir)
    assert window.calls == [(window.dir, {})]
    # That JAX's own default traces Python: the window says so.
    assert started["ok"] and started["python"] is True
    assert window.prof.stop()["ok"]


# ---------------------------------------------------------------------------
# A tiny engine on the CPU
# ---------------------------------------------------------------------------


class _Ticks:
    """The engine module's ``time``: every reading is a millisecond after
    the last, so a run's cycles are as long as the readings taken in them
    and no pause of the test machine is ever a stall."""

    def __init__(self):
        self.t = 1000.0
        self.sleep = time.sleep

    def monotonic(self):
        self.t += 0.001
        return self.t


def _engine(monkeypatch, depth, **over):
    monkeypatch.setenv("ARKS_TRACE", "1")
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    return harness.warmed("tiny", base=dict(
        num_slots=2, max_cache_len=64, prefill_buckets=(8, 16, 32),
        steps_per_dispatch=4, prefill_chunk=16, kv_layout="paged"), **over)


def _serve(eng, tag, n=5, max_tokens=12):
    reqs = [Request(f"{tag}{i}", [5 + i, 6, 7] + list(range(3, 3 + 5 * i)),
                    SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                   ignore_eos=True)) for i in range(n)]
    for r in reqs:
        eng.add_request(r)
    for _ in range(3000):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling and eng.step_clock._kind is None):
            break
    out = {}
    for r in reqs:
        out[r.request_id] = []
        while not r.outputs.empty():
            out[r.request_id].append(r.outputs.get())
    return out


@pytest.mark.parametrize("depth", [0, 2])
def test_a_tiny_engine_accounts_for_every_dispatch(monkeypatch, depth):
    eng = _engine(monkeypatch, depth)
    ticks = _Ticks()
    monkeypatch.setattr(engine_mod, "time", ticks)
    m = eng.metrics
    t0 = ticks.t
    got = _serve(eng, "a")
    # Left idle between two requests, for a thousand seconds of this
    # clock: the time belongs to no leg, and no stall is counted, though
    # the kind the pod went idle in is warm by then.
    warm = eng.step_clock._trails["pipe" if depth else "seq"]
    assert warm.n >= stepclock.WARM_CYCLES and warm.median < 0.1
    for _ in range(3):
        eng.step(block_s=0.01)
    ticks.t += 1000.0
    got.update(_serve(eng, "b", n=3))
    wall = ticks.t - t0 - 1000.0
    assert all(f[-1].finished for f in got.values())
    text = m.registry.render()
    for family in ("step_leg_seconds_total", "step_call_seconds_total",
                   "step_cycle_seconds_bucket", "step_stalls_total",
                   "step_stall_seconds_total", "host_wake_late_seconds",
                   "stream_deliver_lag_seconds", "stream_defer_lag_seconds"):
        assert f"# TYPE {family.removesuffix('_bucket')} " in text, family
    assert "decode_resolve_wait_seconds_total" not in text
    kinds = {dict(k)["kind"] for k in m.step_cycle_seconds._data}
    assert kinds == ({"seq", "pipe"} if depth else {"seq"})
    for kind in kinds:
        assert f'step_leg_seconds_total{{kind="{kind}",leg="wait"}}' in text
        assert sum(_legs(m, kind).values()) == pytest.approx(
            _cycles(m, kind)[1])
    stalls = _stalls(m)
    assert all(v == (0.0, 0.0) for v in stalls.values()), stalls
    legs = sum(m.step_leg_seconds_total._values.values())
    assert 0 < legs + sum(s for _, s in stalls.values()) <= wall
    # Every dispatch opened one cycle, and each cycle ended in the
    # histogram, as a stall, or abandoned at idle.
    dispatches = m.mixed_batch_tokens._data[()][2]
    if depth:
        dispatches += m.pipeline_depth_occupancy._data[()][2]
    assert eng.step_clock._kind is None and eng.step_clock.abandoned == 2
    assert (_cycles(m)[0] + sum(n for n, _ in stalls.values())
            + eng.step_clock.abandoned) == dispatches
    # Every frame was stamped at the door; none was deferred here (callers
    # never outnumber the slots by more than the queue holds free).
    frames = [o for f in got.values() for o in f]
    assert all(o.t_put is not None for o in frames)
    assert all((o.t_made is None) or o.t_made <= o.t_put for o in frames)


def test_the_budget_counter_adds_the_budget_of_the_shape_the_step_took(
        monkeypatch):
    """A tail step adds the tail's rows to
    ``mixed_chunk_budget_tokens_total``, not the whole budget's, and the
    cycles are counted by shape."""
    monkeypatch.setenv("ARKS_MIXED_CHUNK_TOKENS", "64")
    eng = _engine(monkeypatch, 0, num_slots=3, max_cache_len=256,
                  prefill_buckets=(16,), weight_dtype="bf16",
                  kv_cache_dtype="bf16")
    assert (eng._mixed_budget, eng._mixed_tail) == (64, 16)
    seen, shape = [], eng._mixed_shape

    def spy():
        pack, budget = shape()
        seen.append((budget, bool(eng._prefilling)
                     or eng._queue.qsize() > 0))
        return pack, budget
    eng._mixed_shape = spy
    # 79 prompt rows: one step of the whole budget, then 15 rows left.
    reqs = [Request(f"t{i}", [2 + (7 * j + i) % 200 for j in range(n)],
                    SamplingParams(max_tokens=6, temperature=0.0,
                                   ignore_eos=True))
            for i, n in enumerate((70, 9))]
    for r in reqs:
        eng.add_request(r)
    for _ in range(1000):
        eng.step(block_s=0.01)
        if eng.idle and eng.step_clock._kind is None:
            break
    m = eng.metrics
    assert {b for b, _ in seen} == {16, 64}
    assert m.mixed_chunk_budget_tokens_total.get() == sum(
        b for b, wanted in seen if wanted)
    assert any(b == 16 and wanted for b, wanted in seen)
    tails = sum(b == 16 for b, _ in seen)
    assert _cycles(m, "seq_tail")[0] + _cycles(m, "seq")[0] \
        + eng.step_clock.abandoned == len(seen)
    assert tails - 1 <= _cycles(m, "seq_tail")[0] <= tails


@pytest.mark.parametrize("depth", [0, 2])
def test_a_deferred_frame_keeps_when_it_was_made(monkeypatch, depth):
    """A resolve that leaves nothing in flight holds its frames for the
    next dispatch; each keeps when it was made and when it was put.  At
    depth 0 that is every resolve; at depth 2 a steady resolve's frames
    go straight out and carry no making time."""
    eng = _engine(monkeypatch, depth)
    got = _serve(eng, "d", n=6)
    frames = [o for f in got.values() for o in f]
    held = [o for o in frames if o.t_made is not None]
    assert held and (len(held) < len(frames) if depth
                     else len(held) == len(frames))
    assert all(o.t_made <= o.t_put for o in held)
    assert eng.metrics.fanout_deferred_outputs_total.get() == len(held)


# ---------------------------------------------------------------------------
# The handlers' lag behind the door
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    from arks_tpu.server import OpenAIServer
    # (A tools declaration alone is ~270 tokens of the byte tokenizer.)
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=640,
                        prefill_buckets=(64, 128, 256, 512),
                        steps_per_dispatch=1)
    engine = InferenceEngine(get_config("tiny"), ecfg, ByteTokenizer())
    engine.start()
    srv = OpenAIServer(engine, served_model_name="tiny-serve",
                       host="127.0.0.1", port=0)
    srv.start(background=True)
    yield srv
    srv.stop()
    engine.stop()


def _lag_observations(srv):
    data = srv.engine.metrics.stream_deliver_lag_seconds._data.get(())
    return data[2] if data else 0


def _stream(srv, path, body, abort_after=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}",
        data=json.dumps(dict(body, model="tiny-serve", stream=True,
                             temperature=0, ignore_eos=True)).encode(),
        headers={"Content-Type": "application/json"})
    frames = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            frames.append(json.loads(line[6:]))
            if abort_after is not None and len(frames) == abort_after:
                srv.engine.abort(frames[-1]["id"])
    return frames


def _settled(srv, want):
    for _ in range(200):                # the handler's ``finally``
        if _lag_observations(srv) >= want:
            break
        time.sleep(0.01)
    return _lag_observations(srv)


@pytest.mark.parametrize("path, body", [
    ("/v1/completions", {"prompt": "hi", "max_tokens": 6}),
    ("/v1/chat/completions", {"messages": [
        {"role": "user", "content": "hello"}], "max_tokens": 5}),
    ("/v1/chat/completions", {"messages": [
        {"role": "user", "content": "hello"}], "max_tokens": 5,
        "tools": [{"type": "function", "function": {"name": "f"}}]}),
], ids=["completions", "chat", "chat-tools"])
def test_a_stream_observes_its_lag_exactly_once(server, path, body):
    n0 = _lag_observations(server)
    frames = _stream(server, path, body)
    assert frames and frames[-1]["choices"][0]["finish_reason"] == "length"
    assert _settled(server, n0 + 1) == n0 + 1
    hist = server.engine.metrics.stream_deliver_lag_seconds
    assert hist._data[()][1] >= 0.0
    # Nothing waited for a slot: no frame was deferred, so the other
    # family has no observation.
    assert not server.engine.metrics.stream_defer_lag_seconds._data


def test_an_aborted_stream_observes_its_lag_once_too(server):
    n0 = _lag_observations(server)
    frames = _stream(server, "/v1/completions",
                     {"prompt": "hi", "max_tokens": 40}, abort_after=2)
    reasons = [f["choices"][0]["finish_reason"] for f in frames
               if f["choices"]]
    assert reasons[-1] in ("abort", "length") and len(frames) < 42
    assert _settled(server, n0 + 1) == n0 + 1


def test_a_non_streamed_request_observes_no_stream_lag(server):
    n0 = _lag_observations(server)
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/v1/completions",
        data=json.dumps({"model": "tiny-serve", "prompt": "hi",
                         "max_tokens": 4, "temperature": 0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        json.load(r)
    assert _lag_observations(server) == n0
