"""The readers of the step clock over the TRACED slice (PR 52):
``slice_cycle_ratio``, ``slice_starved_share``, ``slice_stall_s`` and their
``.tput`` twins, against a stub engine whose profiler keeps a
``last_window`` and two made-up scrapes of ``/metrics`` at the untraced
window's ends, and against what the program's own window hands back."""

import types

import pytest

from benchmarks import manifest
from benchmarks import pod as podlib
from benchmarks.tests.test_step_clock_readers import _scrape

READERS = ("slice_cycle_ratio", "slice_starved_share", "slice_stall_s")
BOTH = [n + t for n in READERS for t in ("", ".tput")]


def _kind(cycles, wait, starved, overlap, call=0.0):
    return {"cycles": cycles, "cycle_s": wait + starved + overlap,
            "wait_s": wait, "starved_s": starved, "overlap_s": overlap,
            "call_s": call}


def _engine(window):
    """What a reader takes of the pod: ``engine.profiler.last_window``."""
    return types.SimpleNamespace(
        profiler=types.SimpleNamespace(last_window=window))


def _ctx(clock, opened=None, closed=None):
    window = {"dir": "d", "t0_monotonic": 1.0, "t1_monotonic": 4.0,
              "spans": []}
    if clock is not None:
        window["clock"] = clock
    return {"engine": _engine(window), "metrics_open": opened or {},
            "metrics_close": closed or {}}


def _read(name, ctx):
    return manifest.load_reader(name)(ctx)


OPENED = _scrape(cycles={"seq": (100, 7.0), "pipe": (1000, 11.0)})
CLOSED = _scrape(cycles={"seq": (300, 21.0), "pipe": (3000, 33.0)})
# The window's means: a seq cycle 70 ms, a pipe cycle 11 ms.


@pytest.mark.parametrize("name", BOTH)
def test_a_run_without_a_marked_slice_reads_none(name):
    """A ``--trace 0`` run has no window, the parent's window has no
    ``clock`` key: the reader finds nothing, returns None, does not raise,
    and the line leaves the metric out."""
    read = manifest.load_reader(name)
    parent = _ctx(None, OPENED, CLOSED)
    assert "clock" not in parent["engine"].profiler.last_window
    assert read(parent) is None
    assert read(dict(parent, engine=_engine(None))) is None
    assert read(dict(parent, engine=object())) is None
    assert read({"metrics_open": OPENED, "metrics_close": CLOSED}) is None


def test_the_ratio_weighs_the_windows_means_by_the_slices_own_kinds():
    # The slice held 10 seq cycles of 77 ms and 100 pipe cycles of 11 ms:
    # 1.87 s against 10 x 70 + 100 x 11 ms = 1.80 s of the same cycles.
    clock = {"kinds": {"seq": _kind(10, 0.60, 0.07, 0.10),
                       "pipe": _kind(100, 0.5, 0.0, 0.6)},
             "stall_s": 0.0, "stalls": []}
    for name in ("slice_cycle_ratio", "slice_cycle_ratio.tput"):
        assert _read(name, _ctx(clock, OPENED, CLOSED)) \
            == pytest.approx(1.87 / 1.80)
    # A slice of more chunk steps at the window's own means is no slower:
    # the mix of kinds is the slice's on both sides of the ratio.
    heavy = {"kinds": {"seq": _kind(30, 2.0, 0.05, 0.05),
                       "pipe": _kind(10, 0.05, 0.0, 0.06)},
             "stall_s": 0.0, "stalls": []}
    assert _read("slice_cycle_ratio", _ctx(heavy, OPENED, CLOSED)) \
        == pytest.approx(1.0)
    # A kind the window did not run is left out of BOTH sums; one that
    # only made a call in the slice (no cycle closed) weighs nothing.
    odd = {"kinds": {"seq": _kind(10, 0.60, 0.07, 0.10),
                     "seq_tail": _kind(4, 0.1, 0.0, 0.0),
                     "spec": _kind(0, 0.0, 0.0, 0.0, call=0.002)},
           "stall_s": 0.0, "stalls": []}
    assert _read("slice_cycle_ratio", _ctx(odd, OPENED, CLOSED)) \
        == pytest.approx(0.77 / 0.70)


def test_the_ratio_is_none_when_the_window_ran_none_of_the_slices_kinds():
    clock = {"kinds": {"seq_tail": _kind(4, 0.1, 0.0, 0.0)},
             "stall_s": 0.0, "stalls": []}
    assert _read("slice_cycle_ratio", _ctx(clock, OPENED, CLOSED)) is None
    # Nor with no cycle in the slice, nor on a parent's scrapes (no
    # step-clock family at all), nor where the window's count stood still.
    empty = {"kinds": {}, "stall_s": 0.0, "stalls": []}
    assert _read("slice_cycle_ratio", _ctx(empty, OPENED, CLOSED)) is None
    seq = {"kinds": {"seq": _kind(10, 0.6, 0.07, 0.1)}, "stall_s": 0.0,
           "stalls": []}
    assert _read("slice_cycle_ratio", _ctx(seq, _scrape(), _scrape())) is None
    assert _read("slice_cycle_ratio", _ctx(seq, CLOSED, CLOSED)) is None
    # The two other readers need no scrape at all.
    assert _read("slice_starved_share", _ctx(seq)) == pytest.approx(
        100 * 0.07 / 0.77)
    assert _read("slice_stall_s", _ctx(seq)) == 0.0


def test_the_starved_share_is_the_starved_leg_over_all_legs_of_the_slice():
    clock = {"kinds": {"seq": _kind(10, 0.60, 0.07, 0.10),
                       "pipe": _kind(100, 0.5, 0.03, 0.7)},
             "stall_s": 0.0, "stalls": []}
    for name in ("slice_starved_share", "slice_starved_share.tput"):
        assert _read(name, _ctx(clock)) == pytest.approx(100 * 0.10 / 2.0)
    empty = {"kinds": {}, "stall_s": 0.0, "stalls": []}
    assert _read("slice_starved_share", _ctx(empty)) is None


def test_a_slice_that_stood_still_says_so_by_itself():
    sound = {"kinds": {"seq": _kind(10, 0.6, 0.07, 0.1)}, "stall_s": 0.0,
             "stalls": []}
    stood = dict(sound, stall_s=1.12, stalls=[{"where": "host"}])
    for name in ("slice_stall_s", "slice_stall_s.tput"):
        assert _read(name, _ctx(sound)) == 0.0
        assert isinstance(_read(name, _ctx(sound)), float)
        assert _read(name, _ctx(stood)) == pytest.approx(1.12)


def test_the_readers_read_what_the_programs_own_window_hands_back(tmp_path):
    """The key's names are the program's own: a clock driven on a made-up
    time line through a real window, the registry rendered at the two ends
    of an 'untraced' stretch before it."""
    from arks_tpu.engine.engine import EngineMetrics
    from arks_tpu.obs.profiler import ProfilerWindows
    from arks_tpu.obs.stepclock import StepClock
    m = EngineMetrics()
    clock = StepClock(m)
    prof = ProfilerWindows(str(tmp_path), clock=clock)

    def run(n, t, gap=0.007):
        for _ in range(n):
            clock.dispatched("seq", t, t + 0.001)
            clock.waited(t + 0.003, t + 0.053, 0)
            t += 0.053 + gap
        return t

    t = run(1, 0.0)
    opened = podlib.parse_metrics(m.registry.render())
    t = run(50, t)                      # the window: cycles of 60 ms
    closed = podlib.parse_metrics(m.registry.render())
    assert prof.start(str(tmp_path / "p"))["ok"]
    run(20, t, gap=0.013)               # the slice: the host 6 ms slower
    assert prof.stop()["ok"]
    ctx = {"engine": types.SimpleNamespace(profiler=prof),
           "metrics_open": opened, "metrics_close": closed}
    # The first cycle the slice closes opened in the window (60 ms), the
    # 19 behind it are the slice's own (66 ms).
    assert _read("slice_cycle_ratio", ctx) == pytest.approx(
        (0.060 + 19 * 0.066) / (20 * 0.060))
    assert _read("slice_starved_share", ctx) == pytest.approx(
        100 * (0.008 + 19 * 0.014) / (0.060 + 19 * 0.066))
    assert _read("slice_stall_s", ctx) == 0.0
