"""The readers of the step clock's families (PR 38): ``device_starved_share``,
``seq_cycle_ms_mean``, ``pipe_cycle_ms_mean``, ``step_stall_s``,
``deliver_lag_p95_ms`` and their ``.tput`` twins, on made-up scrapes of
``/metrics`` at a window's two ends and on what the program's own registry
renders."""

import pytest

from benchmarks import manifest
from benchmarks import pod as podlib

READERS = ("device_starved_share", "seq_cycle_ms_mean", "pipe_cycle_ms_mean",
           "step_stall_s", "deliver_lag_p95_ms")

LAG_LE = ("0.001", "0.005", "0.01", "0.05", "+Inf")


def _scrape(legs=None, cycles=None, stalls=None, lag=None):
    """Exposition text -> the parsed scrape a run keeps.  ``legs``:
    {(kind, leg): s}; ``cycles``: {kind: (count, sum)}; ``stalls``:
    {where: s}; ``lag``: cumulative counts by ``LAG_LE``."""
    lines = ["# TYPE mixed_batch_tokens histogram",
             "mixed_batch_tokens_count 7"]
    for (kind, leg), v in (legs or {}).items():
        lines.append(
            f'step_leg_seconds_total{{kind="{kind}",leg="{leg}"}} {v!r}')
    for kind, (n, total) in (cycles or {}).items():
        lines += [f'step_cycle_seconds_bucket{{kind="{kind}",le="+Inf"}} {n}',
                  f'step_cycle_seconds_sum{{kind="{kind}"}} {total!r}',
                  f'step_cycle_seconds_count{{kind="{kind}"}} {n}']
    for where, v in (stalls or {}).items():
        lines.append(f'step_stall_seconds_total{{where="{where}"}} {v!r}')
    if lag is not None:
        lines += [f'stream_deliver_lag_seconds_bucket{{le="{le}"}} {c}'
                  for le, c in zip(LAG_LE, lag)]
        lines += ["stream_deliver_lag_seconds_sum 1.0",
                  f"stream_deliver_lag_seconds_count {lag[-1]}"]
    return podlib.parse_metrics("\n".join(lines) + "\n")


def _read(name, opened, closed):
    return manifest.load_reader(name)(
        {"metrics_open": opened, "metrics_close": closed})


@pytest.mark.parametrize("name", [n + t for n in READERS
                                  for t in ("", ".tput")])
def test_a_parent_without_the_family_reads_none(name):
    """The parent has no step clock: the reader finds nothing, returns
    None and does not raise, and the line leaves the metric out."""
    parent = _scrape()
    assert _read(name, parent, parent) is None
    assert _read(name, {}, {}) is None


def test_the_starved_share_is_the_starved_leg_over_all_legs_of_all_kinds():
    opened = _scrape(legs={("seq", "wait"): 10.0, ("seq", "starved"): 1.0,
                           ("seq", "overlap"): 1.0,
                           ("pipe", "wait"): 5.0})
    closed = _scrape(legs={("seq", "wait"): 30.0, ("seq", "starved"): 4.0,
                           ("seq", "overlap"): 2.0, ("pipe", "wait"): 9.0,
                           ("pipe", "starved"): 1.0,
                           ("seq_tail", "overlap"): 11.0})
    # Deltas: starved 3 + 1 of 20 + 3 + 1 + 4 + 1 + 11 = 40.
    for name in ("device_starved_share", "device_starved_share.tput"):
        assert _read(name, opened, closed) == pytest.approx(10.0)
    # A window in which no cycle closed has no share.
    assert _read("device_starved_share", closed, closed) is None


def test_a_cycle_mean_is_its_kinds_own():
    opened = _scrape(cycles={"seq": (100, 7.0), "pipe": (1000, 11.0),
                             "seq_tail": (5, 0.25)})
    closed = _scrape(cycles={"seq": (300, 21.0), "pipe": (3000, 33.5),
                             "seq_tail": (50, 2.5)})
    for t in ("", ".tput"):
        assert _read("seq_cycle_ms_mean" + t, opened, closed) \
            == pytest.approx(70.0)
        assert _read("pipe_cycle_ms_mean" + t, opened, closed) \
            == pytest.approx(11.25)
    # The flood has no pipelined cycle: nothing to read, not zero.
    flood = (_scrape(cycles={"seq": (100, 7.0)}),
             _scrape(cycles={"seq": (300, 21.0)}))
    assert _read("pipe_cycle_ms_mean", *flood) is None
    assert _read("seq_cycle_ms_mean", *flood) == pytest.approx(70.0)
    assert _read("seq_cycle_ms_mean", closed, closed) is None


def test_stalled_seconds_read_zero_in_a_sound_run_and_sum_every_where():
    sound = _scrape(stalls={"dispatch": 0, "wait": 0, "host": 0,
                            "compile": 0})
    assert _read("step_stall_s", sound, sound) == 0.0
    assert _read("step_stall_s.tput", sound, sound) == 0.0
    stood = _scrape(stalls={"dispatch": 0, "wait": 3.25, "host": 11.0,
                            "compile": 0})
    assert _read("step_stall_s", sound, stood) == pytest.approx(14.25)
    assert _read("step_stall_s", stood, stood) == 0.0


def test_the_lag_percentile_is_read_off_the_bucket_deltas():
    opened = _scrape(lag=(10, 10, 10, 10, 10))
    # In the window: 50 streams under 1 ms, 40 in (1, 5], 8 in (5, 10],
    # 2 in (10, 50]: the 95th of 100 lies 5/8 into (5, 10] ms.
    closed = _scrape(lag=(60, 100, 108, 110, 110))
    for name in ("deliver_lag_p95_ms", "deliver_lag_p95_ms.tput"):
        assert _read(name, opened, closed) == pytest.approx(5.0 + 5.0 * 5 / 8)
    # All in the first bucket: linear from zero.
    first = _scrape(lag=(30, 30, 30, 30, 30))
    assert _read("deliver_lag_p95_ms", opened, first) \
        == pytest.approx(0.95)
    # Past the last finite bound it reads that bound.
    late = _scrape(lag=(10, 10, 10, 10, 30))
    assert _read("deliver_lag_p95_ms", opened, late) == pytest.approx(50.0)
    # No stream ended in the window.
    assert _read("deliver_lag_p95_ms", opened, opened) is None


def test_the_readers_read_what_the_programs_registry_renders():
    """The names, labels and bucket lines are the program's own: a clock
    driven on a made-up time line, the registry rendered at two ends."""
    from arks_tpu.engine.engine import EngineMetrics
    from arks_tpu.obs.stepclock import StepClock
    m = EngineMetrics()
    clock = StepClock(m)

    def run(n, t):
        for _ in range(n):
            clock.dispatched("seq", t, t + 0.001)
            clock.waited(t + 0.003, t + 0.053, 0)
            t += 0.060
        return t

    t = run(40, 0.0)
    opened = podlib.parse_metrics(m.registry.render())
    t = run(1, t + 5.0)                 # closes a stalled cycle (host)
    run(50, t)
    for lag in (0.0004,) * 9 + (0.004,):
        m.stream_deliver_lag_seconds.observe(lag)
    closed = podlib.parse_metrics(m.registry.render())
    assert _read("step_stall_s", opened, closed) == pytest.approx(5.06)
    assert _read("seq_cycle_ms_mean", opened, closed) == pytest.approx(60.0)
    assert _read("pipe_cycle_ms_mean", opened, closed) is None
    # starved: from the wait's end to the next call's return, 8 of 60 ms.
    assert _read("device_starved_share", opened, closed) \
        == pytest.approx(100 * 8 / 60)
    # Nine of ten streams under 0.5 ms, one in (3, 5]: the 95th lies
    # halfway into that bucket.
    assert _read("deliver_lag_p95_ms", opened, closed) == pytest.approx(4.0)
