import json

import pytest

from benchmarks import manifest
from benchmarks.traffic import Schedule

MIXES = ["chat.closed", "chat.flood", "chat.open", "rehearsal.open"]


def _take(mix_name, seed, n=300):
    with open(manifest.traffic_path(mix_name)) as f:
        mix = json.load(f)
    load = 240 if mix["loop"] == "closed" else 12.0
    s = Schedule(mix, seed, load=load, seconds=30)
    n = min(n, s.count() or n)
    return s, [s.request(i) for i in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests_other_seed_other_requests(mix):
    _, a = _take(mix, 2**31 + 77)
    _, b = _take(mix, 2**31 + 77)
    _, c = _take(mix, 5)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


@pytest.mark.parametrize("mix", ["chat.closed", "chat.flood", "chat.open"])
def test_the_seed_changes_the_text_and_not_the_work(mix):
    sa, a = _take(mix, 1, n=10**6 if mix.endswith("open") else 480)
    sb, b = _take(mix, 2, n=10**6 if mix.endswith("open") else 480)
    assert sa.due == sb.due
    assert [(len(r["prompt"]), r["max_tokens"]) for r in a] \
        == [(len(r["prompt"]), r["max_tokens"]) for r in b]
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    assert all(len(r["prompt"]) + r["max_tokens"] <= 1024 for r in a)
    assert all(32 <= len(r["prompt"]) <= 768 for r in a)
    # not one size for all: the pool is a distribution
    assert len({len(r["prompt"]) for r in a}) > 50


def test_sessions_share_history_and_never_pass_the_window():
    # the generator's sharing, on the one mix that uses it today (the CPU
    # rehearsal's; the sessions cell is an open question of PERF.md)
    s, reqs = _take("rehearsal.open", 9, n=360)
    spec = s.mix["sessions"]
    by = {}
    grew = 0
    for r in reqs:
        assert len(r["prompt"]) + r["max_tokens"] \
            <= s.mix["max_total_tokens"]
        prev = by.get(r["session"])
        if prev is not None and r["prompt"].startswith(prev):
            grew += 1
        by[r["session"]] = r["prompt"]
    assert grew > 100          # most turns extend their session's last prompt
    prime = s.prime()
    assert len(prime) == spec["count"]
    assert all(len(p["prompt"]) == spec["system_tokens"] for p in prime)
    assert all(r["prompt"].startswith(prime[r["session"]]["prompt"])
               for r in reqs)
    # no session comes back within its `recent` following arrivals
    last = {}
    for i, r in enumerate(reqs):
        assert i - last.get(r["session"], -10**9) > spec["recent"]
        last[r["session"]] = i
