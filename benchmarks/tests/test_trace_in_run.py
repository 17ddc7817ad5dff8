"""``--trace 2`` and ``trace_in_run``: one run that measures first and traces
afterwards.  The manifest's optional key, the schedule's tail and the whole
command on the CPU at tiny size (the generator's tail alone:
``test_loadgen_tail.py``)."""

import copy
import json
import subprocess
import sys

import pytest

from benchmarks import manifest
from benchmarks.traffic import Schedule


def _broken(edit):
    man = copy.deepcopy(manifest.load())
    edit(man)
    return manifest.validate(man)


@pytest.mark.parametrize("value, faults", [
    (True, 0), (False, 0), ("yes", 1), (1, 1)])
def test_trace_in_run_is_an_optional_boolean(value, faults):
    got = _broken(lambda m: m.update(trace_in_run=value))
    assert len(got) == faults, got
    assert all("trace_in_run" in e for e in got)


def test_an_unknown_top_level_key_is_still_refused():
    assert any("top-level" in e for e in _broken(
        lambda m: m.update(trace_in_run=True, trace_after_run=True)))
    assert any("top-level" in e for e in _broken(
        lambda m: m.pop("per_layer")))
    # the rehearsal's manifest needs no such key
    assert "trace_in_run" not in manifest.load(rehearsal=True)


@pytest.mark.parametrize("mix", ["chat.open", "rehearsal.open", "chat.closed"])
def test_a_tail_leaves_the_run_as_it_was(mix):
    """A ``--trace 2`` run keeps the load going after the window: the
    run's own arrivals, sizes and due times stay bit for bit what a run
    without a tail offers, and the tail comes after them at the run's
    rate."""
    with open(manifest.traffic_path(mix)) as f:
        spec = json.load(f)
    load = 64 if spec["loop"] == "closed" else 1.6
    plain = Schedule(spec, 11, load=load, seconds=45)
    tailed = Schedule(spec, 11, load=load, seconds=45, tail_s=94.0)
    assert tailed.count() == plain.count()
    n = plain.count() or 200
    assert [plain.request(i) for i in range(n)] \
        == [tailed.request(i) for i in range(n)]
    if spec["loop"] == "closed":
        assert tailed.due is None
        return
    assert tailed.due[:n] == plain.due and len(tailed.due) > n
    extra = tailed.due[n:]
    assert extra == sorted(extra)
    assert plain.total_s <= extra[0] and extra[-1] < plain.total_s + 94.0
    # at the run's rate: 94 s at 1.6 a second, give or take
    assert 0.5 * 94 * load < len(extra) < 1.6 * 94 * load
    more = [tailed.request(i) for i in range(n, len(tailed.due))]
    assert all(len(r["prompt"]) + r["max_tokens"]
               <= spec["max_total_tokens"] for r in more)
    assert [r["due"] for r in more] == extra
    # and the same tail every time
    again = Schedule(spec, 12, load=load, seconds=45, tail_s=94.0)
    assert again.due == tailed.due


def _run(*extra):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--seed", "2147483999",
         "--seconds", "3", *extra], cwd=manifest.ROOT, capture_output=True,
        text=True, timeout=600)


@pytest.mark.parametrize("cell, e2e, layer", [
    ("tiny.rehearsal.open", {"itl_p95_ms", "ttft_p50_ms", "setup_s"},
     {"gen_late_p95_ms", "queue_wait_p50_ms", "pipelined_step_share"}),
    ("tiny.rehearsal.closed", {"output_tok_s", "itl_p95_ms", "setup_s"},
     {"pipelined_step_share", "rows_per_step_mean"}),
])
def test_trace_2_measures_first_and_traces_afterwards(cell, e2e, layer):
    p = _run("--workload", cell, "--trace", "2", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    # both kinds of metric side by side on the one last line
    assert e2e | layer <= set(last["metrics"]), last["metrics"]
    # the end-to-end numbers are those of the closed window: what
    # client_metrics.reduce gave on the records as they stood at the end
    # of the drain (the ``client`` line), not on the tail's
    client = next(x for x in lines if x.get("phase") == "client")
    for name in e2e - {"setup_s"}:
        assert last["metrics"][name]["value"] == client[name]
    assert last["attempted"] == client["attempted"] > 0
    # the traced slice came after the drain, and the load went on in it
    tail = next(x for x in lines if x.get("phase") == "tail")
    assert tail["spans"] > 0 and tail["slice_s"] > 0.5
    assert tail["traced"]["output_tok_s"] > 0
    assert "busy_s" not in last["device"]          # the CPU has no device


def test_trace_0_and_trace_2_offer_the_same_run():
    """Same seed: the requests the window saw are the same ones."""
    got = {}
    for t in ("0", "2"):
        p = _run("--workload", "tiny.rehearsal.open", "--trace", t,
                 "--rehearse")
        assert p.returncode == 0, p.stderr[-2000:]
        lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
        got[t] = next(x for x in lines if x.get("phase") == "client")
    for key in ("attempted", "due_in_window", "prompt_tokens_due",
                "ttft_samples"):
        assert got["0"][key] == got["2"][key], key
