"""The per-layer readers PR 24 added: spans, counters, named scopes, the
clock anchors; on made-up inputs and on a small trace recorded on a v5e
(``data/small2.xplane.pb`` with the window's spans beside it,
``data/small2.spans.json``: a tiny model on one chip, one window of the
program's own of a tenth of a second, so it holds the two anchors, named
programs and scope paths; trimmed of what no reader needs, the
``/host:metadata`` plane's HLO protos and the host plane's Python-tracer
events, from 2.9 MB to 0.8 MB; the device plane is whole)."""

import json
import os

import pytest

from benchmarks import clock, manifest
from benchmarks import trace_reduce as tr
from benchmarks.layer_metrics import _scopes

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "data", "small.xplane.pb")
NEW = os.path.join(HERE, "data", "small2.xplane.pb")
NEW_SPANS = os.path.join(HERE, "data", "small2.spans.json")


class _Engine:
    class profiler:
        last_window = None


def _span(name, a, b, arg=None):
    return {"name": name, "start": a, "end": b, "arg": arg}


def _step(t, gap, pipelined_after=False):
    """One sequential step beginning at ``t``: sections in order; the next
    step's dispatch ends ``gap`` after this one's wait."""
    out = [_span("phase.mixed.retire", t, t + .001),
           _span("phase.mixed.pack", t + .001, t + .003, [10, 4, 1]),
           _span("phase.mixed.put", t + .003, t + .008),
           _span("phase.mixed.dispatch", t + .008, t + .010,
                 "arks_mixed_seq"),
           _span("phase.mixed", t, t + .010),
           _span("phase.admit", t + .010, t + .011, 0),
           _span("phase.mixed.wait", t + .011, t + .100 - gap + .010),
           _span("phase.mixed.fanout", t + .100 - gap + .010,
                 t + .100 - gap + .012, [3, 0]),
           _span("phase.mixed", t + .011, t + .100 - gap + .013)]
    if pipelined_after:
        out.append(_span("phase.decode", t + .0995, t + .0999))
    return out


def _ctx(spans, t0=0.0, t1=100.0):
    eng = _Engine()
    eng.profiler = type("P", (), {"last_window": {
        "t0_monotonic": t0, "t1_monotonic": t1, "spans": spans}})()
    return {"device": {"slice_monotonic": (t0, t1)}, "engine": eng}


def test_step_host_gap_is_wait_end_to_next_dispatch_end():
    read = manifest.load_reader("step_host_gap_ms_p50")
    spans = (_step(1.0, .020) + _step(1.1, .020) + _step(1.2, .030)
             + _step(1.3, .020, pipelined_after=True) + _step(1.4, .020))
    # steps begin every 100 ms and each wait ends (gap - 10 ms) before the
    # next begins, whose dispatch ends 10 ms in: gaps 20, 20, 30 ms; the
    # pair with a pipelined decode phase in between is left out
    assert read(_ctx(spans)) == pytest.approx(20.0)
    assert manifest.load_reader("step_host_gap_ms_p50.tput")(
        _ctx(spans)) == pytest.approx(20.0)
    # cut to the slice: only spans that ended inside it (gaps 20 and 30)
    assert read(_ctx(spans, t0=1.15, t1=1.35)) == pytest.approx(25.0)
    # a program that keeps no window, a run without a trace: nothing read
    assert read({"device": {"slice_monotonic": (0, 9)},
                 "engine": object()}) is None
    assert read({"device": None, "engine": _ctx(spans)["engine"]}) is None
    assert read(_ctx([])) is None


def test_prefill_leg_and_budget_fill():
    traces = [{"spans": [
        {"name": "queue", "component": "engine", "start": 0.0, "end": 0.1},
        {"name": "prefill", "component": "engine", "start": 0.1,
         "end": 0.1 + leg},
        {"name": "prefill", "component": "engine", "start": 9.0,
         "end": None}]} for leg in (0.2, 0.4, 0.9)]
    assert manifest.load_reader("ttft_prefill_p50_ms")(
        {"traces": traces}) == pytest.approx(400.0)
    assert manifest.load_reader("ttft_prefill_p50_ms")({"traces": []}) is None

    def metrics(taken, offered):
        m = {"mixed_chunk_tokens_total": [({}, taken)]}
        if offered is not None:
            m["mixed_chunk_budget_tokens_total"] = [({}, offered)]
        return m
    for name in ("chunk_budget_fill", "chunk_budget_fill.burst"):
        read = manifest.load_reader(name)
        assert read({"metrics_open": metrics(100.0, 256.0),
                     "metrics_close": metrics(740.0, 1280.0)}) \
            == pytest.approx(62.5)
        # the parent's program has no budget counter: nothing to read
        assert read({"metrics_open": metrics(1.0, None),
                     "metrics_close": metrics(9.0, None)}) is None


def test_scope_of_and_self_time_by_scope():
    assert _scopes.scope_of(
        "jit(arks_mixed_seq)/while/body/arks.attn_layout/gather:") \
        == "arks.attn_layout"
    assert _scopes.scope_of(
        "jit(x)/while/body/arks.attn_layout/arks.attn_kernel/pallas_call") \
        == "arks.attn_kernel"                    # the innermost
    assert _scopes.scope_of("jit(x)/arks.ffn/arks.moe_dot/dot:") \
        == "arks.moe_dot"
    assert _scopes.scope_of("jit(x)/while/body/dot_general:") is None
    assert _scopes.scope_of("jit(x)/marks.x/mul") is None
    assert _scopes.scope_of(None) is None
    paths = {"while": "jit(p)/while", "a": "jit(p)/while/body/arks.ffn/dot:",
             "b": "jit(p)/while/body/arks.attn_layout/gather",
             "c": "jit(p)/arks.sampler/reduce"}
    ops = [("while", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 3.0, 4.0),
           ("a", 7.5, 1.5), ("c", 10.0, 1.0), ("nameless", 11.0, 0.5)]
    got = _scopes.self_seconds(ops, paths)
    assert got == {"arks.ffn": 3.5, "arks.attn_layout": 4.0,
                   "arks.sampler": 1.0, None: 2.5 + 0.5}
    ctx = {"device": {"xplane": OLD, "ops": ops, "busy_s": 12.0,
                      "scope_seconds": got}}
    assert manifest.load_reader("attn_layout_share")(ctx) \
        == pytest.approx(100 * 4.0 / 12.0)
    assert manifest.load_reader("sampler_share")(ctx) \
        == pytest.approx(100 / 12.0)
    assert manifest.load_reader("moe_dequant_share.tput")(ctx) == 0.0
    assert manifest.load_reader("attn_layout_share")({"device": None}) is None


def test_the_old_recorded_trace_has_paths_and_no_scope():
    """PR 23's trace, from before the scopes: its ops have ``tf_op`` paths
    (``jit(<unknown>)/...``), none under an ``arks.`` scope, and the scope
    readers read nothing from it (what the parent's program gives)."""
    paths = _scopes.op_paths(OLD)
    assert len(paths) > 100
    assert any(p.startswith("jit(<unknown>)/while/body") for p in
               paths.values())
    out = tr.reduce(tr.read_events(OLD))
    named = {o[0] for o in out["ops"]} & set(paths)
    assert len(named) > 100                  # the names match the events'
    ctx = {"device": dict(out, xplane=OLD)}
    assert _scopes.by_scope(ctx) is None
    for name in ("attn_layout_share", "sampler_share",
                 "moe_dequant_share.tput", "attn_layout_share.tput"):
        assert manifest.load_reader(name)(ctx) is None
    assert clock.offset(OLD) == {"offset_s": None, "drift_s": None,
                                 "anchors": 0}
    assert clock.offset(None)["offset_s"] is None


needs_new = pytest.mark.skipif(
    not (os.path.exists(NEW) and os.path.exists(NEW_SPANS)),
    reason="no second recorded trace in benchmarks/tests/data")


@needs_new
def test_the_new_recorded_trace_joins_the_clocks_by_its_anchors():
    with open(NEW_SPANS) as f:
        win = json.load(f)
    joined = clock.offset(NEW)
    assert joined["anchors"] == 2
    assert abs(joined["drift_s"]) < 1e-3
    found = clock.anchors(NEW)
    # the anchors bracket the window on both clocks
    assert found[0][1] <= win["t0_monotonic"] < win["t1_monotonic"] \
        <= found[1][1] + 1e-3
    out = tr.reduce(tr.read_events(NEW), win["spans"], joined["offset_s"])
    assert out["chips"] == 1 and out["modules"]
    # laid on the trace by the anchors, every dispatch section ends
    # before the program it dispatched ends on the device
    names = {m[0].split("(")[0] for m in out["modules"]}
    assert any(n.startswith("jit_arks_mixed") for n in names), names
    assert not any("unknown" in n or "lambda" in n for n in names), names
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert any(k.startswith("inside a step: phase.") and k.count(".") >= 2
               for k in gaps), gaps


@needs_new
def test_the_new_recorded_trace_has_scopes_and_a_host_gap():
    with open(NEW_SPANS) as f:
        win = json.load(f)
    paths = _scopes.op_paths(NEW)
    scopes = {_scopes.scope_of(p) for p in paths.values()}
    assert {"arks.ffn", "arks.attn_qkv", "arks.lm_head",
            "arks.sampler"} <= scopes, scopes
    out = tr.reduce(tr.read_events(NEW))
    eng = _ctx(win["spans"], win["t0_monotonic"],
               win["t1_monotonic"])["engine"]
    ctx = {"device": dict(out, xplane=NEW, slice_monotonic=(
        win["t0_monotonic"], win["t1_monotonic"])), "engine": eng}
    got = _scopes.by_scope(ctx)
    assert got and sum(got.values()) == pytest.approx(out["busy_s"],
                                                      rel=0.1)
    shares = {n: manifest.load_reader(n)(ctx) for n in (
        "attn_layout_share", "sampler_share", "moe_dequant_share.tput")}
    assert 0 < shares["sampler_share"] < 100
    assert shares["moe_dequant_share.tput"] == 0.0     # a dense model
    gap = manifest.load_reader("step_host_gap_ms_p50")(ctx)
    assert gap is not None and 0 < gap < 1000
