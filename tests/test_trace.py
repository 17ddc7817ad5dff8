"""End-to-end request tracing: W3C context propagation, the per-thread
event rings and off-thread assembly, tail-based retention, the flight
recorder, and the two serving gates —

- **byte identity**: the same workload with tracing on and with
  ``ARKS_TRACE=0`` must emit byte-identical token streams (the tracer
  records, it never schedules) at pipeline depths 0 and 2 for plain,
  guided, and speculative traffic;
- **correlation**: a gateway-originated request's exported trace carries
  spans from all three components (gateway admit, router pick, engine
  lifecycle) under the ONE trace id minted at the gateway, including a
  park/unpark pair and the pipelined issue->resolve spans.
"""

import functools
import json
import time
import urllib.error
import urllib.request

import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config
from arks_tpu.obs import trace as trace_mod
from arks_tpu.obs.trace import TraceCtx, Tracer, TraceStore

import harness


# ------------------------------------------------------------ W3C context

def test_traceparent_roundtrip():
    ctx = TraceCtx()
    hdr = ctx.traceparent()
    assert hdr == f"00-{ctx.trace_id}-{ctx.span_id}-01"
    parsed = TraceCtx.parse(hdr)
    assert parsed.trace_id == ctx.trace_id
    assert parsed.parent_id == ctx.span_id
    assert parsed.span_id != ctx.span_id  # a new span id for the next hop


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-abc-def-01",
    "00-" + "g" * 32 + "-" + "1" * 16 + "-01",      # non-hex trace id
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",      # all-zero trace id
    "00-" + "1" * 32 + "-" + "0" * 16 + "-01",      # all-zero span id
    "00-" + "1" * 31 + "-" + "1" * 16 + "-01",      # wrong length
])
def test_traceparent_rejects_malformed(bad):
    assert TraceCtx.parse(bad) is None


def test_child_keeps_trace_id_and_links_parent():
    root = TraceCtx()
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id


def test_from_headers_folds_upstream_spans():
    root = TraceCtx()
    spans = [{"component": "gateway", "name": "gateway.admit",
              "start": 1.0, "end": 2.0}]
    headers = {trace_mod.TRACEPARENT_HEADER: root.traceparent(),
               trace_mod.SPANS_HEADER: trace_mod.spans_header(spans)}
    ctx = TraceCtx.from_headers(headers)
    assert ctx.trace_id == root.trace_id
    assert ctx.upstream == spans
    # Absent/garbage headers -> a fresh root, never an exception.
    fresh = TraceCtx.from_headers({trace_mod.SPANS_HEADER: "not json"})
    assert fresh.trace_id != root.trace_id and fresh.upstream == []


# ----------------------------------------------------- tracer unit tests

def _mk_tracer(monkeypatch, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    return Tracer()  # collector thread NOT started: flush() driven by hand


def test_tracer_assembles_paired_spans(monkeypatch):
    tr = _mk_tracer(monkeypatch, ARKS_TRACE="1", ARKS_TRACE_SAMPLE="1.0")
    tr.register("r1", ctx=None, tier="gold")
    tr.evt("r1", "queue", "B")
    tr.evt("r1", "queue", "E")
    tr.evt("r1", "prefill", "B", 7)
    tr.evt("r1", "prefill", "E")
    tr.evt("r1", "first_token", "I", 0.01)
    tr.evt("r1", "finish", "I", "length")
    tr.flush()
    t = tr.store.get("r1")
    assert t is not None and t["tier"] == "gold" and t["flags"] == []
    by_name = {s["name"]: s for s in t["spans"]}
    assert by_name["queue"]["end"] >= by_name["queue"]["start"]
    assert by_name["prefill"]["arg"] == 7
    assert by_name["finish"]["arg"] == "length"
    assert t["end"] >= t["start"]


def test_tail_retention_keeps_flagged_traces_only(monkeypatch):
    tr = _mk_tracer(monkeypatch, ARKS_TRACE="1", ARKS_TRACE_SAMPLE="0.0")
    tr.evt("ok", "queue", "B")
    tr.evt("ok", "finish", "I", "length")
    tr.evt("bad", "queue", "B")
    tr.evt("bad", "fault", "I", "decode/runtime")
    tr.evt("bad", "finish", "I", "length")
    tr.flush()
    assert tr.store.get("ok") is None          # sampled out
    t = tr.store.get("bad")
    assert t is not None and t["flags"] == ["faulted"]


def test_slo_violation_flags_trace(monkeypatch):
    tr = _mk_tracer(monkeypatch, ARKS_TRACE="1", ARKS_TRACE_SAMPLE="0.0")
    tr.evt("s", "slo_violation", "I", (120.0, 100.0))
    tr.evt("s", "finish", "I", "stop")
    tr.flush()
    assert tr.store.get("s")["flags"] == ["slo_violation"]


def test_store_evicts_oldest_unflagged_first():
    store = TraceStore(cap=2)

    def t(rid, flags):
        return {"trace_id": rid + "-tid", "request_id": rid,
                "flags": flags, "spans": [], "start": 0, "end": 1}
    store.add(t("a", ["faulted"]))
    store.add(t("b", []))
    store.add(t("c", []))
    assert store.get("a") is not None, "flagged trace evicted before bulk"
    assert store.get("b") is None
    assert store.get("c") is not None


def test_flight_recorder_tail_orders_across_threads(monkeypatch):
    import threading

    tr = _mk_tracer(monkeypatch, ARKS_TRACE="1")
    tr.evt("x", "queue", "B")
    th = threading.Thread(target=lambda: tr.evt("", "spill", "I", 3))
    th.start()
    th.join(30)
    assert not th.is_alive(), "the spill event's thread did not end in 30 s"
    tr.evt("x", "finish", "I", "stop")
    tail = tr.tail(10)
    assert [r["name"] for r in tail] == ["queue", "spill", "finish"]
    assert len({r["thread"] for r in tail}) == 2


def test_disabled_tracer_is_inert(monkeypatch):
    tr = _mk_tracer(monkeypatch, ARKS_TRACE="0")
    tr.evt("r", "queue", "B")
    tr.evt("r", "finish", "I", "stop")
    tr.flush()
    tr.register("r")
    assert tr.tail() == [] and tr.store.get("r") is None


def test_pending_gc_bounds_terminal_less_timelines(monkeypatch):
    tr = _mk_tracer(monkeypatch, ARKS_TRACE="1")
    tr._PENDING_CAP = 4
    for i in range(8):  # aborted requests: no terminal event, ever
        tr.evt(f"zombie-{i}", "queue", "B")
    tr.flush()
    assert len(tr._pending) == 4


# -------------------------------------------------- engine-level fixtures

def _mk_engine(monkeypatch, *, depth=0, trace="1", spec=False, **kw):
    monkeypatch.setenv("ARKS_TRACE", trace)
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    defaults = dict(num_slots=2, max_cache_len=64,
                    prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                    prefill_chunk=16, kv_layout="paged")
    if spec:
        defaults.update(draft_model="tiny", draft_len=3)
    eng = harness.warmed("tiny", base=defaults, **kw)
    return eng.cfg, eng


_drive = functools.partial(harness.drive, recover=True)


def _collect(req):
    ids, fin = [], None
    while True:
        out = req.outputs.get(timeout=120)
        ids.extend(out.token_ids)
        if out.finished:
            fin = out
            break
    return ids, fin.finish_reason


def _run_workload(eng, cfg, guided=False):
    reqs = [
        Request("g0", [5, 6, 7], SamplingParams(
            max_tokens=5, temperature=0.0, ignore_eos=True)),
        Request("s0", [int(x) % cfg.vocab_size for x in range(3, 40)],
                SamplingParams(max_tokens=5, temperature=0.8, top_p=0.9,
                               seed=7, ignore_eos=True)),
    ]
    if guided:
        reqs.append(Request("j0", [4, 8, 2], SamplingParams(
            max_tokens=6, temperature=0.0, guide=("json", ""))))
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r) for r in reqs]


# -------------------------------------------------- byte-identity gates

@pytest.mark.parametrize("depth", [0, 2])
def test_stream_identity_tracing_on_vs_off(monkeypatch, depth):
    """Plain + guided traffic: token streams with the tracer recording
    are byte-identical to ARKS_TRACE=0 at this pipeline depth."""
    outs = {}
    for trace in ("1", "0"):
        cfg, eng = _mk_engine(monkeypatch, depth=depth, trace=trace)
        assert eng.trace.enabled == (trace == "1")
        outs[trace] = _run_workload(eng, cfg, guided=True)
        if trace == "1":
            eng.trace.flush()
            # The traced run really recorded: finished timelines landed.
            assert eng.trace.store.get("g0") is not None
            if depth:
                spans = eng.trace.store.get("g0")["spans"]
                assert any(s["name"] == "pipe" for s in spans)
    assert outs["1"] == outs["0"]


@pytest.mark.parametrize("depth", [0, 2])
def test_stream_identity_spec_traffic(monkeypatch, depth):
    """Speculative traffic (draft+verify in the mixed dispatch): accepted
    streams are identical with tracing on and off at this depth."""
    outs = {}
    for trace in ("1", "0"):
        cfg, eng = _mk_engine(monkeypatch, depth=depth, trace=trace,
                              spec=True)
        outs[trace] = _run_workload(eng, cfg)
    assert outs["1"] == outs["0"]


# ------------------------------------------------- chaos / flight recorder

def test_fault_trace_retained_with_replay_and_flight_tail(monkeypatch):
    """A chaos-injected decode fault must leave a RETAINED trace (despite
    a 0.0 sample rate — tail-based retention) carrying the recovery and
    replay spans plus the flight-recorder tail."""
    monkeypatch.setenv("ARKS_TRACE_SAMPLE", "0.0")
    cfg, eng = _mk_engine(monkeypatch, depth=0)
    # Third decode dispatch: survivors hold generated tokens by then, so
    # recovery takes the token-REPLAY path (not a cold re-admit).
    eng._faults.arm("decode:3:runtime")
    outs = _run_workload(eng, cfg)
    assert [fin for _, fin in outs] == ["length", "length"]
    eng.trace.flush()
    flagged = [t for t in eng.trace.store.all() if "faulted" in t["flags"]]
    assert flagged, "fault-flagged trace was not retained"
    t = flagged[0]
    names = [s["name"] for s in t["spans"]]
    assert "replay" in names
    assert "recover" in names            # engine-scope recovery span attached
    assert t["flight_tail"], "flight-recorder tail not attached"
    # The tail is the PRE-fault timeline: the scheduler-phase events that
    # led up to the dispatch that blew, ending at the recovery entry.
    assert any(r["name"].startswith("phase.") for r in t["flight_tail"])
    assert {"t", "rid", "name", "ph", "thread"} <= set(t["flight_tail"][-1])


# ------------------------------------- three-component correlation (e2e)

def test_gateway_router_engine_one_trace(monkeypatch):
    """A request through gateway -> router -> engine server exports ONE
    trace: the id minted at the gateway, the gateway admit + router pick
    spans, a park/unpark pair (guide compile), and the pipelined
    issue->resolve spans — plus the Perfetto export of the same."""
    from arks_tpu.control import resources as res
    from arks_tpu.control.store import Store
    from arks_tpu.engine import guides as guides_mod
    from arks_tpu.gateway.server import Gateway
    from arks_tpu.router import Discovery, Router
    from arks_tpu.server import OpenAIServer

    monkeypatch.setenv("ARKS_TRACE", "1")
    monkeypatch.setenv("ARKS_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")

    # Make the cold guide compile span several scheduler passes so the
    # guided request deterministically parks (park.guide B ... E).
    orig_build = guides_mod.GuideCompiler._build

    def slow_build(self, rx):
        time.sleep(0.5)
        return orig_build(self, rx)
    monkeypatch.setattr(guides_mod.GuideCompiler, "_build", slow_build)

    cfg = get_config("tiny")
    engine = InferenceEngine(cfg, EngineConfig(
        model="tiny", num_slots=2, max_cache_len=64,
        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
        prefill_chunk=16, kv_layout="paged"), ByteTokenizer())
    assert engine._pipe_warm_wait(120) == "ready"
    engine.start()
    srv = OpenAIServer(engine, served_model_name="m1",
                       host="127.0.0.1", port=0)
    srv.start(background=True)

    monkeypatch.setenv("ARKS_DECODE_ADDRS", f"127.0.0.1:{srv.port}")
    monkeypatch.delenv("ARKS_PREFILL_ADDRS", raising=False)
    router = Router(Discovery(None), "m1", host="127.0.0.1", port=0,
                    policy="round_robin", unified=True)
    router.start(background=True)

    store = Store()
    store.create(res.Endpoint(name="m1", namespace="team-a", spec={},
                              status={"routes": [{"backend": {
                                  "addresses": [f"127.0.0.1:{router.port}"]},
                                  "weight": 1}]}))
    store.create(res.Token(name="alice", namespace="team-a", spec={
        "token": "sk-alice", "qos": [{"endpoint": {"name": "m1"}}]}))
    gw = Gateway(store, host="127.0.0.1", port=0, quota_sync_s=0.2)
    gw.start(background=True)
    deadline = time.monotonic() + 10
    while not gw.qos.token_known("sk-alice") and time.monotonic() < deadline:
        time.sleep(0.02)

    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.port}/v1/completions",
            data=json.dumps({
                "model": "m1", "prompt": "hello", "max_tokens": 5,
                "temperature": 0, "ignore_eos": True,
                "response_format": {"type": "json_object"},
            }).encode(),
            headers={"Content-Type": "application/json",
                     "Authorization": "Bearer sk-alice"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert json.load(r)["usage"]["completion_tokens"] >= 1

        def _get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=30) as r:
                return json.load(r)

        # Find the gateway-originated trace among retained timelines.
        trace = None
        for _ in range(50):
            for entry in _get("/v1/traces")["traces"]:
                t = _get(f"/v1/traces/{entry['trace_id']}")
                if any(s.get("component") == "gateway" for s in t["spans"]):
                    trace = t
                    break
            if trace:
                break
            time.sleep(0.1)
        assert trace, "no gateway-correlated trace retained"

        comps = {s.get("component") for s in trace["spans"]}
        assert {"gateway", "router", "engine"} <= comps
        by_name = {}
        for s in trace["spans"]:
            by_name.setdefault(s["name"], []).append(s)
        assert "gateway.admit" in by_name and "router.pick" in by_name
        # One trace id end to end: the engine kept the gateway's root id
        # (64-bit-hex trace id from the traceparent the gateway minted).
        assert len(trace["trace_id"]) == 32
        # A park/unpark pair: the guided request parked on its compile.
        park = by_name["park.guide"][0]
        assert park["end"] is not None and park["end"] > park["start"]
        # Pipelined issue->resolve spans overlap the request's lifetime.
        pipe = by_name.get("pipe", [])
        assert pipe and all(p["end"] >= p["start"] for p in pipe)

        # The Perfetto export carries the same correlated timeline.
        export = _get("/v1/traces/export")
        names = {e["name"] for e in export["traceEvents"]}
        assert {"gateway.admit", "router.pick"} <= names
        pids = {e["pid"] for e in export["traceEvents"]}
        assert len(pids) >= 2  # gateway/router/engine rows are distinct
    finally:
        gw.stop()
        router.stop()
        srv.stop()
        engine.stop()


def test_trace_endpoint_404_when_unknown(monkeypatch):
    from arks_tpu.server import OpenAIServer

    monkeypatch.setenv("ARKS_TRACE", "1")
    cfg, eng = _mk_engine(monkeypatch, depth=0)
    eng.start()
    srv = OpenAIServer(eng, served_model_name="m1",
                       host="127.0.0.1", port=0)
    srv.start(background=True)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/traces/nope", timeout=30)
        assert ei.value.code == 404
    finally:
        srv.stop()
        eng.stop()


# ------------------------------------------------------ profiler windows

def test_profiler_window_start_stop(monkeypatch, tmp_path):
    from arks_tpu.obs import profiler as prof_mod

    monkeypatch.setenv("ARKS_PROF_DIR", str(tmp_path / "prof"))
    pw = prof_mod.ProfilerWindows()
    out = pw.start()
    assert out["ok"] and out["dir"].startswith(str(tmp_path))
    assert pw.start() == {"ok": False, "error": "already_active",
                          "dir": out["dir"]}
    stopped = pw.stop()
    assert stopped["ok"] and stopped["dir"] == out["dir"]
    assert pw.stop() == {"ok": False, "error": "not_active"}


def test_profiler_auto_arm_threshold(monkeypatch, tmp_path):
    from arks_tpu.obs import profiler as prof_mod

    monkeypatch.setenv("ARKS_PROF_DIR", str(tmp_path / "prof"))
    monkeypatch.setenv("ARKS_PROF_AUTO_ARM", "4.0")
    monkeypatch.setenv("ARKS_PROF_WINDOW_S", "0.05")
    # The trailing median is the step clock's (PR 38): forty cycles of
    # 10 ms on a made-up time line.
    from arks_tpu.engine.engine import EngineMetrics
    from arks_tpu.obs.stepclock import StepClock
    clock = StepClock(EngineMetrics())
    for i in range(42):
        clock.dispatched("seq", i * 0.01, i * 0.01 + 0.001)
    assert clock.last_median == pytest.approx(0.01)
    pw = prof_mod.ProfilerWindows()
    pw.on_step(0.01, clock.last_median)     # steady
    assert not pw.active
    pw.on_step(0.2, clock.last_median)      # 20x the median: arm a window
    assert pw.active
    time.sleep(0.1)
    pw.on_step(0.01, clock.last_median)     # window elapsed: closes itself
    assert not pw.active
