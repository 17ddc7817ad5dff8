"""Elastic parallelism: live resize over HTTP, streaming
scale-from-zero, router planned membership, and the signals-mode
autoscaler — the control loop that turns overload evidence (per-tier
SLO burn, admission saturation) into topology changes.

Byte-identity note: greedy (argmax) streams are byte-identical across a
TP shape change; seeded SAMPLED streams are distribution-exact but not
byte-exact (the psum reduction order shifts with the mesh), so every
cross-shape assertion here rides greedy streams.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from arks_tpu import prefix_sketch as ps
from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                             SamplingParams)
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config
from arks_tpu.router import Discovery, Router
from arks_tpu.server import OpenAIServer

import harness


def _mk_engine(monkeypatch, **kw):
    cfg = get_config("tiny")
    defaults = dict(model="tiny", num_slots=2, max_cache_len=64,
                    prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    defaults.update(kw)
    return cfg, InferenceEngine(cfg, EngineConfig(**defaults),
                                ByteTokenizer())


def _greedy(cfg, rid, prompt, max_tokens=10):
    return Request(rid, [int(x) % cfg.vocab_size for x in prompt],
                   SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                  ignore_eos=True))


_collect = harness.collect


# ---------------------------------------------------------------------------
# Engine: resize request surface + scale-to-zero / re-arm
# ---------------------------------------------------------------------------

def test_resize_reject_matrix(monkeypatch):
    """Cheap-shape validation raises immediately; capability rejections
    land as outcome="rejected" on the engine thread where the check can
    read coherent scheduler state."""
    cfg, eng = _mk_engine(monkeypatch)
    with pytest.raises(ValueError):
        eng.request_resize(tensor_parallel=0)
    hold = eng.request_resize(tensor_parallel=1024)  # > visible devices
    with pytest.raises(RuntimeError):
        eng.request_resize(tensor_parallel=2)        # one in flight already
    eng.step(block_s=0.01)
    assert hold.wait(10) and hold.outcome == "rejected"
    assert "devices" in hold.error
    assert eng.metrics.engine_resizes_total.get(
        mode="resize", outcome="rejected") == 1
    assert eng._mesh_shape_str() == "tp1xdp1"


def test_resize_to_current_shape_is_trivially_ok(monkeypatch):
    cfg, eng = _mk_engine(monkeypatch)
    hold = eng.request_resize(tensor_parallel=1, data_parallel=1)
    eng.step(block_s=0.01)
    assert hold.wait(10) and hold.outcome == "ok"
    assert eng.elastic_status()["resize_inflight"] is False


def test_scale_to_zero_and_rearm_on_demand(monkeypatch):
    """An idle engine disarms after ARKS_ELASTIC_IDLE_ZERO_S (weights +
    device KV dropped), then a queue arrival re-arms it and the demand
    stream completes byte-identical to a never-disarmed run."""
    monkeypatch.setenv("ARKS_ELASTIC_IDLE_ZERO_S", "0.05")
    cfg, base_eng = _mk_engine(monkeypatch)
    r0 = _greedy(cfg, "b0", [5, 6, 7])
    base_eng.add_request(r0)
    for _ in range(200):
        base_eng.step(block_s=0.01)
        if (base_eng.num_running == 0 and base_eng._queue.empty()
                and base_eng._deferred is None):
            break
    base = _collect(r0)

    cfg, eng = _mk_engine(monkeypatch)
    deadline = time.monotonic() + 30
    while eng.armed and time.monotonic() < deadline:
        eng.step(block_s=0.01)
        time.sleep(0.01)
    assert not eng.armed, "idle engine never scaled to zero"
    assert eng.params is None and eng._cache is None
    st = eng.elastic_status()
    assert st["armed"] is False
    assert eng.metrics.engine_resizes_total.get(
        mode="scale_to_zero", outcome="ok") == 1

    # Demand re-arms: the warm-up request compiles the programs, then
    # the client stream rides them.
    r1 = _greedy(cfg, "d0", [5, 6, 7])
    eng.add_request(r1)
    for _ in range(400):
        eng.step(block_s=0.01)
        if (eng.armed and eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling):
            break
    assert eng.armed, "demand did not re-arm the engine"
    got = _collect(r1)
    assert (got[0], got[1].finish_reason) == (base[0], "length"), \
        "post-re-arm stream diverged"
    assert eng.last_rearm_stats is not None
    assert eng.last_rearm_stats["seconds"] > 0
    assert eng.metrics.engine_resizes_total.get(
        mode="rearm", outcome="ok") == 1


def test_disarmed_resize_rearms_at_requested_shape(monkeypatch):
    """request_resize against a scaled-to-zero engine re-arms it AT the
    requested shape — the streaming scale-up path the autoscaler's
    actuator drives (no demand needed)."""
    monkeypatch.setenv("ARKS_ELASTIC_IDLE_ZERO_S", "0.05")
    cfg, eng = _mk_engine(monkeypatch)
    deadline = time.monotonic() + 30
    while eng.armed and time.monotonic() < deadline:
        eng.step(block_s=0.01)
        time.sleep(0.01)
    assert not eng.armed
    hold = eng.request_resize(tensor_parallel=2)
    for _ in range(200):
        eng.step(block_s=0.01)
        if hold.outcome is not None:
            break
    assert hold.outcome == "ok", hold.error
    assert eng.armed and eng._mesh_shape_str() == "tp2xdp1"
    assert eng.last_rearm_stats["shape"] == "tp2xdp1"


# ---------------------------------------------------------------------------
# Server: /v1/elastic endpoints + disarmed readiness
# ---------------------------------------------------------------------------

def _get_json(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _post_json(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_elastic_http_surface(monkeypatch):
    """The operator surface end to end: status, a live resize over
    POST /v1/elastic/resize (2xx with the new shape), the reject matrix
    as HTTP codes, and the elastic/slo_burn blocks on /readiness."""
    cfg, eng = _mk_engine(monkeypatch)
    eng.start()
    srv = OpenAIServer(eng, served_model_name="t", host="127.0.0.1", port=0)
    srv.start(background=True)
    try:
        code, st = _get_json(srv.port, "/v1/elastic/status")
        assert code == 200 and st["armed"] and st["shape"] == "tp1xdp1"

        code, rdy = _get_json(srv.port, "/readiness")
        assert code == 200
        assert rdy["elastic"]["armed"] is True
        assert "slo_burn" in rdy and "admission" in rdy

        code, out = _post_json(srv.port, "/v1/elastic/resize",
                               {"tensor_parallel": 2})
        assert code == 200 and out["status"] == "ok"
        assert out["elastic"]["shape"] == "tp2xdp1"
        assert out["seconds"] > 0

        code, out = _post_json(srv.port, "/v1/elastic/resize",
                               {"tensor_parallel": 1024})
        assert code == 422 and out["status"] == "rejected"
        code, out = _post_json(srv.port, "/v1/elastic/resize",
                               {"tensor_parallel": 0})
        assert code == 400
        code, out = _post_json(srv.port, "/v1/elastic/resize",
                               {"tensor_parallel": "nope"})
        assert code == 400
    finally:
        srv.stop()
        eng.stop()


@pytest.mark.slow
def test_disarmed_readiness_and_http_rearm(monkeypatch):
    """A scaled-to-zero replica 503s /readiness with a "disarmed" reason
    (the router's planned-join gate and the autoscaler's disarmed count
    both read it) while /v1/elastic/status stays reachable; a resize
    POST re-arms it and readiness returns 200."""
    monkeypatch.setenv("ARKS_ELASTIC_IDLE_ZERO_S", "0.05")
    cfg, eng = _mk_engine(monkeypatch)
    eng.start()
    srv = OpenAIServer(eng, served_model_name="t", host="127.0.0.1", port=0)
    srv.start(background=True)
    try:
        deadline = time.monotonic() + 30
        while eng.armed and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not eng.armed
        code, out = _get_json(srv.port, "/readiness")
        assert code == 503 and "disarmed" in out["error"]["message"]
        code, st = _get_json(srv.port, "/v1/elastic/status")
        assert code == 200 and st["armed"] is False

        code, out = _post_json(srv.port, "/v1/elastic/resize",
                               {"tensor_parallel": 1})
        assert code == 200 and out["status"] == "ok", out
        code, rdy = _get_json(srv.port, "/readiness")
        assert code == 200 and rdy["elastic"]["armed"] is True
    finally:
        srv.stop()
        eng.stop()


# ---------------------------------------------------------------------------
# Router: planned membership (join/leave without a dropped byte)
# ---------------------------------------------------------------------------

class _Backend:
    """A decode backend stub: scripted /readiness (ready flag), a
    mutable sketch payload, and a counting completion handler."""

    def __init__(self, ready=True, sketch=None, name=None):
        backend = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code, data):
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path == "/readiness":
                    if backend.ready:
                        self._send(200, json.dumps(
                            {"status": "ready",
                             "admission": {"saturation": backend.saturation},
                             "slo_burn": backend.burn,
                             "elastic": {"armed": backend.armed}}).encode())
                    else:
                        self._send(503, json.dumps(
                            {"error": {"message": backend.reason}}).encode())
                elif self.path == "/v1/cache/sketch" and backend.sketch:
                    self._send(200, json.dumps(backend.sketch).encode())
                else:
                    self._send(404, b"{}")

            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                backend.calls += 1
                if backend.fail:
                    self._send(503, b'{"error":{"code":503}}')
                    return
                self._send(200, json.dumps(
                    {"id": "ok", "served_by": backend.name,
                     "choices": []}).encode())

        self.ready = ready
        self.sketch = sketch
        self.calls = 0
        self.fail = False
        self.armed = True
        self.saturation = 0.0
        self.burn = {}
        self.reason = "engine scaled to zero (disarmed)"
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.addr = f"127.0.0.1:{self._httpd.server_port}"
        self.name = name or self.addr
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def _sketch_payload(epoch="boot1.0"):
    ex = ps.SketchExporter(4)
    p = ex.build([], ("k", 1), [], 1)
    p["epoch"] = epoch
    return p


def _mk_router(monkeypatch, decode="", **kw):
    monkeypatch.setenv("ARKS_PREFILL_ADDRS", "")
    monkeypatch.setenv("ARKS_DECODE_ADDRS", decode)
    monkeypatch.setenv("ARKS_ROUTER_RETRY_BACKOFF_S", "0.01")
    monkeypatch.setenv("ARKS_ROUTER_SKETCH_POLL_S", "60")
    return Router(Discovery(None), "tiny", host="127.0.0.1", port=0,
                  policy="cache_aware", **kw)


def test_discovery_overlay_add_remove(monkeypatch):
    monkeypatch.setenv("ARKS_PREFILL_ADDRS", "")
    monkeypatch.setenv("ARKS_DECODE_ADDRS", "10.0.0.1:1")
    d = Discovery(None)
    assert d.backends()[1] == ["10.0.0.1:1"]
    d.add("decode", "10.0.0.2:1")
    assert d.backends()[1] == ["10.0.0.1:1", "10.0.0.2:1"]
    d.add("decode", "10.0.0.2:1")  # idempotent
    assert d.backends()[1] == ["10.0.0.1:1", "10.0.0.2:1"]
    # remove masks even env/file-listed backends, and survives re-reads.
    d.remove("decode", "10.0.0.1:1")
    assert d.backends()[1] == ["10.0.0.2:1"]
    assert d.backends()[1] == ["10.0.0.2:1"]
    d.add("decode", "10.0.0.1:1")  # unmask by re-adding
    assert "10.0.0.1:1" in d.backends()[1]
    with pytest.raises(ValueError):
        d.add("frontend", "10.0.0.3:1")


def test_plan_join_admits_mid_workload_with_zero_5xx(monkeypatch):
    """A new backend joins THROUGH plan_join while a client workload
    runs: every request in flight across the handoff gets a 2xx (the
    joiner is admitted only after its readiness gate + sketch prime),
    and post-join traffic reaches the joiner."""
    a = _Backend(sketch=_sketch_payload("a.0"))
    b = _Backend(sketch=_sketch_payload("b.0"))
    r = _mk_router(monkeypatch, decode=a.addr, unified=True)
    r.start(background=True)
    failures, done = [], threading.Event()

    def workload():
        n = 0
        while not done.is_set():
            # Varied prompts: rendezvous hashing spreads distinct prefix
            # keys across the rotation, so the joiner takes a share.
            n += 1
            body = json.dumps({"model": "tiny",
                               "prompt": [1, 2, 3, n % 97]}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{r.port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    if resp.status != 200:
                        failures.append(resp.status)
            except Exception as e:  # noqa: BLE001 — any 5xx/raise counts
                failures.append(repr(e))
            time.sleep(0.002)

    t = threading.Thread(target=workload, daemon=True)
    t.start()
    try:
        time.sleep(0.1)
        out = r.plan_join(b.addr)
        assert out["addr"] == b.addr and out["seconds"] >= 0
        assert out["sketch_primed"], "join must prime the sketch"
        time.sleep(0.3)
    finally:
        done.set()
        t.join(timeout=10)
        r.stop()
        a.stop()
        b.stop()
    assert not failures, f"client-visible failures across the join: {failures}"
    assert b.addr in r.discovery.backends()[1]
    assert b.calls > 0, "the joined backend never took traffic"
    assert r.metrics.planned_membership_total.get(
        op="join", outcome="ok") == 1
    assert r.metrics.join_seconds.get(backend=b.addr) >= 0


def test_plan_join_primes_sketch_then_resize_epoch_drops_once(monkeypatch):
    """The join's sketch prime is DROP-FREE (first observation, no
    pre-resize epoch to discard); the backend's post-resize epoch bump
    then drops the stale membership EXACTLY once on the next poll."""
    b = _Backend(sketch=_sketch_payload("boot1.0"))
    r = _mk_router(monkeypatch, decode="")
    try:
        r.plan_join(b.addr)
        assert r.sketches.get(b.addr) is not None
        assert r.metrics.sketch_epoch_drops_total.get(backend=b.addr) == 0, \
            "the prime must not count an epoch drop"
        # The backend live-resizes: its sketch epoch bumps (the tier-0
        # index restarted empty at the new shape).
        b.sketch = _sketch_payload("boot1.1-resize")
        r.sketches.poll_once()
        assert r.metrics.sketch_epoch_drops_total.get(backend=b.addr) == 1
        r.sketches.poll_once()
        assert r.metrics.sketch_epoch_drops_total.get(backend=b.addr) == 1, \
            "a stable epoch must not keep dropping"
    finally:
        b.stop()


def test_plan_join_times_out_on_unready_backend(monkeypatch):
    """An unready (still re-arming) backend never joins: plan_join
    bounds the readiness poll and leaves the membership untouched."""
    b = _Backend(ready=False)
    r = _mk_router(monkeypatch, decode="")
    try:
        with pytest.raises(TimeoutError):
            r.plan_join(b.addr, timeout_s=0.3)
        assert b.addr not in r.discovery.backends()[1]
        assert r.metrics.planned_membership_total.get(
            op="join", outcome="timeout") == 1
    finally:
        b.stop()


def test_plan_leave_removes_backend_and_sketch(monkeypatch):
    b = _Backend(sketch=_sketch_payload())
    r = _mk_router(monkeypatch, decode=b.addr)
    try:
        r.sketches.poll_once()
        assert r.sketches.get(b.addr) is not None
        r.plan_leave(b.addr)
        assert b.addr not in r.discovery.backends()[1]
        assert r.sketches.get(b.addr) is None
        assert r.metrics.planned_membership_total.get(
            op="leave", outcome="ok") == 1
    finally:
        b.stop()


def test_joined_backend_failover_restabilizes(monkeypatch):
    """The joined backend starts 503ing: requests fail over to the
    incumbent exactly like pre-join failover — the planned membership
    changes the rotation, never the retry semantics."""
    a = _Backend()
    b = _Backend()
    r = _mk_router(monkeypatch, decode=a.addr, unified=True)
    r.start(background=True)
    try:
        r.plan_join(b.addr)
        b.fail = True
        body = json.dumps({"model": "tiny", "prompt": [1, 2, 3]}).encode()
        for _ in range(6):
            req = urllib.request.Request(
                f"http://127.0.0.1:{r.port}/v1/completions", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
    finally:
        r.stop()
        a.stop()
        b.stop()


# ---------------------------------------------------------------------------
# Autoscaler: signals mode (SLO burn / saturation -> replicas)
# ---------------------------------------------------------------------------

def _mk_autoscaler(sig, actuator=None):
    from arks_tpu.control import resources as res
    from arks_tpu.control.autoscaler import AutoscalerController
    from arks_tpu.control.store import Store

    store = Store()
    app = store.create(res.Application(name="app", spec={
        "replicas": 1, "servedModelName": "m",
        "autoscale": {"minReplicas": 0, "maxReplicas": 3,
                      "scaleDownStabilizationSeconds": 0},
    }))
    ctl = AutoscalerController(store, rate_source=lambda ns, m: 0.0,
                               signals_source=lambda ns, m: sig["v"],
                               actuator=actuator)
    return store, app, ctl


def _reconcile(store, ctl):
    from arks_tpu.control import resources as res
    app = store.get(res.Application, "app")
    ctl.reconcile(app)
    return store.get(res.Application, "app")


def test_signals_scale_up_on_burn_with_cooldown(monkeypatch):
    """An SLO burn over the high-water mark adds ONE replica; the next
    burning tick inside the cooldown holds (reason="cooldown")."""
    monkeypatch.setenv("ARKS_ELASTIC_COOLDOWN_S", "60")
    sig = {"v": {"burn": 2.0, "saturation": 0.1, "ready": 1}}
    store, app, ctl = _mk_autoscaler(sig)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 2
    st = app.status["autoscale"]
    assert st["mode"] == "signals" and st["reason"] == "signal_high"
    assert st["burnRate"] == 2.0 and st["ready"] == 1
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 2, "cooldown must damp the second step"
    assert app.status["autoscale"]["reason"] == "cooldown"


def test_signals_saturation_alone_scales_up(monkeypatch):
    sig = {"v": {"burn": 0.0, "saturation": 0.95}}
    store, app, ctl = _mk_autoscaler(sig)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 2
    assert app.status["autoscale"]["reason"] == "signal_high"


def test_signals_hysteresis_band_holds_shape(monkeypatch):
    """Between the water marks (burn under HI but over LO) the shape
    holds — the band is what keeps an oscillating signal from flapping
    the fleet."""
    sig = {"v": {"burn": 0.5, "saturation": 0.5}}
    store, app, ctl = _mk_autoscaler(sig)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 1
    assert app.status["autoscale"]["reason"] == "steady"


def test_signals_scale_down_requires_all_signals_low(monkeypatch):
    monkeypatch.setenv("ARKS_ELASTIC_COOLDOWN_S", "0")
    sig = {"v": {"burn": 0.0, "saturation": 0.8}}  # sat still mid-band
    store, app, ctl = _mk_autoscaler(sig)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 1, "one low signal is not enough"
    sig["v"] = {"burn": 0.0, "saturation": 0.0}
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 0, \
        "all-low signals with min=0 scale to zero"
    assert app.status["autoscale"]["reason"] == "signal_low"


def test_signals_scale_up_from_zero_skips_cooldown(monkeypatch):
    """The cooldown exemption: a burn against ZERO replicas scales up
    immediately even right after a scaling action — rescuing a
    scaled-to-zero fleet is the loop's whole point."""
    monkeypatch.setenv("ARKS_ELASTIC_COOLDOWN_S", "3600")
    sig = {"v": {"burn": 0.0, "saturation": 0.0}}
    store, app, ctl = _mk_autoscaler(sig)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 0
    sig["v"] = {"burn": 5.0, "saturation": 0.0, "disarmed": 1}
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 1, \
        "scale-up from zero must not sit out the cooldown"
    assert app.status["autoscale"]["disarmed"] == 1


def test_signals_missing_evidence_holds_shape(monkeypatch):
    sig = {"v": None}
    store, app, ctl = _mk_autoscaler(sig)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 1
    assert "autoscale" not in app.status, \
        "no evidence: no action, no status churn"


def test_signals_actuator_fires_on_scale_and_failure_is_contained(
        monkeypatch):
    calls = []

    def actuator(app, desired, sig):
        calls.append((desired, sig["burn"]))
        raise RuntimeError("boom")  # must be contained

    sig = {"v": {"burn": 2.0, "saturation": 0.0}}
    store, app, ctl = _mk_autoscaler(sig, actuator=actuator)
    app = _reconcile(store, ctl)
    assert app.spec["replicas"] == 2, "actuator failure must not derail"
    assert calls == [(2, 2.0)]


def test_scrape_and_fleet_signals(monkeypatch):
    """scrape_signals parses the readiness payload (saturation, worst
    per-tier burn, armed); a 503 disarmed replica yields a row with
    disarmed=True; fleet_signals merges worst-case across the fleet."""
    from arks_tpu.control.autoscaler import fleet_signals, scrape_signals
    up = _Backend()
    up.saturation = 0.4
    up.burn = {"gold": 1.5, "best_effort": 0.2}
    down = _Backend(ready=False)
    try:
        s = scrape_signals(up.addr)
        assert s == {"ready": True, "saturation": 0.4, "burn": 1.5,
                     "disarmed": False, "reason": ""}
        s = scrape_signals(down.addr)
        assert s["ready"] is False and s["disarmed"] is True
        assert scrape_signals("127.0.0.1:1") is None  # unreachable
        fleet = fleet_signals([up.addr, down.addr, "127.0.0.1:1"])
        assert fleet["burn"] == 1.5 and fleet["saturation"] == 0.4
        assert fleet["ready"] == 1 and fleet["disarmed"] == 1
        assert fleet_signals(["127.0.0.1:1"]) is None
    finally:
        up.stop()
        down.stop()
