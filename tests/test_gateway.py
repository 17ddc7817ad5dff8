"""Gateway data-plane tests: auth, QoS, rate limits, quota, routing, SSE
usage extraction — the behaviors of the reference's ext_proc plugin
(pkg/gateway), asserted over a stub OpenAI backend."""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from arks_tpu.control import resources as res
from arks_tpu.control.store import Store
from arks_tpu.gateway.server import Gateway

import harness

PROMPT_TOKENS, COMPLETION_TOKENS = 7, 5


class _StubBackend:
    """Minimal OpenAI-compatible backend with fixed usage numbers."""

    def __init__(self, fail_with: int | None = None):
        self.requests: list[dict] = []
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length))
                stub.requests.append(
                    {"body": body,
                     "headers": {k.lower(): v for k, v in self.headers.items()}})
                if stub.fail_with:
                    self.send_response(stub.fail_with)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                usage = {"prompt_tokens": PROMPT_TOKENS,
                         "completion_tokens": COMPLETION_TOKENS,
                         "total_tokens": PROMPT_TOKENS + COMPLETION_TOKENS}
                if body.get("stream"):
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    frames = [
                        {"id": "x", "choices": [{"delta": {"content": "hi"}}]},
                        {"id": "x", "choices": [], "usage": usage},
                    ]
                    payload = b"".join(
                        b"data: " + json.dumps(f).encode() + b"\n\n" for f in frames
                    ) + b"data: [DONE]\n\n"
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                else:
                    data = json.dumps({"id": "x", "choices": [
                        {"message": {"content": "hello"}}], "usage": usage}).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)

        self.fail_with = fail_with
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_port
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    @property
    def addr(self):
        return f"127.0.0.1:{self.port}"

    def stop(self):
        self.httpd.shutdown()


@pytest.fixture()
def world():
    store = Store()
    backend = _StubBackend()
    store.create(res.Endpoint(name="m1", namespace="team-a", spec={}, status={
        "routes": [{"backend": {"addresses": [backend.addr]}, "weight": 1}]}))
    store.create(res.Token(name="alice", namespace="team-a", spec={
        "token": "sk-alice",
        "qos": [{"endpoint": {"name": "m1"},
                 "rateLimits": [{"type": "rpm", "value": 4}],
                 "quota": {"name": "alice-quota"}}]}))
    store.create(res.Quota(name="alice-quota", namespace="team-a", spec={
        "quotas": [{"type": "total", "value": 60}]}))
    gw = Gateway(store, host="127.0.0.1", port=0, quota_sync_s=0.2)
    gw.start(background=True)
    deadline = time.monotonic() + 10
    while not gw.qos.token_known("sk-alice") and time.monotonic() < deadline:
        time.sleep(0.02)  # wait for the token index pump
    yield gw, store, backend
    gw.stop()
    backend.stop()


def _token_index_caught_up(gw, store):
    """The gateway's token index is fed by a watch thread: wait until it
    holds alice's token as the store has it now (a fixed sleep is a bet on
    the host's clock)."""
    want = store.get(res.Token, "alice", "team-a").spec
    harness.wait_for(
        lambda: gw.qos._by_token["sk-alice"].spec == want, timeout=10,
        interval=0.01, what="the token index to hold alice's updated token")


def _post(gw, body, token="sk-alice", path="/v1/chat/completions"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 **({"Authorization": f"Bearer {token}"} if token else {})})
    return urllib.request.urlopen(req, timeout=30)


def _err(fn):
    try:
        fn()
        raise AssertionError("expected HTTPError")
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def test_auth_required(world):
    gw, _, _ = world
    code, body = _err(lambda: _post(gw, {"model": "m1"}, token=None))
    assert code == 401 and "Authorization" in body["error"]["message"]


def test_unknown_token_401(world):
    gw, _, _ = world
    code, _ = _err(lambda: _post(gw, {"model": "m1"}, token="sk-mallory"))
    assert code == 401


def test_model_not_in_qos_403(world):
    gw, store, _ = world
    store.create(res.Endpoint(name="m2", namespace="team-a", spec={}))
    code, _ = _err(lambda: _post(gw, {"model": "m2"}))
    assert code == 403


def test_unknown_model_404(world):
    gw, store, _ = world
    t = store.get(res.Token, "alice", "team-a")
    t.spec["qos"].append({"endpoint": {"name": "ghost"}, "rateLimits": []})
    store.update(t)
    _token_index_caught_up(gw, store)
    code, _ = _err(lambda: _post(gw, {"model": "ghost"}))
    assert code == 404


def test_stream_requires_include_usage(world):
    gw, _, _ = world
    code, body = _err(lambda: _post(gw, {"model": "m1", "stream": True}))
    assert code == 400 and "include_usage" in body["error"]["message"]


def test_proxy_non_stream_and_usage_accounting(world):
    gw, store, backend = world
    with _post(gw, {"model": "m1", "messages": []}) as r:
        data = json.load(r)
    assert data["usage"]["total_tokens"] == 12
    # Routing headers injected toward the backend.
    hdrs = backend.requests[-1]["headers"]
    assert hdrs["x-arks-model"] == "m1"
    assert hdrs["x-arks-namespace"] == "team-a"
    assert hdrs["x-arks-username"] == "alice"
    # Quota accounted + persisted into the CR status by the syncer.
    assert gw.quota.get_usage("team-a", "alice-quota")["total"] == 12
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        q = store.get(res.Quota, "alice-quota", "team-a")
        used = {s["type"]: s["used"] for s in q.status.get("quotaStatus", [])}
        if used.get("total") == 12:
            break
        time.sleep(0.05)
    else:
        raise AssertionError("quota status not synced")


def test_streaming_relay_and_usage(world):
    gw, _, _ = world
    frames = []
    with _post(gw, {"model": "m1", "stream": True,
                    "stream_options": {"include_usage": True}}) as r:
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: "):
                frames.append(line[6:])
    assert frames[-1] == "[DONE]"
    assert gw.quota.get_usage("team-a", "alice-quota")["total"] == 12


def test_rpm_limit_429(world):
    gw, _, _ = world
    for _ in range(4):
        _post(gw, {"model": "m1"}).read()
    code, body = _err(lambda: _post(gw, {"model": "m1"}))
    assert code == 429 and "rpm" in body["error"]["message"]


def test_quota_exhaustion_429(world):
    gw, store, _ = world
    t = store.get(res.Token, "alice", "team-a")
    t.spec["qos"][0]["rateLimits"] = [{"type": "rpm", "value": 100}]
    store.update(t)
    _token_index_caught_up(gw, store)
    for _ in range(5):  # 5 * 12 = 60 >= limit 60
        _post(gw, {"model": "m1"}).read()
    code, body = _err(lambda: _post(gw, {"model": "m1"}))
    assert code == 429 and "quota" in body["error"]["message"]


def test_models_list_scoped_to_token(world):
    gw, _, _ = world
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/models",
        headers={"Authorization": "Bearer sk-alice"})
    with urllib.request.urlopen(req, timeout=10) as r:
        data = json.load(r)
    assert [m["id"] for m in data["data"]] == ["m1"]


def test_backend_failover(world):
    gw, store, backend = world
    ep = store.get(res.Endpoint, "m1", "team-a")
    # Dead backend first; gateway must fail over to the live one.
    ep.status["routes"] = [
        {"backend": {"addresses": ["127.0.0.1:1", backend.addr]}, "weight": 1}]
    store.update_status(ep)
    ok = 0
    for _ in range(4):
        with _post(gw, {"model": "m1"}) as r:
            ok += r.status == 200
    assert ok == 4


def test_restart_recovery_reseeds_from_cr(world):
    gw, store, backend = world
    _post(gw, {"model": "m1"}).read()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        q = store.get(res.Quota, "alice-quota", "team-a")
        if q.status.get("quotaStatus"):
            break
        time.sleep(0.05)
    # Simulate a gateway restart: fresh QuotaService, empty counters.
    gw.quota._usage.clear()
    gw.syncer.sync_once()
    assert gw.quota.get_usage("team-a", "alice-quota")["total"] == 12


def test_no_backends_503(world):
    gw, store, _ = world
    ep = store.get(res.Endpoint, "m1", "team-a")
    ep.status["routes"] = []
    store.update_status(ep)
    code, _ = _err(lambda: _post(gw, {"model": "m1"}))
    assert code == 503


def test_oversize_body_413(world):
    """Client-buffer parity (dist/gateway.yaml:250-261): bodies beyond the
    cap are rejected up front, before buffering."""
    gw, _, _ = world
    gw.max_body_bytes = 1024
    big = {"model": "m1", "messages": [{"role": "user", "content": "x" * 4096}]}
    code, body = _err(lambda: _post(gw, big))
    assert code == 413
    assert "exceeds" in body["error"]["message"]


def test_processing_deadline_504(world):
    """Per-stage timeout (ext_proc messageTimeout parity): a wedged counter
    backend turns into a clean 504, not a hung connection."""
    gw, _, _ = world

    class SlowLimiter:
        def check_limit(self, *a, **k):
            time.sleep(0.2)
            return []

        def do_limit(self, *a, **k):
            return None

    gw.limiter = SlowLimiter()
    gw.process_timeout_s = 0.05
    code, body = _err(lambda: _post(
        gw, {"model": "m1", "messages": [{"role": "user", "content": "hi"}]}))
    assert code == 504
    assert "processing" in body["error"]["message"]


def test_slow_body_trickle_408(world):
    """A client trickling its body cannot pin the handler past the total
    deadline: the incremental read aborts with 408."""
    import socket as _socket

    gw, _, _ = world
    gw.process_timeout_s = 0.3
    s = _socket.create_connection(("127.0.0.1", gw.port), timeout=10)
    try:
        s.sendall(b"POST /v1/chat/completions HTTP/1.1\r\n"
                  b"Host: x\r\nAuthorization: Bearer sk-alice\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: 1000\r\n\r\n")
        t0 = time.monotonic()
        # Trickle a few bytes, then just wait for the server's verdict.
        for _ in range(3):
            s.sendall(b"{")
            time.sleep(0.1)
        s.settimeout(10)
        resp = s.recv(4096)
        assert b"408" in resp.split(b"\r\n")[0]
        assert time.monotonic() - t0 < 5
    finally:
        s.close()


# ---------------------------------------------------------------------------
# SLO tiers (arks_tpu.slo): x-arks-tier validation, forwarding, 503 headers
# ---------------------------------------------------------------------------


def _post_tier(gw, body, tier, token="sk-alice"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/chat/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": f"Bearer {token}",
                 "x-arks-tier": tier})
    return urllib.request.urlopen(req, timeout=30)


def test_tier_header_rejected_without_ladder(world):
    """With no ARKS_SLO_TIERS configured, a tier header is a config
    mismatch — reject it instead of silently ignoring the QoS ask."""
    gw, _, _ = world
    assert not gw.slo
    code, body = _err(lambda: _post_tier(gw, {"model": "m1"}, "latency"))
    assert code == 400 and "ARKS_SLO_TIERS" in body["error"]["message"]


def test_tier_header_unknown_tier_400(world):
    from arks_tpu import slo as slo_mod
    gw, _, _ = world
    gw.slo = slo_mod.parse_tiers("latency:ttft_ms=300,batch:")
    code, body = _err(lambda: _post_tier(gw, {"model": "m1"}, "bogus"))
    assert code == 400
    assert "bogus" in body["error"]["message"]
    assert "latency" in body["error"]["message"]  # lists the valid ladder


def test_tier_header_forwarded_to_backend(world):
    from arks_tpu import slo as slo_mod
    gw, _, backend = world
    gw.slo = slo_mod.parse_tiers("latency:ttft_ms=300,batch:")
    with _post_tier(gw, {"model": "m1", "messages": []}, "latency") as r:
        assert r.status == 200
    assert backend.requests[-1]["headers"]["x-arks-tier"] == "latency"


def test_rpm_429_carries_retry_after_to_window_edge(world):
    """Every rate-limit 429 carries Retry-After derived from the
    wall-clock window edge (satellite contract: precise backoff, not
    guess-retry) plus the tenant identity header."""
    gw, _, _ = world
    for _ in range(4):
        _post(gw, {"model": "m1"}).read()
    try:
        _post(gw, {"model": "m1"})
        raise AssertionError("expected HTTPError")
    except urllib.error.HTTPError as e:
        assert e.code == 429
        ra = e.headers.get("Retry-After")
        assert ra is not None and 1 <= int(ra) <= 60
        assert e.headers.get("x-arks-tenant") == "team-a/alice"


def test_quota_429_carries_retry_after(world):
    """Quota-exhaustion 429s carry Retry-After too (the syncer's status
    cadence horizon) — BOTH 429 classes are retryable-with-a-clock."""
    gw, store, _ = world
    t = store.get(res.Token, "alice", "team-a")
    t.spec["qos"][0]["rateLimits"] = [{"type": "rpm", "value": 100}]
    store.update(t)
    _token_index_caught_up(gw, store)
    for _ in range(5):
        _post(gw, {"model": "m1"}).read()
    try:
        _post(gw, {"model": "m1"})
        raise AssertionError("expected HTTPError")
    except urllib.error.HTTPError as e:
        assert e.code == 429
        assert "quota" in json.load(e)["error"]["message"]
        assert e.headers.get("Retry-After") is not None
        assert int(e.headers["Retry-After"]) >= 1
        assert e.headers.get("x-arks-tenant") == "team-a/alice"


def test_tier_capacity_503_carries_retry_after_and_tier(world):
    """A tier-carrying request that hits capacity (no ready backends)
    gets 503 + Retry-After + x-arks-tier, so per-tier clients back off
    independently (satellite contract)."""
    from arks_tpu import slo as slo_mod
    gw, store, _ = world
    gw.slo = slo_mod.parse_tiers("latency:ttft_ms=300,batch:")
    gw.cold_start_wait_s = 0.3
    ep = store.get(res.Endpoint, "m1", "team-a")
    ep.status = {"routes": []}
    store.update(ep)
    time.sleep(0.3)
    try:
        _post_tier(gw, {"model": "m1"}, "latency")
        raise AssertionError("expected HTTPError")
    except urllib.error.HTTPError as e:
        assert e.code == 503
        assert e.headers.get("Retry-After") is not None
        assert e.headers.get("x-arks-tier") == "latency"


# ---------------------------------------------------------------------------
# Tenant-fair admission: identity mint, edge shed, bounded tracker state
# ---------------------------------------------------------------------------


def test_tenant_header_minted_toward_backend(world):
    """The gateway mints x-arks-tenant from the token's resolved
    namespace/username — clients cannot spoof tenant identity by
    sending the header themselves."""
    gw, _, backend = world
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/chat/completions",
        data=json.dumps({"model": "m1", "messages": []}).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer sk-alice",
                 "x-arks-tenant": "spoofed/identity"})
    urllib.request.urlopen(req, timeout=30).read()
    assert backend.requests[-1]["headers"]["x-arks-tenant"] == "team-a/alice"


def test_edge_shed_rejects_most_over_share_tenant(world):
    """At the in-flight cap the MOST over-share tenant is shed with
    429 + Retry-After + tenant header; an under-share tenant still
    flows (pre-emptive edge protection, not a blanket 429)."""
    gw, _, _ = world
    gw.shed_inflight_max = 5
    # A phantom tenant holds most of the in-flight budget.
    with gw._inflight_lock:
        gw._inflight["team-b/flood"] = 5
    try:
        # alice: prospective share (0+1)/1 = 1 < flood's 5 -> admitted.
        with _post(gw, {"model": "m1", "messages": []}) as r:
            assert r.status == 200
        # The handler counts alice's request out AFTER the client has its
        # reply: planting the next state before that lands would lose one
        # of its five to the late decrement (4 in flight < the cap of 5,
        # and the shed below never fires: the failure seen under load).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with gw._inflight_lock:
                if "team-a/alice" not in gw._inflight:
                    break
            time.sleep(0.005)
        # Now alice IS the most over-share prospective tenant.
        with gw._inflight_lock:
            assert "team-a/alice" not in gw._inflight
            gw._inflight.clear()
            gw._inflight["team-a/alice"] = 5
        try:
            _post(gw, {"model": "m1"})
            raise AssertionError("expected HTTPError")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert e.headers.get("Retry-After") == "1"
            assert e.headers.get("x-arks-tenant") == "team-a/alice"
            assert "fair share" in json.load(e)["error"]["message"]
        assert gw.metrics.shed_total.get(
            tenant="team-a/alice", reason="inflight_overshare") == 1
    finally:
        gw.shed_inflight_max = 0
        with gw._inflight_lock:
            gw._inflight.clear()


def test_rate_tracker_lru_bound():
    from arks_tpu.gateway.server import RequestRateTracker
    tr = RequestRateTracker(max_keys=3)
    for i in range(3):
        tr.record("ns", f"ep{i}")
    # Touch ep0 so it becomes most-recently-used, then overflow.
    tr.record("ns", "ep0")
    tr.record("ns", "ep3")
    assert len(tr._counts) == 3
    assert tr.rpm("ns", "ep1") == 0.0     # LRU victim: evicted
    assert tr.rpm("ns", "ep0") >= 2.0     # survived via the touch
    assert tr.rpm("ns", "ep3") >= 1.0


def test_ejector_lru_bound():
    from arks_tpu.gateway.server import _Ejector
    ej = _Ejector(max_addrs=4)
    for i in range(1000):
        ej.fail(f"10.0.0.{i}:80")
    assert len(ej._bad) <= 4
    assert len(ej._ejected_until) <= 4


# ---------------------------------------------------------------------------
# SSE metering: exact accounting across mid-stream client disconnect
# ---------------------------------------------------------------------------


class _SlowStreamBackend:
    """Streams SSE frames with a pause before the usage frame so a test
    client can hang up mid-stream.  ``usage_delay_s`` paces the frames;
    with ``send_usage=False`` the stream trickles fillers and never
    delivers usage (the unmetered-giveup case)."""

    def __init__(self, usage_delay_s=0.3, send_usage=True):
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                self.rfile.read(length)
                usage = {"prompt_tokens": PROMPT_TOKENS,
                         "completion_tokens": COMPLETION_TOKENS,
                         "total_tokens": PROMPT_TOKENS + COMPLETION_TOKENS}
                first = (b"data: " + json.dumps(
                    {"id": "x", "choices": [{"delta": {"content": "hi"}}]}
                ).encode() + b"\n\n")
                if stub.send_usage:
                    rest = (b"data: " + json.dumps(
                        {"id": "x", "choices": [], "usage": usage}
                    ).encode() + b"\n\n" + b"data: [DONE]\n\n")
                else:
                    filler = (b"data: " + json.dumps(
                        {"id": "x", "choices": [{"delta": {"content": "z"}}]}
                    ).encode() + b"\n\n")
                    rest = filler * 6 + b"data: [DONE]\n\n"
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Content-Length",
                                 str(len(first) + len(rest)))
                self.end_headers()
                self.wfile.write(first)
                self.wfile.flush()
                if stub.send_usage:
                    time.sleep(stub.usage_delay_s)
                    self.wfile.write(rest)
                else:
                    step = len(rest) // 6
                    for i in range(0, len(rest), step):
                        time.sleep(stub.usage_delay_s)
                        try:
                            self.wfile.write(rest[i:i + step])
                            self.wfile.flush()
                        except OSError:
                            return

        self.usage_delay_s, self.send_usage = usage_delay_s, send_usage
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_port
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    @property
    def addr(self):
        return f"127.0.0.1:{self.port}"

    def stop(self):
        self.httpd.shutdown()


def _disconnect_mid_stream(gw, slow):
    """Open a streaming request, read up to the first frame, then RST
    the connection (SO_LINGER 0) so the gateway's next relay write
    fails immediately."""
    import socket as _socket
    import struct as _struct

    body = json.dumps({"model": "m1", "stream": True,
                       "stream_options": {"include_usage": True}}).encode()
    s = _socket.create_connection(("127.0.0.1", gw.port), timeout=10)
    try:
        s.sendall(b"POST /v1/chat/completions HTTP/1.1\r\n"
                  b"Host: x\r\nAuthorization: Bearer sk-alice\r\n"
                  b"Content-Type: application/json\r\n"
                  + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        got = b""
        while b"delta" not in got:
            got += s.recv(4096)
    finally:
        s.setsockopt(_socket.SOL_SOCKET, _socket.SO_LINGER,
                     _struct.pack("ii", 1, 0))
        s.close()


def test_disconnect_mid_stream_still_meters_exactly_once(world):
    """Client hangs up after the first SSE frame; the backend only
    emits usage later.  The gateway drains to the usage frame and
    accounts it EXACTLY once — no unmetered leak, no double-count."""
    gw, store, _ = world
    slow = _SlowStreamBackend(usage_delay_s=0.3)
    try:
        ep = store.get(res.Endpoint, "m1", "team-a")
        ep.status["routes"] = [
            {"backend": {"addresses": [slow.addr]}, "weight": 1}]
        store.update_status(ep)
        _disconnect_mid_stream(gw, slow)
        deadline = time.monotonic() + 5
        while (gw.metrics.client_disconnects_total.total() < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert gw.metrics.client_disconnects_total.total() == 1
        assert gw.metrics.usage_unmetered_total.total() == 0
        # Exactly once: the full usage object, not zero, not doubled.
        deadline = time.monotonic() + 5
        while (gw.quota.get_usage("team-a", "alice-quota").get("total", 0) < 12
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert gw.quota.get_usage("team-a", "alice-quota")["total"] == 12
    finally:
        slow.stop()


def test_disconnect_drain_window_bounds_the_babysit(world):
    """Client gone AND the backend never sends usage: the gateway gives
    up at ARKS_GW_DISCONNECT_DRAIN_S and records the unmetered leak
    instead of hanging on a dead stream — and nothing is billed."""
    gw, store, _ = world
    slow = _SlowStreamBackend(usage_delay_s=0.25, send_usage=False)
    gw.disconnect_drain_s = 0.3
    try:
        ep = store.get(res.Endpoint, "m1", "team-a")
        ep.status["routes"] = [
            {"backend": {"addresses": [slow.addr]}, "weight": 1}]
        store.update_status(ep)
        t0 = time.monotonic()
        _disconnect_mid_stream(gw, slow)
        deadline = time.monotonic() + 5
        while (gw.metrics.usage_unmetered_total.total() < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert gw.metrics.usage_unmetered_total.total() == 1
        assert time.monotonic() - t0 < 4, "drain window did not bound"
        assert gw.quota.get_usage("team-a", "alice-quota").get("total", 0) == 0
    finally:
        gw.disconnect_drain_s = 10.0
        slow.stop()
