"""Sketch-scored routing: deepest-expected-hit selection, the fallback
ladder (tie -> least-loaded -> rendezvous; stale -> rendezvous), epoch
discipline on backend restart, and the interplay with failover — sketch
scoring shapes the retry ORDER, never the failover semantics."""

import hashlib
import json
import socket
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from arks_tpu import prefix_sketch as ps
from arks_tpu.router import Discovery, Router

PAGE = 4
IDS = list(range(32))                      # 8 token blocks at PAGE=4
CHAIN = ps.chain_digests(IDS, PAGE, 8)


def _payload(dev=(), host=(), epoch="e.0", page=PAGE):
    ex = ps.SketchExporter(page)
    p = ex.build(list(dev), ("k", 1), list(host), 1)
    p["epoch"] = epoch
    return p


def _body(ids=IDS):
    return json.dumps({"model": "tiny", "prompt": ids}).encode()


def _inject(router, addr, payload, age_s=0.0):
    bs = ps.BackendSketch.from_payload(payload)
    router.sketches._state[addr] = {"sketch": bs,
                                    "at": time.monotonic() - age_s}


def _mk_router(monkeypatch, decode="", prefill="", **kw):
    monkeypatch.setenv("ARKS_PREFILL_ADDRS", prefill)
    monkeypatch.setenv("ARKS_DECODE_ADDRS", decode)
    monkeypatch.setenv("ARKS_ROUTER_RETRY_BACKOFF_S", "0.01")
    # Keep the background poller inert: tests drive poll_once() directly.
    monkeypatch.setenv("ARKS_ROUTER_SKETCH_POLL_S", "60")
    return Router(Discovery(None), "tiny", host="127.0.0.1", port=0,
                  policy="cache_aware", **kw)


def _rz_order(key, backends):
    return sorted(backends, reverse=True,
                  key=lambda b: hashlib.sha1(key + b"\x00"
                                             + b.encode()).digest())


# ---------------------------------------------------------------------------
# Scoring order (white-box: _pick with injected sketches)
# ---------------------------------------------------------------------------

def test_deepest_hit_wins_and_orders_failover_candidates(monkeypatch):
    r = _mk_router(monkeypatch)
    a, b, c = "10.0.0.1:1", "10.0.0.2:1", "10.0.0.3:1"
    _inject(r, a, _payload(dev=CHAIN[:1]))
    _inject(r, b, _payload(dev=CHAIN[:3]))
    _inject(r, c, _payload())
    p, cands = r._pick(_body(), [], [a, b, c])
    assert p == ""
    assert list(cands) == [b, a, c], "deepest-first, shallower next, cold last"
    assert r.metrics.route_decisions_total.get(reason="sketch_hit") == 1
    assert r.metrics.expected_hit_blocks_total.get(
        backend=b, tier="device") == 3


def test_device_blocks_outweigh_host_blocks(monkeypatch):
    """w=1.0: two device blocks (4.0) beat three host blocks (3.0) — a
    host hit still costs the H2D restore."""
    r = _mk_router(monkeypatch)
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    _inject(r, a, _payload(host=CHAIN[:3]))
    _inject(r, b, _payload(dev=CHAIN[:2]))
    _, cands = r._pick(_body(), [], [a, b])
    assert cands[0] == b
    assert r.metrics.expected_hit_blocks_total.get(
        backend=b, tier="device") == 2


def test_tie_falls_back_to_least_loaded_then_rendezvous(monkeypatch):
    r = _mk_router(monkeypatch)
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    _inject(r, a, _payload(dev=CHAIN[:2]))
    _inject(r, b, _payload(dev=CHAIN[:2]))
    r._inflight = {a: 3, b: 0}
    _, cands = r._pick(_body(), [], [a, b])
    assert cands[0] == b, "tied scores: the quieter backend wins"
    assert r.metrics.route_decisions_total.get(reason="tie_fallback") == 1
    # Load tied too: rendezvous on the prefix key breaks the tie — stable.
    r._inflight = {a: 1, b: 1}
    key = json.dumps(IDS[:64]).encode()
    expect = _rz_order(key, [a, b])[0]
    for _ in range(3):
        _, cands = r._pick(_body(), [], [a, b])
        assert cands[0] == expect


def test_all_zero_scores_are_a_tie_not_a_hit(monkeypatch):
    r = _mk_router(monkeypatch)
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    _inject(r, a, _payload())
    _inject(r, b, _payload())
    r._pick(_body(), [], [a, b])
    assert r.metrics.route_decisions_total.get(reason="sketch_hit") == 0
    assert r.metrics.route_decisions_total.get(reason="tie_fallback") == 1


def test_stale_or_absent_sketches_fall_back_to_rendezvous(monkeypatch):
    r = _mk_router(monkeypatch)
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    # No sketches at all.
    _, cands = r._pick(_body(), [], [a, b])
    key = json.dumps(IDS[:64]).encode()
    assert list(cands) == _rz_order(key, [a, b])
    assert r.metrics.route_decisions_total.get(reason="stale_sketch") == 1
    # A sketch past the staleness deadline counts as absent (default
    # ARKS_ROUTER_SKETCH_STALE_S=10).
    _inject(r, a, _payload(dev=CHAIN[:3]), age_s=100.0)
    _, cands = r._pick(_body(), [], [a, b])
    assert list(cands) == _rz_order(key, [a, b])
    assert r.metrics.route_decisions_total.get(reason="stale_sketch") == 2


def test_promptless_body_counts_no_key(monkeypatch):
    r = _mk_router(monkeypatch)
    r._pick(json.dumps({"model": "tiny"}).encode(), [], ["10.0.0.1:1"])
    assert r.metrics.route_decisions_total.get(reason="no_key") == 1


def test_sketch_env_kill_switch(monkeypatch):
    monkeypatch.setenv("ARKS_ROUTER_SKETCH", "0")
    r = _mk_router(monkeypatch)
    assert not r.sketch_on
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    _inject(r, a, _payload(dev=CHAIN[:3]))
    _, cands = r._pick(_body(), [], [a, b])
    key = json.dumps(IDS[:64]).encode()
    assert list(cands) == _rz_order(key, [a, b]), "pre-sketch rendezvous behavior"
    assert r.metrics.route_decisions_total.total() == 0


def test_multi_turn_affinity_follows_the_growing_chain(monkeypatch):
    """A conversation's prompt grows turn over turn; the sketch hit depth
    keeps the session pinned to the backend that holds its prefix even as
    other backends stay fresh (and would win rendezvous)."""
    r = _mk_router(monkeypatch)
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    _inject(r, a, _payload(dev=CHAIN[:2]))
    _inject(r, b, _payload())
    history = IDS[:8]                       # turn 1: exactly the cached depth
    for turn in range(4):
        _, cands = r._pick(_body(history), [], [b, a])
        assert cands[0] == a, f"turn {turn} left its cached prefix"
        history = history + [100 + turn] * 4    # next turn grows the chain
    assert r.metrics.route_decisions_total.get(reason="sketch_hit") == 4


def test_text_domain_scoring_without_tokenizer(monkeypatch):
    """Text prompts score through the text-digest chain — no tokenizer in
    the router; the backend's alignment ledger decided what to advertise."""
    r = _mk_router(monkeypatch)
    text = "s" * 600
    ex = ps.SketchExporter(PAGE)
    tds = list(ps.iter_text_digests(text, ex.text_chars))
    assert len(tds) == 2
    # Hand-build a payload whose text-domain views cover the chain.
    toks = ps.chain_digests(list(range(8)), PAGE, 2)
    ex.link(None, [])  # no-op; ledger stays empty — link directly instead
    ex._links[tds[0]] = toks[0]
    ex._links[tds[1]] = toks[1]
    payload = ex.build(toks, ("k", 1), [], 1)
    a, b = "10.0.0.1:1", "10.0.0.2:1"
    _inject(r, a, _payload())
    _inject(r, b, payload)
    body = json.dumps({"model": "tiny", "prompt": text}).encode()
    _, cands = r._pick(body, [], [a, b])
    assert cands[0] == b
    assert r.metrics.expected_hit_blocks_total.get(
        backend=b, tier="device") == 2


# ---------------------------------------------------------------------------
# Poller + live backends
# ---------------------------------------------------------------------------

class _SketchBackend:
    """A decode backend stub serving both the scripted POST behavior of
    the failover tests and GET /v1/cache/sketch from a mutable payload."""

    def __init__(self, script, payload=None):
        backend = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code, data, headers=()):
                self.send_response(code)
                for k, v in headers:
                    self.send_header(k, v)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path == "/v1/cache/sketch" and backend.payload:
                    self._send(200, json.dumps(backend.payload).encode())
                else:
                    self._send(404, b"{}")

            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                backend.last_path = self.path
                backend.last_headers = dict(self.headers)
                i = min(backend.calls, len(backend.script) - 1)
                backend.calls += 1
                if backend.script[i] == "503":
                    self._send(503, b'{"error":{"code":503}}')
                    return
                self._send(200, json.dumps(
                    {"id": "ok", "served_by": backend.name,
                     "choices": []}).encode())

        self.script = script
        self.payload = payload
        self.calls = 0
        self.last_path = None
        self.last_headers = {}
        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.addr = f"127.0.0.1:{self._httpd.server_port}"
        self.name = self.addr
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def _free_port_addr() -> str:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"127.0.0.1:{port}"


def _post(router, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{router.port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=30)


def test_poller_drops_restarted_backend_epoch(monkeypatch):
    be = _SketchBackend(["ok"], _payload(dev=CHAIN[:3], epoch="boot1.0"))
    off = _SketchBackend(["ok"], {"enabled": False})
    r = _mk_router(monkeypatch, decode=f"{be.addr},{off.addr}")
    try:
        r.sketches.poll_once()
        assert r.sketches.get(be.addr).epoch == "boot1.0"
        assert r.sketches.get(off.addr) is None, "disabled export: no sketch"
        # The backend restarts: new epoch, cold cache.  The next poll must
        # REPLACE the copy — the pre-restart membership is gone.
        be.payload = _payload(epoch="boot2.0")
        r.sketches.poll_once()
        bs = r.sketches.get(be.addr)
        assert bs.epoch == "boot2.0"
        assert bs.score_chain(CHAIN, "token") == (0, 0, 0)
        assert r.metrics.sketch_epoch_drops_total.get(backend=be.addr) == 1
        # An unreachable poll keeps the last copy (staleness retires it).
        be.stop()
        r.sketches.poll_once()
        assert r.sketches.get(be.addr).epoch == "boot2.0"
    finally:
        be.stop()
        off.stop()


def test_sketch_winner_still_fails_over_and_unified_forwarding(monkeypatch):
    """The sketch-preferred backend 503s: the request must move on to the
    next candidate exactly like pre-sketch failover — and in unified mode
    it travels the plain completion path with no prefill header."""
    win = _SketchBackend(["503"], _payload(dev=CHAIN[:4]))
    other = _SketchBackend(["ok"], _payload())
    r = _mk_router(monkeypatch, decode=f"{win.addr},{other.addr}",
                   unified=True)
    r.start(background=True)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{r.port}/readiness", timeout=10) as resp:
            assert json.load(resp)["status"] == "ready", \
                "unified mode is ready with decode backends only"
        r.sketches.poll_once()
        with _post(r, _body()) as resp:
            out = json.load(resp)
        assert out["served_by"] == other.name
        assert win.calls == 1, "the sketch winner was tried first"
        assert win.last_path == "/v1/completions"
        assert "X-Arks-Prefill-Addr" not in win.last_headers
        assert r.metrics.route_decisions_total.get(reason="sketch_hit") == 1
        assert r.retries_total.get(reason="backend_503") >= 1
    finally:
        r.stop()
        win.stop()
        other.stop()


def test_connection_error_invalidates_the_dead_backends_sketch(monkeypatch):
    """A restarting backend must not keep winning on its pre-restart
    sketch until the poll interval catches up: the forward path's
    connection error drops the sketch immediately."""
    dead = _free_port_addr()
    good = _SketchBackend(["ok"], _payload())
    r = _mk_router(monkeypatch, decode=f"{dead},{good.addr}", unified=True)
    r.start(background=True)
    try:
        _inject(r, dead, _payload(dev=CHAIN[:4]))
        with _post(r, _body()) as resp:
            out = json.load(resp)
        assert out["served_by"] == good.name
        assert r.retries_total.get(reason="connect_error") >= 1
        assert r.sketches.get(dead) is None, "dead backend's sketch lingered"
        # The NEXT pick no longer scores the dead backend a sketch hit.
        r._pick(_body(), [], [dead, good.addr])
        assert r.metrics.route_decisions_total.get(reason="sketch_hit") == 1, \
            "only the pre-invalidation pick may count a sketch hit"
    finally:
        r.stop()
        good.stop()


# ---------------------------------------------------------------------------
# A real fleet: two engines behind OpenAIServers behind a Router
# ---------------------------------------------------------------------------

def _fleet_workload(vocab, chunk=16, clients=4, turns=3):
    """A shared system prefix, then per-client histories that each turn
    extend the PREVIOUS prompt plus fresh tokens, in a shuffled arrival
    order (so a round-robin counter cannot fake affinity by arithmetic)."""
    import random
    rng = random.Random(42)
    lo, hi = 3, min(200, vocab)
    system = [rng.randrange(lo, hi) for _ in range(2 * chunk)]
    histories = [list(system) for _ in range(clients)]
    seq = []
    for turn in range(turns):
        for ci in rng.sample(range(clients), clients):
            prompt = histories[ci] + [rng.randrange(lo, hi)
                                      for _ in range(chunk)]
            seq.append((f"c{ci}-t{turn}", turn, prompt))
            histories[ci] = prompt
    return seq


def _run_fleet(monkeypatch, policy):
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config
    from arks_tpu.server import OpenAIServer
    cfg = get_config("tiny")
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "8")
    engines, servers, router = [], [], None
    try:
        for _ in range(2):
            # prefix_cache_mb=1: a retention surplus, so a session's history
            # STAYS device-resident on its home backend — the locality the
            # policies compete to exploit.
            eng = InferenceEngine(cfg, EngineConfig(
                model="tiny", num_slots=2, max_cache_len=128,
                prefill_buckets=(16, 32), steps_per_dispatch=4,
                prefill_chunk=16, kv_layout="paged", prefix_cache_mb=1),
                ByteTokenizer())
            eng.start()
            srv = OpenAIServer(eng, served_model_name="tiny",
                               host="127.0.0.1", port=0)
            srv.start(background=True)
            engines.append(eng)
            servers.append(srv)
        monkeypatch.setenv("ARKS_PREFILL_ADDRS", "")
        monkeypatch.setenv("ARKS_DECODE_ADDRS", ",".join(
            f"127.0.0.1:{s.port}" for s in servers))
        # The test polls the sketches itself, between turns.
        monkeypatch.setenv("ARKS_ROUTER_SKETCH_POLL_S", "600")
        router = Router(Discovery(None), "tiny", host="127.0.0.1", port=0,
                        policy=policy, unified=True)
        router.start(background=True)
        texts, last_turn = {}, -1
        for rid, turn, prompt in _fleet_workload(cfg.vocab_size):
            if turn != last_turn:
                if router.sketch_on:
                    router.sketches.poll_once()
                last_turn = turn
            with _post(router, json.dumps({
                    "model": "tiny", "prompt": prompt, "max_tokens": 4,
                    "temperature": 0, "ignore_eos": True}).encode()) as r:
                texts[rid] = json.load(r)["choices"][0]["text"]
        hit = sum(e.metrics.prefix_cache_hit_tokens_total.get(tier=t)
                  for e in engines for t in ("device", "host"))
        query = sum(e.metrics.prefix_cache_query_tokens_total.total()
                    for e in engines)
        hits = int(router.metrics.route_decisions_total.get(
            reason="sketch_hit"))
        return texts, int(query - hit), hits
    finally:
        if router is not None:
            router.stop()
        for s in servers:
            s.stop()
        for e in engines:
            e.stop()


def test_sketch_routing_reprefills_less_than_round_robin_on_a_real_fleet(
        monkeypatch):
    """The same multi-turn shared-prefix workload through two real engines
    behind a real Router, once per policy on a fresh fleet: every request's
    generated text is the same whichever replica served it, and sketch
    routing re-prefills strictly fewer tokens (prefix-query tokens less the
    per-tier hit tokens, summed over the backends) than round robin."""
    sk_texts, sk_reprefill, sk_hits = _run_fleet(monkeypatch, "cache_aware")
    rr_texts, rr_reprefill, _ = _run_fleet(monkeypatch, "round_robin")
    assert sk_texts == rr_texts
    assert sk_hits > 0, "no request was routed by a sketch hit"
    assert sk_reprefill < rr_reprefill, (sk_reprefill, rr_reprefill)
