"""Fleet-wide prefix restore: a replica fetches a peer's warm prefix
blocks over ``GET /v1/cache/blocks/{digest}`` instead of re-prefilling.

Engine A warms a shared prefix and (after churn spills it to its host
tier) serves the raw pool-native blocks from its OpenAI server; engine B
admits the same prompt with ``X-Arks-Peer-Hint`` semantics (the
``Request.peer_hint`` field the server maps the header to), parks in the
fetch path, stages A's blocks into its own tier 1, and restores — the
generated stream is byte-identical to both A's and a no-fetch control,
with strictly fewer chunk-prefilled tokens.  A peer dying mid-fetch
degrades to re-prefill of the unfetched span; the request is unharmed.
"""

import functools
import http.server
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                             SamplingParams)
from arks_tpu.engine import kv_transfer
from arks_tpu.engine.paged import chain_digests
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config
from arks_tpu.server import OpenAIServer

import harness


def _mk(monkeypatch, peer_fetch="0"):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "0")
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
    monkeypatch.delenv("ARKS_PREFIX_DISK_MB", raising=False)
    monkeypatch.delenv("ARKS_PEER_ADDRS", raising=False)
    monkeypatch.setenv("ARKS_PEER_FETCH", peer_fetch)
    monkeypatch.setenv("ARKS_PEER_FETCH_TIMEOUT_S", "5")
    cfg = get_config("tiny")
    eng = InferenceEngine(
        cfg, EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                          prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                          prefill_chunk=16, kv_layout="paged",
                          prefix_cache_mb=0),
        ByteTokenizer())
    return cfg, eng


_drive = functools.partial(harness.drive, recover=True)


_collect = harness.collect


def _run_one(eng, rid, ids, peer_hint=None, max_tokens=4):
    req = Request(rid, ids, SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True),
        peer_hint=peer_hint)
    eng.add_request(req)
    _drive(eng)
    return _collect(req)


def _warm_peer(monkeypatch):
    """Engine A with the warm prefix resident in its HOST tier (churn
    evicts the device pages, spilling them into tier 1 — which is what
    block_for_export serves)."""
    cfg, a = _mk(monkeypatch)
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]  # 2 pages + tail
    base = _run_one(a, "w1", warm)
    for i in range(5):
        _run_one(a, f"ch{i}", [(9 + i) % cfg.vocab_size] * 33, max_tokens=3)
    digests = chain_digests(warm, 16, 2)
    assert all(a._host.has(d) for d in digests), \
        "churn did not spill the warm prefix into the host tier"
    return a, warm, digests, base


def test_block_export_endpoint_round_trips(monkeypatch):
    a, warm, digests, _ = _warm_peer(monkeypatch)
    srv = OpenAIServer(a, served_model_name="t", host="127.0.0.1", port=0)
    srv.start(background=True)
    try:
        url = f"http://127.0.0.1:{srv.port}/v1/cache/blocks/"
        with urllib.request.urlopen(url + digests[0].hex(), timeout=30) as r:
            assert r.status == 200
            buf = r.read()
        blk = kv_transfer.unpack_block(buf, digests[0], a.kv_epoch)
        ref = a.block_for_export(digests[0])
        assert set(blk) == set(ref)
        for f in ref:
            assert blk[f].tobytes() == np.asarray(ref[f]).tobytes()

        # Absent digest and junk both map to 404, never a traceback.
        for tail in ("ff" * 20, "not-hex"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(url + tail, timeout=30)
            assert ei.value.code == 404
    finally:
        srv.stop()
        a.stop()


def test_peer_fetch_restores_instead_of_reprefilling(monkeypatch):
    a, warm, digests, base = _warm_peer(monkeypatch)
    srv = OpenAIServer(a, served_model_name="t", host="127.0.0.1", port=0)
    srv.start(background=True)

    _, ctrl = _mk(monkeypatch)          # no-fetch control: re-prefills
    got_ctrl = _run_one(ctrl, "c1", warm)
    ctrl_chunk = ctrl.metrics.mixed_chunk_tokens_total.total()

    _, b = _mk(monkeypatch, peer_fetch="1")
    try:
        got = _run_one(b, "w2", warm,
                       peer_hint=f"127.0.0.1:{srv.port}")
        assert got[0] == base[0] == got_ctrl[0], \
            "peer-fetched stream diverged from the re-prefilled one"
        assert got[1].finish_reason == base[1].finish_reason == "length"
        m = b.metrics
        assert m.prefix_peer_fetch_blocks_total.get(source="peer") == 2
        assert m.prefix_cache_hit_tokens_total.get(tier="peer") == 32
        assert m.prefix_restore_blocks_total.total() >= 2
        # Strictly fewer chunk-prefilled tokens than the no-fetch control.
        assert m.mixed_chunk_tokens_total.total() < ctrl_chunk
        assert sum(m.engine_faults_total._values.values()) == 0
        assert b.state == "serving"
    finally:
        b.stop()
        ctrl.stop()
        srv.stop()
        a.stop()


class _DyingPeer(http.server.ThreadingHTTPServer):
    """Serves ONE valid block, then drops every later connection mid-
    request — the peer-death-during-fetch shape."""

    daemon_threads = True

    def __init__(self, payloads):
        self.payloads = dict(payloads)  # path -> bytes
        self.served = 0
        super().__init__(("127.0.0.1", 0), _DyingPeerHandler)


class _DyingPeerHandler(http.server.BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 — http.server API
        srv = self.server
        buf = srv.payloads.get(self.path)
        if srv.served >= 1 or buf is None:
            # Mid-fetch death: slam the connection, no HTTP response.
            self.connection.close()
            return
        srv.served += 1
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(buf)))
        self.end_headers()
        self.wfile.write(buf)

    def log_message(self, *a):  # quiet
        pass


def test_mid_fetch_peer_death_falls_back_to_reprefill(monkeypatch):
    """The peer serves block 1 then dies: the staged partial run
    restores, the rest chunk-prefills, and the request finishes
    byte-identical to a never-fetched run — latency cost only."""
    a, warm, digests, base = _warm_peer(monkeypatch)
    payloads = {
        f"/v1/cache/blocks/{d.hex()}":
            kv_transfer.pack_block(d, a.kv_epoch, a.block_for_export(d))
        for d in digests
    }
    a.stop()
    peer = _DyingPeer(payloads)
    threading.Thread(target=peer.serve_forever, daemon=True).start()

    _, b = _mk(monkeypatch, peer_fetch="1")
    try:
        got = _run_one(b, "w2", warm,
                       peer_hint=f"127.0.0.1:{peer.server_address[1]}")
        assert got[0] == base[0], "stream diverged after mid-fetch peer death"
        assert got[1].finish_reason == "length"
        m = b.metrics
        assert m.prefix_peer_fetch_blocks_total.get(source="peer") == 1
        assert m.prefix_cache_hit_tokens_total.get(tier="peer") == 16
        assert sum(m.engine_faults_total._values.values()) == 0
        assert sum(m.requests_quarantined_total._values.values()) == 0
        assert b.state == "serving"
    finally:
        b.stop()
        peer.shutdown()


def test_dead_peer_from_the_start_costs_nothing_but_latency(monkeypatch):
    """A hint pointing at a closed port: the fetch stages nothing and the
    admission degrades to plain chunked prefill."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()

    _, ctrl = _mk(monkeypatch)
    cfg = get_config("tiny")
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]
    got_ctrl = _run_one(ctrl, "c1", warm)
    ctrl.stop()

    _, b = _mk(monkeypatch, peer_fetch="1")
    try:
        got = _run_one(b, "w2", warm, peer_hint=f"127.0.0.1:{dead_port}")
        assert got[0] == got_ctrl[0]
        assert got[1].finish_reason == "length"
        assert b.metrics.prefix_peer_fetch_blocks_total.get(
            source="peer") == 0
        assert b.state == "serving"
    finally:
        b.stop()
