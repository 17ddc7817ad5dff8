"""The load generator's tail (``--tail-s``), against a small SSE server of
the test's own: the records written at the end of the drain are what a run
without a tail would have written; the load goes on after them until the
parent closes the generator's input."""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from benchmarks import client_metrics, manifest


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("Connection", "close")
        self.end_headers()

        def frame(obj):
            data = b"data: " + (obj if isinstance(obj, bytes)
                                else json.dumps(obj).encode()) + b"\n\n"
            self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
            self.wfile.flush()

        n = body["max_tokens"]
        try:
            for i in range(n):
                time.sleep(0.02)
                frame({"choices": [{"text": "x", "finish_reason":
                                    "length" if i == n - 1 else None}]})
            frame({"choices": [], "usage": {
                "completion_tokens": n,
                "prompt_tokens": len(body["prompt"])}})
            frame(b"[DONE]")
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            pass                      # the generator hung up: its right


@pytest.fixture()
def server():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address[1]
    srv.shutdown()


def _loadgen(port, mix, out, *extra, stdin=None):
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.loadgen", "--port", str(port),
         "--model", "tiny", "--mix", manifest.traffic_path(mix),
         "--seed", "7", "--load", "3" if mix.endswith("closed") else "6",
         "--seconds", "1.5", "--out", str(out), *extra],
        cwd=manifest.ROOT, stdout=subprocess.PIPE, stdin=stdin, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("mix", ["rehearsal.closed", "rehearsal.open"])
def test_the_snapshot_is_what_a_run_without_a_tail_writes(server, tmp_path,
                                                          mix):
    out = tmp_path / "rec.json"
    p = _loadgen(server, mix, out, "--tail-s", "30", stdin=subprocess.PIPE)
    events = []
    for line in p.stdout:
        ev = json.loads(line)
        events.append(ev)
        if ev["event"] == "tail":
            # the records of the closed window are there the moment the
            # tail is announced
            snap = json.loads(out.read_text())
            time.sleep(0.8)           # the parent's traced slice
            p.stdin.close()
    assert p.wait(60) == 0
    kinds = [e["event"] for e in events]
    assert kinds[-2:] == ["tail", "done"] and "window_close" in kinds
    tail_ev = next(e for e in events if e["event"] == "tail")
    assert tail_ev["t"] >= tail_ev["t_end"] == snap["t_end"]
    # the generator left when its input closed, long before --tail-s
    full = json.loads((tmp_path / "rec.json.tail").read_text())
    assert json.loads(out.read_text()) == snap       # not written again

    # nothing of the snapshot was sent after the window closed, nothing in
    # it happened after the drain; the full records go on beyond both
    assert all(r["sent"] < snap["t_close"] for r in snap["records"])
    assert all(t <= tail_ev["t"] for r in snap["records"]
               for t, _ in r["frames"])
    assert any(r["sent"] >= snap["t_close"] for r in full["records"])
    assert max(t for r in full["records"] for t, _ in r["frames"]) \
        > snap["t_end"] + 0.5
    by_id = {r["id"]: r for r in full["records"]}
    for r in snap["records"]:
        later = by_id[r["id"]]
        assert later["frames"][:len(r["frames"])] == r["frames"]
        assert later["sent"] == r["sent"] and later["due"] == r["due"]
    for key in ("t0", "t_open", "t_close", "t_end", "loop", "offered"):
        assert full[key] == snap[key]

    # and the reduction sees a run without a tail: the same requests as one
    plain_out = tmp_path / "plain.json"
    q = _loadgen(server, mix, plain_out)
    assert q.wait(60) == 0
    plain = json.loads(plain_out.read_text())
    assert not (tmp_path / "plain.json.tail").exists()
    a, b = (client_metrics.reduce(x) for x in (snap, plain))
    assert a["window_s"] == b["window_s"]
    if mix.endswith("open"):          # the schedule fixes who is due when
        assert [r["id"] for r in snap["records"]] \
            == [r["id"] for r in plain["records"]]
        for key in ("attempted", "due_in_window", "prompt_tokens_due"):
            assert a[key] == b[key], key
    assert a["failed"] == b["failed"] == 0
    assert a["n_wrong_streams"] == 0
