"""OpenAI-compatible HTTP serving surface.

Same wire contract the reference's gateway counts on from vLLM/SGLang
runtime pods (port 8080 — /root/reference/internal/controller/
arksapplication_controller.go:631-634; usage extraction —
/root/reference/pkg/gateway/handle_response.go:113-182):

- POST /v1/chat/completions, /v1/completions (stream + non-stream; SSE
  frames ``data: {...}`` terminated by ``data: [DONE]``; when
  ``stream_options.include_usage`` is set, the final data frame carries the
  usage object and an empty choices list).
- GET /v1/models, /metrics (Prometheus, normalized runtime names),
  /healthz, /readiness.

Stdlib-only (ThreadingHTTPServer): requests are I/O-bound handoffs to the
engine thread; all device work stays on the engine thread.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from arks_tpu import slo as slo_mod
from arks_tpu import tenancy
from arks_tpu.engine import fairqueue
from arks_tpu.engine.engine import InferenceEngine
from arks_tpu.engine.tokenizer import IncrementalDetokenizer
from arks_tpu.engine.types import Request, SamplingParams
from arks_tpu.obs import logctx
from arks_tpu.obs import perfetto as perfetto_mod
from arks_tpu.obs import trace as trace_mod
from arks_tpu.utils import knobs
from arks_tpu.utils.swallow import swallowed

log = logging.getLogger("arks_tpu.server")

# SLO tier header (gateway/router forward it; arks_tpu.gateway.server
# validates it against the same ARKS_SLO_TIERS ladder).
HDR_TIER = "x-arks-tier"


class _StreamLag:
    """One stream's lag behind the engine's door, kept by its handler
    thread in plain attributes: no lock and no registry call a frame.
    ``took`` reads the stamps of an output as it comes off the request's
    queue, ``flushed`` the clock once the first frame made of it is on the
    wire, and ``close`` makes the stream's ONE observation of each family:
    its worst put-to-wire lag (what the handler threads and the GIL cost
    this client) and, if a frame of it was deferred, its worst made-to-put
    lag (what waiting for the next dispatch cost it)."""

    __slots__ = ("put", "worst", "defer")

    def __init__(self) -> None:
        self.put: float | None = None
        self.worst = 0.0
        self.defer: float | None = None

    def took(self, out) -> None:
        self.put = out.t_put
        if out.t_made is not None and out.t_put is not None:
            held = out.t_put - out.t_made
            if self.defer is None or held > self.defer:
                self.defer = held

    def flushed(self) -> None:
        if self.put is not None:
            lag = time.monotonic() - self.put
            self.put = None
            if lag > self.worst:
                self.worst = lag

    def close(self, metrics) -> None:
        metrics.stream_deliver_lag_seconds.observe(self.worst)
        if self.defer is not None:
            metrics.stream_defer_lag_seconds.observe(self.defer)


def _find_stop(text: str, stop_strings: list[str], min_end: int = 0
               ) -> int | None:
    """Earliest index at which any stop string begins, else None.

    A match whose END falls at or before ``min_end`` is ignored: text
    before that boundary was generated under min_tokens and is exempt
    from stopping, but a stop straddling the boundary still counts."""
    best = None
    for s in stop_strings:
        start = 0
        while True:
            i = text.find(s, start)
            if i < 0:
                break
            if i + len(s) > min_end:
                if best is None or i < best:
                    best = i
                break
            start = i + 1
    return best


def _sampling_from_body(body: dict, tokenizer,
                        engine=None) -> tuple[SamplingParams, list[str]]:
    """Build engine sampling params; returns (params, stop_strings).

    ``stop_token_ids`` go to the engine directly.  ``stop`` strings that
    encode to a single token also become stop ids; multi-token stop strings
    are matched against streamed text by the server (which then aborts the
    engine request).  With ``engine``, logit_bias token ids are validated
    against the vocab and min_tokens' suppress set against the device
    column budget — raising ValueError (HTTP 400) instead of silently
    ignoring entries."""
    stop = body.get("stop") or []
    if isinstance(stop, str):
        stop = [stop]
    stop_ids = [int(t) for t in (body.get("stop_token_ids") or [])]
    stop_strings: list[str] = []
    for s in stop:
        ids = tokenizer.encode(s)
        if len(ids) == 1:
            stop_ids.append(ids[0])
        else:
            stop_strings.append(s)
    # logprobs: completions take an int (top-N alternatives per token,
    # 0 = chosen only); chat takes logprobs=true + top_logprobs=N.  The
    # engine param is None (off) / 0 (chosen only) / N (plus top-N).
    lp = body.get("logprobs")
    if lp is True:
        n_lp = int(body.get("top_logprobs") or 0)
    elif lp is None or lp is False:
        n_lp = None
    else:
        n_lp = int(lp)
    from arks_tpu.engine.sampler import LOGIT_BIAS_MAX, TOP_LOGPROBS_MAX
    # OpenAI logit_bias: {"token_id": bias in [-100, 100]}.  Rejected when
    # it exceeds the device column budget (silently dropping entries would
    # bias the WRONG subset).
    raw_bias = body.get("logit_bias") or {}
    if not isinstance(raw_bias, dict):
        raise ValueError("logit_bias must be an object of token_id -> bias")
    if len(raw_bias) > LOGIT_BIAS_MAX:
        raise ValueError(
            f"logit_bias supports at most {LOGIT_BIAS_MAX} entries")
    logit_bias = tuple(
        (int(t), max(-100.0, min(100.0, float(b))))
        for t, b in raw_bias.items())
    if engine is not None and logit_bias:
        vocab = engine.cfg.vocab_size
        bad = [t for t, _ in logit_bias if not 0 <= t < vocab]
        if bad:
            raise ValueError(
                f"logit_bias token ids out of range [0, {vocab}): {bad[:5]}")
    min_tokens = max(int(body.get("min_tokens", 0)), 0)
    # Guided decoding: OpenAI response_format json_object, plus the
    # vLLM-style guided_regex extra.  Compiled HERE (cached per pattern)
    # so an invalid pattern 400s before the request ever queues.
    guide = None
    rf = body.get("response_format")
    if isinstance(rf, dict) and rf.get("type"):
        rft = rf["type"]
        if rft == "json_object":
            guide = ("json", "")
        elif rft == "regex" and rf.get("regex"):
            guide = ("regex", str(rf["regex"]))
        elif rft == "json_schema":
            # OpenAI structured outputs: {"type": "json_schema",
            # "json_schema": {"name": ..., "schema": {...}}}; a bare
            # "schema" key is accepted too.  The cache key preserves the
            # body's own key order — sort_keys would reorder
            # "properties", breaking the declaration-order contract.
            wrapper = rf.get("json_schema")
            schema = (wrapper.get("schema") if isinstance(wrapper, dict)
                      else rf.get("schema"))
            if not isinstance(schema, dict):
                raise ValueError("response_format json_schema needs "
                                 "json_schema.schema")
            guide = ("json_schema", json.dumps(schema))
        elif rft != "text":
            raise ValueError(f"unknown response_format type {rft!r}")
    if body.get("guided_regex"):
        guide = ("regex", str(body["guided_regex"]))
    if isinstance(body.get("guided_json"), dict):
        # vLLM extra: guided_json carries the schema directly.
        guide = ("json_schema", json.dumps(body["guided_json"]))
    if body.get("guided_choice") is not None:
        # vLLM extra: the completion must be one of these literal strings,
        # compiled as an escaped alternation over the DFA machinery.
        # Non-string entries 400 here — coercing them (numbers, nulls)
        # would constrain to text the caller never wrote.
        choices = body["guided_choice"]
        if (not isinstance(choices, list) or not choices
                or any(not isinstance(c, str) for c in choices)):
            raise ValueError(
                "guided_choice must be a non-empty array of strings")
        guide = ("choice", json.dumps(choices))
    if guide is not None and engine is not None:
        # Syntactic check only (ValueError -> 400 on bad patterns): the
        # expensive DFA build runs on the compiler's worker pool once the
        # request is queued (engine.add_request kicks it), so a cold
        # schema never blocks this server thread for the ~seconds-scale
        # compile.  Compile-time failures (budgets exhausted with every
        # guide pinned) surface as a per-request 400 through the
        # finish_reason="error" output.
        engine.guides.validate(*guide)
    params = SamplingParams(
        max_tokens=int(body.get("max_tokens") or body.get("max_completion_tokens") or 256),
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", 0)),
        seed=body.get("seed"),
        ignore_eos=bool(body.get("ignore_eos", False)),
        stop_token_ids=tuple(stop_ids),
        presence_penalty=float(body.get("presence_penalty", 0.0)),
        frequency_penalty=float(body.get("frequency_penalty", 0.0)),
        logprobs=None if n_lp is None else min(max(n_lp, 0), TOP_LOGPROBS_MAX),
        logit_bias=logit_bias,
        min_tokens=min_tokens,
        priority=int(body.get("priority") or 0),
        guide=guide,
    )
    if engine is not None and min_tokens:
        # Same composition the engine admits with (min_tokens_suppress_ids
        # is the single source of truth): reject oversized suppress sets
        # with a 400 here instead of a late engine-side ValueError.
        from arks_tpu.engine.sampler import SUPPRESS_MAX
        if len(engine.min_tokens_suppress_ids(params)) > SUPPRESS_MAX:
            raise ValueError(
                f"min_tokens supports at most {SUPPRESS_MAX} eos/stop "
                "token ids to suppress (silently dropping one could end "
                "the stream before the minimum)")
    return params, stop_strings


class OpenAIServer:
    def follower_wedge(self) -> str | None:
        """Non-None when a gang follower's dispatch-channel heartbeat is
        stale (hung-but-connected worker): the readiness reason string.
        ARKS_GANG_STALE_S bounds the detection window."""
        disp = getattr(self.engine, "dispatcher", None)
        if disp is None or not hasattr(disp, "follower_health"):
            return None
        h = disp.follower_health(knobs.get_float("ARKS_GANG_STALE_S"))
        if h["stale"]:
            return (f"follower heartbeat stale: {h['stale']} "
                    f"(max age {h['max_heartbeat_age_s']}s)")
        return None

    def __init__(self, engine: InferenceEngine, served_model_name: str,
                 host: str = "0.0.0.0", port: int = 8080) -> None:
        self.engine = engine
        self.served_model_name = served_model_name
        self.host, self.port = host, port
        # SLO-tier ladder: x-arks-tier maps onto params.priority here (the
        # header wins over a body "priority" — the gateway already
        # validated it, but a direct-to-pod client gets the same 400).
        self.slo = slo_mod.from_env()
        self._httpd: ThreadingHTTPServer | None = None
        self._ready = threading.Event()
        # Graceful drain (SIGTERM): readiness drops (Services/routes pull
        # this backend), new completions get 503, in-flight ones finish.
        # _active counts POST handlers between their admission check and
        # their last byte — the drain gate that closes the accept-vs-drain
        # race (engine queues alone can read idle while a handler is still
        # tokenizing, streaming tail frames, or running a detached prefill).
        self.draining = False
        self._active = 0
        self._active_lock = threading.Lock()
        self._stopped = False

    # ------------------------------------------------------------------

    def start(self, background: bool = True) -> None:
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            # Default rbufsize(-1) is fine; but the server-level accept
            # backlog must absorb connection bursts (hundreds of clients
            # reconnecting at once) — see request_queue_size below.

            def _json(self, code: int, payload: dict,
                      headers: dict | None = None) -> None:
                data = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(data)

            def _error(self, code: int, message: str) -> None:
                self._json(code, {"error": {"message": message, "code": code}})

            def do_GET(self):
                if self.path == "/v1/models":
                    self._json(200, server._models_payload())
                elif self.path == "/metrics":
                    text = server.engine.metrics.registry.render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(text)))
                    self.end_headers()
                    self.wfile.write(text)
                elif self.path in ("/healthz", "/health"):
                    self._json(200, {"status": "ok"})
                elif self.path == "/v1/traces/export":
                    # Chrome trace-event JSON of every retained trace —
                    # open at ui.perfetto.dev / chrome://tracing.
                    tracer = server.engine.trace
                    tracer.flush()
                    self._json(200, perfetto_mod.chrome_trace(
                        tracer.store.all(), tracer.phase_spans()))
                elif self.path == "/v1/traces":
                    tracer = server.engine.trace
                    tracer.flush()
                    self._json(200, {"traces": [
                        {"trace_id": t["trace_id"],
                         "request_id": t["request_id"],
                         "flags": t["flags"], "tier": t.get("tier"),
                         "spans": len(t["spans"])}
                        for t in tracer.store.all()]})
                elif self.path.startswith("/v1/traces/"):
                    # By trace id OR request id.
                    tracer = server.engine.trace
                    tracer.flush()
                    tr = tracer.store.get(self.path[len("/v1/traces/"):])
                    if tr is None:
                        self._error(404, "trace not found (expired, "
                                    "sampled out, or still in flight)")
                    else:
                        self._json(200, tr)
                elif self.path.startswith("/v1/cache/blocks/"):
                    # Fleet prefix cache: serve one raw AKV1 block to a
                    # fetching peer (host tier peeked, then disk).  404 =
                    # not resident; the peer falls back to re-prefill.
                    buf = server._block_payload(
                        self.path[len("/v1/cache/blocks/"):])
                    if buf is None:
                        self._error(404, "block not resident")
                    else:
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "application/octet-stream")
                        self.send_header("Content-Length", str(len(buf)))
                        self.end_headers()
                        self.wfile.write(buf)
                elif self.path == "/v1/cache/sketch":
                    # Prefix-digest sketch for cache-aware routing: a
                    # compact per-tier summary of the digest chains this
                    # backend holds (engine.cache_sketch reads host-side
                    # snapshots only — the export never touches device
                    # data, same non-blocking discipline as spills).
                    self._json(200, server._sketch_payload())
                elif self.path == "/v1/elastic/status":
                    # Elastic state snapshot (armed, current shape, last
                    # resize/rearm stats) — reachable even while the
                    # replica is disarmed/draining, unlike /readiness.
                    self._json(200, server._elastic_meta())
                elif self.path == "/readiness":
                    # Multi-host gangs: only process 0 (the leader) accepts
                    # traffic — workers participate in collectives but must
                    # stay out of Service endpoints (the K8s front Service
                    # selects the whole gang and relies on this gate).
                    if knobs.raw("ARKS_PROCESS_ID") not in ("", "0"):
                        self._error(503, "worker process (leader serves)")
                    elif server.draining:
                        self._error(503, "draining")
                    elif not server._ready.is_set():
                        self._error(503, "not ready")
                    elif getattr(server.engine, "state",
                                 "serving") != "serving":
                        # Fault recovery in progress ("recovering") or a
                        # wedged dispatch awaiting the watchdog's exit
                        # ("wedged"): pull this backend from Service
                        # endpoints; in-flight streams keep draining.
                        self._error(503, server.engine.state)
                    elif not getattr(server.engine, "armed", True):
                        # Scaled to zero: no device state exists.  The
                        # router's planned join polls this gate — the
                        # replica re-enters routing only once re-armed
                        # (and warm-up issued) flips it back to 200.
                        self._error(503, "scaled to zero (disarmed)")
                    else:
                        # Worker-wedge gate: a follower that is alive but
                        # hung (SIGSTOP, OOM-thrash) stops heartbeating on
                        # the dispatch channel — the gang must leave the
                        # Service endpoints within a bounded window, not
                        # when a collective finally times out.
                        wedged = server.follower_wedge()
                        if wedged:
                            self._error(503, wedged)
                        else:
                            # Sketch age/version metadata rides readiness
                            # so operators (and the router's monitoring)
                            # can spot a wedged/stale sketch export
                            # without scraping the sketch itself.  The
                            # admission block is the saturation signal:
                            # edges read queue depth/drain here to back
                            # off BEFORE the bounded queue starts 503ing.
                            # The admission block + per-tier SLO burn +
                            # elastic state together are the autoscaler's
                            # scrape surface (control.autoscaler.
                            # scrape_signals) — live saturation/burn
                            # drive scaling instead of raw RPM.
                            self._json(200, {"status": "ready",
                                             "sketch": server._sketch_meta(),
                                             "admission":
                                                 server.engine.saturation(),
                                             "slo_burn":
                                                 server._slo_burn(),
                                             "elastic":
                                                 server._elastic_meta()})
                else:
                    self._error(404, f"no route {self.path}")

            def do_POST(self):
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length) or b"{}")
                except (ValueError, json.JSONDecodeError):
                    return self._error(400, "invalid JSON body")
                if self.path == "/v1/profiler/start":
                    # On-demand jax.profiler window (operator tooling —
                    # exempt from the drain gate, like GET diagnostics).
                    # "python": true asks for the Python tracer too.
                    return self._json(
                        200, server.engine.profiler.start(
                            body.get("logdir") or None,
                            python=body.get("python") is True))
                if self.path == "/v1/profiler/stop":
                    return self._json(200, server.engine.profiler.stop())
                if self.path == "/v1/elastic/resize":
                    # Live topology resize / scale-from-zero re-arm
                    # (operator + autoscaler actuator — exempt from the
                    # drain gate like the profiler: a resize request must
                    # land even while completions are gated).
                    return server._handle_resize(self, body)
                # Admission check and active-count increment are ATOMIC:
                # drain() waiting for _active == 0 is then guaranteed no
                # handler slips in after its last look.
                with server._active_lock:
                    if server.draining:
                        return self._error(503, "server is draining")
                    server._active += 1
                try:
                    if server.handle_post(self, body, self.path):
                        pass  # subclass route (disaggregated prefill/decode)
                    elif self.path == "/v1/chat/completions":
                        server._handle_completion(self, body, chat=True)
                    elif self.path == "/v1/completions":
                        server._handle_completion(self, body, chat=False)
                    else:
                        self._error(404, f"no route {self.path}")
                except BrokenPipeError:
                    pass
                except Exception as e:  # engine/request failure → 500
                    log.exception("request handler failure on %s",
                                  self.path)
                    try:
                        self._error(500, f"internal error: {e}")
                    except Exception as e2:
                        # Client hung up before the 500 went out.
                        swallowed("server.error-response", e2)
                finally:
                    with server._active_lock:
                        server._active -= 1

        class Server(ThreadingHTTPServer):
            # A burst of N-hundred concurrent (re)connects overflows the
            # default backlog of 5 and the kernel RSTs the overflow —
            # clients saw "connection reset by peer" under load.
            request_queue_size = 512
            daemon_threads = True

        httpd = Server((self.host, self.port), Handler)
        with self._active_lock:
            self._httpd = httpd
            stopped = self._stopped
        if stopped:
            # stop()/drain() raced ahead of start() (e.g. SIGTERM between
            # installing the handler and binding the socket): entering
            # serve_forever now would hang the process unready forever.
            httpd.server_close()
            return
        self.port = httpd.server_port
        self._ready.set()
        if background:
            threading.Thread(target=httpd.serve_forever,
                             name="http", daemon=True).start()
        else:
            httpd.serve_forever()

    def stop(self) -> None:
        with self._active_lock:
            self._stopped = True
            httpd = self._httpd
        if httpd is not None:
            httpd.shutdown()

    def drain(self, timeout_s: float = 20.0) -> None:
        """Graceful shutdown: flip readiness off (routes pull this backend),
        reject new completions with 503, wait for in-flight requests to
        finish (bounded by ``timeout_s``), then stop the HTTP server.  The
        local gang driver and K8s both SIGTERM before SIGKILL — this is
        what makes rolling updates request-lossless when the grace period
        covers the longest request."""
        self.draining = True
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            # Engine idle AND no live POST handler: the handler count
            # covers the gaps the engine cannot see (tokenizing before
            # add_request, streaming tail frames to a slow client,
            # synchronous detached prefills on the prefill tier).
            with self._active_lock:
                active = self._active
            if active == 0 and self.engine.idle:
                break
            time.sleep(0.1)
        self.stop()

    def handle_post(self, h, body: dict, path: str) -> bool:
        """Subclass hook for extra POST routes; True = handled."""
        return False

    # ------------------------------------------------------------------

    def _sketch_payload(self) -> dict:
        fn = getattr(self.engine, "cache_sketch", None)
        return fn() if callable(fn) else {"enabled": False}

    def _block_payload(self, hexdigest: str) -> bytes | None:
        """One prefix block, packed for the peer-fetch wire (GET
        /v1/cache/blocks/{digest}).  The engine's export path does the
        tier lookups; the AKV1 packing (json header) happens HERE, on
        the server thread, outside the engine hot path."""
        try:
            digest = bytes.fromhex(hexdigest)
        except ValueError:
            return None
        fn = getattr(self.engine, "block_for_export", None)
        blk = fn(digest) if callable(fn) else None
        if blk is None:
            return None
        from arks_tpu.engine import kv_transfer
        return kv_transfer.pack_block(digest, self.engine.kv_epoch, blk)

    def _sketch_meta(self) -> dict:
        """Age/version metadata for /readiness (not the full sketch)."""
        p = self._sketch_payload()
        if not p.get("enabled"):
            return {"enabled": False}
        return {"enabled": True, "epoch": p.get("epoch"),
                "version": p.get("version"),
                "age_s": round(max(0.0, time.time()
                                   - float(p.get("built_unix", 0.0))), 3)}

    def _elastic_meta(self) -> dict:
        """Elastic snapshot for /readiness and /v1/elastic/status."""
        fn = getattr(self.engine, "elastic_status", None)
        return fn() if callable(fn) else {"armed": True}

    def _slo_burn(self) -> dict:
        fn = getattr(self.engine, "slo_burn", None)
        return fn() if callable(fn) else {}

    def _handle_resize(self, h, body: dict) -> None:
        """POST /v1/elastic/resize: {"tensor_parallel": N,
        "data_parallel": M, "timeout_s": T}.  Posts the resize to the
        engine's elastic state machine and waits (bounded) for it to
        drain/reshard/resume; a resize posted to a scaled-to-zero replica
        re-arms it at the requested shape (streaming scale-from-zero).
        200 = resumed at the new shape, 202 = still in flight past the
        wait budget, 409 = another resize in flight, 422 = shape refused
        (fallback matrix, docs/application-usage.md)."""
        fn = getattr(self.engine, "request_resize", None)
        if not callable(fn):
            return h._error(501, "engine has no elastic resize support")
        try:
            tp = body.get("tensor_parallel")
            dp = body.get("data_parallel")
            req = fn(tensor_parallel=None if tp is None else int(tp),
                     data_parallel=None if dp is None else int(dp))
        except (ValueError, TypeError) as e:
            return h._error(400, str(e))
        except RuntimeError as e:
            return h._error(409, str(e))
        timeout_s = float(body.get("timeout_s", 120.0))
        if not req.wait(timeout_s):
            return h._json(202, {"status": "pending",
                                 "elastic": self._elastic_meta()})
        payload = {"status": req.outcome, "seconds": req.seconds,
                   "error": str(req.error) if req.error else None,
                   "elastic": self._elastic_meta()}
        code = {"ok": 200, "rejected": 422}.get(req.outcome, 500)
        h._json(code, payload)

    def _models_payload(self) -> dict:
        data = [{
            "id": self.served_model_name, "object": "model",
            "created": int(time.time()), "owned_by": "arks-tpu",
        }]
        pool = getattr(self.engine, "pool", None)
        if pool is not None:
            # Pool residency listing: every registered model is routable by
            # its ``model`` field; the served_model_name stays the public
            # alias of the engine's primary.  The "arks" block is extra
            # metadata OpenAI clients ignore.
            primary = getattr(self.engine, "_primary_model", None)
            for row in pool.snapshot():
                if row["name"] == primary:
                    data[0]["arks"] = {
                        "state": row["state"], "pinned": row["pinned"],
                        "resident_bytes": row["resident_bytes"],
                        "cold_starts": row["cold_starts"]}
                    continue
                data.append({
                    "id": row["name"], "object": "model",
                    "created": int(time.time()), "owned_by": "arks-tpu",
                    "arks": {"state": row["state"], "pinned": row["pinned"],
                             "resident_bytes": row["resident_bytes"],
                             "cold_starts": row["cold_starts"]}})
        return {"object": "list", "data": data}

    def _prompt_ids_batch(self, body: dict, chat: bool,
                          tools: list | None = None) -> list[list[int]]:
        """One id-list per prompt. Chat is always a single prompt; completions
        accept a string, a token-id list, or a list of strings (OpenAI batch
        form -> one choice per prompt)."""
        tok = self.engine.tokenizer
        if chat:
            messages = body.get("messages") or []
            if not isinstance(messages, list) or not messages:
                raise ValueError("messages must be a non-empty list")
            return [tok.apply_chat_template(messages, tools=tools)]
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            if all(isinstance(p, int) for p in prompt) and prompt:
                batch = [[int(t) for t in prompt]]
            elif all(isinstance(p, str) for p in prompt) and prompt:
                batch = [tok.encode(p) for p in prompt]
            else:
                raise ValueError("prompt list must be all strings or all token ids")
        else:
            batch = [tok.encode(str(prompt))]
        for ids in batch:
            if not ids:
                raise ValueError("prompt must not be empty")
        return batch

    def _handle_completion(self, h, body: dict, chat: bool) -> None:
        model = body.get("model") or self.served_model_name
        # Multi-model routing: served_model_name is the primary's public
        # alias; any other pool-registered name rides the request into the
        # engine's awaiting_model machinery.  engine_model None = primary.
        engine_model = None
        if model != self.served_model_name:
            served = getattr(self.engine, "served_models", None)
            pool_names = served() if served is not None else []
            if model not in pool_names:
                return h._error(404, f"model {model!r} not found")
            if model != pool_names[0]:
                engine_model = model
        try:
            from arks_tpu.server import tools as tools_mod
            tools = None
            tool_choice = "none"
            if chat:
                tools, tool_choice = tools_mod.validate_tools(body)
            tools_on = bool(tools) and tool_choice != "none"
            batch = self._prompt_ids_batch(body, chat,
                                           tools=tools if tools_on else None)
            params, stop_strings = _sampling_from_body(
                body, self.engine.tokenizer, self.engine)
            tier = (h.headers.get(HDR_TIER) or "").strip() or None
            if tier is not None:
                pri = self.slo.priority_of(tier) if self.slo else None
                if pri is None:
                    raise ValueError(
                        f"unknown SLO tier {tier!r} (configured: "
                        f"{', '.join(self.slo.names) or 'none'})")
                import dataclasses as _dct
                params = _dct.replace(params, priority=pri)
            tools_ctx = None
            if tools_on:
                tools_ctx = knobs.get_str("ARKS_TOOL_PARSER")
                forced = tools_mod.forced_call_guide(tools, tool_choice)
                if forced is not None:
                    if params.guide is not None:
                        raise ValueError(
                            "tool_choice required/named cannot combine "
                            "with response_format/guided_regex")
                    self.engine.guides.validate(*forced)
                    import dataclasses as _dc0
                    params = _dc0.replace(params, guide=forced)
            # OpenAI n: independent samples per prompt (choices are
            # prompt-major).  Seeded requests derive child seeds seed+j so
            # the choices differ while staying reproducible.
            n_raw = body.get("n", 1)
            if n_raw is None:
                n_raw = 1
            if isinstance(n_raw, bool) or not isinstance(n_raw, int):
                raise ValueError("n must be an integer")
            n = n_raw
            if not 1 <= n <= 16:
                raise ValueError("n must be between 1 and 16")
        except ValueError as e:
            return h._error(400, str(e))
        stream = bool(body.get("stream", False))
        if stream and (len(batch) > 1 or n > 1):
            return h._error(
                400, "streaming is not supported for batched prompts or n > 1")
        echo = bool(body.get("echo", False))
        if echo and chat:
            return h._error(400, "echo is a completions-only parameter")
        if echo and stream:
            return h._error(400, "echo is not supported with streaming")

        # Reject oversize prompts BEFORE queueing (OpenAI semantics: 400
        # context_length_exceeded — never silent truncation, which would
        # corrupt long-context results and billing).
        limit = self.engine.max_prompt_len
        for prompt_ids in batch:
            if len(prompt_ids) > limit:
                return self._context_length_error(h, len(prompt_ids), limit)

        # Routing-sketch text ledger: this is the one place that sees a
        # text prompt NEXT TO its token ids, so record the alignment the
        # tokenize-free router scoring depends on (host hashing only).
        note = getattr(self.engine, "note_prompt_text", None)
        if callable(note):
            note(body, batch[0])

        import dataclasses as _dc
        # W3C trace context: continue the gateway/router-propagated trace
        # (folding in their completed spans from the x-arks-trace-spans
        # header) or mint a fresh root for direct-to-pod clients.  Only a
        # single-choice request carries it — sibling choices would collide
        # in the trace store under one trace id; they mint engine-local ids.
        ctx = (trace_mod.TraceCtx.from_headers(h.headers)
               if self.engine.trace.enabled else None)
        single = len(batch) == 1 and n == 1
        # Tenant identity: minted by the gateway (x-arks-tenant), forwarded
        # verbatim by the router.  Direct-to-pod clients carry none — their
        # requests share the fair queue's single untenanted lane.
        tenant = (h.headers.get(tenancy.HDR_TENANT) or "").strip() or None
        # Fleet prefix cache: the router's deepest-covering-replica hint
        # (X-Arks-Peer-Hint) — the engine's peer fetch pulls warm blocks
        # from there on an admission miss (ARKS_PEER_FETCH).
        peer_hint = (h.headers.get("x-arks-peer-hint") or "").strip() or None
        reqs = []
        for prompt_ids in batch:
            for j in range(n):
                p = params
                if n > 1 and params.seed is not None:
                    p = _dc.replace(params, seed=params.seed + j)
                req = Request(request_id=f"req-{uuid.uuid4().hex[:16]}",
                              prompt_ids=list(prompt_ids), params=p,
                              model=engine_model, tenant=tenant,
                              trace=ctx if single else None,
                              peer_hint=peer_hint)
                try:
                    with logctx.bound(req.request_id,
                                      ctx.trace_id if ctx is not None else None):
                        self.engine.add_request(req)
                except fairqueue.QueueFullError as e:
                    # Overload ladder: the bounded admission queue refused
                    # this request.  Roll back the siblings already queued
                    # (a batch admits atomically or not at all) and map the
                    # scope: the GLOBAL bound means this backend is
                    # saturated (503 — router should fail over), while a
                    # per-TENANT bound is the caller's own backlog (429 —
                    # slow down; other tenants are fine).
                    for prev in reqs:
                        self.engine.abort(prev.request_id)
                    return self._queue_full_error(h, e)
                reqs.append(req)

        if len(reqs) > 1:
            self._batch_response(h, reqs, model, stop_strings, chat=chat,
                                 echo=echo, tools_ctx=tools_ctx)
        else:
            self._respond(h, reqs[0], chat, model, body, stop_strings,
                          echo=echo, tools_ctx=tools_ctx)

    def _queue_full_error(self, h, e: "fairqueue.QueueFullError") -> None:
        """Map a bounded-queue rejection to HTTP, with the backoff hints
        the edge needs: Retry-After derived from the queue's observed
        drain rate and the saturation signal so the gateway can shed
        pre-emptively instead of retry-hammering a full backend."""
        sat = self.engine.saturation()
        headers = {"Retry-After": str(e.retry_after),
                   tenancy.HDR_SATURATION: f"{sat['saturation']:.2f}"}
        if e.tenant:
            headers[tenancy.HDR_TENANT] = e.tenant
        if e.scope == "tenant":
            h._json(429, {"error": {
                "message": (f"tenant queue is full ({e.depth}/{e.limit} "
                            "queued requests for this tenant)"),
                "type": "rate_limit_error",
                "code": "tenant_queue_full",
            }}, headers=headers)
        else:
            h._json(503, {"error": {
                "message": (f"admission queue is full ({e.depth}/{e.limit} "
                            "queued requests)"),
                "type": "server_error",
                "code": "queue_full",
            }}, headers=headers)

    def _context_length_error(self, h, got: int, limit: int) -> None:
        h._json(400, {"error": {
            "message": (f"This model's maximum context length is {limit} "
                        f"tokens, but your prompt has {got} tokens."),
            "type": "invalid_request_error",
            "code": "context_length_exceeded",
        }})

    def _request_error(self, h, fin) -> None:
        """Map a finish_reason="error" engine output to HTTP.  Client-
        caused rejections (context length, bad guide) stay 400s; a request
        quarantined by fault recovery (error "engine_fault: ...") is the
        SERVER's failure — OpenAI-style 500 so clients and the gateway
        retry/alert correctly instead of blaming the request."""
        if fin.error == "context_length_exceeded":
            return self._context_length_error(
                h, fin.num_prompt_tokens, self.engine.max_prompt_len)
        if fin.error and fin.error.startswith("engine_fault"):
            return h._json(500, {"error": {
                "message": ("The server had an error while processing "
                            f"your request ({fin.error})."),
                "type": "server_error",
                "code": "engine_fault",
            }})
        if fin.error and fin.error.startswith("shed_deadline"):
            # Deadline-aware shed: the request waited so long in the
            # admission queue that its tier's TTFT budget is already
            # unmeetable — burning prefill on it would only delay work
            # that can still meet its SLO.  503 + drain-derived
            # Retry-After, same capacity semantics as queue_full.
            sat = self.engine.saturation()
            return h._json(503, {"error": {
                "message": f"request shed before prefill ({fin.error})",
                "type": "server_error",
                "code": "shed_deadline",
            }}, headers={
                "Retry-After": str(self.engine.queue_retry_after()),
                tenancy.HDR_SATURATION: f"{sat['saturation']:.2f}"})
        if fin.error and fin.error.startswith("model_pool_exhausted"):
            # Capacity, not client error: the pool can't fit the model
            # right now (pinned/in-use residents).  503 + Retry-After so
            # clients and the gateway queue-and-retry instead of failing
            # the request class permanently.
            return h._json(503, {"error": {
                "message": f"model is not loadable right now ({fin.error})",
                "type": "server_error",
                "code": "model_pool_exhausted",
            }}, headers={"Retry-After": "5"})
        if fin.error and fin.error.startswith("model_load_failed"):
            return h._json(500, {"error": {
                "message": f"model failed to load ({fin.error})",
                "type": "server_error",
                "code": "model_load_failed",
            }})
        if fin.error and fin.error.startswith("model_not_found"):
            return h._error(404, fin.error)
        return h._error(400, fin.error or "request rejected")

    def _respond(self, h, req: Request, chat: bool, model: str, body: dict,
                 stop_strings: list[str], echo: bool = False,
                 tools_ctx: str | None = None) -> None:
        """Stream-or-full dispatch tail, shared with the disaggregated path.
        ``tools_ctx`` is the tool-call parser name when the request carries
        active tools (chat only)."""
        if bool(body.get("stream", False)):
            # Peek the first engine output BEFORE committing to SSE: an
            # admission-time rejection (async guide-compile failure,
            # engine-side context check) must map to a clean HTTP 400,
            # not a text/event-stream carrying finish_reason "error".
            first = req.outputs.get()
            if first.finished and first.finish_reason == "error":
                return self._request_error(h, first)
            include_usage = bool(
                (body.get("stream_options") or {}).get("include_usage"))
            if tools_ctx is not None and chat:
                return self._stream_tools_response(
                    h, req, model, include_usage, stop_strings, tools_ctx,
                    first_out=first)
            self._stream_response(h, req, chat, model, include_usage,
                                  stop_strings, first_out=first)
        else:
            self._full_response(h, req, chat, model, stop_strings, echo=echo,
                                tools_ctx=tools_ctx)

    # ------------------------------------------------------------------

    def _collect_text(self, req: Request, stop_strings: list[str]):
        """Drain a request to completion, applying stop-string truncation to
        every chunk — including the final one and flushed tail text.
        Returns (text, finish_reason, final RequestOutput, token_ids,
        logprob entries, per-token text pieces)."""
        detok = IncrementalDetokenizer(self.engine.tokenizer)
        # Per-token text pieces come from the SAME incremental stream as the
        # response text, so stop-cut trimming and text_offset stay aligned
        # even for multi-byte BPE pieces (an isolated tok.decode([tid])
        # renders replacement chars of the wrong length).  Only paid when
        # logprobs are on — that is the only consumer of the alignment.
        track = req.params.logprobs is not None
        text = ""
        tokens: list[int] = []
        lps: list = []
        pieces: list[str] = []
        # min_tokens defers ALL stops (vLLM semantics): text generated
        # before the minimum (length ``exempt``) is exempt from stop
        # matching; _find_stop still cuts a stop straddling the boundary.
        min_tok = int(getattr(req.params, "min_tokens", 0) or 0)
        exempt = 0
        while True:
            out = req.outputs.get()
            start_len = len(tokens)
            if track:
                for j, t in enumerate(out.token_ids):
                    piece = detok.push([t])
                    text += piece
                    pieces.append(piece)
                    if stop_strings and start_len + j + 1 < min_tok:
                        exempt = len(text)
            elif stop_strings and start_len < min_tok:
                # Token-wise pushes while below min_tokens so the exemption
                # boundary lands on the exact token, not the chunk.
                for j, t in enumerate(out.token_ids):
                    text += detok.push([t])
                    if start_len + j + 1 < min_tok:
                        exempt = len(text)
            else:
                text += detok.push(out.token_ids)
            tokens.extend(out.token_ids)
            if out.logprobs:
                lps.extend(out.logprobs)
            if out.finished:
                tail = detok.flush()
                text += tail
                if track and pieces and tail:
                    # Window residue resolves after the last token; for
                    # offset/trim purposes it belongs to that token.
                    pieces[-1] += tail
            if stop_strings and len(tokens) >= min_tok:
                cut = _find_stop(text, stop_strings, min_end=exempt)
                if cut is not None:
                    text = text[:cut]
                    if not out.finished:
                        self.engine.abort(req.request_id)
                        while not out.finished:
                            out = req.outputs.get()
                    # Trim token/logprob arrays to the visible text: entries
                    # past the cut would make text_offset index out of the
                    # returned string.
                    tokens, lps, pieces = self._trim_to_text(
                        tokens, lps, pieces, cut)
                    return text, "stop", out, tokens, lps, pieces
            if out.finished:
                return text, out.finish_reason, out, tokens, lps, pieces

    def _trim_to_text(self, tokens: list[int], lps: list, pieces: list[str],
                      cut: int):
        """Keep the longest token prefix whose streamed text fits in
        ``cut`` characters (a token straddling the cut is dropped)."""
        if not pieces and tokens:
            # The logprobs-off path records no stream pieces; isolated
            # per-token decode is the best-effort fallback (lazy, stops at
            # the cut; nothing downstream consumes offsets then).
            tok = self.engine.tokenizer
            pieces = (tok.decode([t]) for t in tokens)
        keep, acc, kept = 0, 0, []
        for piece in pieces:
            if acc + len(piece) > cut:
                break
            acc += len(piece)
            keep += 1
            kept.append(piece)
        return tokens[:keep], lps[:keep], kept

    def _lp_completions_obj(self, token_ids: list[int], lps: list,
                            top_n: int, pieces: list[str] | None = None,
                            offset_base: int = 0) -> dict:
        """Legacy completions logprobs object (tokens / token_logprobs /
        top_logprobs / text_offset).  ``pieces`` (per-token text from the
        response's own incremental stream) keeps text_offset aligned with
        the returned text; alternatives in top_logprobs are hypothetical
        tokens with no stream context, so they decode in isolation.
        ``offset_base`` shifts text_offset past echoed prompt text."""
        tok = self.engine.tokenizer
        tokens, token_lps, tops, offsets = [], [], [], []
        off = offset_base
        for i, (tid, (clp, top)) in enumerate(zip(token_ids, lps)):
            s = pieces[i] if pieces is not None and i < len(pieces) \
                else tok.decode([tid])
            tokens.append(s)
            token_lps.append(clp)
            tops.append({tok.decode([j]): v for j, v in top[:top_n]})
            offsets.append(off)
            off += len(s)
        return {"tokens": tokens, "token_logprobs": token_lps,
                "top_logprobs": tops, "text_offset": offsets}

    def _lp_chat_content(self, token_ids: list[int], lps: list,
                         top_n: int, pieces: list[str] | None = None
                         ) -> list[dict]:
        """Chat logprobs.content entries ({token, logprob, bytes,
        top_logprobs})."""
        tok = self.engine.tokenizer

        def entry(tid_text: str, lp_val: float) -> dict:
            return {"token": tid_text, "logprob": lp_val,
                    "bytes": list(tid_text.encode("utf-8", "surrogatepass"))}

        out = []
        for i, (tid, (clp, top)) in enumerate(zip(token_ids, lps)):
            s = pieces[i] if pieces is not None and i < len(pieces) \
                else tok.decode([tid])
            e = entry(s, clp)
            e["top_logprobs"] = [entry(tok.decode([j]), v)
                                 for j, v in top[:top_n]]
            out.append(e)
        return out

    def _batch_response(self, h, reqs: list[Request], model: str,
                        stop_strings: list[str], chat: bool = False,
                        echo: bool = False,
                        tools_ctx: str | None = None) -> None:
        """Multi-choice responses: batched prompts and/or n > 1 (one
        engine request per choice, prompt-major indexes)."""
        choices, usage = [], {"prompt_tokens": 0, "completion_tokens": 0,
                              "total_tokens": 0}
        echo_cache: dict = {}
        for i, req in enumerate(reqs):
            text, finish_reason, fin, toks, lps, pieces = self._collect_text(
                req, stop_strings)
            if finish_reason == "error":
                # One rejected choice fails the whole batch (the OpenAI
                # response has no per-choice error channel); release the
                # siblings' slots instead of decoding for nobody.
                for r in reqs:
                    self.engine.abort(r.request_id)
                return self._request_error(h, fin)
            if chat:
                message, finish_reason = self._chat_message(
                    text, finish_reason, tools_ctx)
                choice = {"index": i, "message": message,
                          "finish_reason": finish_reason}
                if req.params.logprobs is not None and lps:
                    choice["logprobs"] = {"content": self._lp_chat_content(
                        toks, lps, req.params.logprobs, pieces)}
            else:
                prefix = ""
                if echo:
                    key = tuple(req.prompt_ids)
                    if key not in echo_cache:  # n children share one prompt
                        echo_cache[key] = self.engine.tokenizer.decode(
                            req.prompt_ids)
                    prefix = echo_cache[key]
                    text = prefix + text
                choice = {"index": i, "text": text,
                          "finish_reason": finish_reason}
                if req.params.logprobs is not None and lps:
                    choice["logprobs"] = self._lp_completions_obj(
                        toks, lps, req.params.logprobs, pieces,
                        offset_base=len(prefix))
            choices.append(choice)
            usage["prompt_tokens"] += fin.num_prompt_tokens
            usage["completion_tokens"] += fin.num_generated_tokens
        usage["total_tokens"] = usage["prompt_tokens"] + usage["completion_tokens"]
        h._json(200, {
            "id": reqs[0].request_id,
            "object": "chat.completion" if chat else "text_completion",
            "created": int(time.time()), "model": model,
            "choices": choices, "usage": usage,
        })

    def _chat_message(self, text: str, finish_reason: str,
                      tools_ctx: str | None) -> tuple[dict, str]:
        """Assistant message dict (+ effective finish_reason): with active
        tools, generated text is parsed for tool calls; a call flips the
        finish_reason to "tool_calls" (OpenAI contract — but never over a
        truncation, clients must see length limits)."""
        if tools_ctx is not None:
            from arks_tpu.server.tools import parse_tool_calls
            content, calls = parse_tool_calls(text, tools_ctx)
            if calls:
                msg = {"role": "assistant", "content": content,
                       "tool_calls": calls}
                fr = ("tool_calls" if finish_reason == "stop"
                      else finish_reason)
                return msg, fr
        return {"role": "assistant", "content": text}, finish_reason

    def _full_response(self, h, req: Request, chat: bool, model: str,
                       stop_strings: list[str], echo: bool = False,
                       tools_ctx: str | None = None) -> None:
        text, finish_reason, fin, toks, lps, pieces = self._collect_text(
            req, stop_strings)
        echo_prefix = ""
        if echo and not chat:
            # OpenAI completions echo: the prompt text precedes the
            # generated text in the same choice (non-stream only).
            echo_prefix = self.engine.tokenizer.decode(req.prompt_ids)
            text = echo_prefix + text
        if finish_reason == "error":
            # Engine-level rejection (defense for direct add_request users;
            # the HTTP path normally pre-checks) or a fault-quarantined
            # request (engine_fault -> 500).
            return self._request_error(h, fin)
        usage = {
            "prompt_tokens": fin.num_prompt_tokens,
            "completion_tokens": fin.num_generated_tokens,
            "total_tokens": fin.num_prompt_tokens + fin.num_generated_tokens,
        }
        rid = req.request_id
        n_lp = req.params.logprobs
        if chat:
            message, finish_reason = self._chat_message(text, finish_reason,
                                                        tools_ctx)
            choice = {"index": 0, "message": message,
                      "finish_reason": finish_reason}
            if n_lp is not None and lps:
                choice["logprobs"] = {
                    "content": self._lp_chat_content(toks, lps, n_lp, pieces)}
            payload = {
                "id": rid, "object": "chat.completion", "created": int(time.time()),
                "model": model, "choices": [choice], "usage": usage,
            }
        else:
            choice = {"index": 0, "text": text,
                      "finish_reason": finish_reason}
            if n_lp is not None and lps:
                choice["logprobs"] = self._lp_completions_obj(
                    toks, lps, n_lp, pieces,
                    offset_base=len(echo_prefix))
            payload = {
                "id": rid, "object": "text_completion", "created": int(time.time()),
                "model": model, "choices": [choice], "usage": usage,
            }
        h._json(200, payload)

    def _stream_tools_response(self, h, req: Request, model: str,
                               include_usage: bool, stop_strings: list[str],
                               parser: str, first_out=None) -> None:
        """Chat streaming with active tools: content streams normally until
        a tool-call marker appears; from there the text buffers and is
        emitted as ``delta.tool_calls`` when the stream ends (each call's
        arguments arrive in one delta — permitted by the protocol, and the
        only faithful option when calls must parse as complete JSON).
        Stop strings are applied over the full text, like the non-stream
        path (the stream runs fully buffered when any are set), including
        the min_tokens exemption."""
        from arks_tpu.server.tools import (TOOL_OPEN, call_spans,
                                           parse_tool_calls)
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-cache")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()

        lag = _StreamLag()

        def send_frame(obj) -> None:
            data = b"data: " + (obj if isinstance(obj, bytes)
                                else json.dumps(obj).encode()) + b"\n\n"
            h.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            h.wfile.flush()
            lag.flushed()

        rid = req.request_id
        created = int(time.time())

        def chunk(delta: dict | None, finish: str | None = None,
                  usage: dict | None = None,
                  empty_choices: bool = False) -> dict:
            choices = [] if empty_choices else [
                {"index": 0, "delta": delta or {}, "finish_reason": finish}]
            payload = {"id": rid, "object": "chat.completion.chunk",
                       "created": created, "model": model,
                       "choices": choices}
            if usage is not None:
                payload["usage"] = usage
            return payload

        detok = IncrementalDetokenizer(self.engine.tokenizer)
        text = ""
        emitted = 0
        buffering = bool(stop_strings)
        hold = len(TOOL_OPEN) - 1
        fin = None
        min_tok = int(getattr(req.params, "min_tokens", 0) or 0)
        ntok = 0
        exempt = 0
        try:
            send_frame(chunk({"role": "assistant"}))
            while True:
                out = first_out if first_out is not None \
                    else req.outputs.get()
                first_out = None  # _respond peeked the first output
                lag.took(out)
                prev_ntok = ntok
                ntok += len(out.token_ids)
                if stop_strings and prev_ntok < min_tok:
                    # Token-wise pushes below min_tokens: the stop
                    # exemption boundary must land on the exact token
                    # (same semantics as _collect_text).
                    for j, t in enumerate(out.token_ids):
                        text += detok.push([t])
                        if prev_ntok + j + 1 < min_tok:
                            exempt = len(text)
                else:
                    text += detok.push(out.token_ids)
                if out.finished:
                    text += detok.flush()
                    fin = out
                if not buffering:
                    m = text.find(TOOL_OPEN)
                    if m >= 0:
                        if m > emitted:
                            send_frame(chunk({"content": text[emitted:m]}))
                            emitted = m
                        buffering = True
                    elif (parser in ("auto", "llama3")
                          and text.lstrip()[:1] == "{"):
                        buffering = True  # llama3: whole message is a call
                    elif not out.finished:
                        # Hold back a window so a straddling marker isn't
                        # half-emitted as content.
                        safe = len(text) - hold
                        if safe > emitted:
                            send_frame(chunk({"content": text[emitted:safe]}))
                            emitted = safe
                if out.finished:
                    break
            finish = fin.finish_reason
            if stop_strings and ntok >= min_tok:
                cut = _find_stop(text, stop_strings, min_end=exempt)
                if cut is not None:
                    text = text[:cut]
                    finish = "stop"
            content, calls = parse_tool_calls(text, parser)
            if calls:
                # Leftover content in RAW coordinates: everything outside
                # the call spans and past what was already streamed
                # (parse_tool_calls' stripped content doesn't line up
                # with the emitted offset).
                pos = emitted
                rest_parts = []
                for s, e in call_spans(text, parser):
                    if s > pos:
                        rest_parts.append(text[pos:s])
                    pos = max(pos, e)
                if pos < len(text):
                    rest_parts.append(text[pos:])
                rest = "".join(rest_parts)
                if rest:
                    send_frame(chunk({"content": rest}))
                for idx, call in enumerate(calls):
                    send_frame(chunk({"tool_calls": [{
                        "index": idx, "id": call["id"], "type": "function",
                        "function": dict(call["function"])}]}))
                if finish == "stop":
                    finish = "tool_calls"
            elif len(text) > emitted:
                send_frame(chunk({"content": text[emitted:]}))
            send_frame(chunk(None, finish=finish))
            if include_usage:
                send_frame(chunk(None, usage={
                    "prompt_tokens": fin.num_prompt_tokens,
                    "completion_tokens": fin.num_generated_tokens,
                    "total_tokens": (fin.num_prompt_tokens
                                     + fin.num_generated_tokens),
                }, empty_choices=True))
            send_frame(b"[DONE]")
            h.wfile.write(b"0\r\n\r\n")
            h.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            self.engine.abort(req.request_id)
        finally:
            lag.close(self.engine.metrics)

    def _stream_response(self, h, req: Request, chat: bool, model: str,
                         include_usage: bool, stop_strings: list[str],
                         first_out=None) -> None:
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-cache")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()

        lag = _StreamLag()

        def send_frame(obj) -> None:
            data = b"data: " + (obj if isinstance(obj, bytes) else json.dumps(obj).encode()) + b"\n\n"
            h.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            h.wfile.flush()
            lag.flushed()

        rid = req.request_id
        created = int(time.time())
        obj = "chat.completion.chunk" if chat else "text_completion"

        n_lp = req.params.logprobs
        # Logprob entries accumulate per engine output and flush with
        # emitted frames — but never ahead of their text: entries whose
        # pieces sit in the stop-string hold-back tail stay pending (a
        # later cut may drop them), so the streamed entry set matches the
        # non-stream response exactly.
        pend_lp_toks: list[int] = []
        pend_lps: list = []
        pend_pieces: list[str] = []
        lp_flush_n: list[int | None] = [None]  # entries next frame may flush

        def lp_within(pending_text: str, boundary: int) -> int:
            """How many pending entries' text ends within the first
            ``boundary`` chars of ``pending_text``.  Pending tokens' text is
            the trailing sum(pend_pieces) chars of emitted+pending text, so
            walk from that (possibly negative) offset."""
            acc = len(pending_text) - sum(len(p) for p in pend_pieces)
            keep = 0
            for p in pend_pieces:
                if acc + len(p) > boundary:
                    break
                acc += len(p)
                keep += 1
            return keep

        def take_lp():
            if n_lp is None or not pend_lps:
                return None
            n = lp_flush_n[0]
            n = len(pend_lps) if n is None else min(n, len(pend_lps))
            if n <= 0:
                return None
            toks_, lps_, pieces_ = (pend_lp_toks[:n], pend_lps[:n],
                                    pend_pieces[:n])
            del pend_lp_toks[:n]
            del pend_lps[:n]
            del pend_pieces[:n]
            if chat:
                return {"content": self._lp_chat_content(
                    toks_, lps_, n_lp, pieces_)}
            return self._lp_completions_obj(toks_, lps_, n_lp, pieces_)

        def chunk(delta_text: str | None, finish: str | None = None, role: str | None = None,
                  usage: dict | None = None, empty_choices: bool = False) -> dict:
            if empty_choices:
                choices = []
            elif chat:
                delta: dict = {}
                if role:
                    delta["role"] = role
                if delta_text:
                    delta["content"] = delta_text
                choices = [{"index": 0, "delta": delta, "finish_reason": finish}]
            else:
                choices = [{"index": 0, "text": delta_text or "", "finish_reason": finish}]
            if choices and (delta_text or finish):
                lp_obj = take_lp()
                if lp_obj is not None:
                    choices[0]["logprobs"] = lp_obj
            payload = {"id": rid, "object": obj, "created": created,
                       "model": model, "choices": choices}
            if usage is not None:
                payload["usage"] = usage
            return payload

        detok = IncrementalDetokenizer(self.engine.tokenizer)
        fin = None
        # Text already emitted to the client; used for stop-string matching
        # across chunk boundaries (a stop string can straddle two deltas).
        pending = ""
        hold = max((len(s) for s in stop_strings), default=1) - 1
        # min_tokens defers ALL stops (vLLM semantics); ``exempt`` is the
        # pending-relative boundary below which text is exempt from
        # stop-string matching (_find_stop still cuts a stop whose end
        # crosses the boundary).
        min_tok = int(getattr(req.params, "min_tokens", 0) or 0)
        ntok = 0
        exempt = 0
        try:
            if chat:
                send_frame(chunk(None, role="assistant"))
            while True:
                out = first_out if first_out is not None \
                    else req.outputs.get()
                first_out = None  # _respond peeked the first output
                lag.took(out)
                prev_ntok = ntok
                ntok += len(out.token_ids)
                if n_lp is not None:
                    # Per-token pushes through the same stream keep logprob
                    # entries aligned with real text boundaries (see
                    # _collect_text); chunk-wise push stays the no-logprobs
                    # hot path.
                    for j, t in enumerate(out.token_ids):
                        piece = detok.push([t])
                        pending += piece
                        if out.logprobs:
                            pend_pieces.append(piece)
                        if stop_strings and prev_ntok + j + 1 < min_tok:
                            exempt = len(pending)
                    if out.logprobs:
                        pend_lp_toks.extend(out.token_ids)
                        pend_lps.extend(out.logprobs)
                elif stop_strings and prev_ntok < min_tok:
                    # Token-wise pushes while below min_tokens so the
                    # stop-exemption boundary lands on the exact token.
                    for j, t in enumerate(out.token_ids):
                        pending += detok.push([t])
                        if prev_ntok + j + 1 < min_tok:
                            exempt = len(pending)
                else:
                    pending += detok.push(out.token_ids)
                if out.finished:
                    # Flush window residue BEFORE the stop check: the tail
                    # can complete a stop string, and the non-stream path
                    # (_collect_text) cuts it — paths must agree.
                    tail = detok.flush()
                    pending += tail
                    if pend_pieces and tail:
                        pend_pieces[-1] += tail
                if stop_strings and ntok >= min_tok:
                    cut = _find_stop(pending, stop_strings, min_end=exempt)
                    if cut is not None:
                        # Drop only the logprob entries whose text falls
                        # PAST the cut; kept entries flush with the cut
                        # frame (or the stop frame when the cut text is
                        # empty).
                        keep = lp_within(pending, cut)
                        del pend_lp_toks[keep:]
                        del pend_lps[keep:]
                        del pend_pieces[keep:]
                        if pending[:cut]:
                            send_frame(chunk(pending[:cut]))
                        self.engine.abort(req.request_id)
                        while not out.finished:
                            out = req.outputs.get()
                        fin = out
                        send_frame(chunk(None, finish="stop"))
                        break
                if out.finished:
                    if pending:
                        send_frame(chunk(pending))
                    send_frame(chunk(None, finish=out.finish_reason))
                    fin = out
                    break
                # Hold back enough tail to catch a straddling stop string.
                safe = len(pending) - hold
                if safe > 0:
                    # Flush only logprob entries whose text is fully inside
                    # the emitted prefix; entries in the hold-back tail wait
                    # (a later stop cut may drop them).
                    lp_flush_n[0] = lp_within(pending, safe)
                    send_frame(chunk(pending[:safe]))
                    lp_flush_n[0] = None
                    pending = pending[safe:]
                    exempt = max(0, exempt - safe)
            if include_usage and fin is not None:
                usage = {
                    "prompt_tokens": fin.num_prompt_tokens,
                    "completion_tokens": fin.num_generated_tokens,
                    "total_tokens": fin.num_prompt_tokens + fin.num_generated_tokens,
                }
                send_frame(chunk(None, usage=usage, empty_choices=True))
            send_frame(b"[DONE]")
            h.wfile.write(b"0\r\n\r\n")
            h.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            # Client went away: release the slot instead of decoding to
            # max_tokens for nobody.
            self.engine.abort(req.request_id)
        finally:
            lag.close(self.engine.metrics)
