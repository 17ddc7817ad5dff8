"""HTTP surface tests: OpenAI wire contract incl. SSE streaming + usage."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config
from arks_tpu.server import OpenAIServer


@pytest.fixture(scope="module")
def server():
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    engine = InferenceEngine(cfg, ecfg, ByteTokenizer())
    engine.start()
    srv = OpenAIServer(engine, served_model_name="tiny-serve", host="127.0.0.1", port=0)
    srv.start(background=True)
    yield srv
    srv.stop()
    engine.stop()


def _post(server, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}{path}",
        data=json.dumps(body).encode(), headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=120)


def test_models_list(server):
    with urllib.request.urlopen(f"http://127.0.0.1:{server.port}/v1/models") as r:
        data = json.load(r)
    assert data["object"] == "list"
    assert data["data"][0]["id"] == "tiny-serve"


def test_completion_non_stream(server):
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 6,
        "temperature": 0, "ignore_eos": True,
    }) as r:
        data = json.load(r)
    assert data["object"] == "text_completion"
    assert data["choices"][0]["finish_reason"] == "length"
    u = data["usage"]
    assert u["prompt_tokens"] == 2 and u["completion_tokens"] == 6
    assert u["total_tokens"] == 8


def test_chat_completion_non_stream(server):
    with _post(server, "/v1/chat/completions", {
        "model": "tiny-serve",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 4, "temperature": 0, "ignore_eos": True,
    }) as r:
        data = json.load(r)
    assert data["object"] == "chat.completion"
    assert data["choices"][0]["message"]["role"] == "assistant"
    assert data["usage"]["completion_tokens"] == 4


def test_chat_stream_with_usage(server):
    frames = []
    with _post(server, "/v1/chat/completions", {
        "model": "tiny-serve",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 5, "temperature": 0, "ignore_eos": True,
        "stream": True, "stream_options": {"include_usage": True},
    }) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: "):
                frames.append(line[len("data: "):])
    assert frames[-1] == "[DONE]"
    chunks = [json.loads(f) for f in frames[:-1]]
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    finishes = [c["choices"][0]["finish_reason"] for c in chunks if c["choices"]]
    assert "length" in finishes
    usage_frames = [c for c in chunks if c.get("usage") is not None]
    assert len(usage_frames) == 1 and usage_frames[0]["choices"] == []
    assert usage_frames[0]["usage"]["completion_tokens"] == 5


def test_wrong_model_404(server):
    try:
        _post(server, "/v1/completions", {"model": "nope", "prompt": "x"})
        assert False, "expected HTTPError"
    except urllib.error.HTTPError as e:
        assert e.code == 404
        assert "not found" in json.load(e)["error"]["message"]


def test_bad_json_400(server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/v1/completions",
        data=b"{not json", headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=30)
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_metrics_endpoint(server):
    with urllib.request.urlopen(f"http://127.0.0.1:{server.port}/metrics") as r:
        text = r.read().decode()
    assert "num_requests_running" in text
    assert "generation_tokens_total" in text




def test_stop_string_multi_token(server):
    # Learn greedy output first, then use a 2-char substring of it as stop.
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "zq", "max_tokens": 8,
        "temperature": 0, "ignore_eos": True,
    }) as r:
        full = json.load(r)["choices"][0]["text"]
    assert len(full) >= 3
    stop = full[1:3]
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "zq", "max_tokens": 8,
        "temperature": 0, "ignore_eos": True, "stop": [stop],
    }) as r:
        data = json.load(r)
    assert data["choices"][0]["finish_reason"] == "stop"
    assert stop not in data["choices"][0]["text"]
    assert data["choices"][0]["text"] == full[: full.find(stop)]


def test_engine_abort_frees_slot():
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.types import Request, SamplingParams
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config
    ecfg = EngineConfig(model="tiny", num_slots=1, max_cache_len=64,
                        prefill_buckets=(8,), steps_per_dispatch=2)
    eng = InferenceEngine(get_config("tiny"), ecfg, ByteTokenizer())
    req = Request("abort-me", [3, 4], SamplingParams(max_tokens=10_000, temperature=0.0,
                                                     ignore_eos=True))
    eng.add_request(req)
    eng.step(block_s=0.01)  # admit + first dispatch
    assert eng.num_running == 1
    eng.abort("abort-me")
    eng.step(block_s=0.01)  # abort consumed at the dispatch boundary
    assert eng.num_running == 0
    fin = None
    while True:
        out = req.outputs.get(timeout=30)
        if out.finished:
            fin = out
            break
    assert fin.finish_reason == "abort"


def test_small_max_model_len_no_crash():
    # Regression: max_cache_len below the smallest bucket must still admit.
    from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config
    ecfg = EngineConfig(model="tiny", num_slots=1, max_cache_len=20,
                        prefill_buckets=(32, 64), steps_per_dispatch=2)
    eng = InferenceEngine(get_config("tiny"), ecfg, ByteTokenizer())
    req = Request("tiny-cache", [1, 2, 3], SamplingParams(max_tokens=4, temperature=0.0,
                                                          ignore_eos=True))
    eng.add_request(req)
    for _ in range(50):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None):
            break
    outs = []
    while True:
        out = req.outputs.get(timeout=30)
        outs.append(out)
        if out.finished:
            break
    assert outs[-1].finished

def test_batched_prompt_multi_choice(server):
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": ["ab", "cd"], "max_tokens": 3,
        "temperature": 0, "ignore_eos": True,
    }) as r:
        data = json.load(r)
    assert [c["index"] for c in data["choices"]] == [0, 1]
    assert all(c["finish_reason"] == "length" for c in data["choices"])
    assert data["usage"]["prompt_tokens"] == 4
    assert data["usage"]["completion_tokens"] == 6


def test_context_length_exceeded_400(server):
    """Oversize prompts get HTTP 400 with code context_length_exceeded
    (OpenAI semantics) — never silent truncation.  The tiny server's usable
    window is 64 - 4 - 1 = 59 tokens (ByteTokenizer: 1 byte = 1 token)."""
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server, "/v1/completions", {
            "model": "tiny-serve", "prompt": "x" * 80, "max_tokens": 2,
        })
    assert ei.value.code == 400
    err = json.load(ei.value)["error"]
    assert err["code"] == "context_length_exceeded"
    assert "80" in err["message"]

    # Streaming path rejects the same way (before any SSE frame).
    with pytest.raises(urllib.error.HTTPError) as ei2:
        _post(server, "/v1/chat/completions", {
            "model": "tiny-serve", "stream": True,
            "messages": [{"role": "user", "content": "y" * 200}],
        })
    assert ei2.value.code == 400
    assert json.load(ei2.value)["error"]["code"] == "context_length_exceeded"


def test_long_prompt_chunked_through_server(server):
    """A prompt beyond the one-shot buckets (32) but inside the window (59)
    serves fine via chunked prefill."""
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "z" * 50, "max_tokens": 3,
        "temperature": 0, "ignore_eos": True,
    }) as r:
        data = json.load(r)
    assert data["choices"][0]["finish_reason"] == "length"
    assert data["usage"]["prompt_tokens"] == 50


def test_empty_prompt_400(server):
    try:
        _post(server, "/v1/completions", {"model": "tiny-serve", "prompt": ""})
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_stream_batch_prompt_400(server):
    try:
        _post(server, "/v1/completions", {
            "model": "tiny-serve", "prompt": ["a", "b"], "stream": True})
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 400


def _run_drain_scenario(extra_env=None):
    """Shared SIGTERM-drain scenario: start a serving subprocess, stream a
    long request, SIGTERM mid-stream, assert readiness/admission 503
    during the drain, the in-flight stream finishes to its LAST byte, and
    the process exits 0.  ``extra_env`` overrides engine env knobs (the
    pipelined-decode variant rides this)."""
    import json as _json
    import os
    import signal
    import subprocess
    import sys
    import threading
    import time as _time
    import urllib.error
    import urllib.request

    import socket as _socket
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "arks_tpu.server",
         "--model", "tiny", "--port", str(port), "--platform", "cpu",
         "--num-slots", "2", "--max-model-len", "64",
         "--steps-per-dispatch", "1", "--drain-timeout", "30"],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, env=env)
    base = f"http://127.0.0.1:{port}"
    try:
        for _ in range(120):
            try:
                urllib.request.urlopen(base + "/readiness", timeout=2)
                break
            except Exception:
                _time.sleep(1)

        # Long streamed request (40 tokens at 1 step/dispatch: plenty of
        # wall time to SIGTERM in the middle).
        frames: list[str] = []
        err: list[Exception] = []

        def stream():
            req = urllib.request.Request(
                base + "/v1/completions",
                data=_json.dumps({"model": "tiny", "prompt": "drain me",
                                  "max_tokens": 40, "temperature": 0,
                                  "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    for raw in r:
                        line = raw.decode().strip()
                        if line.startswith("data: "):
                            frames.append(line[6:])
            except Exception as e:  # noqa: BLE001 — recorded for the assert
                err.append(e)

        t = threading.Thread(target=stream)
        t.start()
        # Wait until tokens are flowing, then SIGTERM.
        deadline = _time.monotonic() + 60
        while not frames and _time.monotonic() < deadline:
            _time.sleep(0.1)
        assert frames, "stream never started"
        os.kill(proc.pid, signal.SIGTERM)

        # While draining: readiness 503 and new completions 503.
        _time.sleep(0.5)
        try:
            urllib.request.urlopen(base + "/readiness", timeout=5)
            raise AssertionError("readiness should be 503 while draining")
        except urllib.error.HTTPError as e:
            assert e.code == 503
        try:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/completions",
                data=_json.dumps({"model": "tiny", "prompt": "new",
                                  "max_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"}), timeout=10)
            raise AssertionError("new work should be 503 while draining")
        except urllib.error.HTTPError as e:
            assert e.code == 503

        # The in-flight stream finishes COMPLETELY (to its last byte: the
        # finish frame carries finish_reason) and the process exits 0.
        t.join(timeout=120)
        assert not err, f"in-flight stream died during drain: {err}"
        assert frames[-1] == "[DONE]"
        payloads = [_json.loads(f) for f in frames[:-1]]
        text = "".join(c["text"] for p in payloads
                       for c in p.get("choices", []) if "text" in c)
        assert len(text) > 0
        finishes = [c["finish_reason"] for p in payloads
                    for c in p.get("choices", []) if c.get("finish_reason")]
        assert finishes == ["length"], finishes
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_sigterm_drains_in_flight_requests():
    """Graceful drain: SIGTERM mid-request flips readiness to 503, rejects
    NEW completions, lets the in-flight streamed request finish, and the
    process exits cleanly — what makes rolling updates request-lossless."""
    _run_drain_scenario()


def test_sigterm_drains_under_pipelined_decode():
    """The same drain contract with ARKS_PIPELINE_DEPTH=2: SIGTERM with
    pipelined dispatches in flight must flip readiness, resolve/drain the
    in-flight pipeline, finish every live stream to its last byte, and
    exit within --drain-timeout.  (The conftest pins depth 0 for the
    suite; this subprocess re-enables the production default.)"""
    _run_drain_scenario({"ARKS_PIPELINE_DEPTH": "2"})


def test_logprobs_completions_and_chat(server):
    """OpenAI logprobs: completions int form and chat logprobs/top_logprobs
    form, with chosen-token logprobs matching a real log-softmax (negative,
    and for greedy the chosen token is the max of its top list)."""
    import math

    with _post(server, "/v1/completions",
               {"model": "tiny-serve", "prompt": "hello", "max_tokens": 6,
                "temperature": 0, "ignore_eos": True, "logprobs": 3}) as r:
        out = json.load(r)
    lp = out["choices"][0]["logprobs"]
    assert len(lp["tokens"]) == 6
    assert len(lp["token_logprobs"]) == 6
    assert all(v <= 0 for v in lp["token_logprobs"])
    # Dict keyed by token TEXT (the legacy format): distinct ids that
    # render identically (byte-tokenizer replacement chars) collapse.
    assert all(1 <= len(d) <= 3 for d in lp["top_logprobs"])
    for tok_lp, top in zip(lp["token_logprobs"], lp["top_logprobs"]):
        # Greedy: the chosen token is the global argmax, so its logprob
        # bounds every listed alternative (text-key collisions can hide
        # the chosen entry itself from the dict).
        assert tok_lp >= max(top.values()) - 1e-5
    assert lp["text_offset"][0] == 0
    assert lp["text_offset"] == sorted(lp["text_offset"])

    with _post(server, "/v1/chat/completions",
               {"model": "tiny-serve", "max_tokens": 4, "temperature": 0,
                "ignore_eos": True, "logprobs": True, "top_logprobs": 2,
                "messages": [{"role": "user", "content": "hi"}]}) as r:
        out = json.load(r)
    content = out["choices"][0]["logprobs"]["content"]
    assert len(content) == 4
    for e in content:
        assert e["logprob"] <= 0
        assert isinstance(e["bytes"], list)
        assert len(e["top_logprobs"]) == 2

    with _post(server, "/v1/completions",
               {"model": "tiny-serve", "prompt": "x", "max_tokens": 2,
                "temperature": 0, "ignore_eos": True}) as r:
        out = json.load(r)
    assert "logprobs" not in out["choices"][0]


def test_logprobs_streaming(server):
    entries = []
    with _post(server, "/v1/chat/completions",
               {"model": "tiny-serve", "max_tokens": 6, "temperature": 0,
                "ignore_eos": True, "logprobs": True, "top_logprobs": 1,
                "stream": True,
                "messages": [{"role": "user", "content": "go"}]}) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            for c in json.loads(line[6:]).get("choices", []):
                lp = c.get("logprobs")
                if lp:
                    entries.extend(lp["content"])
    assert len(entries) == 6  # one per generated token, across chunks
    assert all(e["logprob"] <= 0 for e in entries)


def test_logprobs_zero_means_chosen_only(server):
    """completions logprobs=0 and chat top_logprobs=0: logprob data present,
    alternatives lists empty (distinct from 'off')."""
    with _post(server, "/v1/completions",
               {"model": "tiny-serve", "prompt": "z", "max_tokens": 3,
                "temperature": 0, "ignore_eos": True, "logprobs": 0}) as r:
        out = json.load(r)
    lp = out["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 3
    assert all(d == {} for d in lp["top_logprobs"])

    with _post(server, "/v1/chat/completions",
               {"model": "tiny-serve", "max_tokens": 3, "temperature": 0,
                "ignore_eos": True, "logprobs": True, "top_logprobs": 0,
                "messages": [{"role": "user", "content": "q"}]}) as r:
        out = json.load(r)
    content = out["choices"][0]["logprobs"]["content"]
    assert len(content) == 3
    assert all(e["top_logprobs"] == [] for e in content)


def test_logprobs_streaming_stop_cut_parity(server):
    """On a streamed stop-string cut, logprob entries for visible tokens
    still flush (only past-the-cut entries drop) — entry count and text
    match the non-stream path for the same request."""
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "zq", "max_tokens": 8,
        "temperature": 0, "ignore_eos": True,
    }) as r:
        full = json.load(r)["choices"][0]["text"]
    assert len(full) >= 5
    # full[3:5] straddles the 4-token dispatch boundary: the first frame is
    # emitted (with its hold-back) before the cut is even detectable —
    # entries in the hold-back tail must NOT flush early.
    for stop in (full[1:3], full[3:5]):
        body = {"model": "tiny-serve", "prompt": "zq", "max_tokens": 8,
                "temperature": 0, "ignore_eos": True, "stop": [stop],
                "logprobs": 1}
        with _post(server, "/v1/completions", body) as r:
            ref = json.load(r)["choices"][0]
        assert ref["finish_reason"] == "stop"

        text, n_entries = "", 0
        with _post(server, "/v1/completions", dict(body, stream=True)) as r:
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                for c in json.loads(line[6:]).get("choices", []):
                    text += c.get("text") or ""
                    lp = c.get("logprobs")
                    if lp:
                        n_entries += len(lp["tokens"])
        assert text == ref["text"]
        assert n_entries == len(ref["logprobs"]["tokens"])
        # The cut kept the visible-prefix tokens and dropped the rest.
        assert 0 < n_entries < 8


def test_logit_bias_and_min_tokens_api(server):
    """OpenAI logit_bias flows through the HTTP surface (+100 forces a
    token id across the stream) and oversized bias objects 400 instead
    of silently truncating; min_tokens passes through."""
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 4,
        "temperature": 0, "ignore_eos": True,
        "logit_bias": {"123": 100},
    }) as r:
        data = json.load(r)
    from arks_tpu.engine.tokenizer import ByteTokenizer
    assert data["choices"][0]["text"] == ByteTokenizer().decode([123] * 4)

    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 4,
        "temperature": 0, "ignore_eos": True, "min_tokens": 3,
    }) as r:
        assert json.load(r)["usage"]["completion_tokens"] == 4

    from arks_tpu.engine.sampler import LOGIT_BIAS_MAX
    too_many = {str(i): 1 for i in range(LOGIT_BIAS_MAX + 1)}
    try:
        _post(server, "/v1/completions", {
            "model": "tiny-serve", "prompt": "hi", "max_tokens": 2,
            "logit_bias": too_many,
        })
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_min_tokens_defers_stop_strings(server):
    """vLLM semantics: stop strings do not terminate or cut the stream
    until min_tokens completion tokens exist; text generated before the
    minimum is exempt from matching (the min-th token itself can stop)."""
    from arks_tpu.engine.tokenizer import ByteTokenizer
    ch = ByteTokenizer().decode([123])
    # Two-char stop -> multi-token, so it is matched server-side as a
    # string (single-token stops become device stop ids instead).
    body = {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 8,
        "temperature": 0, "ignore_eos": True, "min_tokens": 4,
        "logit_bias": {"123": 100}, "stop": [ch * 2],
    }
    with _post(server, "/v1/completions", body) as r:
        data = json.load(r)
    # Tokens 1-3 are exempt; the stop spanning tokens 3-4 matches (the
    # min-th token may complete a stop) and cuts at position 2.
    assert data["choices"][0]["finish_reason"] == "stop"
    assert data["choices"][0]["text"] == ch * 2

    frames = []
    with _post(server, "/v1/completions", {**body, "stream": True}) as r:
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: "):
                frames.append(line[len("data: "):])
    chunks = [json.loads(f) for f in frames[:-1]]
    text = "".join(c["choices"][0]["text"] for c in chunks if c["choices"])
    finishes = [c["choices"][0]["finish_reason"] for c in chunks if c["choices"]]
    assert text == ch * 2
    assert "stop" in finishes


def test_guided_decoding_api(server):
    """Guided decoding over HTTP: guided_regex forces an exact JSON shape;
    response_format json_object keeps the stream inside the JSON grammar;
    invalid patterns 400."""
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 32,
        "temperature": 0, "guided_regex": '\\{"ok": (true|false)\\}',
    }) as r:
        data = json.load(r)
    assert data["choices"][0]["finish_reason"] == "stop"
    assert json.loads(data["choices"][0]["text"])["ok"] in (True, False)

    with _post(server, "/v1/chat/completions", {
        "model": "tiny-serve",
        "messages": [{"role": "user", "content": "produce json"}],
        "max_tokens": 12, "temperature": 0,
        "response_format": {"type": "json_object"},
    }) as r:
        data = json.load(r)
    text = data["choices"][0]["message"]["content"]
    from arks_tpu.engine.guides import compile_regex_dfa, json_mode_regex
    t, _ = compile_regex_dfa(json_mode_regex(3))
    st = 0
    for b in text.encode():
        st = t[st, b]
        assert st >= 0, f"dead JSON transition in {text!r}"

    # json_schema structured output.  eos (id 0) biased +100: the random
    # test model then ends at the FIRST grammar-legal point (the guide
    # masks eos everywhere before the object closes; the grammar's
    # trailing-whitespace star would otherwise let greedy wander to
    # max_tokens).
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "s", "max_tokens": 48,
        "temperature": 0, "logit_bias": {"0": 100},
        "response_format": {"type": "json_schema", "json_schema": {
            "name": "t", "schema": {"type": "object", "properties": {
                "ok": {"type": "boolean"}}}}},
    }) as r:
        data = json.load(r)
    assert data["choices"][0]["finish_reason"] == "stop"
    assert json.loads(data["choices"][0]["text"])["ok"] in (True, False)

    try:
        _post(server, "/v1/completions", {
            "model": "tiny-serve", "prompt": "x", "max_tokens": 4,
            "guided_regex": "(unclosed"})
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_guided_choice_api(server):
    """vLLM-style guided_choice round-trip: the completion is EXACTLY one
    of the literal choices (regex metacharacters escaped); non-string
    entries and empty lists 400."""
    choices = ["red", "green", "blu.e(x)"]  # metachars must be literal
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "pick", "max_tokens": 16,
        "temperature": 0, "guided_choice": choices,
    }) as r:
        data = json.load(r)
    assert data["choices"][0]["finish_reason"] == "stop"
    assert data["choices"][0]["text"] in choices

    # Chat surface takes the extra too.
    with _post(server, "/v1/chat/completions", {
        "model": "tiny-serve",
        "messages": [{"role": "user", "content": "pick"}],
        "max_tokens": 16, "temperature": 0,
        "guided_choice": ["alpha", "beta"],
    }) as r:
        data = json.load(r)
    assert data["choices"][0]["message"]["content"] in ("alpha", "beta")

    for bad in (["ok", 3], [], "red", [None]):
        try:
            _post(server, "/v1/completions", {
                "model": "tiny-serve", "prompt": "x", "max_tokens": 4,
                "guided_choice": bad})
            raise AssertionError(f"expected HTTP 400 for {bad!r}")
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_find_stop_min_end_exemption():
    """A stop match ending at or before min_end is exempt, regardless of
    OTHER (longer) stop strings in the set; a straddling match cuts."""
    from arks_tpu.server.openai_server import _find_stop
    # "ab" lies wholly inside the exempt region: a longer stop in the set
    # must not widen the window and resurrect it.
    assert _find_stop("xxabyy", ["ab", "xxxxx"], min_end=4) is None
    # Straddle: the match's end crosses the boundary.
    assert _find_stop("xxabyy", ["ab"], min_end=3) == 2
    # A later, non-exempt occurrence is still found.
    assert _find_stop("abzzab", ["ab"], min_end=4) == 4
    # min_end=0 keeps the plain earliest-match behavior.
    assert _find_stop("zab", ["ab"], min_end=0) == 1


def test_engine_rejects_oversized_suppress_set():
    """add_request validates the min_tokens suppress budget on the CALLER's
    thread; overflowing inside the scheduler would abort every in-flight
    request (engine._run's blanket fault handler)."""
    from arks_tpu.engine.sampler import SUPPRESS_MAX, np_suppress_col
    from arks_tpu.engine.types import Request, SamplingParams
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8,), steps_per_dispatch=2)
    engine = InferenceEngine(cfg, ecfg, ByteTokenizer())
    params = SamplingParams(
        max_tokens=4, min_tokens=2, ignore_eos=True,
        stop_token_ids=tuple(range(SUPPRESS_MAX + 1)))
    req = Request(request_id="over", prompt_ids=[1, 2], params=params)
    with pytest.raises(ValueError, match="suppress set"):
        engine.add_request(req)
    with pytest.raises(ValueError, match="suppress set"):
        np_suppress_col(range(SUPPRESS_MAX + 1))


def test_n_choices(server):
    """OpenAI n: one independent sample per choice.  Greedy choices are
    identical; seeded sampled choices differ (child seeds seed+j) while
    the whole request stays reproducible."""
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 4,
        "temperature": 0, "ignore_eos": True, "n": 3,
    }) as r:
        data = json.load(r)
    texts = [c["text"] for c in data["choices"]]
    assert len(texts) == 3 and len(set(texts)) == 1  # greedy: identical
    assert [c["index"] for c in data["choices"]] == [0, 1, 2]
    assert data["usage"]["completion_tokens"] == 12

    def sampled():
        with _post(server, "/v1/completions", {
            "model": "tiny-serve", "prompt": "hi", "max_tokens": 6,
            "temperature": 1.0, "seed": 11, "ignore_eos": True, "n": 3,
        }) as r:
            return [c["text"] for c in json.load(r)["choices"]]

    a = sampled()
    assert len(set(a)) > 1          # distinct child seeds -> diverse
    assert a == sampled()           # but reproducible end to end

    # Chat n: message choices.
    with _post(server, "/v1/chat/completions", {
        "model": "tiny-serve",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 3, "temperature": 0, "ignore_eos": True, "n": 2,
    }) as r:
        data = json.load(r)
    assert data["object"] == "chat.completion"
    assert [c["message"]["role"] for c in data["choices"]] == ["assistant"] * 2

    # Streaming with n > 1 is rejected, not silently single-choice.
    try:
        _post(server, "/v1/completions", {
            "model": "tiny-serve", "prompt": "hi", "max_tokens": 2,
            "stream": True, "n": 2,
        })
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_echo_parameter(server):
    """Completions echo=true prepends the prompt text to the choice
    (non-stream only; chat and streaming reject it)."""
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 3,
        "temperature": 0, "ignore_eos": True, "echo": True,
    }) as r:
        data = json.load(r)
    text = data["choices"][0]["text"]
    assert text.startswith("hi") and len(text) > 2
    assert data["usage"]["completion_tokens"] == 3

    # echo + logprobs: text_offset starts past the echoed prompt, so
    # clients slicing choice.text by offset get the right substrings.
    with _post(server, "/v1/completions", {
        "model": "tiny-serve", "prompt": "hi", "max_tokens": 3,
        "temperature": 0, "ignore_eos": True, "echo": True, "logprobs": 0,
    }) as r:
        lp = json.load(r)["choices"][0]["logprobs"]
    assert lp["text_offset"][0] == len("hi")

    for bad in ({"stream": True}, {"_chat_probe": True}):
        body = {"model": "tiny-serve", "prompt": "hi", "max_tokens": 2,
                "echo": True, **bad}
        path = "/v1/completions"
        if bad.get("_chat_probe"):
            body = {"model": "tiny-serve", "max_tokens": 2, "echo": True,
                    "messages": [{"role": "user", "content": "x"}]}
            path = "/v1/chat/completions"
        try:
            _post(server, path, body)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_tier_header_maps_to_priority(server):
    """x-arks-tier -> params.priority (arks_tpu.slo): the header wins
    over a body "priority", and an unknown tier 400s even direct-to-pod
    (the gateway normally validates first, but must not be the only
    line)."""
    from arks_tpu import slo as slo_mod
    old = server.slo
    server.slo = slo_mod.parse_tiers("latency:ttft_ms=300,batch:")
    try:
        seen = []
        orig = server.engine.add_request

        def spy(req):
            seen.append(req.params.priority)
            return orig(req)

        server.engine.add_request = spy
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/completions",
                data=json.dumps({"model": "tiny-serve", "prompt": "hi",
                                 "max_tokens": 2, "ignore_eos": True,
                                 "priority": 0}).encode(),
                headers={"Content-Type": "application/json",
                         "x-arks-tier": "batch"})
            with urllib.request.urlopen(req, timeout=120) as r:
                assert r.status == 200
            assert seen == [1], seen  # batch = index 1, beats body 0
        finally:
            server.engine.add_request = orig
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/completions",
            data=json.dumps({"model": "tiny-serve", "prompt": "hi",
                             "max_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "x-arks-tier": "bogus"})
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "bogus" in json.load(e)["error"]["message"]
    finally:
        server.slo = old


def test_tenant_header_maps_to_request(server):
    """x-arks-tenant (gateway-minted, router-forwarded) lands on
    Request.tenant — the engine's fair-queue key."""
    seen = []
    orig = server.engine.add_request

    def spy(req):
        seen.append(req.tenant)
        return orig(req)

    server.engine.add_request = spy
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/completions",
            data=json.dumps({"model": "tiny-serve", "prompt": "hi",
                             "max_tokens": 2, "ignore_eos": True}).encode(),
            headers={"Content-Type": "application/json",
                     "x-arks-tenant": "acme/alice"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
        # No header -> None (untenanted single lane).
        with _post(server, "/v1/completions",
                   {"model": "tiny-serve", "prompt": "hi",
                    "max_tokens": 2, "ignore_eos": True}) as r:
            assert r.status == 200
    finally:
        server.engine.add_request = orig
    assert seen == ["acme/alice", None], seen


def test_queue_full_maps_to_http(server):
    """Bounded-queue rejections map by scope: the global cap is a
    saturated backend (503 queue_full), a per-tenant cap is the caller's
    own backlog (429 tenant_queue_full) — both with Retry-After and the
    saturation header."""
    from arks_tpu.engine import fairqueue
    orig = server.engine.add_request

    def reject_tenant(req):
        raise fairqueue.QueueFullError("tenant", "acme/alice", 8, 8, 3)

    def reject_queue(req):
        raise fairqueue.QueueFullError("queue", "acme/alice", 64, 64, 7)

    try:
        server.engine.add_request = reject_tenant
        try:
            _post(server, "/v1/completions",
                  {"model": "tiny-serve", "prompt": "hi", "max_tokens": 2})
            raise AssertionError("expected HTTP 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert e.headers["Retry-After"] == "3"
            assert e.headers["x-arks-tenant"] == "acme/alice"
            assert e.headers["x-arks-saturation"] is not None
            assert json.load(e)["error"]["code"] == "tenant_queue_full"
        server.engine.add_request = reject_queue
        try:
            _post(server, "/v1/completions",
                  {"model": "tiny-serve", "prompt": "hi", "max_tokens": 2})
            raise AssertionError("expected HTTP 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers["Retry-After"] == "7"
            assert json.load(e)["error"]["code"] == "queue_full"
    finally:
        server.engine.add_request = orig


def test_shed_deadline_maps_to_503_with_retry_after(server):
    """A deadline-shed engine output (queued past the tier's TTFT
    budget) is capacity, not client error: 503 + drain-derived
    Retry-After, code shed_deadline."""
    from arks_tpu.engine.types import RequestOutput
    orig = server.engine.add_request

    def shed(req):
        req.outputs.put(RequestOutput(
            request_id=req.request_id, token_ids=[], finished=True,
            finish_reason="error",
            error="shed_deadline: queued 9.00s, tier 1 ttft budget "
                  "already unmeetable", num_prompt_tokens=2))

    server.engine.add_request = shed
    try:
        try:
            _post(server, "/v1/completions",
                  {"model": "tiny-serve", "prompt": "hi", "max_tokens": 2})
            raise AssertionError("expected HTTP 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert int(e.headers["Retry-After"]) >= 1
            assert json.load(e)["error"]["code"] == "shed_deadline"
    finally:
        server.engine.add_request = orig


def test_readiness_exports_admission_saturation(server):
    """/readiness carries the queue-saturation block so edges can back
    off BEFORE the bounded queue starts rejecting."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/readiness", timeout=30) as r:
        data = json.load(r)
    adm = data["admission"]
    for key in ("queue_depth", "queue_max", "tenants_waiting",
                "drain_per_s", "saturation", "fair"):
        assert key in adm, adm
    assert adm["queue_depth"] >= 0
