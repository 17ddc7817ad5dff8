"""Guided decoding: regex/JSON grammars -> token-table DFAs -> engine.

Parity target: vLLM/SGLang guided decoding (JSON mode, guided_regex)
reachable through the reference's runtime launch path
(arksapplication_controller.go:941-1014)."""

import json
import queue
import threading
import time

import numpy as np
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine
from arks_tpu.engine.guides import (GuideCompiler, GuideError,
                                    compile_regex_dfa, json_mode_regex)
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.engine.types import Request, SamplingParams
from arks_tpu.models import get_config


def _match(table, acc, s: str) -> bool:
    st = 0
    for b in s.encode():
        st = table[st, b]
        if st < 0:
            return False
    return bool(acc[st])


# ---------------------------------------------------------------------------
# Character DFA
# ---------------------------------------------------------------------------

def test_regex_dfa_basics():
    t, a = compile_regex_dfa(r"[a-c]+x?")
    assert _match(t, a, "abc") and _match(t, a, "abcx")
    assert not _match(t, a, "") and not _match(t, a, "x")
    assert not _match(t, a, "abxy")

    t, a = compile_regex_dfa(r"(foo|ba*r)\d{2,3}")
    assert _match(t, a, "foo12") and _match(t, a, "br123")
    assert _match(t, a, "baaar99")
    assert not _match(t, a, "foo1") and not _match(t, a, "foo1234")

    # Escapes, classes, negation, dot-excludes-newline.
    t, a = compile_regex_dfa(r"[^x]\.")
    assert _match(t, a, "y.") and not _match(t, a, "x.")
    t, a = compile_regex_dfa(r".")
    assert _match(t, a, "q") and not _match(t, a, "\n")


def test_regex_dfa_rejects_bad_patterns():
    # Includes non-ASCII class bounds and escapes: they must raise
    # GuideError (HTTP 400), never OverflowError (HTTP 500).
    for bad in ["(", "a{2,1}", "[z-a]", "*a", "a{x}", "[a-Ā]",
                "\\é"]:
        with pytest.raises(GuideError):
            compile_regex_dfa(bad)


def test_json_mode_grammar():
    t, a = compile_regex_dfa(json_mode_regex(3))
    good = ['{}', '{"a": 1}', '{"a": [1, 2.5e3, "x"], "b": {"c": null}}',
            '{"k": {"l": {"m": true}}}', ' { "a" : -0.5 } ',
            '{"s": "esc \\" \\\\ \\u00ff ok"}']
    bad = ['', '[]', '{"a": }', '{a: 1}', '{"a": 1,}', '{"a": 01}',
           '{"a": "\n"}', '{"k": {"l": {"m": {"n": 1}}}}']  # depth 4 > 3
    for s in good:
        assert _match(t, a, s), s
    for s in bad:
        assert not _match(t, a, s), s


def test_json_schema_regex():
    from arks_tpu.engine.guides import json_schema_regex
    schema = {
        "type": "object",
        "properties": {
            "name": {"type": "string", "maxLength": 10},
            "age": {"type": "integer"},
            "tags": {"type": "array", "items": {"type": "string"},
                     "minItems": 1, "maxItems": 2},
            "mood": {"enum": ["happy", "sad", 3]},
            "nick": {"type": "string"},
        },
        "required": ["name", "age", "tags", "mood"],
    }
    t, a = compile_regex_dfa(json_schema_regex(schema))
    good = [
        '{"name": "bo", "age": 3, "tags": ["x"], "mood": "sad"}',
        '{"name": "", "age": 0, "tags": ["a", "b"], "mood": 3, '
        '"nick": "z"}',
    ]
    bad = [
        '{"age": 3, "name": "bo", "tags": ["x"], "mood": "sad"}',  # order
        '{"name": "bo", "age": 3.5, "tags": ["x"], "mood": "sad"}',
        '{"name": "bo", "age": 3, "tags": [], "mood": "sad"}',     # minItems
        '{"name": "bo", "age": 3, "tags": ["a","b","c"], "mood": "sad"}',
        '{"name": "bo", "age": 3, "tags": ["x"], "mood": "angry"}',
        '{"name": "longerthanten!", "age": 3, "tags": ["x"], "mood": 3}',
        '{"name": "bo", "age": 3, "tags": ["x"]}',                 # missing
    ]
    for s in good:
        assert _match(t, a, s), s
    for s in bad:
        assert not _match(t, a, s), s

    # anyOf, const, $refs with bounded recursion.
    t, a = compile_regex_dfa(json_schema_regex({
        "anyOf": [{"const": "yes"}, {"type": "object", "properties": {
            "next": {"$ref": "#/$defs/node"}}, "required": ["next"]}],
        "$defs": {"node": {"type": "null"}}}))
    assert _match(t, a, '"yes"') and _match(t, a, '{"next": null}')
    assert not _match(t, a, "no")

    with pytest.raises(GuideError):
        json_schema_regex({"type": "object", "properties": {
            "opt": {"type": "integer"}}, "required": []})
    # required names absent from properties must raise, not silently drop.
    with pytest.raises(GuideError, match="not declared"):
        json_schema_regex({"type": "object", "properties": {
            "a": {"type": "integer"}}, "required": ["a", "b"]})
    # minLength alone leaves the tail unbounded (no invented max).
    t, a = compile_regex_dfa(json_schema_regex(
        {"type": "string", "minLength": 2}))
    assert _match(t, a, '"' + "x" * 5000 + '"')
    assert not _match(t, a, '"x"')
    # Property names are JSON-escaped, not just regex-escaped.
    t, a = compile_regex_dfa(json_schema_regex({
        "type": "object", "properties": {'a"b': {"type": "null"}}}))
    assert _match(t, a, '{"a\\"b": null}')
    assert not _match(t, a, '{"a"b": null}')


# ---------------------------------------------------------------------------
# Token tables / compiler registry
# ---------------------------------------------------------------------------

def test_guide_compiler_walk_and_budget():
    tok = ByteTokenizer()
    gc = GuideCompiler(tok, tok.vocab_size, eos_ids=(0,))
    g = gc.compile("json")
    assert gc.compile("json") is g  # cached
    row = g.start_row
    for tid in tok.encode('{"a": [1, true]}'):
        assert gc.allowed(row)[tid]
        row = gc.next_row(row, tid)
    assert gc.allowed(row)[0], "eos allowed once the object closes"
    term = gc.next_row(row, 0)
    assert gc.allowed(term).all(), "terminal row must not degenerate logits"
    # eos is NOT allowed mid-object.
    row = g.start_row
    for tid in tok.encode('{"a"'):
        row = gc.next_row(row, tid)
    assert not gc.allowed(row)[0]
    # Specials without byte representations never advance a guide.
    assert not gc.allowed(g.start_row)[1]  # bos

    tiny = GuideCompiler(tok, tok.vocab_size, eos_ids=(0,), max_rows=4)
    with pytest.raises(GuideError, match="row budget"):
        tiny.compile("json")


def test_multiple_guides_independent_rows():
    tok = ByteTokenizer()
    gc = GuideCompiler(tok, tok.vocab_size, eos_ids=(0,))
    g1 = gc.compile("regex", "(yes|no)")
    g2 = gc.compile("regex", "[0-9]+")
    assert g1.guide_id != g2.guide_id
    assert (g1.start_row + g1.n_states) <= g2.start_row
    row = g2.start_row
    digits = tok.encode("42")
    for tid in digits:
        assert gc.allowed(row)[tid]
        row = gc.next_row(row, tid)
    assert gc.allowed(row)[0]          # accept: eos ok
    assert gc.allowed(row)[digits[0]]  # [0-9]+ continues


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=96,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    yield eng
    eng.stop()


def _run(eng, prompt: str, guide, temperature=0.0, seed=None,
         max_tokens=48):
    req = Request(
        request_id=f"g-{guide}-{temperature}-{seed}",
        prompt_ids=ByteTokenizer().encode(prompt),
        params=SamplingParams(max_tokens=max_tokens,
                              temperature=temperature, seed=seed,
                              guide=guide))
    eng.add_request(req)
    toks, fin = [], None
    while True:
        out = req.outputs.get(timeout=60)
        toks.extend(out.token_ids)
        if out.finished:
            fin = out
            break
    return ByteTokenizer().decode(toks), fin, toks


def test_engine_regex_guide_greedy_and_sampled(engine):
    """A closed-form regex forces the full round trip: the DFA reaches its
    accept state, only eos remains legal, and the output matches the
    pattern exactly — greedy AND sampled paths."""
    pat = r'\{"k": (true|false)\}'
    text, fin, _ = _run(engine, "zz", ("regex", pat))
    assert fin.finish_reason == "stop"
    obj = json.loads(text)
    assert obj["k"] in (True, False)
    text2, fin2, _ = _run(engine, "zz", ("regex", pat), temperature=1.0,
                          seed=7)
    assert fin2.finish_reason == "stop"
    assert json.loads(text2)["k"] in (True, False)


def test_engine_json_mode_prefix_valid(engine):
    """JSON mode: every generated prefix stays inside the JSON DFA (no
    dead transition was ever sampled), greedy and sampled."""
    table, acc = compile_regex_dfa(json_mode_regex(3))
    for temp, seed in ((0.0, None), (1.0, 3)):
        text, fin, toks = _run(engine, "qq", ("json", ""), temperature=temp,
                               seed=seed, max_tokens=24)
        st = 0
        for b in text.encode():
            st = table[st, b]
            assert st >= 0, f"dead transition in {text!r}"
        if fin.finish_reason == "stop":
            assert acc[st], f"stopped outside an accept state: {text!r}"


def test_engine_total_guide_matches_unconstrained(engine):
    """A total DFA (over byte tokens) must not change greedy decoding —
    masking is identity when nothing is masked."""
    lo, hi = ByteTokenizer.OFFSET, ByteTokenizer.OFFSET + 256
    for prompt in ("parity", "zq", "ab", "hello", "x7", "mn"):
        _, _, toks_b = _run(engine, prompt, None, max_tokens=8)
        if all(lo <= t < hi for t in toks_b):
            break
    else:
        pytest.skip("tiny model's greedy outputs always leave the byte "
                    "range (vocab rows past the tokenizer are disallowed "
                    "under any guide by design)")
    _, fin_b, toks_b = _run(engine, prompt, None, max_tokens=8)
    guided, fin_g, toks_g = _run(engine, prompt, ("regex", r"(.|\n)*"),
                                 max_tokens=8)
    assert toks_g == toks_b
    assert fin_g.finish_reason == fin_b.finish_reason


def test_engine_bad_pattern_rejected_on_caller_thread(engine):
    req = Request(request_id="bad", prompt_ids=[5, 6],
                  params=SamplingParams(max_tokens=4,
                                        guide=("regex", "(unclosed")))
    with pytest.raises(GuideError):
        engine.add_request(req)


@pytest.fixture(scope="module")
def hf_tokenizer(tmp_path_factory):
    """A real byte-level-BPE HF tokenizer built locally (no hub access):
    the production tokenizer shape (Qwen2/Llama-3/GPT-2 style), with
    multi-byte merged tokens like '{\"' and 'Ġtrue'."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=400, special_tokens=["<|end|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet())
    tok.train_from_iterator(
        ['{"name": "value", "ok": true, "n": 123}',
         'hello world json {"a": [1, 2], "b": false}'] * 50, trainer)
    d = tmp_path_factory.mktemp("hftok")
    tok.save(str(d / "tokenizer.json"))
    (d / "config.json").write_text('{"model_type": "gpt2"}')
    from arks_tpu.engine.tokenizer import HFTokenizer

    hf = HFTokenizer(str(d))
    hf._tok.eos_token = "<|end|>"
    return hf


def test_token_byte_table_hf(hf_tokenizer):
    """The byte table inverts the GPT-2 byte<->unicode mapping: joining a
    real encoding's token bytes reproduces the input bytes exactly."""
    from arks_tpu.engine.guides import token_byte_table

    hf = hf_tokenizer
    vocab = len(hf._tok)
    arr, lens = token_byte_table(hf, vocab)
    for s in ['{"ok": true}', 'hello world', '{"n": 123, "b": false}']:
        ids = hf.encode(s)
        got = b"".join(bytes(arr[i, : lens[i]]) for i in ids)
        assert got == s.encode(), s
    # The special token has no byte representation.
    assert lens[hf._tok.eos_token_id if hf._tok.eos_token_id is not None
                else 0] == 0


def test_guide_walk_hf_tokenizer(hf_tokenizer):
    """Guided decoding against merged multi-byte BPE tokens: a real
    encoding of a matching document walks the token DFA to accept, and
    eos flips legal exactly there."""
    hf = hf_tokenizer
    gc = GuideCompiler(hf, len(hf._tok), eos_ids=(0,))
    gc.compile("json")
    g = gc.compile("regex", r'\{"ok": (true|false)\}')
    row = g.start_row
    for tid in hf.encode('{"ok": true}'):
        assert gc.allowed(row)[tid], (row, tid)
        row = gc.next_row(row, tid)
    assert gc.allowed(row)[0]
    # Mid-document eos is illegal.
    row = g.start_row
    for tid in hf.encode('{"ok"'):
        row = gc.next_row(row, tid)
    assert not gc.allowed(row)[0]
    # JSON mode accepts the same doc through merged tokens.
    gj = gc.lookup("json")
    row = gj.start_row
    for tid in hf.encode('{"n": 1, "b": [true, null]}'):
        assert gc.allowed(row)[tid]
        row = gc.next_row(row, tid)
    assert gc.allowed(row)[0]


def test_engine_guided_with_hf_tokenizer(hf_tokenizer):
    """Full engine round trip on the HF tokenizer: the guide must drive
    multi-byte BPE pieces to a valid document."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16), steps_per_dispatch=2)
    eng = InferenceEngine(cfg, ecfg, hf_tokenizer)
    eng.start()
    try:
        req = Request(request_id="hf1",
                      prompt_ids=hf_tokenizer.encode("hello"),
                      params=SamplingParams(
                          max_tokens=24, temperature=0.0,
                          guide=("regex", r'\{"ok": (true|false)\}')))
        eng.add_request(req)
        toks = []
        while True:
            out = req.outputs.get(timeout=120)
            toks.extend(out.token_ids)
            if out.finished:
                break
        assert out.finish_reason == "stop"
        assert json.loads(hf_tokenizer.decode(toks))["ok"] in (True, False)
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Non-blocking compile pipeline + LRU eviction
# ---------------------------------------------------------------------------

def test_concurrent_compiles_of_same_key_build_once():
    """N threads compiling one (kind, pattern) dedupe onto a single
    expensive build through the in-flight ticket."""
    tok = ByteTokenizer()
    gc = GuideCompiler(tok, tok.vocab_size, eos_ids=(0,))
    builds: list[str] = []
    orig = gc._build

    def counting_build(rx):
        builds.append(rx)
        time.sleep(0.2)  # widen the race window
        return orig(rx)

    gc._build = counting_build
    out: list = []
    threads = [threading.Thread(
        target=lambda: out.append(gc.compile("regex", "[0-9]+")))
        for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive(), "a compile of [0-9]+ did not return in 60 s"
    assert len(builds) == 1, "same-key compiles must dedupe onto one build"
    assert len(out) == 6 and all(g is out[0] for g in out)


def test_lru_eviction_pins_and_row_reuse():
    tok = ByteTokenizer()
    gc = GuideCompiler(tok, tok.vocab_size, eos_ids=(0,), max_guides=2)
    g1 = gc.compile("regex", "a+")
    g2 = gc.compile("regex", "b+")
    v0 = gc.version
    gc.acquire("regex", "b+")  # pin g2 (simulates an active slot)
    g3 = gc.compile("regex", "c+")  # budget full -> evicts g1 (LRU, unpinned)
    assert gc.lookup("regex", "a+") is None
    assert gc.lookup("regex", "b+") is g2, "pinned guide must survive"
    assert gc.version > v0, "eviction + publish must bump version"
    assert g3.guide_id == g1.guide_id, "evicted id is reused"
    assert g3.start_row == g1.start_row, "evicted row span is reused"
    # The interval index resolves rows correctly after the repack.
    row = g3.start_row
    for tid in tok.encode("cc"):
        assert gc.allowed(row)[tid]
        row = gc.next_row(row, tid)
    assert gc.allowed(row)[0]
    # Every guide pinned -> a new pattern fails with a clean GuideError...
    gc.acquire("regex", "c+")
    with pytest.raises(GuideError, match="budget"):
        gc.compile("regex", "d+")
    # ...and releasing a pin makes the same pattern compile (evicting it).
    gc.release("regex", "b+")
    g4 = gc.compile("regex", "d+")
    assert gc.lookup("regex", "b+") is None
    assert gc.lookup("regex", "d+") is g4


def test_engine_slow_compile_does_not_block_unguided_stream():
    """A cold guide compile (artificially slowed to 2.5 s) must not stall
    the scheduler: a concurrent unguided stream decodes to completion
    while the compile runs, and the guided request then completes with
    grammar-valid output."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=96,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    try:
        _run(eng, "warm", None, max_tokens=4)  # jit warmup off the clock
        orig = eng.guides._build

        def slow_build(rx):
            time.sleep(2.5)
            return orig(rx)

        eng.guides._build = slow_build
        pat = r'\{"k": (true|false)\}'
        greq = Request(request_id="slowg",
                       prompt_ids=ByteTokenizer().encode("zz"),
                       params=SamplingParams(max_tokens=48, temperature=0.0,
                                             guide=("regex", pat)))
        eng.add_request(greq)
        time.sleep(0.1)  # compile is now in flight on the worker pool
        t0 = time.monotonic()
        _, fin_u, _ = _run(eng, "ab", None, max_tokens=8)
        unguided_s = time.monotonic() - t0
        assert unguided_s < 2.0, (
            f"unguided stream took {unguided_s:.2f}s — it stalled behind "
            "the guide compile")
        toks: list[int] = []
        while True:
            out = greq.outputs.get(timeout=60)
            toks.extend(out.token_ids)
            if out.finished:
                break
        assert out.finish_reason == "stop"
        assert json.loads(ByteTokenizer().decode(toks))["k"] in (True, False)
    finally:
        eng.stop()


def _counter_total(counter) -> float:
    return sum(counter._values.values())


def test_engine_lru_eviction_end_to_end(monkeypatch):
    """ARKS_GUIDE_MAX + 4 distinct schemas served sequentially on one
    engine: LRU eviction keeps admitting (no restart, no 400), evictions
    advance the metric, and guided outputs stay grammar-valid after
    eviction-driven device-table refreshes."""
    monkeypatch.setenv("ARKS_GUIDE_MAX", "3")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=96,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng.guides.max_guides == 3
    eng.start()
    try:
        for i in range(3 + 4):
            pat = r'\{"k%d": (true|false)\}' % i
            text, fin, _ = _run(eng, "zz", ("regex", pat), max_tokens=48)
            assert fin.finish_reason == "stop", (i, fin)
            assert json.loads(text)[f"k{i}"] in (True, False)
        assert _counter_total(
            eng.metrics.guide_cache_evictions_total) >= 4
        assert eng.metrics.guide_registry_guides_in_use.get() <= 3
    finally:
        eng.stop()


def test_engine_all_guides_pinned_rejects_cleanly(monkeypatch):
    """With ARKS_GUIDE_MAX=1 and the only guide pinned by a running slot,
    a second pattern gets a per-request error (HTTP 400 at the server),
    not a dropped stream — and once the pin releases, the same pattern
    compiles via eviction."""
    monkeypatch.setenv("ARKS_GUIDE_MAX", "1")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=256,
                        prefill_buckets=(8, 16), steps_per_dispatch=4)
    tok = ByteTokenizer()
    eng = InferenceEngine(cfg, ecfg, tok)
    eng.start()
    try:
        # Long-running guided request: pins the single guide slot.
        r1 = Request(request_id="pin1", prompt_ids=tok.encode("zz"),
                     params=SamplingParams(max_tokens=180, temperature=0.0,
                                           guide=("regex", "(a|b)+")))
        eng.add_request(r1)
        out1 = r1.outputs.get(timeout=60)  # first token -> slot registered
        assert not out1.finished
        # Second pattern: compiles fine, but publish finds the budget full
        # with every guide pinned -> per-request error output.
        r2 = Request(request_id="pin2", prompt_ids=tok.encode("q"),
                     params=SamplingParams(max_tokens=8, temperature=0.0,
                                           guide=("regex", "[0-9]+")))
        eng.add_request(r2)
        while True:
            out2 = r2.outputs.get(timeout=60)
            if out2.finished:
                break
        assert out2.finish_reason == "error"
        assert "guide" in (out2.error or "")
        # Drain the pinning request; its _finish releases the pin.
        toks1 = list(out1.token_ids)
        while True:
            o = r1.outputs.get(timeout=120)
            toks1.extend(o.token_ids)
            if o.finished:
                break
        assert set(tok.decode(toks1)) <= {"a", "b"}
        # Now the same second pattern succeeds (evicts the released guide).
        text3, fin3, _ = _run(eng, "q", ("regex", "[0-9]{2}"), max_tokens=24)
        assert fin3.finish_reason == "stop"
        assert text3.isdigit() and len(text3) == 2
    finally:
        eng.stop()


@pytest.mark.slow
def test_guided_cold_vs_warm_admit_bench():
    """Micro-benchmark, CPU tier: admit-to-first-token with a cold vs
    warm guide, plus the headline assertion that scheduler progress during
    a background compile stays bounded on CPU."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=96,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    try:
        _run(eng, "warm", None, max_tokens=4)  # jit warmup

        def ttft(pat: str) -> float:
            req = Request(request_id=f"b-{pat}",
                          prompt_ids=ByteTokenizer().encode("zz"),
                          params=SamplingParams(max_tokens=8,
                                                temperature=0.0,
                                                guide=("regex", pat)))
            t0 = time.monotonic()
            eng.add_request(req)
            first = req.outputs.get(timeout=120)
            dt = time.monotonic() - t0
            while not first.finished:
                first = req.outputs.get(timeout=120)
            return dt

        cold = ttft(r'\{"bench": [0-9]\}')
        warm = ttft(r'\{"bench": [0-9]\}')
        assert cold > 0 and warm > 0
        # Scheduler responsiveness during a background compile: an
        # unguided request admitted mid-compile must reach its first
        # token well before the compile finishes (loose CPU bound).
        orig = eng.guides._build

        def slow_build(rx):
            time.sleep(2.0)
            return orig(rx)

        eng.guides._build = slow_build
        greq = Request(request_id="b-bg",
                       prompt_ids=ByteTokenizer().encode("zz"),
                       params=SamplingParams(max_tokens=8, temperature=0.0,
                                             guide=("regex", "[a-f]+")))
        eng.add_request(greq)
        time.sleep(0.05)
        ureq = Request(request_id="b-un",
                       prompt_ids=ByteTokenizer().encode("ab"),
                       params=SamplingParams(max_tokens=4, temperature=0.0))
        t0 = time.monotonic()
        eng.add_request(ureq)
        out = ureq.outputs.get(timeout=60)
        step_bound = time.monotonic() - t0
        while not out.finished:
            out = ureq.outputs.get(timeout=60)
        while True:
            o = greq.outputs.get(timeout=60)
            if o.finished:
                break
        assert step_bound < 1.5, (
            f"admit-to-first-token {step_bound:.2f}s during a background "
            "compile — the scheduler blocked on compilation")
        print(f"guided admit-to-first-token: cold={cold:.3f}s "
              f"warm={warm:.3f}s mid-compile-unguided={step_bound:.3f}s")
    finally:
        eng.stop()


def test_engine_guide_with_chunked_prefill():
    """Guided first-token sampling on the chunked-prefill path: the prompt
    exceeds the one-shot buckets, so the first token comes from
    _sample_one with the guide columns, and the DFA row is host-advanced
    into the slot registration."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8,), prefill_chunk=8,
                        steps_per_dispatch=2)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    try:
        pat = r'\{"n": [0-9]\}'
        text, fin, _ = _run(eng, "x" * 20, ("regex", pat), max_tokens=24)
        assert fin.finish_reason == "stop"
        assert json.loads(text)["n"] in range(10)
    finally:
        eng.stop()
