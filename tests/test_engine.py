"""Engine-level tests: continuous batching, stop handling, streaming."""

import queue

import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    yield eng


_collect = harness.collect


_drive = harness.drive


def test_single_request_greedy(engine):
    req = Request("r1", [5, 6, 7], SamplingParams(max_tokens=8, temperature=0.0,
                                                  ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    ids, fin = _collect(req)
    assert len(ids) == 8
    assert fin.finish_reason == "length"
    assert fin.num_prompt_tokens == 3

    # Determinism: same request again gives the same tokens.
    req2 = Request("r2", [5, 6, 7], SamplingParams(max_tokens=8, temperature=0.0,
                                                   ignore_eos=True))
    engine.add_request(req2)
    _drive(engine)
    ids2, _ = _collect(req2)
    assert ids2 == ids


def test_more_requests_than_slots(engine):
    reqs = [Request(f"m{i}", [10 + i, 20], SamplingParams(max_tokens=5, temperature=0.0,
                                                          ignore_eos=True))
            for i in range(5)]
    for r in reqs:
        engine.add_request(r)
    _drive(engine, 400)
    for r in reqs:
        ids, fin = _collect(r)
        assert fin.finished and len(ids) == 5


def test_stop_token(engine):
    # Force a stop token that greedy decoding actually produces: run once to
    # learn the first generated token, then use it as the stop token.
    probe = Request("p", [9, 9], SamplingParams(max_tokens=3, temperature=0.0,
                                                ignore_eos=True))
    engine.add_request(probe)
    _drive(engine)
    probe_ids, _ = _collect(probe)

    stop = probe_ids[1]
    req = Request("s", [9, 9], SamplingParams(max_tokens=10, temperature=0.0,
                                              stop_token_ids=(stop,), ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    ids, fin = _collect(req)
    assert fin.finish_reason == "stop"
    assert stop not in ids
    assert ids == probe_ids[:1]


def test_sampled_request_valid(engine):
    req = Request("t", [1, 2, 3], SamplingParams(max_tokens=6, temperature=0.8,
                                                 top_p=0.9, top_k=40, seed=42,
                                                 ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    ids, fin = _collect(req)
    assert len(ids) == 6
    assert all(0 <= t < get_config("tiny").vocab_size for t in ids)

    # Same seed → same sample path.
    req2 = Request("t2", [1, 2, 3], SamplingParams(max_tokens=6, temperature=0.8,
                                                   top_p=0.9, top_k=40, seed=42,
                                                   ignore_eos=True))
    engine.add_request(req2)
    _drive(engine)
    ids2, _ = _collect(req2)
    assert ids2 == ids


def test_seeded_sampling_independent_of_scheduler_timing(monkeypatch):
    """A seeded sampled request produces the same tokens whether it is
    admitted alone or while another request is mid-decode with its
    admission resolution DELAYED.  With deferred resolution, decode
    dispatches land between a slot's admit program (which seeds its PRNG
    key) and its registration — the fused loop's active mask must freeze
    pending/free slots' keys or the stream would depend on scheduler
    timing.  (CPU resolves admissions near-instantly, so the deferral
    window is forced by holding back the drain for a few steps — the
    shape a device that answers slowly produces naturally.)"""
    from arks_tpu.engine.engine import InferenceEngine as IE
    cfg = get_config("tiny")

    orig_drain = IE._drain_ready_admits

    def run(with_load, delay_steps):
        calls = {"n": 0}

        def delayed(self, force_one=False):
            # Pretend the admit program is still in flight for a few
            # scheduler steps; decode dispatches keep flowing meanwhile.
            calls["n"] += 1
            if calls["n"] <= delay_steps and self._slots:
                return False
            return orig_drain(self, force_one=force_one)

        monkeypatch.setattr(IE, "_drain_ready_admits", delayed)
        ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                            prefill_buckets=(8, 16, 32),
                            steps_per_dispatch=4)
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        eng.start()
        try:
            if with_load:
                # A long-running greedy request keeps decode dispatches
                # flowing while the sampled request's admission pends.
                load = Request("load", [9, 9, 9], SamplingParams(
                    max_tokens=40, temperature=0.0, ignore_eos=True))
                eng.add_request(load)
                load.outputs.get(timeout=60)  # wait until it is decoding
                calls["n"] = 0  # arm the delay for the sampled admission
            req = Request("s", [1, 2, 3], SamplingParams(
                max_tokens=6, temperature=0.8, top_p=0.9, top_k=40,
                seed=42, ignore_eos=True))
            eng.add_request(req)
            ids, _ = _collect(req)
            if with_load:
                _collect(load)
            return ids
        finally:
            eng.stop()

    assert run(True, delay_steps=6) == run(False, delay_steps=0)


def test_long_prompt_chunked_prefill(engine):
    # 57 tokens exceeds the largest one-shot bucket (32) but fits the cache
    # (64 - 4 - 1 = 59 usable): served via chunked prefill.
    req = Request("lp", list(range(3, 60)), SamplingParams(max_tokens=3, temperature=0.0,
                                                           ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    ids, fin = _collect(req)
    assert fin.finished and len(ids) == 3
    assert fin.num_prompt_tokens == 57


def test_oversize_prompt_rejected_not_truncated(engine):
    # 100 tokens exceeds the usable window: the request is REJECTED with a
    # machine-readable error (silent truncation would corrupt long-context
    # results and billing) — OpenAI servers surface this as HTTP 400.
    req = Request("lp2", list(range(3, 103)), SamplingParams(max_tokens=3, temperature=0.0,
                                                             ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    ids, fin = _collect(req)
    assert fin.finished and not ids
    assert fin.finish_reason == "error"
    assert fin.error == "context_length_exceeded"
    assert fin.num_prompt_tokens == 100


def test_chunked_prefill_matches_one_shot():
    """A prompt served via chunked prefill must produce the same greedy
    tokens as the same prompt through one-shot prefill (same math,
    blockwise — only fp reassociation differs)."""
    cfg = get_config("tiny")
    prompt = [int(x) % cfg.vocab_size for x in range(7, 55)]  # 48 tokens

    def run(prefill_buckets, prefill_chunk):
        ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                            prefill_buckets=prefill_buckets,
                            steps_per_dispatch=4, prefill_chunk=prefill_chunk)
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        req = Request("c", prompt, SamplingParams(max_tokens=6, temperature=0.0,
                                                  ignore_eos=True, seed=7))
        eng.add_request(req)
        _drive(eng)
        ids, fin = _collect(req)
        return ids, fin

    # One-shot: bucket 64 covers the prompt.  Chunked: largest bucket is 16,
    # so the 48-token prompt runs as 16-token chunks.
    ids_one, fin_one = run((16, 32, 64), None)
    ids_chunk, fin_chunk = run((8, 16), 16)
    assert fin_chunk.num_prompt_tokens == fin_one.num_prompt_tokens == 48
    assert ids_chunk == ids_one


def test_decode_flows_during_chunked_prefill():
    """Decode slots must keep producing tokens while a long prompt is being
    chunk-prefilled — the whole point of chunking (one chunk per scheduler
    step, decode dispatch in the same step)."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8,), steps_per_dispatch=1,
                        prefill_chunk=8)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())

    # Short request occupies a decode slot first.
    short = Request("s", [5, 6], SamplingParams(max_tokens=40, temperature=0.0,
                                                ignore_eos=True))
    eng.add_request(short)
    eng.step(block_s=0.01)  # admits + first decode
    # Long prompt: 48 tokens = 6 chunks of 8.
    long_req = Request("l", [int(x) % cfg.vocab_size for x in range(3, 51)],
                       SamplingParams(max_tokens=2, temperature=0.0,
                                      ignore_eos=True))
    eng.add_request(long_req)

    # Step until the long prompt's first token appears; the short request
    # must have produced tokens in the SAME window (interleaved).
    short_tokens_during = 0
    for _ in range(200):
        eng.step(block_s=0.01)
        if eng._prefilling:
            # Chunked prefill still in progress — decode output must flow.
            while not short.outputs.empty():
                short_tokens_during += len(short.outputs.get().token_ids)
        if long_req.outputs.qsize() > 0:
            break
    assert short_tokens_during > 0, "decode stalled during chunked prefill"
    _drive(eng)
    ids, fin = _collect(long_req)
    assert fin.finished and fin.num_prompt_tokens == 48


def test_metrics_populated(engine):
    text = engine.metrics.registry.render()
    assert "prompt_tokens_total" in text
    assert "generation_tokens_total" in text
    assert "time_to_first_token_seconds_bucket" in text


def test_resolved_config_surfaced(engine):
    """The RESOLVED engine configuration (auto decisions included) rides
    /metrics as an _info gauge and the engine object, so the benchmark's
    expect_labels and dashboards can tell which path produced a number."""
    rc = engine.resolved_config
    assert rc["kv_layout"] in ("paged", "slot")
    assert rc["decode_impl"] in ("pallas", "xla")
    assert rc["pad_head"] in ("true", "false")
    assert rc["overlap"] in ("true", "false")
    text = engine.metrics.registry.render()
    assert "engine_config_info{" in text
    assert f'kv_layout="{rc["kv_layout"]}"' in text
    assert f'decode_impl="{rc["decode_impl"]}"' in text
    # The pure device wait rides every decode resolve (the
    # overlap-mode-trustworthy signal; the step clock's ``wait`` leg since
    # PR 38).  Drive one
    # tiny request HERE so a sample line exists even when this test runs
    # alone, then assert a non-comment line (comment lines start '# ').
    # (A leg is written when its cycle closes, at the NEXT dispatch: a
    # request of several dispatches.)
    req = Request("rc-cfg", [5, 6, 7], SamplingParams(
        max_tokens=13, temperature=0.0, ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    text = engine.metrics.registry.render()
    # Split by the kind of dispatch waited for: whichever path this
    # engine resolved to, its leg proves the clock rides the resolves.
    waits = [ln for ln in text.splitlines()
             if ln.startswith("step_leg_seconds_total{")
             and 'leg="wait"' in ln]
    assert waits and all(float(ln.rpartition(" ")[2]) > 0 for ln in waits)
    assert "decode_resolve_wait_seconds_total" not in text
    assert f'pipeline_depth="{rc["pipeline_depth"]}"' in text


def _sampled_streams(eng):
    """Seeded sampling requests with logprobs through ``eng``: their tokens
    and top logprobs."""
    reqs = [Request(f"w{i}", [7 + i, 9, 11], SamplingParams(
        max_tokens=6, temperature=0.9, top_k=k, top_p=p, seed=100 + i,
        logprobs=3, ignore_eos=True))
        for i, (k, p) in enumerate([(0, 0.9), (5, 1.0), (64, 0.9)])]
    got = []
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    for r in reqs:
        ids, lps = [], []
        while True:
            out = r.outputs.get(timeout=60)
            ids.extend(out.token_ids)
            lps.extend(out.logprobs or [])
            if out.finished:
                break
        got.append((ids, repr(lps)))
    return got


def test_sampler_window_form_surfaced(engine, monkeypatch):
    """Which form the sampler's window search took is a build fact on
    /metrics (0: the one top_k call, every test model), and an engine whose
    step takes the two-stage form serves the same streams."""
    from arks_tpu.engine import sampler as sm
    assert engine.metrics.sampler_window_blocks.get() == 0
    assert "sampler_window_blocks 0" in engine.metrics.registry.render()

    def build():
        return InferenceEngine(get_config("tiny"), EngineConfig(
            model="tiny", num_slots=2, max_cache_len=64,
            prefill_buckets=(8, 16, 32)), ByteTokenizer())

    want = _sampled_streams(build())
    # The rule's constants at a test model's scale: 512 columns in 256
    # blocks of 2, whatever the lanes.
    monkeypatch.setattr(sm, "WINDOW_BLOCK", 2)
    monkeypatch.setattr(sm, "_MIN_VALUES", 1)
    two = build()
    assert two.metrics.sampler_window_blocks.get() == 256
    assert _sampled_streams(two) == want


def test_cache_len_alignment_rounds_up_for_pallas(monkeypatch):
    """A misaligned --max-model-len must self-correct at startup, not raise
    deep inside the first decode dispatch (kernel DMA tiling constraints)."""
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    ecfg = EngineConfig(model="tiny", max_cache_len=1000, kv_cache_dtype="int8")
    ecfg.align_cache_len()
    assert ecfg.max_cache_len == 1024  # multiple of 256 covers all kernels
    ecfg2 = EngineConfig(model="tiny", max_cache_len=100, kv_cache_dtype="int8")
    ecfg2.align_cache_len()
    assert ecfg2.max_cache_len == 128  # int8 scale tile below 256
    ecfg3 = EngineConfig(model="tiny", max_cache_len=50, kv_cache_dtype="bf16")
    ecfg3.align_cache_len()
    assert ecfg3.max_cache_len == 64  # bf16 update tile


def test_cache_len_untouched_on_xla_path(monkeypatch):
    monkeypatch.setenv("ARKS_ATTN_IMPL", "xla")
    ecfg = EngineConfig(model="tiny", max_cache_len=1000, kv_cache_dtype="int8")
    ecfg.align_cache_len()
    assert ecfg.max_cache_len == 1000


def test_mesh_plan_validation_raises_value_error():
    from arks_tpu.parallel.mesh import resolve_plan
    with pytest.raises(ValueError):
        resolve_plan(8, tensor_parallel=3)
    with pytest.raises(ValueError):
        resolve_plan(8, context_parallel=3)
    with pytest.raises(ValueError):
        resolve_plan(8, data_parallel=3)


def test_frequency_penalty_reduces_repetition():
    """End to end through the engine: the tiny greedy model repeats itself;
    a frequency penalty must strictly reduce the max token repeat count,
    deterministically."""
    from collections import Counter

    def run(presence, frequency):
        cfg = get_config("tiny")
        ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=128,
                            prefill_buckets=(16, 32), steps_per_dispatch=4,
                            prefix_cache_mb=0)
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        req = Request("p", [5, 6, 7], SamplingParams(
            max_tokens=60, temperature=0.0, ignore_eos=True,
            presence_penalty=presence, frequency_penalty=frequency))
        eng.add_request(req)
        _drive(eng, n_steps=400)
        ids, _ = _collect(req)
        return ids

    plain = run(0.0, 0.0)
    penalized = run(0.5, 1.5)
    top_plain = Counter(plain).most_common(1)[0][1]
    top_pen = Counter(penalized).most_common(1)[0][1]
    assert top_pen < top_plain, (top_plain, top_pen)
    assert len(set(penalized)) > len(set(plain))
    # Deterministic (greedy + penalties is still deterministic).
    assert run(0.5, 1.5) == penalized


def test_overlap_decode_matches_sequential(monkeypatch):
    """ARKS_OVERLAP_DECODE=1 (the TPU default: decode issued async,
    admissions overlap the in-flight dispatch) must produce byte-identical
    outputs to the sequential order, including slot churn and prefix
    sharing."""
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.engine.types import Request, SamplingParams
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    prompts = [[3] * 20, [3] * 20, [5, 6, 7], [9] * 33, [4, 8]]

    def run(overlap):
        monkeypatch.setenv("ARKS_OVERLAP_DECODE", overlap)
        ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                            prefill_buckets=(8, 16, 32),
                            steps_per_dispatch=4, prefill_chunk=16,
                            kv_layout="paged")
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        assert eng._overlap == (overlap == "1")
        eng.start()
        outs = []
        try:
            reqs = [Request(request_id=f"o{i}", prompt_ids=list(p),
                            params=SamplingParams(max_tokens=6,
                                                  temperature=0.0,
                                                  ignore_eos=True))
                    for i, p in enumerate(prompts)]
            for r in reqs:  # burst: more requests than slots -> churn
                eng.add_request(r)
            for r in reqs:
                toks = []
                while True:
                    o = r.outputs.get(timeout=120)
                    toks.extend(o.token_ids)
                    if o.finished:
                        break
                outs.append(toks)
        finally:
            eng.stop()
        return outs

    assert run("1") == run("0")


def test_admit_batch_sizes_env_override(monkeypatch):
    """ARKS_ADMIT_BATCH_SIZES tunes the fused-admission fill sizes without
    a code change (the serving sweep's knob): parsed, normalized
    descending, floor of 1 enforced, surfaced in resolved config, and the
    engine still serves correctly with a deeper ladder."""
    monkeypatch.setenv("ARKS_ADMIT_BATCH_SIZES", "2,16,4")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=4, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng._admit_sizes == (16, 4, 2, 1)
    assert eng.resolved_config["admit_batch_sizes"] == "16,4,2,1"
    reqs = [Request(f"ab{i}", [3 + i, 9, 11], SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True)) for i in range(3)]
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    for r in reqs:
        ids, fin = _collect(r)
        assert len(ids) == 4 and fin.finished


def test_priority_admission_order():
    """Waiting requests admit in priority order (lower value first, FIFO
    within a priority); running slots are never preempted.  Driven
    manually (no engine thread) so all three contenders are queued before
    any admission step — the ordering is then purely the queue's."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=1, max_cache_len=64,
                        prefill_buckets=(8,), steps_per_dispatch=2)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    hold = Request("hold", [3, 4], SamplingParams(
        max_tokens=30, temperature=0.0, ignore_eos=True))
    eng.add_request(hold)
    for _ in range(50):
        eng.step(block_s=0.01)
        if eng.num_running == 1 and eng._queue.empty():
            break
    assert eng.num_running == 1  # hold occupies the single slot

    def submit(rid, prio):
        r = Request(rid, [5, 6], SamplingParams(
            max_tokens=2, temperature=0.0, ignore_eos=True, priority=prio))
        eng.add_request(r)
        return r

    low1 = submit("low-1", 5)
    low2 = submit("low-2", 5)
    high = submit("high", -1)
    reqs = {"low-1": low1, "low-2": low2, "high": high}
    order, pending = [], set(reqs)
    for _ in range(600):
        eng.step(block_s=0.01)
        for name in list(pending):
            try:
                out = reqs[name].outputs.get_nowait()
            except queue.Empty:
                continue
            if out.finished:
                order.append(name)
                pending.discard(name)
        if not pending:
            break
    # One slot: completions happen in admission order.
    assert order == ["high", "low-1", "low-2"]


def test_abort_after_finish_does_not_linger(engine):
    """An abort that loses the race with _finish (or targets a request id
    that never existed) must not sit in the abort set forever — the idle
    scheduler purges it (regression: the purge used to run only while
    slots existed, so an idle engine leaked every late abort)."""
    req = Request("late-abort", [5, 6], SamplingParams(
        max_tokens=2, temperature=0.0, ignore_eos=True))
    engine.add_request(req)
    _drive(engine)
    _, out = _collect(req)
    assert out.finish_reason == "length"
    engine.abort("late-abort")      # after _finish: nothing to abort
    engine.abort("never-existed")   # garbage id
    for _ in range(5):
        engine.step(block_s=0.01)   # idle steps run the purge
    with engine._abort_lock:
        assert not engine._aborted, "stale abort ids leaked"
