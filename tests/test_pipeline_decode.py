"""Pipelined decode (ARKS_PIPELINE_DEPTH): token-exact parity vs the
sequential issue/resolve path at depths 1-3, mid-stream aborts, stop-token
overshoot truncation, slot-reuse-after-overshoot KV correctness, multihost
follower replay of the pipelined op stream, and the emit-stream depth
bound."""

import functools
import contextlib

import numpy as np
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness

# This file's engine: ``tiny`` on two slots of 64 tokens, the legacy slot
# scheduler unless a test says otherwise.
BASE = dict(num_slots=2, max_cache_len=64, prefill_buckets=(8, 16, 32),
            steps_per_dispatch=4)


class RecordingDispatcher:
    def __init__(self):
        self.ops = []

    def broadcast(self, op, payload):
        self.ops.append((op, payload))


def _mk_engine(monkeypatch, depth, mixed="0", **kw):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", mixed)
    eng = harness.warmed("tiny", base=BASE, **kw)
    return eng.cfg, eng


@contextlib.contextmanager
def _served_by(monkeypatch, depth, mixed="0", **kw):
    """``_mk_engine``'s engine, stopped behind the test."""
    eng = _mk_engine(monkeypatch, depth, mixed, **kw)[1]
    try:
        yield eng
    finally:
        eng.stop()


def _engaged(eng) -> int:
    """Pipelined dispatches the engine has observed."""
    return sum(n for _, _, n in
               eng.metrics.pipeline_depth_occupancy._data.values())


_collect = functools.partial(harness.collect, logprobs=True)


_drive = harness.drive


def _run_workload(eng, depth):
    """Greedy + fixed-seed sampled + logprob requests with slot churn
    (more requests than slots); returns each request's full output."""
    assert eng._pipe_depth == (depth if depth >= 0 else 0)
    prompts = [[5, 6, 7], list(range(3, 23)), [9] * 5, [4] * 12, [8, 3]]
    reqs = []
    for i, p in enumerate(prompts):
        sp = SamplingParams(max_tokens=9,
                            temperature=0.0 if i % 2 == 0 else 0.8,
                            top_p=0.9, top_k=40, seed=7 + i, ignore_eos=True,
                            logprobs=2 if i == 2 else None)
        reqs.append(Request(f"r{i}", [int(x) % eng.cfg.vocab_size for x in p],
                            sp))
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r) for r in reqs]


@pytest.mark.parametrize("mixed,kw", [
    ("0", {}),
    ("auto", dict(prefill_chunk=16, kv_layout="paged")),
])
def test_pipeline_token_parity_depths(monkeypatch, mixed, kw):
    """Depths 1/2/3 must produce BYTE-IDENTICAL streams (tokens, logprobs,
    finish reasons) to the sequential path (depth 0), on both the legacy
    slot engine and the mixed paged engine."""
    with _served_by(monkeypatch, 0, mixed, **kw) as eng:
        base = _run_workload(eng, 0)
    for depth in (1, 2, 3):
        with _served_by(monkeypatch, depth, mixed, **kw) as eng:
            got = _run_workload(eng, depth)
            assert got == base, f"depth {depth} diverged from sequential"
            # The pipelined path actually ran (occupancy histogram observed).
            assert _engaged(eng), "pipelined path never engaged"


_TRAFFIC = {
    "plain": dict(max_tokens=12, temperature=0.8, top_p=0.9, top_k=40,
                  seed=7, ignore_eos=True),
    "guided": dict(max_tokens=8, temperature=0.0, guide=("json", "")),
    "logprob": dict(max_tokens=12, temperature=0.0, ignore_eos=True,
                    logprobs=2),
}


@pytest.mark.parametrize("traffic", sorted(_TRAFFIC))
def test_depth0_steady_decode_is_the_mixed_dispatch(monkeypatch, traffic):
    """A depth-0 mixed engine has ONE decode path: every model dispatch of
    a run, steady state included, is the sequential ``mixed`` op (no pipe
    program is warmed or issued), one a step, and the streams (ids,
    logprob floats, finish reasons) are those of a depth-2 engine, whose
    steady state rides ``decode_pipe``."""
    def run(depth):
        cfg, eng = _mk_engine(monkeypatch, depth, "1", prefill_chunk=16,
                              kv_layout="paged", prefix_cache_mb=0)
        eng.dispatcher = RecordingDispatcher()
        reqs = [Request(f"r{i}", [int(x) % cfg.vocab_size for x in p],
                        SamplingParams(**_TRAFFIC[traffic]))
                for i, p in enumerate([[5, 6, 7], list(range(3, 40)),
                                       [9] * 20])]
        for r in reqs:
            eng.add_request(r)
        for _ in range(4000):       # .idle: a guide's compile parks all three
            eng.step(block_s=0.01)
            if eng.idle:
                break
        outs = [(ids, lps, fin.finish_reason)
                for ids, lps, fin in map(_collect, reqs)]
        ops = [op for op, _ in eng.dispatcher.ops]
        return outs, ops, eng

    outs0, ops0, eng0 = run(0)
    assert eng0._pipe_warm_state is None and not eng0._pipe_exec
    assert "decode_pipe" not in ops0
    model_ops = [op for op in ops0 if op in ("mixed", "decode", "chunk",
                                             "chunk_paged", "admit_batch")]
    assert model_ops and set(model_ops) == {"mixed"}
    # One token a stream a dispatch: at least as many dispatches as the
    # longest stream has tokens.
    assert len(model_ops) >= max(len(ids) for ids, _, _ in outs0)
    assert not eng0.metrics.pipeline_depth_occupancy._data

    outs2, ops2, _ = run(2)
    assert "decode_pipe" in ops2, "the depth-2 engine never pipelined"
    assert outs0 == outs2


def test_pipeline_one_dispatch_per_iteration_and_depth_bound(monkeypatch):
    """Emit-stream contract: in steady state exactly ONE model dispatch is
    issued per scheduler iteration, and the advertised occupancy never
    exceeds ARKS_PIPELINE_DEPTH."""
    depth = 2
    cfg, eng = _mk_engine(monkeypatch, depth)
    eng.dispatcher = RecordingDispatcher()
    r = Request("p0", [5, 6, 7], SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True))
    eng.add_request(r)
    per_step = []
    for _ in range(400):
        before = sum(1 for op, _ in eng.dispatcher.ops if op == "decode_pipe")
        eng.step(block_s=0.01)
        after = sum(1 for op, _ in eng.dispatcher.ops if op == "decode_pipe")
        per_step.append(after - before)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None):
            break
    _collect(r)
    pipe_ops = [p for op, p in eng.dispatcher.ops if op == "decode_pipe"]
    assert pipe_ops, "no pipelined dispatches on the emit stream"
    assert max(per_step) == 1, "more than one pipelined dispatch per step"
    occs = [p["occupancy"] for p in pipe_ops]
    assert max(occs) <= depth, occs
    assert depth in occs, "pipeline never filled to the configured depth"
    # Exactly the first dispatch of the run carries fresh host state.
    assert pipe_ops[0]["fresh"] is True
    assert all(not p["fresh"] for p in pipe_ops[1:])


def test_pipeline_midstream_abort(monkeypatch):
    """An abort raised while dispatches are in flight drains the pipeline
    and frees the slot; the engine keeps serving afterwards."""
    with _served_by(monkeypatch, 2) as eng:
        victim = Request("v", [5, 6, 7], SamplingParams(
            max_tokens=10_000, temperature=0.0, ignore_eos=True))
        eng.add_request(victim)
        for _ in range(50):
            eng.step(block_s=0.01)
            if eng._pipe_inflight:
                break
        assert eng._pipe_inflight, "pipeline never engaged"
        eng.abort("v")
        _drive(eng)
        ids, _, fin = _collect(victim)
        assert fin.finish_reason == "abort"
        assert not eng._pipe_inflight and eng._pipe_state is None
        # Slot is reusable: a fresh request completes normally.
        nxt = Request("n", [9, 9], SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True))
        eng.add_request(nxt)
        _drive(eng)
        ids2, _, fin2 = _collect(nxt)
        assert fin2.finish_reason == "length" and len(ids2) == 4


def _serve(eng, request):
    eng.add_request(request)
    _drive(eng)
    return _collect(request)


def _greedy_probe(monkeypatch, prompt, n):
    with _served_by(monkeypatch, 0) as eng:
        return _serve(eng, Request("probe", prompt, SamplingParams(
            max_tokens=n, temperature=0.0, ignore_eos=True)))[0]


def test_pipeline_stop_overshoot_truncation(monkeypatch):
    """A stop token landing mid-dispatch with further dispatches in flight:
    the stream truncates at the stop exactly like the sequential path, and
    the <= depth*K overshoot tokens are discarded."""
    probe = _greedy_probe(monkeypatch, [5, 6, 7], 16)
    stop = probe[9]  # lands mid-dispatch (K=4) with the pipeline deep

    def run(depth):
        with _served_by(monkeypatch, depth) as eng:
            return _serve(eng, Request("s", [5, 6, 7], SamplingParams(
                max_tokens=64, temperature=0.0, ignore_eos=True,
                stop_token_ids=(int(stop),))))

    base = run(0)
    for depth in (2, 3):
        assert run(depth) == base
    ids, _, fin = base
    assert fin.finish_reason == "stop"
    assert int(stop) not in ids  # stop token itself excluded from output


def test_pipeline_slot_reuse_after_overshoot(monkeypatch):
    """After a request dies mid-run (overshoot KV rows written past its
    stop in its pages/rows), the SAME slot must serve the next request
    with correct attention — the reclaimed rows are garbage until real
    prefill/decode overwrites them.  num_slots=1 forces reuse; paged
    layout exercises page reclaim."""
    probe = _greedy_probe(monkeypatch, [5, 6, 7], 12)
    stop = probe[5]

    def run(depth, reuse_first):
        _, eng = _mk_engine(monkeypatch, depth, mixed="auto", num_slots=1,
                            prefill_chunk=16, kv_layout="paged")
        outs = []
        if reuse_first:
            a = Request("a", [5, 6, 7], SamplingParams(
                max_tokens=64, temperature=0.0, ignore_eos=True,
                stop_token_ids=(int(stop),)))
            eng.add_request(a)
            _drive(eng)
            outs.append(_collect(a))
        b = Request("b", list(range(3, 21)), SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True))
        eng.add_request(b)
        _drive(eng)
        outs.append(_collect(b))
        return outs

    # b's stream through a reused slot (garbage overshoot rows reclaimed)
    # must equal b's stream on a fresh engine, at every depth.
    fresh = run(2, reuse_first=False)[-1]
    for depth in (0, 1, 2, 3):
        got = run(depth, reuse_first=True)
        assert got[-1] == fresh, f"slot reuse corrupted stream at depth {depth}"
        assert got[0][2].finish_reason == "stop"


def test_pipeline_follower_replay(monkeypatch):
    """A follower fed the leader's recorded op stream replays the
    pipelined dispatches from its OWN threaded device state (no host token
    values on the wire) and converges to the leader's exact device state."""
    from arks_tpu.engine.multihost import DispatchFollower

    cfg, leader = _mk_engine(monkeypatch, 2, mixed="auto",
                             prefill_chunk=16, kv_layout="paged")
    leader.dispatcher = RecordingDispatcher()
    reqs = []
    for i, p in enumerate([[5, 6, 7], list(range(3, 23)), [9] * 5]):
        sp = SamplingParams(max_tokens=6,
                            temperature=0.0 if i % 2 == 0 else 0.8,
                            seed=11 + i, ignore_eos=True)
        reqs.append(Request(f"f{i}", p, sp))
        leader.add_request(reqs[-1])
    _drive(leader)
    for r in reqs:
        _collect(r)
    ops = leader.dispatcher.ops
    pipe_ops = [p for op, p in ops if op == "decode_pipe"]
    assert pipe_ops, "no pipelined ops on the channel"
    # Pipelined ops carry NO token values except the run-opening fresh one.
    assert all(("tokens" in p) == bool(p["fresh"]) for p in pipe_ops)

    import jax
    import jax.numpy as jnp

    _, feng = _mk_engine(monkeypatch, 2, mixed="auto",
                         prefill_chunk=16, kv_layout="paged")
    follower = DispatchFollower.__new__(DispatchFollower)
    follower.engine = feng
    follower._jax = jax
    follower._pipe_state = None
    follower._pipe_cols = None
    for op, payload in ops:
        follower._apply(feng, jax, jnp, op, payload)
    # Lockstep invariant: identical op replay -> identical device state.
    np.testing.assert_array_equal(np.asarray(leader._cache.k),
                                  np.asarray(feng._cache.k))
    np.testing.assert_array_equal(np.asarray(leader._sampling.key),
                                  np.asarray(feng._sampling.key))


def test_pipeline_survives_parked_guide_compile(monkeypatch):
    """A request parked on a slow guide compile is pure host bookkeeping:
    it must NOT drain the pipeline (live decoding would degrade to the
    sequential path for the whole compile window); once the guide
    publishes, the request admits and its output obeys the grammar."""
    import threading
    import time as _time

    cfg, eng = _mk_engine(monkeypatch, 2, mixed="auto",
                          prefill_chunk=16, kv_layout="paged",
                          max_cache_len=96)
    eng.dispatcher = RecordingDispatcher()
    load = Request("load", [5, 6, 7], SamplingParams(
        max_tokens=400, temperature=0.0, ignore_eos=True))
    eng.add_request(load)

    def pipe_ops():
        return sum(1 for op, _ in eng.dispatcher.ops if op == "decode_pipe")

    for _ in range(100):
        eng.step(block_s=0.01)
        if pipe_ops():
            break
    assert pipe_ops(), "pipeline never engaged"

    release = threading.Event()
    orig = eng.guides._build

    def gated_build(rx):
        release.wait(30)
        return orig(rx)

    eng.guides._build = gated_build
    # A BOUNDED grammar: under ``ab+a`` a greedy tiny model may prefer
    # ``b`` for all 24 tokens (which it does depends on where the request
    # lands) and finish "length"; here the third ``b`` leaves only ``a``.
    greq = Request("g", [9, 9], SamplingParams(
        max_tokens=24, temperature=0.0, guide=("regex", r"ab{1,3}a")))
    eng.add_request(greq)
    deadline = _time.monotonic() + 2.0
    while _time.monotonic() < deadline and not eng._awaiting_guide:
        eng.step(block_s=0.01)
    assert eng._awaiting_guide, "guided request never parked"
    # Parked compile in flight: every iteration keeps issuing pipelined
    # dispatches (no degradation to the sequential path).
    before = pipe_ops()
    for _ in range(10):
        eng.step(block_s=0.01)
    assert eng._awaiting_guide, "guide published before the gate opened"
    assert pipe_ops() - before >= 10, \
        "parked guide compile knocked decoding off the pipelined path"
    release.set()
    _drive(eng, n_steps=2000)
    ids, _, fin = _collect(greq)
    assert fin.finish_reason == "stop"
    import re
    assert re.fullmatch(r"ab{1,3}a", ByteTokenizer().decode(ids))
    _, _, lfin = _collect(load)
    assert lfin.finish_reason == "length"


def test_pipeline_enabled_for_spec_engines(monkeypatch):
    """Speculative engines PIPELINE (the spec_pipe program threads
    accepted-length/last-token state on device): the env depth sticks and
    the per-slot write margin is the draft_len verify block.
    Byte-identity across depths is asserted in
    tests/test_spec_decode.py::test_pipeline_depth_parity."""
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    eng = harness.engine("tiny", base=BASE, prefill_chunk=16,
                         kv_layout="paged", draft_model="tiny", draft_len=3)
    assert eng._pipe_depth == 2
    assert eng.resolved_config["pipeline_depth"] == "2"
    assert eng._pipe_rows == 3


def test_pipeline_env_validation(monkeypatch):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "bogus")
    cfg = get_config("tiny")
    with pytest.raises(ValueError, match="ARKS_PIPELINE_DEPTH"):
        InferenceEngine(cfg, EngineConfig(model="tiny", num_slots=2,
                                          max_cache_len=64,
                                          prefill_buckets=(8, 16, 32)),
                        ByteTokenizer())


def test_pipeline_oversized_stop_set_falls_back(monkeypatch):
    """A request whose stop set exceeds the device column keeps the engine
    on the sequential path (stream still correct, never truncated)."""
    from arks_tpu.engine import sampler as sampler_mod

    big_stops = tuple(range(100, 100 + sampler_mod.STOP_IDS_MAX + 4))

    def run(depth):
        with _served_by(monkeypatch, depth) as eng:
            out = _serve(eng, Request("big", [5, 6, 7], SamplingParams(
                max_tokens=8, temperature=0.0, ignore_eos=True,
                stop_token_ids=big_stops)))
            return out, _engaged(eng)

    base, _ = run(0)
    got, engaged = run(2)
    assert got == base
    # The oversized stop set kept the pipeline cold.
    assert not engaged
