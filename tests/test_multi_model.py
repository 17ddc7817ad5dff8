"""Multi-model serving: pooled token streams must be byte-identical to
single-model engines, switches must be fault-isolated, and a second
model must not grow the program-shape budget.

Three contracts:

1. **Byte identity.**  One engine serving two models through the pool
   (park -> drain -> streaming switch -> unpark) emits, per request,
   exactly the stream a dedicated single-model engine of that config
   would emit — greedy and seeded, at pipeline depths 0 and 2.  The
   second config is structurally DIFFERENT (fewer layers) so a routing
   bug cannot hide behind identical weights.

2. **Fault isolation.**  A fault injected in the new "model_switch"
   phase quarantines at most the requests parked for the target model;
   recovery replays them and the retried switch converges to the same
   byte-identical streams.  With a zero retry budget, the parked
   requests fail ALONE — streams already served on the active model are
   untouched.

3. **Compile budget.**  A same-shape second model re-uses every program
   SHAPE: its per-model context compiles the same (name, variant-count)
   set the first model did, no more.  New executables are expected (jit
   caches are per-context); new shapes are not.

Engines are driven synchronously through the same
step/_recover_from_fault contract the engine thread runs, like
test_chaos.py.
"""

import dataclasses
import threading

import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.model_pool import ModelPool
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness

# The flagship paged/mixed layout; multi-model rides the same scheduler.
DEFAULTS = dict(num_slots=2, max_cache_len=64, prefill_buckets=(8, 16, 32),
                steps_per_dispatch=4, prefill_chunk=16, kv_layout="paged")


def _second_cfg(same_shape=False):
    cfg = get_config("tiny")
    if same_shape:
        return dataclasses.replace(cfg, name="tiny-b")
    return dataclasses.replace(cfg, name="tiny2", num_layers=1)


def _env(monkeypatch, depth, inject=None, retries=None):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    if inject is None:
        monkeypatch.delenv("ARKS_FAULT_INJECT", raising=False)
    else:
        monkeypatch.setenv("ARKS_FAULT_INJECT", inject)
    if retries is None:
        monkeypatch.delenv("ARKS_FAULT_RETRIES", raising=False)
    else:
        monkeypatch.setenv("ARKS_FAULT_RETRIES", str(retries))


def _mk_pool_engine(monkeypatch, depth, cfg_b, inject=None, retries=None):
    _env(monkeypatch, depth, inject, retries)
    cfg = get_config("tiny")
    eng = InferenceEngine(cfg, EngineConfig(model="tiny", **DEFAULTS),
                          ByteTokenizer(), pool=ModelPool())
    eng.register_model(cfg_b)
    if depth:
        assert eng._pipe_warm_wait(120) == "ready"
    return cfg, eng


def _mk_single_engine(monkeypatch, depth, cfg):
    _env(monkeypatch, depth)
    eng = InferenceEngine(cfg, EngineConfig(model=cfg.name, **DEFAULTS),
                          ByteTokenizer())
    if depth:
        assert eng._pipe_warm_wait(120) == "ready"
    return eng


def _drive(eng, n_steps=4000):
    """The engine thread's own step/recover contract, synchronously.
    ``idle`` covers the model-parked state, so this only exits once
    every parked request has been switched to and served."""
    for _ in range(n_steps):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed exactly like _run_loop
            eng._recover_from_fault(e)
        if eng.idle and eng.state == "serving" and not eng._model_loads:
            break


def _quiesce(eng, depth):
    # The active context's pipe warmup compiles on a daemon thread; join
    # it before the test returns so nothing races interpreter teardown.
    if depth:
        assert eng._pipe_warm_wait(120) == "ready"


_collect = harness.collect


# (model-slot, prompt, greedy?) — interleaved across the two models,
# greedy + seeded per model.  Seeds are explicit: the engine's fallback
# seed counter is engine-global and would differ between a pooled run
# and two single-model runs.
WORKLOAD = [
    ("a", [5, 6, 7], True),
    ("b", [9] * 5, True),
    ("a", [11] * 4, False),
    ("b", [3, 1, 4], False),
]


def _traffic(cfg_b, only=None):
    reqs = []
    for i, (slot, prompt, greedy) in enumerate(WORKLOAD):
        if only is not None and slot != only:
            continue
        sp = SamplingParams(max_tokens=12, temperature=0.0 if greedy else 0.9,
                            top_p=0.9, top_k=40, seed=31 + i, ignore_eos=True)
        model = cfg_b.name if slot == "b" else None
        reqs.append(Request(f"m{i}", list(prompt), sp, model=model))
    return reqs


def _single_model_baseline(monkeypatch, depth, cfg_b):
    """Per-request streams from two dedicated engines, one per config."""
    base = {}
    for slot, cfg in (("a", get_config("tiny")), ("b", cfg_b)):
        eng = _mk_single_engine(monkeypatch, depth, cfg)
        reqs = _traffic(cfg_b, only=slot)
        for r in reqs:
            r.model = None  # single-model engine: no routing field
            eng.add_request(r)
        _drive(eng)
        _quiesce(eng, depth)
        for r in reqs:
            base[r.request_id] = _collect(r)
    return base


def _pooled_run(monkeypatch, depth, cfg_b, inject=None, retries=None):
    cfg, eng = _mk_pool_engine(monkeypatch, depth, cfg_b,
                               inject=inject, retries=retries)
    reqs = _traffic(cfg_b)
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    _quiesce(eng, depth)
    return {r.request_id: _collect(r) for r in reqs}, eng


@pytest.mark.parametrize("depth", [0, 2])
def test_pooled_streams_byte_identical_to_single_model_engines(
        monkeypatch, depth):
    base = _single_model_baseline(monkeypatch, depth, _second_cfg())
    got, eng = _pooled_run(monkeypatch, depth, _second_cfg())
    assert {rid: f.finish_reason for rid, (_, f) in got.items()} == \
        {rid: "length" for rid in base}
    assert got == base, "pooled streams diverged from single-model engines"
    # The switch actually happened and was measured.
    assert eng.metrics.model_switch_seconds._data
    assert eng.last_switch_stats is not None
    assert sum(eng.metrics.engine_faults_total._values.values()) == 0


def test_decode_keeps_full_depth_while_the_second_models_weights_load(
        monkeypatch):
    """The second model's first request lands while the first model is
    mid-decode: while its weights load (the loader is held open on an
    event, a stand-in for a multi-GB checkpoint read) every step still
    issues a pipelined dispatch, and the switch's dispatch accounting
    reads the FULL pipeline depth for the load window."""
    depth = 2
    cfg_b = _second_cfg()
    _, eng = _mk_pool_engine(monkeypatch, depth, cfg_b)
    entry = eng.pool.entry(cfg_b.name)
    load, gate = entry.loader, threading.Event()

    def gated_loader():
        params = load()
        assert gate.wait(120), "the test never released the loader"
        return params

    entry.loader = gated_loader
    def piped():
        """Pipelined dispatches so far (one observation each)."""
        return sum(n for _, _, n in
                   eng.metrics.pipeline_depth_occupancy._data.values())

    live = Request("a-live", [5, 6, 7], SamplingParams(
        max_tokens=48, temperature=0.0, ignore_eos=True))
    eng.add_request(live)
    for _ in range(200):
        eng.step(block_s=0.01)
        if piped() >= 2:
            break
    assert piped() >= 2, "never pipelined"
    cold = Request("b-cold", [9] * 5, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True), model=cfg_b.name)
    eng.add_request(cold)
    for _ in range(200):
        eng.step(block_s=0.01)
        if eng._model_loads:
            break
    assert eng._model_loads, "the second model's load never started"
    before = piped()
    for _ in range(12):
        eng.step(block_s=0.01)
    assert eng._model_loads, "the load finished behind the closed gate"
    assert piped() - before >= 12, \
        "a model load in flight knocked decoding off the pipelined path"
    gate.set()
    _drive(eng)
    _quiesce(eng, depth)
    assert _collect(live)[1].finish_reason == "length"
    assert _collect(cold)[1].finish_reason == "length"
    stats = eng.last_switch_stats
    assert stats["overlap_dispatches"] >= 12, stats
    assert stats["overlap_max_depth"] == depth, stats


@pytest.mark.chaos
@pytest.mark.parametrize("depth", [0, 2])
def test_model_switch_fault_recovers_byte_identical(monkeypatch, depth):
    """A fault in the model_switch phase must replay the parked requests
    through a retried switch and still converge to the exact streams of
    a fault-free pooled run."""
    base, _ = _pooled_run(monkeypatch, depth, _second_cfg())
    got, eng = _pooled_run(monkeypatch, depth, _second_cfg(),
                           inject="model_switch:1:runtime")
    assert {rid: f.finish_reason for rid, (_, f) in got.items()} == \
        {rid: "length" for rid in base}
    assert got == base, "streams diverged after a model_switch fault"
    faults = dict(eng.metrics.engine_faults_total._values)
    assert sum(faults.values()) == 1
    assert any("model_switch" in str(k) for k in faults)
    # Both parked-for-tiny2 requests replayed (plain requeue: nothing
    # was emitted for them yet), nobody quarantined.
    assert sum(eng.metrics.requests_recovered_total._values.values()) == 2
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


@pytest.mark.chaos
def test_model_switch_fault_quarantines_parked_culprits_only(monkeypatch):
    """With a zero retry budget the switch's culprits — exactly the
    requests parked for the target model — fail alone; the active
    model's streams are untouched (they had already drained: switches
    run at fully drained boundaries)."""
    base, _ = _pooled_run(monkeypatch, 0, _second_cfg())
    got, eng = _pooled_run(monkeypatch, 0, _second_cfg(),
                           inject="model_switch:1:runtime", retries=0)
    for rid, (ids, fin) in got.items():
        if rid in ("m1", "m3"):  # the two tiny2-routed requests
            assert fin.finish_reason == "error"
            assert "model_switch" in fin.error
        else:
            assert (ids, fin) == base[rid], \
                "a fault in another model's switch touched an active stream"
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 2
    assert eng.state == "serving"


def test_unknown_model_fails_fast(monkeypatch):
    _, eng = _mk_pool_engine(monkeypatch, 0, _second_cfg())
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    req = Request("nope", [1, 2, 3], sp, model="no-such-model")
    eng.add_request(req)
    _drive(eng, n_steps=50)
    _, fin = _collect(req)
    assert fin.finish_reason == "error" and fin.error == "model_not_found"
    assert not eng._awaiting_model


def test_abort_while_parked_for_model(monkeypatch):
    """An abort must reach a request parked on a model load, and the
    waiting gauge must come back down."""
    _, eng = _mk_pool_engine(monkeypatch, 0, _second_cfg())
    entry = eng.pool.entry("tiny2")
    orig, gate = entry.loader, threading.Event()
    entry.loader = lambda: (gate.wait(30), orig())[1]
    sp = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    req = Request("parked", [7, 8, 9], sp, model="tiny2")
    eng.add_request(req)
    for _ in range(200):
        eng.step(block_s=0.01)
        if eng._awaiting_model:
            break
    assert eng._awaiting_model, "request never parked for its model"
    eng.abort("parked")
    for _ in range(200):
        eng.step(block_s=0.01)
        if not eng._awaiting_model:
            break
    gate.set()
    _, fin = _collect(req)
    assert fin.finish_reason == "abort"
    assert not eng._awaiting_model
    assert sum(eng.metrics.num_requests_waiting._values.values()) == 0
    _drive(eng, n_steps=100)  # let the (now unblocked) load settle


def test_second_model_adds_no_new_program_shapes(monkeypatch):
    """A same-shape second model must ride the first model's program
    shapes: after serving identical workloads on both, the per-context
    compiled-variant census (program name -> shape count) matches
    exactly.  New executables are fine — new shapes are a compile-budget
    regression."""
    cfg_b = _second_cfg(same_shape=True)
    _, eng = _mk_pool_engine(monkeypatch, 0, cfg_b)

    def serve(model):
        reqs = []
        for i, (_, prompt, greedy) in enumerate(WORKLOAD):
            sp = SamplingParams(max_tokens=12,
                                temperature=0.0 if greedy else 0.9,
                                top_p=0.9, top_k=40, seed=31 + i,
                                ignore_eos=True)
            reqs.append(Request(f"{model or 'a'}-{i}", list(prompt), sp,
                                model=model))
        for r in reqs:
            eng.add_request(r)
        _drive(eng)
        for r in reqs:
            _collect(r)

    serve(None)
    variants_a = eng.compiled_program_variants()
    assert eng.cfg.name == "tiny"
    serve(cfg_b.name)
    assert eng.cfg.name == cfg_b.name
    variants_b = eng.compiled_program_variants()
    assert variants_b == variants_a, (
        "the second model compiled different program shapes: "
        f"{variants_a} vs {variants_b}")
