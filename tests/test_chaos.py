"""Chaos suite: fault-injected serving must preserve every innocent
stream byte-for-byte.

Scripted ARKS_FAULT_INJECT scenarios kill scheduler phases mid-run on the
slot and paged/mixed engines at pipeline depths 0 and 2, and every
surviving stream's token sequence is asserted IDENTICAL to a fault-free
run of the same engine (no duplicated, dropped, or changed tokens) while
the recovery metrics advance.  The scripted subset here is tier-1; the
randomized sweep at the bottom is additionally marked slow.

The engines are driven synchronously through the same
step/_recover_from_fault contract the engine thread runs (_run_loop), so
faults land deterministically.
"""

import functools
import random
import time

import pytest

from arks_tpu.engine import Request, SamplingParams
from arks_tpu.engine.faults import FaultInjector, InjectedFault, Watchdog
from arks_tpu.engine.paged import chain_digests

import harness

pytestmark = pytest.mark.chaos

SLOT = ("0", {})
MIXED = ("auto", dict(prefill_chunk=16, kv_layout="paged"))
# Speculative engines ride the mixed scheduler (draft+verify inside the
# mixed dispatch) and join token-replay recovery like everyone else.
SPEC = ("auto", dict(prefill_chunk=16, kv_layout="paged",
                     draft_model="tiny", draft_len=3))


def _mk_engine(monkeypatch, depth=0, mixed="0", inject=None, retries=None,
               **kw):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", mixed)
    if inject is None:
        monkeypatch.delenv("ARKS_FAULT_INJECT", raising=False)
    else:
        monkeypatch.setenv("ARKS_FAULT_INJECT", inject)
    if retries is None:
        monkeypatch.delenv("ARKS_FAULT_RETRIES", raising=False)
    else:
        monkeypatch.setenv("ARKS_FAULT_RETRIES", str(retries))
    eng = harness.warmed("tiny", base=dict(
        num_slots=2, max_cache_len=64, prefill_buckets=(8, 16, 32),
        steps_per_dispatch=4), **kw)
    return eng.cfg, eng


_drive = functools.partial(harness.drive, recover=True)


_collect = harness.collect


def _workload(cfg):
    """Greedy + seeded-sampled requests, mixed prompt lengths."""
    prompts = [[5, 6, 7], [9] * 5]
    reqs = []
    for i, p in enumerate(prompts):
        sp = SamplingParams(max_tokens=14,
                           temperature=0.0 if i % 2 == 0 else 0.9,
                           top_p=0.9, top_k=40, seed=21 + i, ignore_eos=True)
        reqs.append(Request(f"r{i}", [int(x) % cfg.vocab_size for x in p], sp))
    return reqs


_CLEAN = {}


def _clean(scenario, *args, **kw):
    """The fault-free pass of ``scenario(monkeypatch, *args, **kw)``, run
    once a module: what a faulted pass (always on a fresh engine of its own)
    is held against.  Its environment is its own and is put back."""
    at = (scenario.__name__, repr(args), repr(sorted(kw.items())))
    if at not in _CLEAN:
        with pytest.MonkeyPatch.context() as mp:
            _CLEAN[at] = scenario(mp, *args, **kw)
    return _CLEAN[at]


@pytest.fixture(scope="module", autouse=True)
def _clean_passes_dropped(tmp_path_factory):
    _CLEAN["dir"] = tmp_path_factory.mktemp("chaos-clean")
    yield
    del _CLEAN["dir"]
    for _, eng, *_ in _CLEAN.values():
        eng.stop()
    _CLEAN.clear()


def _run(monkeypatch, depth, mixed, kw, inject=None, retries=None):
    cfg, eng = _mk_engine(monkeypatch, depth, mixed, inject=inject,
                          retries=retries, **kw)
    reqs = _workload(cfg)
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r) for r in reqs], eng




@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("mixed,kw", [SLOT, MIXED],
                         ids=["slot", "paged-mixed"])
def test_decode_fault_recovers_all_streams_byte_identical(
        monkeypatch, depth, mixed, kw):
    """An injected decode-dispatch fault mid-run must recover EVERY
    in-flight stream byte-identically (same tokens, same finish reasons)
    on both engine layouts and at pipeline depths 0 and 2, with the fault
    and recovery metrics advancing."""
    base, _ = _clean(_run, depth, mixed, kw)
    got, eng = _run(monkeypatch, depth, mixed, kw, inject="decode:3:runtime")
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "surviving streams diverged from the fault-free run"
    faults = sum(eng.metrics.engine_faults_total._values.values())
    assert faults == 1
    recovered = sum(eng.metrics.requests_recovered_total._values.values())
    assert recovered == 2
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.metrics.engine_recovery_seconds._data, \
        "recovery latency never observed"
    assert eng.state == "serving"


@pytest.mark.parametrize("depth", [0, 2])
def test_spec_fault_recovers_all_streams_byte_identical(monkeypatch, depth):
    """A fault injected in the SPEC phase (the spec-mixed dispatch issue,
    or the pipelined spec issue at depth 2) must recover every in-flight
    stream byte-identically via token replay — spec engines joined the
    recovery contract when the fused spec loop was retired."""
    base, _ = _clean(_run, depth, *SPEC)
    got, eng = _run(monkeypatch, depth, *SPEC, inject="spec:3:runtime")
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "surviving spec streams diverged from the fault-free run"
    faults = sum(eng.metrics.engine_faults_total._values.values())
    assert faults == 1
    assert sum(eng.metrics.requests_recovered_total._values.values()) == 2
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


def test_spec_repeated_fault_quarantines_only_the_culprit(monkeypatch):
    """Spec phase fault -> everyone replays; the FIRST replay operation
    then faults too -> that request fails ALONE while the other spec
    stream finishes byte-identical to the fault-free run."""
    base, _ = _clean(_run, 0, *SPEC)
    got, eng = _run(monkeypatch, 0, *SPEC,
                    inject="spec:3:runtime,replay:1:runtime")
    reasons = [f.finish_reason for _, f in got]
    assert reasons.count("error") == 1, reasons
    errs = [f for _, f in got if f.finish_reason == "error"]
    assert errs[0].error.startswith("engine_fault")
    base_streams = {f.request_id: (ids, f.finish_reason) for ids, f in base}
    for ids, f in got:
        if f.finish_reason != "error":
            assert (ids, f.finish_reason) == base_streams[f.request_id], \
                "survivor stream diverged from the fault-free run"
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 1
    assert eng.state == "serving"


@pytest.mark.parametrize("mixed,kw", [SLOT, MIXED],
                         ids=["slot", "paged-mixed"])
def test_repeated_fault_quarantines_only_the_culprit(monkeypatch, mixed, kw):
    """decode fault -> everyone replays; the FIRST replay operation then
    faults too -> that request has exhausted ARKS_FAULT_RETRIES=1 and
    fails ALONE with finish_reason="error"/engine_fault, while the other
    stream still finishes byte-identical to the fault-free run."""
    base, _ = _clean(_run, 0, mixed, kw)
    got, eng = _run(monkeypatch, 0, mixed, kw,
                    inject="decode:3:runtime,replay:1:runtime")
    reasons = [f.finish_reason for _, f in got]
    assert reasons.count("error") == 1, reasons
    errs = [f for _, f in got if f.finish_reason == "error"]
    assert errs[0].error.startswith("engine_fault")
    survivors = [(ids, f.finish_reason) for ids, f in got
                 if f.finish_reason != "error"]
    base_by_rid = {f.request_id: (ids, f.finish_reason) for ids, f in base}
    for ids, fr in survivors:
        assert (ids, fr) in [base_by_rid[rid] for rid in base_by_rid], \
            "survivor stream diverged from the fault-free run"
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 1
    assert eng.state == "serving"


def test_zero_retry_budget_fails_culprits_immediately(monkeypatch):
    """ARKS_FAULT_RETRIES=0: the faulting dispatch's culprits fail at the
    first fault (no replay), and the engine keeps serving new work."""
    got, eng = _run(monkeypatch, 0, *SLOT, inject="decode:3:runtime",
                    retries=0)
    reasons = [f.finish_reason for _, f in got]
    assert reasons == ["error", "error"]
    assert all(f.error.startswith("engine_fault") for _, f in got)
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 2
    # The engine is healthy afterwards: a fresh request completes.
    nxt = Request("post", [4, 4, 4], SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    eng.add_request(nxt)
    _drive(eng)
    ids, fin = _collect(nxt)
    assert fin.finish_reason == "length" and len(ids) == 4


def test_admit_fault_requeues_requests(monkeypatch):
    """A fault inside the fused admission dispatch must re-queue the
    batch's requests (nothing was emitted yet) and the streams come out
    byte-identical to a fault-free run — pinned engine-assigned seeds."""
    base, _ = _clean(_run, 0, *SLOT)
    got, eng = _run(monkeypatch, 0, *SLOT, inject="admit:1:runtime")
    assert got == base
    assert sum(eng.metrics.requests_recovered_total._values.values()) >= 1


def _tenant_workload(cfg):
    """Two tenants' worth of seeded streams for the fair-admission
    chaos scenarios — enough depth that the WDRR pick point fires with
    requests still waiting behind it."""
    reqs = []
    for i in range(3):
        reqs.append(Request(f"a{i}", [5, 6, 7], SamplingParams(
            max_tokens=8, temperature=0.9, top_p=0.9, seed=41 + i,
            ignore_eos=True), tenant="ns/a"))
        reqs.append(Request(f"b{i}", [9] * 5, SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True),
            tenant="ns/b"))
    return reqs


def _run_tenants(monkeypatch, inject=None, retries=None):
    monkeypatch.setenv("ARKS_FAIR", "1")
    cfg, eng = _mk_engine(monkeypatch, 0, "0", inject=inject,
                          retries=retries)
    reqs = _tenant_workload(cfg)
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r) for r in reqs], eng


def test_admit_fair_fault_requeues_through_the_fair_queue(monkeypatch):
    """A fault at the WDRR pick point ("admit_fair" phase): the popped
    request re-queues through the fair queue (nothing was emitted yet)
    and EVERY stream — both tenants — comes out byte-identical to the
    fault-free run."""
    base, _ = _clean(_run_tenants)
    got, eng = _run_tenants(monkeypatch, inject="admit_fair:2:runtime")
    assert got == base, \
        "streams diverged after the admit_fair fault"
    assert sum(eng.metrics.engine_faults_total._values.values()) == 1
    assert eng.metrics.engine_faults_total.get(
        phase="admit_fair", kind="injected") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


def test_admit_fair_repeated_fault_quarantines_only_the_culprit(
        monkeypatch):
    """Zero retry budget: the admit_fair fault fails its ONE popped
    request (the sole culprit), every other stream — same tenant and
    the other tenant alike — finishes byte-identical to the fault-free
    run, and the fair queue keeps serving."""
    base, _ = _clean(_run_tenants)
    got, eng = _run_tenants(monkeypatch, inject="admit_fair:2:runtime",
                            retries=0)
    reasons = [f.finish_reason for _, f in got]
    assert reasons.count("error") == 1, reasons
    errs = [f for _, f in got if f.finish_reason == "error"]
    assert errs[0].error.startswith("engine_fault")
    base_by_rid = {f.request_id: (ids, f.finish_reason) for ids, f in base}
    for ids, f in got:
        if f.finish_reason != "error":
            assert (ids, f.finish_reason) == base_by_rid[f.request_id], \
                "survivor stream diverged from the fault-free run"
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 1
    assert eng.state == "serving"


def test_chunk_fault_on_long_prompt_is_isolated(monkeypatch):
    """A chunked-prefill dispatch fault is attributed to its ONE request:
    within budget it recovers; the co-resident decoding stream is
    byte-identical either way."""
    cfg, eng0 = _mk_engine(monkeypatch, 0, "0")
    short = Request("short", [5, 6, 7], SamplingParams(
        max_tokens=14, temperature=0.0, ignore_eos=True))
    # Beyond the largest one-shot bucket (32) -> chunked prefill.
    long_r = Request("long", [7] * 40, SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    eng0.add_request(short)
    eng0.add_request(long_r)
    _drive(eng0)
    base = [_collect(short), _collect(long_r)]

    cfg, eng = _mk_engine(monkeypatch, 0, "0", inject="chunk:1:runtime")
    short2 = Request("short", [5, 6, 7], short.params)
    long2 = Request("long", [7] * 40, long_r.params)
    eng.add_request(short2)
    eng.add_request(long2)
    _drive(eng)
    got = [_collect(short2), _collect(long2)]
    assert got == base
    assert sum(eng.metrics.requests_recovered_total._values.values()) >= 1


def test_abort_during_recovery_wins_over_replay(monkeypatch):
    """An abort that races the fault/recovery window must finish the
    request as "abort" — never replay it back to life."""
    cfg, eng = _mk_engine(monkeypatch, 0, "0")
    victim = Request("v", [5, 6, 7], SamplingParams(
        max_tokens=10_000, temperature=0.0, ignore_eos=True))
    other = Request("o", [9, 9], SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True))
    eng.add_request(victim)
    eng.add_request(other)
    for _ in range(60):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001
            eng._recover_from_fault(e)
        if eng._slots:
            break
    assert eng._slots, "nothing admitted"
    # Raise the abort, then force a step fault before the scheduler can
    # consume it on the normal path.
    eng.abort("v")
    eng._faults.arm("decode:1:runtime")
    _drive(eng)
    _, fin_v = _collect(victim)
    _, fin_o = _collect(other)
    assert fin_v.finish_reason == "abort"
    assert fin_o.finish_reason == "length"
    with eng._abort_lock:
        assert "v" not in eng._aborted


def test_fault_injector_spec_parsing():
    inj = FaultInjector("decode:2:runtime, replay:1:oom")
    inj.fire("decode")
    with pytest.raises(InjectedFault):
        inj.fire("decode")
    inj.fire("decode")  # each spec entry fires at most once
    with pytest.raises(InjectedFault, match="RESOURCE_EXHAUSTED"):
        inj.fire("replay")
    for bad in ("decode:x:runtime", "decode:0:runtime", "decode:1:nope",
                "decode:1"):
        with pytest.raises(ValueError):
            FaultInjector(bad)
    assert not FaultInjector("").active


def test_watchdog_escalates_on_wedged_step(monkeypatch):
    """A step heartbeat older than the deadline flips the wedged callback
    and escalates through the exit fn with code 70."""
    import time as _time
    events = []
    hb = ("decode", _time.monotonic() - 10.0)
    wd = Watchdog(0.1, lambda: hb, lambda phase, age: events.append(phase),
                  exit_fn=lambda code: events.append(code))
    wd.start()
    deadline = _time.monotonic() + 5
    while len(events) < 2 and _time.monotonic() < deadline:
        _time.sleep(0.02)
    wd.stop()
    assert events == ["decode", 70]


def test_watchdog_quiet_while_healthy():
    import time as _time
    fired = []
    wd = Watchdog(0.2, lambda: None, lambda *a: fired.append(a),
                  exit_fn=lambda code: fired.append(code))
    wd.start()
    _time.sleep(0.6)
    wd.stop()
    assert not fired


def test_engine_state_gauge_and_readiness_mapping(monkeypatch):
    """The engine_state gauge tracks the recovery window (0 -> 1 -> 0)."""
    cfg, eng = _mk_engine(monkeypatch, 0, "0", inject="decode:2:runtime")
    r = Request("r", [5, 6], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True))
    eng.add_request(r)
    states = set()
    for _ in range(400):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001
            eng._recover_from_fault(e)
            states.add(eng.state)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling and eng.state == "serving"):
            break
    _collect(r)
    assert "recovering" in states
    assert eng.state == "serving"
    assert eng.metrics.engine_state.get() == 0


@pytest.mark.slow
@pytest.mark.parametrize("mixed,kw", [SLOT, MIXED],
                         ids=["slot", "paged-mixed"])
def test_randomized_chaos_sweep(monkeypatch, mixed, kw):
    """Randomized injection over phases/offsets: per-stream integrity must
    hold in EVERY round — each stream either matches the fault-free run
    exactly or fails alone with an engine_fault error; the engine always
    returns to "serving"."""
    base, _ = _clean(_run, 0, mixed, kw)
    base_by_rid = {fin.request_id: (ids, fin.finish_reason)
                   for ids, fin in base}
    rng = random.Random(1234)
    phases = ["decode", "resolve", "admit", "admit_fair", "chunk",
              "replay", "pages"]
    for round_i in range(6):
        spec = ",".join(
            f"{rng.choice(phases)}:{rng.randint(1, 6)}:runtime"
            for _ in range(rng.randint(1, 3)))
        got, eng = _run(monkeypatch, 0, mixed, kw, inject=spec)
        for ids, fin in got:
            if fin.finish_reason == "error":
                assert fin.error.startswith("engine_fault"), \
                    f"round {round_i} ({spec}): unexpected error {fin.error}"
                continue
            assert (ids, fin.finish_reason) == base_by_rid[fin.request_id], \
                f"round {round_i} ({spec}): stream integrity violated"
        assert eng.state == "serving", f"round {round_i} ({spec})"


class _RecordingDispatcher:
    def __init__(self):
        self.ops = []

    def broadcast(self, op, payload):
        self.ops.append((op, payload))


def test_recover_op_reaches_followers(monkeypatch):
    """Multihost: a fault broadcasts a "recover" op (surviving-request
    manifest) followed by "reset", and the replayed re-admission rides the
    ordinary op stream — followers rebuild from the leader's manifest."""
    cfg, eng = _mk_engine(monkeypatch, 0, "0", inject="decode:2:runtime")
    eng.dispatcher = _RecordingDispatcher()
    r = Request("m0", [5, 6, 7], SamplingParams(
        max_tokens=8, temperature=0.0, ignore_eos=True))
    eng.add_request(r)
    _drive(eng)
    _collect(r)
    ops = [op for op, _ in eng.dispatcher.ops]
    assert "recover" in ops and "reset" in ops
    assert ops.index("recover") < ops.index("reset")
    recover_payload = next(p for op, p in eng.dispatcher.ops
                           if op == "recover")
    assert [m[0] for m in recover_payload["manifest"]] == ["m0"]
    # The replay re-admission was mirrored too (ops after the reset).
    after = ops[ops.index("reset") + 1:]
    assert any(op in ("admit_batch", "chunk", "chunk_paged", "mixed")
               for op in after)


def test_follower_applies_recover_op(monkeypatch):
    """DispatchFollower handles the recover op: pipeline replay state
    drops so the next decode_pipe must be fresh, and the manifest is
    accepted without touching device state."""
    from arks_tpu.engine.multihost import DispatchFollower
    cfg, eng = _mk_engine(monkeypatch, 0, "0")
    follower = DispatchFollower.__new__(DispatchFollower)
    follower.engine = eng
    import jax as _jax
    follower._jax = _jax
    follower._pipe_state = ("stale",)
    follower._pipe_cols = ("stale",)
    import jax.numpy as _jnp
    follower._apply(eng, _jax, _jnp, "recover",
                    {"manifest": [("r0", 3, 5)], "phase": "decode",
                     "kind": "injected"})
    assert follower._pipe_state is None and follower._pipe_cols is None


def _restore_scenario(monkeypatch, inject=None, retries=None):
    """Shared-prefix workload on the tiered cache: a warm prompt, churn
    that evicts it into the host tier, a co-resident decoding stream,
    then the warm prompt again — whose admission goes through the tier-1
    RESTORE path (the injectable "restore" phase)."""
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
    cfg, eng = _mk_engine(monkeypatch, 0, "auto", inject=inject,
                          retries=retries, prefill_chunk=16,
                          kv_layout="paged", prefix_cache_mb=0)
    assert eng._host is not None
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]  # 2 pages + tail
    outs = []

    def run_one(req):
        eng.add_request(req)
        _drive(eng)
        return req

    # Warm the prefix, then churn it out of the device index (spilled).
    run_one(Request("w1", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True)))
    for i in range(5):
        run_one(Request(f"ch{i}", [(9 + i) % cfg.vocab_size] * 33,
                        SamplingParams(max_tokens=3, temperature=0.0,
                                       ignore_eos=True)))
    # A long-lived innocent stream decodes while the restore happens.
    bystander = Request("by", [5, 6, 7], SamplingParams(
        max_tokens=20, temperature=0.9, top_p=0.9, top_k=40, seed=11,
        ignore_eos=True))
    eng.add_request(bystander)
    for _ in range(60):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed like _run_loop
            eng._recover_from_fault(e)
        if eng._slots:
            break
    victim = Request("w2", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    eng.add_request(victim)
    _drive(eng)
    outs = [_collect(bystander), _collect(victim)]
    return outs, eng


def test_restore_fault_is_isolated_to_the_restoring_request(monkeypatch):
    """A fault injected at the tier-1 restore phase must recover: within
    the retry budget the restoring request re-queues (its retry hits the
    host tier again — it survives the device reset), and the co-resident
    decoding stream is byte-identical to the fault-free run."""
    base, beng = _clean(_restore_scenario)
    assert beng.metrics.prefix_restore_blocks_total.total() > 0, \
        "scenario never exercised the restore path"
    got, eng = _restore_scenario(monkeypatch, inject="restore:1:runtime")
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "streams diverged after the restore fault"
    assert sum(eng.metrics.engine_faults_total._values.values()) == 1
    assert eng.metrics.engine_faults_total.get(
        phase="restore", kind="injected") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


def test_restore_fault_quarantines_only_the_culprit(monkeypatch):
    """With a zero retry budget, the restore fault fails the restoring
    request ALONE (finish_reason="error"/engine_fault); the innocent
    decoding stream still finishes byte-identical to the fault-free
    run."""
    base, _ = _clean(_restore_scenario)
    got, eng = _restore_scenario(monkeypatch, inject="restore:1:runtime",
                                 retries=0)
    (by_ids, by_fin), (_, v_fin) = got
    assert v_fin.finish_reason == "error"
    assert v_fin.error.startswith("engine_fault")
    assert (by_ids, by_fin.finish_reason) == (base[0][0], "length")
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 1
    assert eng.state == "serving"


def _disk_scenario(monkeypatch, depth, ddir=None, inject=None, retries=None,
                   wait_disk=True):
    """Tier-2 traffic on the tiered cache: a warm prompt spills into the
    host tier under churn, a capacity squeeze evicts it into the DISK
    drain (the injectable "disk_spill" phase), and the warm prompt's
    return parks in the fetch path whose unpark is the injectable
    "peer_fetch" phase."""
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
    monkeypatch.setenv("ARKS_PREFIX_DISK_MB", "8")
    # (a clean pass, shared by the module's tests, in a directory of its own)
    monkeypatch.setenv("ARKS_PREFIX_DISK_DIR",
                       str(ddir or _CLEAN["dir"] / f"depth{depth}"))
    cfg, eng = _mk_engine(monkeypatch, depth, "auto", inject=inject,
                          retries=retries, prefill_chunk=16,
                          kv_layout="paged", prefix_cache_mb=0)
    assert eng._disk is not None
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]  # 2 pages + tail

    def run_one(req):
        eng.add_request(req)
        _drive(eng)
        return req

    # Warm the prefix, churn it out of the device index into the host
    # tier, then squeeze the host tier to its current footprint so the
    # NEXT churn round evicts the (LRU) warm blocks into the disk drain.
    run_one(Request("w1", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True)))
    for i in range(5):
        run_one(Request(f"ch{i}", [(9 + i) % cfg.vocab_size] * 33,
                        SamplingParams(max_tokens=3, temperature=0.0,
                                       ignore_eos=True)))
    eng._host.capacity = eng._host.bytes_used
    for i in range(3):
        run_one(Request(f"cv{i}", [(17 + i) % cfg.vocab_size] * 33,
                        SamplingParams(max_tokens=3, temperature=0.0,
                                       ignore_eos=True)))
    if wait_disk:
        # The spill drain is step-driven and the file write is async on
        # the writer thread — give both a bounded moment.
        digests = chain_digests(warm, 16, 2)
        deadline = time.monotonic() + 30
        while (not all(eng._disk.has(d) for d in digests)
               and time.monotonic() < deadline):
            try:
                eng.step(block_s=0.01)
            except Exception as e:  # noqa: BLE001 — routed like _run_loop
                eng._recover_from_fault(e)
            time.sleep(0.01)
        assert all(eng._disk.has(d) for d in digests), \
            "warm blocks never reached the disk tier"
    # A long-lived innocent stream decodes while the fetch happens.
    bystander = Request("by", [5, 6, 7], SamplingParams(
        max_tokens=20, temperature=0.9, top_p=0.9, top_k=40, seed=11,
        ignore_eos=True))
    eng.add_request(bystander)
    for _ in range(60):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed like _run_loop
            eng._recover_from_fault(e)
        if eng._slots:
            break
    victim = Request("w2", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    eng.add_request(victim)
    _drive(eng)
    outs = [_collect(bystander), _collect(victim)]
    return outs, eng


@pytest.mark.parametrize("depth", [0, 2])
def test_disk_spill_fault_leaves_streams_intact(monkeypatch, depth,
                                                tmp_path):
    """A fault in the tier-2 spill drain serves no specific request:
    even with a ZERO retry budget nobody is quarantined, every stream
    finishes byte-identical to the fault-free run, and the engine keeps
    serving — the warm blocks simply never reach disk (dropped spill,
    re-prefill on return)."""
    base, beng = _clean(_disk_scenario, depth)
    assert beng.metrics.prefix_peer_fetch_blocks_total.get(
        source="disk") == 2, "scenario never exercised the disk tier"
    got, eng = _disk_scenario(monkeypatch, depth, tmp_path / "f",
                              inject="disk_spill:1:runtime", retries=0,
                              wait_disk=False)
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "streams diverged after the disk-spill fault"
    assert eng.metrics.engine_faults_total.get(
        phase="disk_spill", kind="injected") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


@pytest.mark.parametrize("depth", [0, 2])
def test_fetch_resolve_fault_recovers_within_budget(monkeypatch, depth,
                                                    tmp_path):
    """A fault at the fetch unpark ("peer_fetch" phase): within the
    retry budget the fetching request re-queues, its retry re-parks on
    the disk tier and restores, and both it and the co-resident decoding
    stream finish byte-identical to the fault-free run."""
    base, beng = _clean(_disk_scenario, depth)
    assert beng.metrics.prefix_peer_fetch_blocks_total.get(
        source="disk") == 2, "scenario never exercised the disk fetch"
    got, eng = _disk_scenario(monkeypatch, depth, tmp_path / "f",
                              inject="peer_fetch:1:runtime")
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "streams diverged after the fetch fault"
    assert eng.metrics.engine_faults_total.get(
        phase="peer_fetch", kind="injected") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


def test_fetch_resolve_fault_quarantines_only_the_fetcher(monkeypatch,
                                                          tmp_path):
    """With a zero retry budget the fetch fault fails the fetching
    request ALONE (finish_reason="error"/engine_fault); the innocent
    decoding stream still finishes byte-identical to the fault-free
    run."""
    base, _ = _clean(_disk_scenario, 0)
    got, eng = _disk_scenario(monkeypatch, 0, tmp_path / "f",
                              inject="peer_fetch:1:runtime", retries=0)
    (by_ids, by_fin), (_, v_fin) = got
    assert v_fin.finish_reason == "error"
    assert v_fin.error.startswith("engine_fault")
    assert (by_ids, by_fin.finish_reason) == (base[0][0], "length")
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 1
    assert eng.state == "serving"


def _residency_scenario(monkeypatch, depth, inject=None, retries=None):
    """Windowed-residency traffic: a long decode stream outgrows the
    6-page resident window (pool = num_slots * window) and engages the
    span-streaming path — the injectable "residency" phase — while an
    innocent seeded stream decodes alongside on the classic mixed path."""
    monkeypatch.setenv("ARKS_RESIDENCY_WINDOW_PAGES", "6")
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    cfg, eng = _mk_engine(monkeypatch, depth, "1", inject=inject,
                          retries=retries, prefill_chunk=16,
                          kv_layout="paged", prefix_cache_mb=0,
                          max_cache_len=256)
    # 40-token prompt + 70 decode tokens = 110 > the 96-token resident
    # budget: the stream engages mid-decode and finishes windowed.
    long_r = Request("win", [int(x) % cfg.vocab_size
                             for x in range(3, 43)],
                     SamplingParams(max_tokens=70, temperature=0.0,
                                    ignore_eos=True))
    bystander = Request("by", [5, 6, 7], SamplingParams(
        max_tokens=80, temperature=0.9, top_p=0.9, top_k=40, seed=11,
        ignore_eos=True))
    eng.add_request(long_r)
    eng.add_request(bystander)
    _drive(eng, n_steps=3000)
    outs = [_collect(long_r), _collect(bystander)]
    return outs, eng


@pytest.mark.slow
@pytest.mark.parametrize("depth", [0, 2])
def test_residency_fault_recovers_all_streams_byte_identical(
        monkeypatch, depth):
    """A fault injected at the windowed span step ("residency" phase):
    within the retry budget the engaged stream token-replays (re-growing
    back through engagement), the co-resident classic-path stream
    replays too, and BOTH finish byte-identical to the fault-free run at
    pipeline depths 0 and 2."""
    base, beng = _clean(_residency_scenario, depth)
    assert beng.metrics.residency_spans_total.total() > 0, \
        "scenario never engaged the windowed path"
    got, eng = _residency_scenario(monkeypatch, depth,
                                   inject="residency:1:runtime")
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "streams diverged after the residency fault"
    assert eng.metrics.engine_faults_total.get(
        phase="residency", kind="injected") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


@pytest.mark.slow
def test_residency_fault_quarantines_only_the_engaged_culprit(monkeypatch):
    """With a zero retry budget the residency fault fails the ENGAGED
    stream alone (finish_reason="error"/engine_fault) — the culprit set
    is the window-engaged slots, never the co-resident classic-path
    stream, which finishes byte-identical to the fault-free run."""
    base, _ = _clean(_residency_scenario, 0)
    got, eng = _residency_scenario(monkeypatch, 0,
                                   inject="residency:1:runtime", retries=0)
    (_, w_fin), (by_ids, by_fin) = got
    assert w_fin.finish_reason == "error"
    assert w_fin.error.startswith("engine_fault")
    assert (by_ids, by_fin.finish_reason) == (base[1][0], "length")
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 1
    assert eng.state == "serving"


def test_decode_fault_while_another_request_prefills(monkeypatch):
    """A decode fault with a long prompt mid-chunked-prefill: the decoding
    stream token-replays, the prefilling one re-runs from the top, both
    byte-identical to the fault-free run."""
    def scenario(inject):
        # prefill_chunk=16: the 40-token prompt needs 3 chunk dispatches,
        # so the injected decode fault lands while it is MID-PREFILL.
        cfg, eng = _mk_engine(monkeypatch, 0, "0", inject=inject,
                              prefill_chunk=16)
        dec = Request("dec", [5, 6, 7], SamplingParams(
            max_tokens=20, temperature=0.9, top_p=0.9, top_k=40, seed=5,
            ignore_eos=True))
        long_r = Request("long", [7] * 40, SamplingParams(
            max_tokens=6, temperature=0.0, ignore_eos=True))
        eng.add_request(dec)
        eng.add_request(long_r)
        for _ in range(40):
            try:
                eng.step(block_s=0.01)
            except Exception as e:  # noqa: BLE001
                eng._recover_from_fault(e)
            if inject is None and eng._prefilling and eng._slots:
                break  # confirm the overlap window exists fault-free
        _drive(eng)
        return [_collect(dec), _collect(long_r)], eng

    base, _ = scenario(None)
    got, eng = scenario("decode:2:runtime")
    assert got == base
    assert sum(eng.metrics.requests_recovered_total._values.values()) == 2
    assert eng.state == "serving"


# ---- elastic resize (live topology change) ---------------------------


def _drive_elastic(eng, n_steps=3000):
    """_drive, but quiet also requires the resize machinery to be done:
    no in-flight resize request and no swapped victims awaiting restore
    (the plain quiet check reads num_running == 0 at the drained
    boundary and would bail mid-resize)."""
    for _ in range(n_steps):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed like _run_loop
            eng._recover_from_fault(e)
        if (eng._resize_req is None and not eng._swapped
                and not eng._swap_pending and not eng._spills
                and eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling and not eng._awaiting_fetch
                and not eng._awaiting_restore and eng.state == "serving"):
            break


def _resize_scenario(monkeypatch, depth, inject=None, retries=None,
                     resize=True, tp=2):
    """Mid-stream live resize: two ALL-GREEDY streams decode on the
    paged-mixed engine, a tp1 -> tp{tp} resize posts once both hold
    slots, and the drive runs the drain/reshard/resume machinery to
    completion.  Greedy only: byte-identity across a TP change holds
    for argmax streams (sampled streams are distribution-exact, not
    byte-exact — the psum reduction order shifts with the mesh)."""
    cfg, eng = _mk_engine(monkeypatch, depth, "auto", inject=inject,
                          retries=retries, prefill_chunk=16,
                          kv_layout="paged")
    reqs = [Request(f"r{i}", [int(x) % cfg.vocab_size for x in p],
                    SamplingParams(max_tokens=14, temperature=0.0,
                                   ignore_eos=True))
            for i, p in enumerate([[5, 6, 7], [9] * 5])]
    for r in reqs:
        eng.add_request(r)
    for _ in range(60):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed like _run_loop
            eng._recover_from_fault(e)
        if eng._slots:
            break
    assert eng._slots, "streams never reached slots before the resize"
    hold = eng.request_resize(tensor_parallel=tp) if resize else None
    _drive_elastic(eng, n_steps=3000)
    outs = [_collect(r) for r in reqs]
    return outs, eng, hold


@pytest.mark.parametrize("depth", [0, 2])
def test_live_resize_preserves_streams_byte_identical(monkeypatch, depth):
    """A tp1 -> tp2 live resize posted MID-STREAM: both greedy streams
    finish byte-identical to a run that never resized, the request
    completes "ok", and the engine reports the new shape — at pipeline
    depths 0 and 2."""
    base, _, _ = _clean(_resize_scenario, depth, resize=False)
    got, eng, hold = _resize_scenario(monkeypatch, depth)
    assert hold.outcome == "ok", hold.error
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "streams diverged across the live resize"
    assert eng._mesh_shape_str() == "tp2xdp1"
    stats = eng.last_resize_stats
    assert stats and stats["from"] == "tp1xdp1" and stats["to"] == "tp2xdp1"
    assert stats["seconds"] > 0
    assert eng.metrics.engine_resizes_total.get(
        mode="resize", outcome="ok") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("seam,expect_shape", [
    (1, "tp1xdp1"),   # drain seam: fault before the reshard -> old shape
    (2, "tp1xdp1"),   # reshard seam: plan ran, commit didn't -> old shape
    (3, "tp2xdp1"),   # resume seam: commit landed -> recover at NEW shape
], ids=["drain", "reshard", "resume"])
def test_resize_seam_fault_recovers_streams_byte_identical(
        monkeypatch, depth, seam, expect_shape):
    """A fault injected at each resize seam (drain / reshard / resume):
    the resize request reports "error", recovery lands at the expected
    shape (old for the first two seams, new for the last), and EVERY
    stream still finishes byte-identical to the never-resized run —
    nobody is quarantined (the resize serves no specific request)."""
    base, _, _ = _clean(_resize_scenario, depth, resize=False)
    got, eng, hold = _resize_scenario(
        monkeypatch, depth, inject=f"resize:{seam}:runtime")
    assert hold.outcome == "error"
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base, "streams diverged after the resize-seam fault"
    assert eng._mesh_shape_str() == expect_shape
    assert eng.metrics.engine_faults_total.get(
        phase="resize", kind="injected") == 1
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


def test_resize_seam_fault_zero_retries_quarantines_nobody(monkeypatch):
    """Even with a ZERO retry budget a resize-seam fault quarantines
    NOBODY: the drained streams were preserved (swapped or re-queued)
    before the seam fired, so the culprit set is empty and every stream
    replays to a byte-identical finish."""
    base, _, _ = _clean(_resize_scenario, 0, resize=False)
    got, eng, hold = _resize_scenario(monkeypatch, 0,
                                      inject="resize:2:runtime", retries=0)
    assert hold.outcome == "error"
    assert [f.finish_reason for _, f in got] == ["length", "length"]
    assert got == base
    assert sum(eng.metrics.requests_quarantined_total._values.values()) == 0
    assert eng.state == "serving"


@pytest.mark.slow
def test_randomized_resize_sweep(monkeypatch):
    """Randomized resize chaos: each round posts a mid-stream resize
    with a fault at a random seam, optionally stacked with a decode
    fault.  Per-stream integrity must hold every round — each stream
    either matches the never-resized run exactly or fails alone with an
    engine_fault error — and the engine always returns to "serving" at
    a coherent shape."""
    base, _, _ = _clean(_resize_scenario, 0, resize=False)
    base_by_rid = {fin.request_id: (ids, fin.finish_reason)
                   for ids, fin in base}
    rng = random.Random(4321)
    for round_i in range(5):
        specs = [f"resize:{rng.randint(1, 3)}:runtime"]
        if rng.random() < 0.5:
            specs.append(f"decode:{rng.randint(1, 4)}:runtime")
        spec = ",".join(specs)
        got, eng, hold = _resize_scenario(monkeypatch, 0, inject=spec)
        for ids, fin in got:
            if fin.finish_reason == "error":
                assert fin.error.startswith("engine_fault"), \
                    f"round {round_i} ({spec}): unexpected error {fin.error}"
                continue
            assert (ids, fin.finish_reason) == base_by_rid[fin.request_id], \
                f"round {round_i} ({spec}): stream integrity violated"
        assert hold.outcome in ("ok", "error"), f"round {round_i} ({spec})"
        assert eng.state == "serving", f"round {round_i} ({spec})"
        assert eng._mesh_shape_str() in ("tp1xdp1", "tp2xdp1"), \
            f"round {round_i} ({spec}): incoherent shape"
