"""SLO-tiered preemptive KV swap: latency-tier requests seize running
slots by spilling low-tier decode state to host RAM.

Acceptance surface for the preemption machinery (engine.py, the
ARKS_PREEMPT paths):

- a preempted-and-resumed stream is BYTE-IDENTICAL to its unpreempted
  run (greedy + seeded + guided, pipeline depths 0 and 2) in both swap
  mode (host tier on) and replay mode (the fallback when there is no
  host tier, or on spec engines — the tested fallback-matrix rows);
- chaos: a fault injected during the preempt spill, the harvest, or the
  victim resume quarantines ONLY the culprit attempt — every stream
  still completes byte-identically via token replay;
- abort-while-swapped-out releases the victim's host bytes and never
  drives the parked/waiting gauges negative;
- ARKS_QUEUE_AGING_S decays a starved batch request's effective
  priority until it admits under sustained latency-tier load.

Engines are driven synchronously through the step/_recover_from_fault
contract (the _run_loop shape) so faults land deterministically.
"""

import pytest

from arks_tpu.engine import Request, SamplingParams

import harness

CHUNK = 16


def _mk_engine(monkeypatch, depth=0, host_mb=64, preempt=True, inject=None,
               **kw):
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", str(host_mb))
    monkeypatch.setenv("ARKS_PREEMPT", "1" if preempt else "0")
    monkeypatch.setenv("ARKS_SLO_TIERS", "latency:ttft_ms=300,batch:")
    if inject is None:
        monkeypatch.delenv("ARKS_FAULT_INJECT", raising=False)
    else:
        monkeypatch.setenv("ARKS_FAULT_INJECT", inject)
    eng = harness.warmed("tiny", base=dict(
        num_slots=1, max_cache_len=64, prefill_buckets=(8, 16, 32),
        steps_per_dispatch=1, prefill_chunk=CHUNK, kv_layout="paged",
        prefix_cache_mb=0), **kw)
    return eng.cfg, eng


def _drive(eng, n_steps=4000):
    """The engine thread's own step/recover contract, synchronously."""
    for _ in range(n_steps):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed exactly like _run_loop
            eng._recover_from_fault(e)
        if eng.idle and eng.state == "serving":
            break


_collect = harness.collect


def _victims(cfg, guided=False):
    """Low-tier (priority 1) long decodes — the preemption victims.
    Greedy and seeded-sampled; optionally one guided stream."""
    sp_greedy = SamplingParams(max_tokens=20, temperature=0.0,
                               ignore_eos=True, priority=1)
    sp_seeded = SamplingParams(max_tokens=20, temperature=0.9, top_p=0.9,
                               top_k=40, seed=21, ignore_eos=True, priority=1)
    reqs = [Request("bt-greedy", [5, 6, 7], sp_greedy),
            Request("bt-seeded", [9] * 5, sp_seeded)]
    if guided:
        reqs.append(Request("bt-guided", [8, 3, 4], SamplingParams(
            max_tokens=24, temperature=0.9, seed=33, ignore_eos=True,
            priority=1, guide=("regex", "[a-f]+"))))
    return reqs


def _latency_req(i=0, max_tokens=4):
    return Request(f"lt-{i}", [2, 2, 2, 3 + i], SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True, priority=0))


def _run_scenario(monkeypatch, depth, host_mb, preempt, inject=None,
                  guided=False, **kw):
    """One victim at a time on a 1-slot engine: admit a batch request,
    decode a few tokens, land a latency-tier arrival (the preemption
    trigger when enabled), drain, repeat for each victim."""
    cfg, eng = _mk_engine(monkeypatch, depth=depth, host_mb=host_mb,
                          preempt=preempt, inject=inject, **kw)
    outs = {}
    for i, victim in enumerate(_victims(cfg, guided=guided)):
        eng.add_request(victim)
        for _ in range(14):
            try:
                eng.step(block_s=0.01)
            except Exception as e:  # noqa: BLE001
                eng._recover_from_fault(e)
        lat = _latency_req(i)
        eng.add_request(lat)
        _drive(eng)
        outs[victim.request_id] = _collect(victim)
        outs[lat.request_id] = _collect(lat)
    return outs, eng


@pytest.mark.parametrize("depth", [0, 2])
def test_preempt_swap_streams_byte_identical(monkeypatch, depth):
    """Swap mode (host tier on): greedy, seeded, and guided victims are
    preempted mid-decode, swapped to host RAM, resumed — and every
    stream (victims AND the latency arrivals that seized their slots) is
    byte-identical to the preemption-off run, at depths 0 and 2."""
    base, _ = _run_scenario(monkeypatch, depth, 64, preempt=False,
                            guided=True)
    got, eng = _run_scenario(monkeypatch, depth, 64, preempt=True,
                             guided=True)
    assert eng.resolved_config["preempt"] == "swap"
    pre = eng.metrics.requests_preempted_total.total()
    assert pre >= 3, f"expected every victim preempted, got {pre}"
    assert got == base, "streams diverged across preempt on/off"
    # Host-byte hygiene: nothing left swapped out after drain.
    assert len(eng._swap) == 0
    assert eng._host.reserved == 0
    assert eng.metrics.requests_parked.get(reason="preempt") == 0


def test_preempt_swap_int4_pool_byte_identical(monkeypatch):
    """int4 KV pool through the preempt-swap path: the swap snapshot
    gathers raw PACKED pool bytes (nibble pairs + scale stripes), so a
    preempted-and-resumed victim's stream is byte-identical to the
    preemption-off run — the int4 counterpart of the swap-mode gate."""
    kw = dict(kv_cache_dtype="int4")
    base, _ = _run_scenario(monkeypatch, 0, 64, preempt=False, **kw)
    got, eng = _run_scenario(monkeypatch, 0, 64, preempt=True, **kw)
    assert eng._cache.kv_bits == 4
    assert eng.resolved_config["preempt"] == "swap"
    assert eng.metrics.requests_preempted_total.total() >= 2
    assert got == base, "int4 streams diverged across preempt on/off"
    assert len(eng._swap) == 0 and eng._host.reserved == 0


def test_preempt_replay_fallback_byte_identical(monkeypatch):
    """Replay mode (no host tier): preemption discards device state and
    re-enters the victim through token replay — streams still
    byte-identical.  This is the fallback-matrix row for slot-layout /
    pp>1 / dp engines (any engine without the host tier)."""
    base, _ = _run_scenario(monkeypatch, 0, 0, preempt=False)
    got, eng = _run_scenario(monkeypatch, 0, 0, preempt=True)
    assert eng.resolved_config["preempt"] == "replay"
    assert eng.metrics.requests_preempted_total.total() >= 2
    assert got == base, "replay-mode streams diverged across preempt on/off"


def test_spec_engine_preempts_via_replay(monkeypatch):
    """Fallback-matrix row for speculative engines: the draft cache has
    no swap snapshot, so a spec engine preempts in REPLAY mode even with
    the host tier on — and streams stay byte-identical."""
    kw = dict(draft_model="tiny", draft_len=3)
    base, _ = _run_scenario(monkeypatch, 0, 64, preempt=False, **kw)
    got, eng = _run_scenario(monkeypatch, 0, 64, preempt=True, **kw)
    assert eng.resolved_config["preempt"] == "replay"
    assert eng.metrics.requests_preempted_total.total() >= 2
    assert got == base, "spec streams diverged across preempt on/off"


@pytest.mark.chaos
@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("nth,where", [(1, "spill-issue"), (2, "harvest"),
                                       (3, "resume")],
                         ids=["spill-issue", "harvest", "resume"])
def test_preempt_fault_recovers_byte_identical(monkeypatch, depth, nth,
                                               where):
    """Chaos rows for the 'preempt' phase: a fault injected during the
    preempt spill issue (1st fire), the D2H harvest (2nd), or the victim
    resume (3rd) must quarantine only that attempt — the victim re-enters
    through token replay and EVERY stream completes byte-identically,
    with zero quarantined requests, at depths 0 and 2."""
    base, _ = _run_scenario(monkeypatch, depth, 64, preempt=False,
                            guided=True)
    got, eng = _run_scenario(monkeypatch, depth, 64, preempt=True,
                             inject=f"preempt:{nth}:runtime", guided=True)
    assert got == base, \
        f"streams diverged after a {where} fault (depth {depth})"
    assert eng.metrics.engine_faults_total.total() == 1
    assert eng.metrics.requests_quarantined_total.total() == 0
    assert eng.state == "serving"
    assert len(eng._swap) == 0
    assert eng._host.reserved == 0


def test_abort_while_swapped_releases_host_bytes(monkeypatch):
    """Aborting a victim while its decode state sits in host RAM must
    free the SwapStore bytes (and the shared tier budget reservation)
    and resolve the request as an abort — with the parked/waiting gauges
    landing at exactly zero, never negative."""
    cfg, eng = _mk_engine(monkeypatch, preempt=True)
    victim = Request("victim", [5, 6, 7], SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True, priority=1))
    eng.add_request(victim)
    for _ in range(14):
        eng.step(block_s=0.01)
    lat = _latency_req(0, max_tokens=30)
    eng.add_request(lat)
    # Step until the victim's swap landed in the SwapStore (it stays
    # there while the latency request holds the only slot).
    for _ in range(400):
        eng.step(block_s=0.01)
        if "victim" in eng._swapped and "victim" in eng._swap:
            break
    else:
        pytest.fail("victim never reached the swapped-out state")
    assert eng._swap.bytes_used > 0
    assert eng._host.reserved > 0
    assert eng.metrics.requests_parked.get(reason="preempt") >= 1
    eng.abort("victim")
    _drive(eng)
    ids, fin = _collect(victim)
    assert fin.finish_reason == "abort"
    _collect(lat)
    assert len(eng._swap) == 0 and eng._swap.bytes_used == 0
    assert eng._host.reserved == 0
    assert eng.metrics.requests_parked.get(reason="preempt") == 0
    assert eng.metrics.num_requests_waiting.get() >= 0
    for key, v in eng.metrics.requests_parked._values.items():
        assert v >= 0, (key, v)


def test_swap_shares_the_host_tier_byte_budget(monkeypatch):
    """The SwapStore carves its bytes out of the host prefix tier's
    budget (reserved), so a swap can evict prefix blocks but the
    combined footprint never exceeds ARKS_PREFIX_HOST_MB."""
    cfg, eng = _mk_engine(monkeypatch, preempt=True, host_mb=64)
    victim = Request("victim", [5, 6, 7], SamplingParams(
        max_tokens=40, temperature=0.0, ignore_eos=True, priority=1))
    eng.add_request(victim)
    for _ in range(14):
        eng.step(block_s=0.01)
    eng.add_request(_latency_req(0, max_tokens=30))
    for _ in range(400):
        eng.step(block_s=0.01)
        if "victim" in eng._swap:
            break
    else:
        pytest.fail("victim never swapped out")
    t = eng._host
    assert t.reserved == eng._swap.bytes_used
    assert t._bytes + t.reserved <= t.capacity
    _drive(eng)
    assert t.reserved == 0


def test_queue_aging_admits_starved_batch_request(monkeypatch):
    """ARKS_QUEUE_AGING_S regression: under sustained latency-tier load
    that would otherwise starve it forever, a batch-tier request's
    effective priority decays to 0 and it admits (and finishes)."""
    monkeypatch.setenv("ARKS_QUEUE_AGING_S", "0.05")
    cfg, eng = _mk_engine(monkeypatch, preempt=False)
    starved = Request("starved", [7, 7, 7], SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True, priority=1))
    eng.add_request(starved)
    fin = None
    i = 0
    for _ in range(1500):
        # Sustained latency-tier pressure: keep the queue non-empty with
        # priority-0 arrivals so, without aging, "starved" never reaches
        # the head.
        if eng._queue.qsize() < 2:
            eng.add_request(_latency_req(i, max_tokens=2))
            i += 1
        eng.step(block_s=0.01)
        while not starved.outputs.empty():
            out = starved.outputs.get_nowait()
            if out.finished:
                fin = out
        if fin is not None:
            break
    assert fin is not None, "batch request starved despite ARKS_QUEUE_AGING_S"
    assert fin.finish_reason == "length"


def test_aging_disabled_keeps_strict_priority_order(monkeypatch):
    """With aging off (the default), a continuous latency-tier stream
    keeps the batch request queued — the behavior aging exists to fix
    (and the control run that makes the regression above meaningful)."""
    monkeypatch.delenv("ARKS_QUEUE_AGING_S", raising=False)
    cfg, eng = _mk_engine(monkeypatch, preempt=False)
    starved = Request("starved", [7, 7, 7], SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True, priority=1))
    eng.add_request(starved)
    i = 0
    for _ in range(300):
        if eng._queue.qsize() < 2:
            eng.add_request(_latency_req(i, max_tokens=2))
            i += 1
        eng.step(block_s=0.01)
        assert starved.outputs.empty(), \
            "batch request admitted without aging — control run is broken"
