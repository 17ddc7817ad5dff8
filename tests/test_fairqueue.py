"""Tenant-fair admission queue (arks_tpu.engine.fairqueue) unit tests.

The invariance contracts the module doc promises are the tests here:
single-tenant order is byte-for-byte the old tier-FIFO order, WDRR
interleaves tenants by token bandwidth (weighted), tiers stay strict,
the urgent lane (priority < 0) preempts everything and dodges bounds,
bounded puts raise typed QueueFullError with a usable Retry-After, and
aging promotes per-tenant in arrival order.  tenancy helpers (weight
parsing, bounded labels) ride along — same PR, same contracts.
"""

import queue as stdq
import time

import pytest

from arks_tpu import tenancy
from arks_tpu.engine.fairqueue import FairQueue, QueueFullError, request_cost
from arks_tpu.engine.types import Request, SamplingParams


def _req(rid, tenant=None, prompt=3, max_tokens=2, priority=0):
    return Request(rid, [7] * prompt,
                   SamplingParams(max_tokens=max_tokens, priority=priority),
                   tenant=tenant)


def _q(**kw):
    kw.setdefault("fair", True)
    kw.setdefault("quantum", 1)
    kw.setdefault("weights", {})
    kw.setdefault("max_total", 0)
    kw.setdefault("max_tenant", 0)
    return FairQueue(**kw)


def _pop_all(q):
    out = []
    while not q.empty():
        out.append(q.get_nowait()[2].request_id)
    return out


# ---------------------------------------------------------------- ordering


def test_single_tenant_keeps_tier_then_fifo_order():
    """With one tenant the fair queue must reproduce the old
    PriorityQueue schedule exactly (untenanted deployments unchanged)."""
    q = _q()
    items = [(1, 0, _req("r0", priority=1)), (0, 1, _req("r1")),
             (2, 2, _req("r2", priority=2)), (0, 3, _req("r3")),
             (1, 4, _req("r4", priority=1))]
    for it in items:
        q.put(it)
    assert _pop_all(q) == ["r1", "r3", "r0", "r4", "r2"]


def test_two_tenants_interleave_within_a_tier():
    q = _q()
    for i in range(3):
        q.put((0, 2 * i, _req(f"a{i}", tenant="ns/a")))
        q.put((0, 2 * i + 1, _req(f"b{i}", tenant="ns/b")))
    order = _pop_all(q)
    # Each tenant's own order is FIFO, and service interleaves: DRR
    # guarantees bandwidth fairness (both tenants appear in every window
    # of three picks), not strict alternation.
    assert [r for r in order if r.startswith("a")] == ["a0", "a1", "a2"]
    assert [r for r in order if r.startswith("b")] == ["b0", "b1", "b2"]
    for w in (order[i:i + 3] for i in range(len(order) - 2)):
        assert len({r[0] for r in w}) == 2, order


def test_flood_does_not_starve_the_other_tenant():
    q = _q()
    for i in range(50):
        q.put((0, i, _req(f"a{i}", tenant="ns/flood")))
    q.put((0, 50, _req("v0", tenant="ns/victim")))
    order = _pop_all(q)
    # The victim is served within a couple of picks, not after the flood.
    assert order.index("v0") <= 2, order


def test_weights_bias_token_bandwidth():
    q = _q(weights={"ns/a": 2.0})
    for i in range(30):
        q.put((0, 2 * i, _req(f"a{i}", tenant="ns/a")))
        q.put((0, 2 * i + 1, _req(f"b{i}", tenant="ns/b")))
    first = [q.get_nowait()[2].request_id for _ in range(18)]
    n_a = sum(1 for r in first if r.startswith("a"))
    # weight 2 vs 1 with equal request costs: ~2/3 of picks go to a.
    assert 10 <= n_a <= 14, first


def test_tiers_stay_strict_across_tenants():
    q = _q()
    q.put((1, 0, _req("slow-a", tenant="ns/a", priority=1)))
    q.put((0, 1, _req("fast-b", tenant="ns/b")))
    q.put((1, 2, _req("slow-b", tenant="ns/b", priority=1)))
    q.put((0, 3, _req("fast-a", tenant="ns/a")))
    order = _pop_all(q)
    assert set(order[:2]) == {"fast-b", "fast-a"}
    assert set(order[2:]) == {"slow-a", "slow-b"}


def test_urgent_lane_served_first_and_exempt_from_bounds():
    q = _q(max_total=1)
    q.put((0, 0, _req("normal")), bounded=True)
    # Replayers carry priority - 2**20: never bounded, always first.
    q.put((-2 ** 20, 1, _req("replay")), bounded=True)
    assert q.get_nowait()[2].request_id == "replay"
    assert q.get_nowait()[2].request_id == "normal"


# ------------------------------------------------------------------ bounds


def test_total_bound_raises_scope_queue():
    q = _q(max_total=2)
    q.put((0, 0, _req("r0", tenant="ns/a")), bounded=True)
    q.put((0, 1, _req("r1", tenant="ns/b")), bounded=True)
    with pytest.raises(QueueFullError) as ei:
        q.put((0, 2, _req("r2", tenant="ns/c")), bounded=True)
    assert ei.value.scope == "queue"
    assert ei.value.retry_after >= 1
    assert q.qsize() == 2


def test_tenant_bound_raises_scope_tenant_and_spares_others():
    q = _q(max_tenant=2)
    q.put((0, 0, _req("a0", tenant="ns/a")), bounded=True)
    q.put((0, 1, _req("a1", tenant="ns/a")), bounded=True)
    with pytest.raises(QueueFullError) as ei:
        q.put((0, 2, _req("a2", tenant="ns/a")), bounded=True)
    assert ei.value.scope == "tenant"
    assert ei.value.tenant == "ns/a"
    # The other tenant still has room.
    q.put((0, 3, _req("b0", tenant="ns/b")), bounded=True)
    assert q.qsize() == 3


def test_unbounded_put_ignores_caps():
    """Engine-internal re-queues (fault survivors, preempt replay) must
    never be shed: the engine already accepted these requests."""
    q = _q(max_total=1)
    q.put((0, 0, _req("r0")), bounded=True)
    q.put((0, 1, _req("r1")))          # internal re-queue
    assert q.qsize() == 2


def test_plain_mode_bounds_apply_too():
    q = _q(fair=False, max_tenant=1)
    q.put((0, 0, _req("a0", tenant="ns/a")), bounded=True)
    with pytest.raises(QueueFullError):
        q.put((0, 1, _req("a1", tenant="ns/a")), bounded=True)


# ------------------------------------------------------------- plain mode


def test_plain_mode_is_the_old_heap():
    q = _q(fair=False)
    for i in range(40):
        q.put((0, i, _req(f"a{i}", tenant="ns/flood")))
    q.put((0, 40, _req("v0", tenant="ns/victim")))
    order = _pop_all(q)
    # FIFO within the tier: the victim waits behind the whole flood —
    # exactly the starvation the fair mode exists to fix (and the bench's
    # ARKS_FAIR=0 control arm).
    assert order.index("v0") == 40


# ----------------------------------------------------------------- blocking


def test_get_timeout_raises_stdlib_empty():
    q = _q()
    t0 = time.monotonic()
    with pytest.raises(stdq.Empty):
        q.get(timeout=0.05)
    assert time.monotonic() - t0 < 5.0
    assert q.head_prio() is None


def test_head_prio_reports_best_tier():
    q = _q()
    q.put((2, 0, _req("r0", priority=2)))
    assert q.head_prio() == 2
    q.put((0, 1, _req("r1")))
    assert q.head_prio() == 0
    q.put((-5, 2, _req("r2")))
    assert q.head_prio() == -5


# -------------------------------------------------------------------- aging


def test_aging_promotes_in_arrival_order():
    q = _q()
    old_a = _req("old-a", tenant="ns/a", priority=2)
    old_b = _req("old-b", tenant="ns/a", priority=2)
    old_a.arrival_time -= 10
    old_b.arrival_time -= 10
    q.put((2, 0, old_a))
    q.put((2, 1, old_b))
    q.put((0, 2, _req("fresh", tenant="ns/a")))
    q.age_tick(time.monotonic(), aging_s=4.0)
    # elapsed 10s / 4s = 2 rungs: both tier-2 entries reach tier 0, in
    # arrival order, behind nothing (same tier now) — seq keeps them
    # ordered among themselves and against the fresh tier-0 entry.
    order = _pop_all(q)
    assert order == ["old-a", "old-b", "fresh"]


def test_aging_plain_mode_matches():
    q = _q(fair=False)
    old = _req("old", priority=2)
    old.arrival_time -= 10
    q.put((2, 0, old))
    q.put((1, 1, _req("mid", priority=1)))
    q.age_tick(time.monotonic(), aging_s=4.0)
    assert _pop_all(q) == ["old", "mid"]


def test_aging_never_touches_urgent():
    q = _q()
    r = _req("replay")
    r.arrival_time -= 100
    q.put((-2 ** 20, 0, r))
    q.age_tick(time.monotonic(), aging_s=1.0)
    assert q.get_nowait()[0] == -2 ** 20


# -------------------------------------------------- retry-after / saturation


def test_retry_after_defaults_without_drain_evidence():
    q = _q()
    assert q.retry_after() == 5


def test_retry_after_derives_from_drain_rate():
    q = _q()
    for i in range(64):
        q.put((0, i, _req(f"r{i}")))
    for _ in range(32):
        q.get_nowait()
    ra = q.retry_after()
    assert 1 <= ra <= 120


def test_saturation_report():
    q = _q(max_total=10)
    for i in range(5):
        q.put((0, i, _req(f"r{i}", tenant=f"ns/t{i % 2}")))
    s = q.saturation()
    assert s["queue_depth"] == 5
    assert s["queue_max"] == 10
    assert s["tenants_waiting"] == 2
    assert s["saturation"] == 0.5
    assert s["fair"] is True


def test_request_cost_floor():
    assert request_cost(_req("r", prompt=0, max_tokens=0)) == 1
    assert request_cost(_req("r", prompt=3, max_tokens=2)) == 5


# ------------------------------------------------------------------ tenancy


def test_parse_weights():
    assert tenancy.parse_weights("ns/a:2,ns/b:0.5") == {
        "ns/a": 2.0, "ns/b": 0.5}
    with pytest.raises(ValueError):
        tenancy.parse_weights("ns/a")
    with pytest.raises(ValueError):
        tenancy.parse_weights("ns/a:zero")
    with pytest.raises(ValueError):
        tenancy.parse_weights("ns/a:0")


def test_tenant_labels_bounded():
    labels = tenancy.TenantLabels(cap=3)
    assert labels.label("ns/a") == "ns/a"
    assert labels.label("ns/b") == "ns/b"
    assert labels.label(None) == tenancy.DEFAULT_TENANT
    # Cap reached: every later tenant shares the overflow bucket, known
    # tenants keep their own label.
    assert labels.label("ns/late") == tenancy.OTHER_LABEL
    assert labels.label("ns/a") == "ns/a"
    with pytest.raises(ValueError):
        tenancy.TenantLabels(cap=0)


# ------------------------------------------------- the engine under a flood
#
# One aggressor tenant keeps a standing backlog of short streams while a
# victim tenant submits one request at a time: the same SLO tier, so only
# the weighted-fair queue separates them.  Counts, not clocks: the steps
# the engine takes before a victim's stream ends.

AGG, VIC = "flood/aggressor", "flood/victim"
WAVES, FLOOD = 4, 12


def _flood_engine(monkeypatch, depth, fair, **env):
    from arks_tpu.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_FAIR", "1" if fair else "0")
    # A quantum of a few requests (costs here are 5-17 tokens): the default
    # 512 lets one ring visit drain a whole backlog before it rotates.
    monkeypatch.setenv("ARKS_FAIR_QUANTUM_TOKENS", "8")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    eng = InferenceEngine(get_config("tiny"), EngineConfig(
        model="tiny", num_slots=4, max_cache_len=64, prefill_buckets=(16,),
        steps_per_dispatch=1, prefill_chunk=16, kv_layout="paged",
        prefix_cache_mb=0), ByteTokenizer())
    if depth:
        assert eng._pipe_warm_wait(120) == "ready"
    return eng


def _agg_req(i):
    return Request(f"agg-{i}", [3 + (i % 5), 5, 7], SamplingParams(
        max_tokens=1, temperature=0.9, top_p=0.9, seed=31 + i,
        ignore_eos=True), tenant=AGG)


def _vic_req(i):
    return Request(f"vic-{i}", [9] * 14 + [2 + (i % 3)], SamplingParams(
        max_tokens=2, temperature=0.8, seed=77 + i, ignore_eos=True),
        tenant=VIC)


def _finish(req):
    toks, fin = [], None
    while fin is None:
        out = req.outputs.get(timeout=120)
        toks.extend(out.token_ids)
        fin = out if out.finished else None
    return toks, fin


def _contended(monkeypatch, depth, fair):
    """The victim's requests one at a time behind a standing backlog of
    FLOOD aggressor requests.  Returns every stream by request id, the
    engine steps each victim took from submission to its last token, and
    whether every finished stream's metered usage equals what it got."""
    eng = _flood_engine(monkeypatch, depth, fair)
    backlog, n_agg = [], 0

    def top_up():
        nonlocal n_agg
        while eng.saturation()["queue_depth"] < FLOOD:
            backlog.append(_agg_req(n_agg))
            eng.add_request(backlog[-1])
            n_agg += 1

    top_up()
    for _ in range(8):          # the flood fills every slot first
        eng.step(block_s=0.01)
    streams, steps, exact = {}, [], True
    for i in range(WAVES):
        top_up()
        v = _vic_req(i)
        eng.add_request(v)
        n = 0
        while v.outputs.empty() or not v.outputs.queue[-1].finished:
            eng.step(block_s=0.01)
            n += 1
            assert n < 5000, "the flood workload did not progress"
        toks, fin = _finish(v)
        steps.append(n)
        streams[v.request_id] = toks
        exact &= fin.num_generated_tokens == len(toks) == v.params.max_tokens
    while not eng.idle:
        eng.step(block_s=0.01)
    for r in backlog:
        toks, fin = _finish(r)
        streams[r.request_id] = toks
        exact &= fin.num_generated_tokens == len(toks) == r.params.max_tokens
    eng.stop()
    return streams, steps, exact


@pytest.mark.parametrize("depth", [0, 2])
def test_engine_fair_queue_is_a_pure_admission_reorder(monkeypatch, depth):
    """Fairness on against off under the same flood: every request both
    arms served streams the same bytes (the fair queue reorders admission,
    nothing else), metered usage is exact in both, and the victim reaches
    its last token in fewer engine steps with fairness on, where the flat
    heap makes it wait out the backlog ahead of it."""
    on, on_steps, on_exact = _contended(monkeypatch, depth, fair=True)
    off, off_steps, off_exact = _contended(monkeypatch, depth, fair=False)
    assert on_exact and off_exact, "metered usage is not what was delivered"
    common = set(on) & set(off)
    assert all(f"vic-{i}" in common for i in range(WAVES))
    assert [k for k in sorted(common) if on[k] != off[k]] == []
    assert sum(on_steps) < sum(off_steps), (on_steps, off_steps)
    assert max(on_steps) < max(off_steps), (on_steps, off_steps)


def test_engine_tenant_cap_sheds_the_flood_and_spares_the_victim(monkeypatch):
    """ARKS_QUEUE_TENANT_MAX at the engine's door: a 10-request flood from
    one tenant is shed at its cap with scope "tenant" and a usable
    Retry-After, and the other tenant's request is still admitted and
    served."""
    eng = _flood_engine(monkeypatch, 0, True, ARKS_QUEUE_TENANT_MAX="4")
    sheds, kept = [], []
    for i in range(10):
        r = _agg_req(i)
        try:
            eng.add_request(r)
            kept.append(r)
        except QueueFullError as e:
            sheds.append(e)
    assert sheds and len(kept) >= 4
    assert all(e.scope == "tenant" and e.retry_after >= 1 for e in sheds)
    v = _vic_req(0)
    eng.add_request(v)
    while not eng.idle:
        eng.step(block_s=0.01)
    assert _finish(v)[1].finish_reason == "length"
    assert all(_finish(r)[1].finish_reason == "length" for r in kept)
    eng.stop()
