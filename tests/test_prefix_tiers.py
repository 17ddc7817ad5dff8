"""Hierarchical prefix KV cache: device page index (tier 0) + host-RAM
spill tier (tier 1).

Acceptance surface for the tiered cache:

- token streams are BYTE-IDENTICAL with the host tier enabled vs
  disabled (greedy + seeded, paged/mixed, pipeline depths 0 and 2);
- a prompt whose prefix was evicted from the device index is served from
  the host tier with ZERO re-prefill of the hit blocks (chunk-token
  dispatch accounting), and the restore never blocks the issue path
  (tests/test_hotpath_guard.py covers the AST side);
- kv-quantized pools spill/restore raw int8 blocks + scales;
- aborts, engine drain, and the disaggregated publish path behave.
"""

import numpy as np
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.prefix_cache import HostPrefixTier
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness

CHUNK = 16  # page size for every engine below


def _mk_engine(monkeypatch, host_mb, depth=0, mixed="auto", **kw):
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", mixed)
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", str(host_mb))
    # prefix_cache_mb=0: zero retention surplus, so finished prompts'
    # index-retained pages are evicted (and spilled) by the next
    # admissions — the shape that exercises the tiers hardest.
    eng = harness.warmed("tiny", base=dict(
        num_slots=2, max_cache_len=64, prefill_buckets=(8, 16, 32),
        steps_per_dispatch=4, prefill_chunk=CHUNK, kv_layout="paged",
        prefix_cache_mb=0), **kw)
    return eng.cfg, eng


def _drive(eng, n_steps=4000):
    for _ in range(n_steps):
        eng.step(block_s=0.01)
        if eng.idle:
            break


def _run_one(eng, req):
    eng.add_request(req)
    _drive(eng)
    toks, fin = [], None
    while True:
        out = req.outputs.get(timeout=120)
        toks.extend(out.token_ids)
        if out.finished:
            fin = out
            break
    return toks, fin


def _workload(cfg):
    """Sequential multi-turn-ish workload: a warm prompt, churn that
    evicts it, then the warm prompt again (the tier-1 hit in enabled
    runs).  Greedy and seeded-sampled, one-shot and chunked lengths."""
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]   # 2 pages + tail
    churn = [[(7 + i) % cfg.vocab_size] * 33 for i in range(5)]
    reqs = [("warm1", warm, 0.0, None),
            *[(f"churn{i}", c, 0.0, None) for i, c in enumerate(churn)],
            ("warm2", warm, 0.0, None),
            ("warm3", warm, 0.9, 21)]
    return [Request(rid, ids, SamplingParams(
        max_tokens=6, temperature=temp, top_p=0.9, top_k=40, seed=seed,
        ignore_eos=True)) for rid, ids, temp, seed in reqs]


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("mixed", ["0", "auto"],
                         ids=["paged-legacy", "paged-mixed"])
def test_streams_byte_identical_with_host_tier_on_and_off(
        monkeypatch, depth, mixed):
    """The host tier is a pure schedule optimization: every stream's
    tokens and finish reasons must be byte-identical with it enabled or
    disabled, on both paged scheduler flavors and at pipeline depths 0
    and 2 — restored pages carry the exact bytes a re-prefill would have
    written."""
    outs = {}
    for host_mb in (0, 64):
        cfg, eng = _mk_engine(monkeypatch, host_mb, depth=depth, mixed=mixed)
        assert (eng._host is not None) == bool(host_mb)
        outs[host_mb] = [_run_one(eng, r) for r in _workload(cfg)]
        if host_mb:
            # The enabled run actually exercised the tier (otherwise the
            # parity assertion is vacuous).
            assert eng.metrics.prefix_restore_blocks_total.total() > 0, \
                "workload never restored from the host tier"
    assert [(t, f.finish_reason) for t, f in outs[64]] == \
           [(t, f.finish_reason) for t, f in outs[0]]


def test_evicted_prefix_restores_with_zero_reprefill(monkeypatch):
    """After churn evicts a prompt's pages from the device index, its
    repeat must be served from the host tier: only the un-hit tail goes
    through chunked prefill (chunk-token accounting — the dispatch-count
    assertion), the restore counters advance, and the restore latency
    histogram observes."""
    cfg, eng = _mk_engine(monkeypatch, 64)
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]   # 33 tokens
    t1, _ = _run_one(eng, Request("w1", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True)))
    for i in range(5):
        _run_one(eng, Request(f"c{i}", [(9 + i) % cfg.vocab_size] * 33,
                              SamplingParams(max_tokens=3, temperature=0.0,
                                             ignore_eos=True)))
    # The warm prompt's 2 full pages fell out of the device index and
    # were spilled to the host tier.
    from arks_tpu.engine.paged import chain_digests
    digs = chain_digests(warm, CHUNK, 2)
    assert all(eng._host.has(d) for d in digs), "spill never landed"
    assert eng.metrics.prefix_spill_blocks_total.total() >= 2

    chunk0 = eng.metrics.mixed_chunk_tokens_total.total()
    host_hit0 = eng.metrics.prefix_cache_hit_tokens_total.get(tier="host")
    t2, _ = _run_one(eng, Request("w2", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True)))
    assert t2 == t1
    # 2 pages (32 tokens) restored; ONLY the 1-token tail was prefilled.
    assert eng.metrics.prefix_cache_hit_tokens_total.get(
        tier="host") - host_hit0 == 32
    assert eng.metrics.mixed_chunk_tokens_total.total() - chunk0 == \
        len(warm) - 32
    assert eng.metrics.prefix_restore_blocks_total.total() == 2
    assert eng.metrics.prefix_restore_seconds._data, \
        "restore latency never observed"
    # The restore repopulated tier 0: pages retained under the digests.
    probe = eng._alloc.match(digs)
    assert len(probe) == 2
    eng._alloc.decref(probe)


def test_quantized_pool_spills_int8_blocks(monkeypatch):
    """kv-int8 pools spill RAW int8 pages + per-token scales (half the
    host bytes, zero re-quantization drift) and restores stay
    byte-identical."""
    outs = {}
    for host_mb in (0, 64):
        cfg, eng = _mk_engine(monkeypatch, host_mb, kv_cache_dtype="int8")
        outs[host_mb] = [_run_one(eng, r) for r in _workload(cfg)]
        if host_mb:
            assert eng.metrics.prefix_restore_blocks_total.total() > 0
            blk = next(iter(eng._host._blocks.values()))
            assert blk["k"].dtype == np.int8
            assert blk["k_scale"].dtype == np.float32
    assert [(t, f.finish_reason) for t, f in outs[64]] == \
           [(t, f.finish_reason) for t, f in outs[0]]


def test_int4_pool_spill_restore_bit_exact(monkeypatch):
    """kv-int4 pools spill RAW packed pages (token pairs per byte + f32
    per-token scales — a quarter of the bf16 host bytes) and a restore
    lands the EXACT bytes back in the pool: stream parity with the tier
    on/off, plus a direct byte comparison of the restored device page
    against the spilled host block."""
    outs = {}
    for host_mb in (0, 64):
        cfg, eng = _mk_engine(monkeypatch, host_mb, kv_cache_dtype="int4")
        assert eng._cache.kv_bits == 4
        outs[host_mb] = [_run_one(eng, r) for r in _workload(cfg)]
        if host_mb:
            assert eng.metrics.prefix_restore_blocks_total.total() > 0
            blk = next(iter(eng._host._blocks.values()))
            assert blk["k"].dtype == np.int8
            # Packed: half the token rows of the scale stripe.
            assert blk["k"].shape[-2] * 2 == blk["k_scale"].shape[-1]
    assert [(t, f.finish_reason) for t, f in outs[64]] == \
           [(t, f.finish_reason) for t, f in outs[0]]

    # Direct bit-exactness: spill a warm prompt's pages, restore them,
    # and compare the device page bytes against the host block.
    cfg, eng = _mk_engine(monkeypatch, 64, kv_cache_dtype="int4")
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]
    _run_one(eng, Request("w1", warm, SamplingParams(
        max_tokens=3, temperature=0.0, ignore_eos=True)))
    for i in range(5):
        _run_one(eng, Request(f"c{i}", [(9 + i) % cfg.vocab_size] * 33,
                              SamplingParams(max_tokens=3, temperature=0.0,
                                             ignore_eos=True)))
    from arks_tpu.engine.paged import chain_digests
    digs = chain_digests(warm, CHUNK, 2)
    assert all(eng._host.has(d) for d in digs), "spill never landed"
    host_blks = [{k: np.array(v) for k, v in eng._host._blocks[d].items()}
                 for d in digs]
    _run_one(eng, Request("w2", warm, SamplingParams(
        max_tokens=3, temperature=0.0, ignore_eos=True)))
    pages = eng._alloc.match(digs)
    assert len(pages) == 2
    for pg, blk in zip(pages, host_blks):
        np.testing.assert_array_equal(
            np.asarray(eng._cache.k[:, pg]), blk["k"])
        np.testing.assert_array_equal(
            np.asarray(eng._cache.v[:, pg]), blk["v"])
        np.testing.assert_array_equal(
            np.asarray(eng._cache.k_scale[:, pg]), blk["k_scale"])
    eng._alloc.decref(pages)


def test_abort_while_parked_on_restore(monkeypatch):
    """An abort raised while the request is parked in awaiting_restore
    finishes it as "abort" and releases every page it held (refcount
    accounting: all non-retained pages return to the free list)."""
    cfg, eng = _mk_engine(monkeypatch, 64)
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]
    _run_one(eng, Request("w1", warm, SamplingParams(
        max_tokens=3, temperature=0.0, ignore_eos=True)))
    for i in range(5):
        _run_one(eng, Request(f"c{i}", [(9 + i) % cfg.vocab_size] * 33,
                              SamplingParams(max_tokens=3, temperature=0.0,
                                             ignore_eos=True)))
    req = Request("victim", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    eng.add_request(req)
    # Step until the request parks, then abort before it can unpark.
    for _ in range(200):
        eng.step(block_s=0.01)
        if eng._awaiting_restore:
            break
    assert eng._awaiting_restore, "request never parked on the restore"
    eng.abort("victim")
    _drive(eng)
    out = req.outputs.get(timeout=60)
    assert out.finished and out.finish_reason == "abort"
    assert not eng._awaiting_restore
    assert eng._alloc.free_pages == (
        eng._alloc.num_pages - eng._alloc.retained_pages)


def test_engine_drain_aborts_parked_restores(monkeypatch):
    """Engine stop with a request parked on a restore must fail it as
    "abort" (no scheduler remains to unpark it) — the SIGTERM-drain
    contract extended to the new park state."""
    cfg, eng = _mk_engine(monkeypatch, 64)
    warm = [int(x) % cfg.vocab_size for x in range(3, 36)]
    _run_one(eng, Request("w1", warm, SamplingParams(
        max_tokens=3, temperature=0.0, ignore_eos=True)))
    for i in range(5):
        _run_one(eng, Request(f"c{i}", [(9 + i) % cfg.vocab_size] * 33,
                              SamplingParams(max_tokens=3, temperature=0.0,
                                             ignore_eos=True)))
    req = Request("parked", warm, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    eng.add_request(req)
    for _ in range(200):
        eng.step(block_s=0.01)
        if eng._awaiting_restore:
            break
    assert eng._awaiting_restore
    assert not eng.idle  # a parked restore is in-flight work
    eng._abort_awaiting_restores()
    out = req.outputs.get(timeout=60)
    assert out.finished and out.finish_reason == "abort"


def test_disagg_prefill_publishes_into_host_tier(monkeypatch):
    """A disaggregated admission (prefilled KV + prompt ids) registers
    the inserted pages in the device index AND publishes them into the
    host tier, so a decode-side device reset keeps the warm prefix."""
    from arks_tpu.engine.types import PrefilledState

    cfg, eng = _mk_engine(monkeypatch, 64, num_slots=2)
    # 32 tokens: the one-shot disagg limit, and exactly 2 full pages.
    ids = [int(x) % cfg.vocab_size for x in range(5, 37)]
    pf = eng.prefill_detached(ids, SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True))
    assert pf.prompt_ids == ids  # the wire meta carries the prompt
    req = Request("dg", [], SamplingParams(
        max_tokens=4, temperature=0.0, ignore_eos=True), prefilled=pf)
    _run_one(eng, req)
    _drive(eng)  # let the spill resolve
    eng._resolve_spills(force=True)
    from arks_tpu.engine.paged import chain_digests
    digs = chain_digests(ids, CHUNK, 2)
    assert all(eng._host.has(d) for d in digs), \
        "disagg prefill was not published into the host tier"
    # Survives the device rebuild (the "decode-side restart" property).
    eng._reset_device_state()
    assert all(eng._host.has(d) for d in digs)


def test_resolved_config_reports_host_budget(monkeypatch):
    _, on = _mk_engine(monkeypatch, 32)
    assert on.resolved_config["prefix_host_mb"] == "32"
    _, off = _mk_engine(monkeypatch, 0)
    assert off.resolved_config["prefix_host_mb"] == "0"
    # Slot-layout engines never build the tier regardless of the budget.
    cfg = get_config("tiny")
    slot = InferenceEngine(cfg, EngineConfig(
        model="tiny", num_slots=2, max_cache_len=64,
        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
        prefill_chunk=16, kv_layout="slot"), ByteTokenizer())
    assert slot.resolved_config["prefix_host_mb"] == "0"
    assert slot._host is None


# ---------------------------------------------------------------------------
# HostPrefixTier unit semantics
# ---------------------------------------------------------------------------


def _blk(seed, nbytes=256):
    rng = np.random.default_rng(seed)
    return {"k": rng.standard_normal(nbytes // 8).astype(np.float32),
            "v": rng.standard_normal(nbytes // 8).astype(np.float32)}


def test_host_tier_lru_eviction_by_bytes():
    blk = _blk(0)
    per = sum(a.nbytes for a in blk.values())
    tier = HostPrefixTier(16, capacity_bytes=2 * per)
    assert tier.put(b"a", _blk(1))
    assert tier.put(b"b", _blk(2))
    assert tier.match_blocks([b"a"], 0)          # touch a -> b is LRU
    assert tier.put(b"c", _blk(3))
    assert tier.has(b"a") and tier.has(b"c") and not tier.has(b"b")
    assert tier.bytes_used <= 2 * per
    # Duplicate put is a no-op touch, not a second copy.
    before = tier.bytes_used
    assert not tier.put(b"a", _blk(1))
    assert tier.bytes_used == before


def test_host_tier_match_blocks_is_consecutive():
    tier = HostPrefixTier(16, capacity_bytes=1 << 20)
    for d in (b"d0", b"d1", b"d3"):
        tier.put(d, _blk(hash(d) % 100))
    # The chain stops at the first missing digest (d2), even though d3
    # is present — a restore must never leave holes in the prefix.
    got = tier.match_blocks([b"d0", b"d1", b"d2", b"d3"], 0)
    assert len(got) == 2
    assert tier.match_blocks([b"d0", b"d1", b"d2", b"d3"], 3) == \
        [tier._blocks[b"d3"]]
    assert tier.match_blocks([b"x"], 0) == []


def test_host_tier_clear():
    tier = HostPrefixTier(16, capacity_bytes=1 << 20)
    tier.put(b"a", _blk(1))
    tier.clear()
    assert tier.bytes_used == 0 and not tier.has(b"a")
