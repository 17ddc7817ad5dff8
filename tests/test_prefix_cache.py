"""Prefix KV cache: block store semantics + engine-level reuse.

VERDICT acceptance for the prefix-caching item: reuse exercised end to end
with the cache-hit-rate metric asserted."""

import numpy as np
import pytest

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.prefix_cache import PrefixKVCache
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

import harness


def _kv(t, seed=0):
    rng = np.random.default_rng(seed)
    shape = (2, 1, t, 2, 4)  # [L, 1, T, Hkv, D]
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# Block store
# ---------------------------------------------------------------------------


def test_match_walks_hash_chain():
    pc = PrefixKVCache(block_tokens=4, capacity_bytes=1 << 20)
    ids = list(range(12))
    k, v = _kv(12)
    pc.put(ids, k, v, 12)
    assert pc.match(ids) == 12
    # Shared prefix matches exactly as far as tokens agree (block-aligned).
    assert pc.match(ids[:8] + [99, 98, 97, 96]) == 8
    assert pc.match([99] + ids[1:]) == 0
    # Sub-block queries can't match.
    assert pc.match(ids[:3]) == 0


def test_get_roundtrips_blocks():
    pc = PrefixKVCache(block_tokens=4, capacity_bytes=1 << 20)
    ids = list(range(8))
    k, v = _kv(8)
    pc.put(ids, k, v, 8)
    gk, gv = pc.get(ids, 8)
    np.testing.assert_array_equal(gk, k)
    np.testing.assert_array_equal(gv, v)
    gk4, _ = pc.get(ids, 4)
    np.testing.assert_array_equal(gk4, k[:, :, :4])


def test_shared_prefix_stored_once():
    pc = PrefixKVCache(block_tokens=4, capacity_bytes=1 << 20)
    a = list(range(8))
    b = list(range(4)) + [50, 51, 52, 53]
    k, v = _kv(8)
    pc.put(a, k, v, 8)
    used = pc.bytes_used
    pc.put(b, k, v, 8)  # first block identical -> only one new block stored
    per_block = used // 2
    assert pc.bytes_used == used + per_block


def test_lru_eviction_by_bytes():
    k, v = _kv(4)
    per_block = k.nbytes + v.nbytes
    pc = PrefixKVCache(block_tokens=4, capacity_bytes=2 * per_block)
    pc.put(list(range(4)), k, v, 4)
    pc.put(list(range(100, 104)), k, v, 4)
    assert pc.match(list(range(4))) == 4
    # Touch the first entry so the second is LRU.
    pc.get(list(range(4)), 4)
    pc.put(list(range(200, 204)), k, v, 4)
    assert pc.bytes_used <= 2 * per_block
    assert pc.match(list(range(4))) == 4
    assert pc.match(list(range(100, 104))) == 0  # evicted


# ---------------------------------------------------------------------------
# Engine-level reuse
# ---------------------------------------------------------------------------


_drive = harness.drive


_collect = harness.collect


@pytest.fixture(scope="module")
def peng():
    cfg = get_config("tiny")
    # chunk = 16 (divides 64); blocks of 16 tokens.
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        prefill_chunk=16, prefix_cache_mb=64)
    return InferenceEngine(cfg, ecfg, ByteTokenizer())


def test_engine_prefix_reuse_same_output(peng):
    cfg = get_config("tiny")
    prompt = [int(x) % cfg.vocab_size for x in range(7, 39)]  # 32 tokens
    r1 = Request("p1", prompt, SamplingParams(max_tokens=6, temperature=0.0,
                                              ignore_eos=True))
    peng.add_request(r1)
    _drive(peng)
    ids1, fin1 = _collect(r1)
    assert peng._prefix.bytes_used > 0  # harvested 2 blocks of 16

    # Identical prompt again: served from the cache (hit tokens recorded),
    # same greedy continuation.
    r2 = Request("p2", prompt, SamplingParams(max_tokens=6, temperature=0.0,
                                              ignore_eos=True))
    peng.add_request(r2)
    _drive(peng)
    ids2, fin2 = _collect(r2)
    assert ids2 == ids1
    assert fin2.num_prompt_tokens == 32
    # Whole-prompt hit is capped one block short: >=1 tail token computes
    # the first-token logits.
    assert peng._prefix.hit_tokens == 16
    assert peng._prefix.hit_rate > 0

    # Metric family exposed under the normalized names.
    text = peng.metrics.registry.render()
    assert "prefix_cache_hit_tokens_total" in text
    assert "prefix_cache_hit_rate" in text


def test_engine_prefix_reuse_divergent_tail(peng):
    cfg = get_config("tiny")
    shared = [int(x) % cfg.vocab_size for x in range(7, 39)]  # 32 cached above
    tail = [3, 4, 5, 6, 7, 8, 9, 10]

    # Oracle: fresh engine with the cache disabled.
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        prefill_chunk=16, prefix_cache_mb=0)
    cold = InferenceEngine(cfg, ecfg, ByteTokenizer())
    rc = Request("c", shared + tail, SamplingParams(max_tokens=5, temperature=0.0,
                                                    ignore_eos=True))
    cold.add_request(rc)
    _drive(cold)
    ids_cold, _ = _collect(rc)

    before = peng._prefix.hit_tokens
    rw = Request("w", shared + tail, SamplingParams(max_tokens=5, temperature=0.0,
                                                    ignore_eos=True))
    peng.add_request(rw)
    _drive(peng)
    ids_warm, fin = _collect(rw)
    assert fin.num_prompt_tokens == 40
    assert peng._prefix.hit_tokens - before == 32  # both shared blocks reused
    assert ids_warm == ids_cold


def test_chunked_prompt_harvested_for_reuse():
    """Long (chunk-prefilled) prompts must also populate the cache — their
    KV is read back out of the slotted cache (transformer.extract)."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8,), steps_per_dispatch=4,
                        prefill_chunk=16, prefix_cache_mb=64)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    prompt = [int(x) % cfg.vocab_size for x in range(3, 51)]  # 48 tokens, chunked
    r1 = Request("h1", prompt, SamplingParams(max_tokens=3, temperature=0.0,
                                              ignore_eos=True))
    eng.add_request(r1)
    _drive(eng)
    ids1, _ = _collect(r1)
    assert eng._prefix.match(prompt) == 48

    r2 = Request("h2", prompt, SamplingParams(max_tokens=3, temperature=0.0,
                                              ignore_eos=True))
    eng.add_request(r2)
    _drive(eng)
    ids2, _ = _collect(r2)
    assert ids2 == ids1
    assert eng._prefix.hit_tokens == 32  # 48 capped one block short
