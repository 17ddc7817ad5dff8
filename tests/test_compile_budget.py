"""Compile-budget regression guard (tier-1).

The mixed scheduler collapses the (bucket, M, lp) admit-program family
into one budget-shaped program.  This test runs a mixed workload —
admissions of several lengths + chunked prefill + decode — and asserts the
number of DISTINCT jitted program variants stays under a declared budget,
so a future scheduler edit that silently reintroduces per-shape retraces
(or a dtype/weak-type wobble that doubles every program) fails CI instead
of surfacing as TPU compile stalls in production.
"""

import json

from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

# One mixed program + its logprob twin, the promotion program (one variant
# a padded size) and clear_penalties state writes, and the handful of
# single-shape helpers the engine always jits.
# The point is the ORDER of magnitude: the legacy scheduler's admit family
# alone is len(buckets) x len(admit_sizes) x 2 programs.
MIXED_TOTAL_BUDGET = 14
MIXED_PER_PROGRAM_BUDGET = 2  # lp twins are separate jit objects already


def _ids_of(req, timeout=120):
    while True:
        out = req.outputs.get(timeout=timeout)
        if out.finished:
            return out


def test_mixed_workload_compile_variant_budget(monkeypatch):
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=4, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        prefill_chunk=16, kv_layout="paged")
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng._mixed

    # Admissions of several lengths (one-shot-sized AND chunk-length),
    # logprobs on/off, sampled and greedy, plus decode churn.
    prompts = [[5, 6], [3] * 12, [7] * 20, list(range(3, 51)), [9] * 30,
               [4] * 5, [8] * 17]
    reqs = []
    for i, p in enumerate(prompts):
        sp = SamplingParams(
            max_tokens=4,
            temperature=0.0 if i % 2 == 0 else 0.7,
            seed=i, ignore_eos=True,
            logprobs=1 if i == 1 else None)
        reqs.append(Request(f"cb{i}", [int(x) % cfg.vocab_size for x in p],
                            sp))
    for r in reqs:
        eng.add_request(r)
    for _ in range(600):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling):
            break
    for r in reqs:
        assert _ids_of(r).finished

    variants = eng.compiled_program_variants()
    assert variants, "no jitted programs discovered on the engine"
    total = sum(variants.values())
    assert total <= MIXED_TOTAL_BUDGET, variants
    for name, n in variants.items():
        # The promotion program compiles once a padded size, at warm-up.
        cap = (len(eng._promote_packs) if name == "_promote_fn"
               else MIXED_PER_PROGRAM_BUDGET)
        assert n <= cap, (name, variants)
    # The admit family must not have compiled at all: mixed mode routes
    # every prompt through the chunked path.
    assert variants.get("_admit_fn", 0) == 0, variants
    assert variants.get("_admit_lp_fn", 0) == 0, variants
    # The mixed program itself is ONE variant per lp flavor.
    assert variants.get("_mixed_fn", 0) == 1, variants
    assert variants.get("_mixed_lp_fn", 0) <= 1, variants


# Spec engines add the draft-prefill program (one per bucket) and the
# spec-mixed program pair on top of the mixed engine's set; the point is
# that draft+verify is ONE budget-shaped program per lp flavor — no
# per-draft-len/per-batch verify family, no fused-loop twins.
SPEC_TOTAL_BUDGET = 18


def test_spec_workload_compile_variant_budget(monkeypatch):
    """The spec program family collapsed into the mixed family: a spec
    workload (several prompt lengths, greedy + sampled + logprobs +
    penalized — enabled AND disabled lanes) compiles exactly one
    spec-mixed program per lp flavor, no legacy decode/admit variants."""
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=4, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        prefill_chunk=16, kv_layout="paged",
                        draft_model="tiny-gqa", draft_len=4,
                        prefix_cache_mb=0)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng._mixed

    prompts = [[5, 6], [3] * 12, [7] * 20, list(range(3, 51)), [9] * 30]
    reqs = []
    for i, p in enumerate(prompts):
        sp = SamplingParams(
            max_tokens=4,
            temperature=0.0 if i % 2 == 0 else 0.7,
            seed=i, ignore_eos=True,
            logprobs=1 if i == 1 else None,
            frequency_penalty=0.5 if i == 2 else 0.0)
        reqs.append(Request(f"sb{i}", [int(x) % cfg.vocab_size for x in p],
                            sp))
    for r in reqs:
        eng.add_request(r)
    for _ in range(600):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling):
            break
    for r in reqs:
        assert _ids_of(r).finished
    assert eng._spec_proposed > 0

    variants = eng.compiled_program_variants()
    assert sum(variants.values()) <= SPEC_TOTAL_BUDGET, variants
    # ONE spec-mixed program per lp flavor — the whole point: verify
    # lanes are just ragged rows of the mixed dispatch, so there is no
    # per-K (or per-enable-mask) recompile family.
    assert variants.get("_spec_mixed_fn", 0) == 1, variants
    assert variants.get("_spec_mixed_lp_fn", 0) <= 1, variants
    # The legacy families are gone/dark.
    assert variants.get("_decode_fn", 0) == 0, variants
    assert variants.get("_admit_fn", 0) == 0, variants
    assert "_spec_fn" not in variants, variants


def test_ragged_kernel_family_budget_with_tuned_cache(monkeypatch, tmp_path):
    """The ragged mixed kernel family under a CACHED autotune entry: the
    tuned block_q must flow from the table into the resolved plan and the
    jitted kernel launcher (_paged_mixed_call) must compile exactly ONE
    variant for the whole mixed workload — a tuned entry swaps the statics'
    VALUES, it must never add a compiled variant next to the default, and
    the engine-level budget is unchanged by a tuned table."""
    from arks_tpu.ops import autotune, paged_attention
    from arks_tpu.models import transformer as tf

    cache = tmp_path / "kernel_tune.json"
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "cached")
    monkeypatch.setenv("ARKS_KERNEL_TUNE_CACHE", str(cache))
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    monkeypatch.setenv("ARKS_MIXED_STEP", "1")
    autotune.invalidate_cache()

    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                        prefill_chunk=16, kv_layout="paged",
                        prefix_cache_mb=0)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng._mixed and eng._paged

    # Seed the tune table for the engine's own mixed signature with a
    # NON-default block_q (the heuristic would pick min(qmax, 32)).
    sig = autotune.mixed_signature(
        hkv=cfg.num_kv_heads, g=cfg.num_heads // cfg.num_kv_heads,
        d=tf.cache_head_dim(cfg, eng._pad_head()), page=eng._page_size(),
        qmax=eng._mixed_budget + 1, kv=str(eng._cache.k.dtype))
    autotune.record("paged_mixed", sig, {"block_q": 8, "dma_depth": 2})
    autotune.invalidate_cache()  # force the load path, not the write-through
    assert json.loads(cache.read_text())  # the entry persisted

    kernel_before = paged_attention._paged_mixed_call._cache_size()
    reqs = [Request(f"rk{i}", [int(x) % cfg.vocab_size for x in p],
                    SamplingParams(max_tokens=3, temperature=0.0,
                                   ignore_eos=True))
            for i, p in enumerate([[5, 6, 7], [3] * 12, [9] * 20])]
    for r in reqs:
        eng.add_request(r)
    for _ in range(600):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None
                and not eng._prefilling):
            break
    for r in reqs:
        assert _ids_of(r).finished

    # The tuned entry reached the resolved plan (counters memoize it).
    plan = eng._grid_plans[eng._mixed_budget + 1]
    assert plan["block_q"] == 8, plan
    # Inside the engine the launcher is INLINED into the jitted step
    # programs — its own cache must not have grown (no stray eager launch
    # escaped the step programs).
    assert paged_attention._paged_mixed_call._cache_size() == kernel_before
    # Engine-level census: one sequential mixed program, whatever the table.
    variants = eng.compiled_program_variants()
    assert sum(variants.values()) <= MIXED_TOTAL_BUDGET, variants
    assert variants.get("_mixed_fn", 0) == 1, variants


def test_mixed_kernel_launcher_variant_census(monkeypatch, tmp_path):
    """Kernel-family census at the launcher itself (direct calls, where
    _paged_mixed_call owns its jit cache): repeated calls reuse one
    variant; an autotune entry matching the heuristic's choice adds ZERO
    variants (the table swaps static VALUES, it is not a second code
    path); only a genuinely different tuned block_q compiles one more."""
    import jax.numpy as jnp
    import numpy as np

    from arks_tpu.ops import autotune
    from arks_tpu.ops import paged_attention as pa

    cache = tmp_path / "kernel_tune.json"
    monkeypatch.setenv("ARKS_KERNEL_TUNE", "cached")
    monkeypatch.setenv("ARKS_KERNEL_TUNE_CACHE", str(cache))
    autotune.invalidate_cache()

    l, s, hkv, g, maxp, page, d, qmax = 1, 2, 1, 1, 2, 8, 8, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(s, hkv, g, qmax, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(l, s * maxp, hkv, page, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=kp.shape), jnp.float32)
    tables = jnp.arange(s * maxp, dtype=jnp.int32).reshape(s, maxp)
    pos = jnp.array([3, 0], jnp.int32)
    qlen = jnp.array([2, 4], jnp.int32)

    def launch():
        out = pa.paged_mixed_attention(q, kp, vp, tables, pos, qlen, 0,
                                       interpret=True)
        return np.asarray(out)

    before = pa._paged_mixed_call._cache_size()
    launch()
    assert pa._paged_mixed_call._cache_size() == before + 1
    launch()  # same resolved plan -> cache hit
    assert pa._paged_mixed_call._cache_size() == before + 1

    sig = autotune.mixed_signature(hkv=hkv, g=g, d=d, page=page, qmax=qmax,
                                   kv="float32")
    # Entry matching the heuristic (block_q = min(qmax, 32) = qmax): the
    # cached table must round-trip into the SAME compiled variant.
    autotune.record("paged_mixed", sig, {"block_q": qmax, "dma_depth": 2})
    autotune.invalidate_cache()
    launch()
    assert pa._paged_mixed_call._cache_size() == before + 1
    # A genuinely different tuned block_q is one more variant, exactly.
    autotune.record("paged_mixed", sig, {"block_q": 2, "dma_depth": 2})
    autotune.invalidate_cache()
    launch()
    assert pa._paged_mixed_call._cache_size() == before + 2
