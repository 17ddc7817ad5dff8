"""Ring attention (context parallelism) vs the single-device oracle.

The reference has no sequence-parallel code at all (SURVEY.md §5); here it
is a first-class mesh axis, testable on the virtual 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import get_config
from arks_tpu.models import transformer as tf
from arks_tpu.ops.attention import prefill_attention
from arks_tpu.parallel.mesh import make_mesh
from arks_tpu.parallel.ring import ring_prefill_attention

import harness

# Two engines' greedy tokens on random weights part where two logits tie
# (ROADMAP D9), so the engine tests below compare with
# ``harness.streams_agree`` where the parent of PR 50 wrote ``==``: equal up
# to the first tie, the chosen log-probabilities there within this, and all
# streams but one whole to their ends (the first test's only one is).  The
# limit stands between two readings (PR 50, this machine): sound runs part
# part by 0.0010-0.0019 (bf16 activations summed in another order; too near
# the harness's 2e-3 for a machine that vectorises otherwise), and a ring
# that attends to its own chunk alone by 0.040, at the FIRST token, which
# also differs (in the first test: the paged engines' prompts are prefilled
# in chunks by the mixed step and trace no ring at all, ROADMAP D9).
RING_ATOL = 1e-2


@pytest.mark.parametrize("cp,h,hkv", [(8, 4, 4), (4, 8, 2), (2, 4, 1)])
def test_ring_attention_matches_dense_causal(cp, h, hkv):
    b, t, d = 2, 64, 16
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    ref = prefill_attention(q, k, v)
    mesh = make_mesh(tensor_parallel=1, context_parallel=cp,
                     devices=jax.devices()[:cp])
    got = ring_prefill_attention(q, k, v, mesh, seq_axis="seq")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_prefill_context_parallel_matches_single_device():
    """Full model prefill with T sharded over the seq axis: logits and the
    KV destined for the cache must match the unsharded path."""
    cfg = get_config("tiny-gqa")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    t, n = 32, 30
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, t), 0, cfg.vocab_size)
    lengths = jnp.asarray([n], jnp.int32)

    ref_logits, ref_k, ref_v = tf.prefill(params, cfg, ids, lengths)
    mesh = make_mesh(tensor_parallel=1, context_parallel=8)
    got_logits, got_k, got_v = tf.prefill(params, cfg, ids, lengths, mesh,
                                          seq_axis="seq")
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(ref_k),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(ref_v),
                               rtol=5e-5, atol=5e-5)


def test_prefill_seq_plus_tensor_parallel():
    """seq and model axes together: long-context prefill on a TP slice."""
    cfg = get_config("tiny-gqa")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, cfg.vocab_size)
    lengths = jnp.asarray([32], jnp.int32)
    ref_logits, _, _ = tf.prefill(params, cfg, ids, lengths)

    mesh = make_mesh(tensor_parallel=2, context_parallel=4)
    params_s = tf.shard_params(params, cfg, mesh)
    got_logits, _, _ = tf.prefill(params_s, cfg, ids, lengths, mesh,
                                  seq_axis="seq")
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-4)


def test_serving_engine_with_context_parallelism():
    """Ring attention is reachable FROM SERVING: an engine configured with
    context_parallel=2 prefills with T sharded over the 'seq' axis and
    produces the same greedy tokens as the single-device engine."""
    from arks_tpu.engine import (
        EngineConfig, InferenceEngine, Request, SamplingParams)
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    prompt = [int(x) % cfg.vocab_size for x in range(5, 37)]  # 32 tokens

    def run(cp):
        ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                            prefill_buckets=(16, 32), steps_per_dispatch=4,
                            context_parallel=cp, prefix_cache_mb=0)
        eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
        req = Request("r", prompt, SamplingParams(max_tokens=6, temperature=0.0,
                                                  ignore_eos=True, logprobs=1))
        try:
            eng.add_request(req)
            harness.drive(eng, 100)
            ids, lps, last = harness.collect(req, timeout=60, logprobs=True)
        finally:
            eng.stop()
        return ({"r": ids}, {"r": [lp for lp, _ in lps]}), last

    (got, fin_cp), (want, _) = run(2), run(1)
    assert fin_cp.num_prompt_tokens == 32
    harness.streams_agree(got, want, atol=RING_ATOL, whole=1)


def _run_cp_engine(prompts, cp, layout, sequential=False):
    """Drive an engine at (cp, kv_layout) over ``prompts``; returns
    ((greedy ids, their log-probabilities) a request id, paged prefix hit
    tokens).  ``sequential``
    waits out each request before adding the next (so earlier prompts'
    pages are registered before later ones admit — concurrent admission
    would batch them into one dispatch)."""
    from arks_tpu.engine import (
        EngineConfig, InferenceEngine, Request, SamplingParams)
    from arks_tpu.engine.tokenizer import ByteTokenizer

    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=4, max_cache_len=64,
                        prefill_buckets=(16, 32), steps_per_dispatch=4,
                        context_parallel=cp, prefix_cache_mb=0,
                        kv_layout=layout, prefill_chunk=16)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    toks, lps = {}, {}
    try:
        def collect(r):
            toks[r.request_id], lps[r.request_id] = [], []
            while True:
                out = r.outputs.get(timeout=120)
                toks[r.request_id] += out.token_ids
                lps[r.request_id] += [lp for lp, _ in (out.logprobs or ())]
                if out.finished:
                    assert out.finish_reason == "length", out.error
                    return

        reqs = []
        for i, p in enumerate(prompts):
            r = Request(f"r{i}", list(p), SamplingParams(
                max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=1))
            eng.add_request(r)
            if sequential:
                collect(r)
            else:
                reqs.append(r)
        for r in reqs:
            collect(r)
        hit = eng._alloc.hit_tokens if layout == "paged" else 0
    finally:
        eng.stop()
    return (toks, lps), hit


def test_engine_paged_with_context_parallelism():
    """The paged layout composes with cp (the round-3 blocker is lifted):
    one-shot ring-sharded prefill inserts through the block tables, decode
    rides the seq-replicated pool, and greedy output matches the cp=1 slot
    oracle."""
    cfg = get_config("tiny")
    prompts = ([int(x) % cfg.vocab_size for x in range(5, 37)],
               [5, 6, 7, 8, 9, 10, 11, 12],
               [int(x) % cfg.vocab_size for x in range(3, 48)])
    harness.streams_agree(_run_cp_engine(prompts, 2, "paged")[0],
                          _run_cp_engine(prompts, 1, "slot")[0],
                          atol=RING_ATOL, whole=2)


def test_engine_paged_cp_prefix_sharing():
    """On-device prefix sharing keeps working under cp: a second prompt
    with a shared prefix points its table at the first prompt's pages and
    only the tail chunk-prefills (unsharded over seq — only one-shot
    prefill rides the ring; chunk tails are bounded dispatches)."""
    prompts = ([7] * 33, [7] * 33 + [9, 10, 11])
    ref, _ = _run_cp_engine(prompts, 1, "slot", sequential=True)
    got, hit = _run_cp_engine(prompts, 2, "paged", sequential=True)
    harness.streams_agree(got, ref, atol=RING_ATOL, whole=1)
    assert hit >= 32  # two full 16-token pages reused on device


def test_cp_extends_one_shot_window_for_long_prompts():
    """With context parallelism the one-shot buckets extend to the full
    cache window, so LONG prompts ride the sharded ring instead of falling
    into the unsharded chunked path — the workload cp exists for."""
    from arks_tpu.engine import (
        EngineConfig, InferenceEngine, Request, SamplingParams)
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.models import get_config

    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(16, 32), steps_per_dispatch=4,
                        context_parallel=2, prefix_cache_mb=0)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert eng._buckets[-1] == 64  # extended beyond the configured 32
    prompt = [int(x) % cfg.vocab_size for x in range(3, 48)]  # 45 > old max
    req = Request("long", prompt, SamplingParams(max_tokens=3, temperature=0.0,
                                                 ignore_eos=True))
    eng.add_request(req)
    # One-shot admission: never chunk-queued at any point (admission may
    # resolve deferred, so drive steps until the request completes).
    for _ in range(100):
        eng.step(block_s=0.01)
        assert not eng._prefilling
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None):
            break
    ids = []
    while True:
        out = req.outputs.get(timeout=60)
        ids.extend(out.token_ids)
        if out.finished:
            break
    assert out.num_prompt_tokens == 45 and len(ids) == 3
