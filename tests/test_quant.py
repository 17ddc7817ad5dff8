"""Weight-only quantization (models.quant): int8 (w8a16) and int4 (w4a16)
numerics, engine wiring, sharded equivalence.

Reference parity note: the reference has no quantization code (dtype flags
pass through runtimeCommonArgs to vLLM/SGLang); w8a16/w4a16 here are the
TPU-native mechanisms that fit 7B-class (int8) and 13B-class (int4) models
on one 16GB v5e chip (BASELINE.md north-star config).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import get_config, quant
from arks_tpu.models import transformer as tf
from arks_tpu.parallel.mesh import make_mesh


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / (np.abs(b).max() + 1e-9)


def test_quantize_tensor_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32) * 0.02
    qt = quant.quantize_tensor(w, axis=-2)
    assert qt["q"].dtype == jnp.int8 and qt["s"].shape == (1, 32)
    deq = quant.dequantize(qt, jnp.float32)
    # Symmetric 8-bit: worst-case error is half a step (~amax/254 per column).
    assert _rel_err(deq, w) < 1.0 / 200


def test_qeinsum_matches_dense_matmul():
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32) * 0.05
    ref = jnp.einsum("be,ef->bf", x, w)
    got = quant.qeinsum("be,ef->bf", x, quant.quantize_tensor(w))
    assert _rel_err(got, ref) < 0.02


@pytest.mark.parametrize("name", ["tiny", "tiny-gqa"])
def test_quantized_forward_close_to_full(name):
    cfg = get_config(name)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quant.quantize_params(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    lengths = jnp.asarray([12, 12], jnp.int32)

    ref, rks, rvs = tf.prefill(params, cfg, toks, lengths)
    got, qks, qvs = tf.prefill(qparams, cfg, toks, lengths)
    # Logits drift accumulates over layers; top-1 agreement + bounded error
    # is the serving-relevant criterion.
    assert _rel_err(got, ref) < 0.1
    np.testing.assert_array_equal(np.argmax(np.asarray(got), -1),
                                  np.argmax(np.asarray(ref), -1))

    # Decode path runs (shape + finiteness) and matches full-width top-1.
    cache = tf.init_cache(cfg, num_slots=2, max_len=32, dtype=jnp.float32)
    cache = tf.insert(cache, qks, qvs, jnp.asarray(0))
    lengths_d = jnp.zeros((2,), jnp.int32).at[0].set(12)
    logits_d, _ = tf.decode_step(qparams, cfg, cache, jnp.zeros((2,), jnp.int32),
                                 lengths_d)
    assert np.isfinite(np.asarray(logits_d)).all()


def test_quantized_moe_forward():
    cfg = get_config("tiny-moe")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quant.quantize_params(params)
    # Router must stay full-width (softmax-sensitive).
    assert not quant.is_quantized(qparams["layers"]["router"])
    assert quant.is_quantized(qparams["layers"]["w_gate"])
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 80), 0, cfg.vocab_size)
    lengths = jnp.asarray([80], jnp.int32)
    ref, _, _ = tf.prefill(params, cfg, toks, lengths)   # grouped path (T>=64)
    got, _, _ = tf.prefill(qparams, cfg, toks, lengths)
    assert _rel_err(got, ref) < 0.15


def test_quantized_sharded_matches_unsharded():
    cfg = get_config("tiny-gqa")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quant.quantize_params(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    lengths = jnp.asarray([8, 8], jnp.int32)
    ref, _, _ = tf.prefill(qparams, cfg, toks, lengths)

    mesh = make_mesh(tensor_parallel=4, data_parallel=2,
                     devices=jax.devices()[:8])
    qsharded = tf.shard_params(qparams, cfg, mesh)
    got, _, _ = tf.prefill(qsharded, cfg, toks, lengths, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_engine_weight_dtype_int8():
    from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
    from arks_tpu.engine.tokenizer import ByteTokenizer
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(16, 32), weight_dtype="int8")
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert quant.is_quantized(eng.params["layers"]["wq"])
    req = Request("q1", [5, 6, 7], SamplingParams(max_tokens=4, temperature=0.0,
                                                  ignore_eos=True))
    eng.add_request(req)
    for _ in range(50):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None):
            break
    out, ids = None, []
    while out is None or not out.finished:
        out = req.outputs.get(timeout=30)
        ids.extend(out.token_ids)
    assert len(ids) == 4


def test_quantize_tensor_int4_roundtrip():
    """w4a16 groupwise: int4 payload + [K/G, N] group scales; bounded
    error (worst case half a step = amax/14 per group-channel)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 32), jnp.float32) * 0.02
    qt = quant.quantize_tensor_int4(w, group=64)
    assert qt["q"].dtype == jnp.int4
    assert qt["gs"].shape == (4, 32)
    deq = quant.dequantize(qt, jnp.float32)
    assert _rel_err(deq, w) < 1.0 / 12


def test_qeinsum_int4_matches_dequant_exactly():
    """The fused qeinsum path must equal einsum against the materialized
    dequantized weight bit-for-bit (same math, different fusion)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 32), jnp.float32) * 0.05
    qt = quant.quantize_tensor_int4(w, group=128)
    got = quant.qeinsum("be,ef->bf", x, qt)
    ref = jnp.einsum("be,ef->bf", x, quant.dequantize(qt, jnp.float32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # And it approximates the dense matmul (int4's per-element error is
    # ~amax/14, so output-relative error sits near 0.1 on random
    # normals — the model-level tests assert the serving-relevant
    # criterion, top-1 agreement).
    dense = jnp.einsum("be,ef->bf", x, w)
    assert _rel_err(got, dense) < 0.15


@pytest.mark.parametrize("name", ["tiny", "tiny-gqa"])
def test_int4_forward_close_to_full(name):
    """w4a16 prefill: bounded drift vs full width, top-1 agreement (the
    embedding stays int8, matmuls go int4 groupwise)."""
    cfg = get_config(name)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quant.quantize_params(params, bits=4)
    assert "gs" in qparams["layers"]["wq"]          # int4 matmul leaves
    assert "s" in qparams["embed"]                  # embedding stays int8
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    lengths = jnp.asarray([12, 12], jnp.int32)
    ref, _, _ = tf.prefill(params, cfg, toks, lengths)
    got, _, _ = tf.prefill(qparams, cfg, toks, lengths)
    assert _rel_err(got, ref) < 0.2
    # Tiny random models have near-uniform logits, so exact top-1 equality
    # is noise-sensitive at 4 bits: assert the full-width argmax stays in
    # the int4 top-3 per row instead.
    ref_top1 = np.argmax(np.asarray(ref), -1)
    got_top3 = np.argsort(np.asarray(got), -1)[..., -3:]
    assert all(t in row for t, row in
               zip(ref_top1.ravel(), got_top3.reshape(-1, 3)))


def test_int4_sharded_matches_unsharded():
    cfg = get_config("tiny-gqa")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    # group 16: whole groups per model-axis shard of the tiny dims (the
    # sharded contraction dims are 64 wide over tp=4 -> local K 16).
    qparams = quant.quantize_params(params, bits=4, group=16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    lengths = jnp.asarray([8, 8], jnp.int32)
    ref, _, _ = tf.prefill(qparams, cfg, toks, lengths)

    mesh = make_mesh(tensor_parallel=4, data_parallel=2,
                     devices=jax.devices()[:8])
    qsharded = tf.shard_params(qparams, cfg, mesh)
    got, _, _ = tf.prefill(qsharded, cfg, toks, lengths, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_int4_moe_forward():
    """int4 expert weights take the dispatch int8 ones take, their group
    dequant the contraction's operand producer, and stay close to full
    width."""
    cfg = get_config("tiny-moe")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qparams = quant.quantize_params(params, bits=4)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    lengths = jnp.asarray([8, 8], jnp.int32)
    ref, _, _ = tf.prefill(params, cfg, toks, lengths)
    got, _, _ = tf.prefill(qparams, cfg, toks, lengths)
    assert _rel_err(got, ref) < 0.25


def test_engine_weight_dtype_int4():
    from arks_tpu.engine import EngineConfig, InferenceEngine, Request, SamplingParams
    from arks_tpu.engine.tokenizer import ByteTokenizer
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(16, 32), weight_dtype="int4")
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    assert "gs" in eng.params["layers"]["wq"]
    assert eng.resolved_config["weight_dtype"] == "int4"
    req = Request("q4", [5, 6, 7], SamplingParams(max_tokens=4, temperature=0.0,
                                                  ignore_eos=True))
    eng.add_request(req)
    for _ in range(80):
        eng.step(block_s=0.01)
        if (eng.num_running == 0 and eng._queue.empty()
                and eng._deferred is None):
            break
    out, ids = None, []
    while out is None or not out.finished:
        out = req.outputs.get(timeout=30)
        ids.extend(out.token_ids)
    assert len(ids) == 4
    assert all(0 <= t < cfg.vocab_size for t in ids)
