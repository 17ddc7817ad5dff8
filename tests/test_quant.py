"""Weight-only quantization (models.quant): int8 (w8a16) and int4 (w4a16)
numerics, engine wiring, sharded equivalence.

Reference parity note: the reference has no quantization code (dtype flags
pass through runtimeCommonArgs to vLLM/SGLang); w8a16/w4a16 here are the
TPU-native mechanisms that fit 7B-class (int8) and 13B-class (int4) models
on one 16GB v5e chip (BASELINE.md north-star config).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
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


# ---------------------------------------------------------------------------
# The stored order of the GQA blocks' q / k / v leaves (PR 48)
# ---------------------------------------------------------------------------

def _flat(tree, pre=""):
    for k, v in tree.items():
        if isinstance(v, dict) and not quant.is_quantized(v):
            yield from _flat(v, pre + k + "/")
        else:
            yield pre + k, v


@functools.partial(jax.jit, static_argnames=("shape", "bits"))
def _drawn(key, shape, bits):
    """A matmul leaf as a seed means it (one program, as the generator's:
    XLA divides by a constant its own way)."""
    w = (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
        jnp.bfloat16)
    return (quant.quantize_tensor_int4(w) if bits == 4
            else quant.quantize_tensor(w, axis=-2))


# (preset, bits, stacks with head-split q / k / v, stacks whose q / k / v
# are plain matmuls).  The whole of every family's tree against its
# reference, in the drawn order: tests/test_benchmark_contract.py.
@pytest.mark.parametrize("name,bits,split,plain", [
    ("tiny", 8, ["layers"], []), ("tiny", 4, ["layers"], []),
    ("tiny-swa-sink-moe", 8, ["dense_layers", "layers", "win_layers"], []),
    ("tiny-linear-moe", 8, ["head_layers", "layers"], ["lin_layers"]),
])
def test_seeded_leaves_are_the_drawn_ones_stored_head_split(name, bits,
                                                            split, plain):
    """``init_params_quantized`` against the rule a seed means, leaf for
    leaf over the attention projections: leaf ``n`` of the tree (depth
    first, a counter from 1) is drawn ``normal(fold_in(key, n), [L, E, H x
    D]) * 0.02`` in the LOGICAL shape, rounded to bfloat16 and quantised
    along ``E``; a GQA stack's q / k / v are that leaf's ``split_heads``
    (values AND scales), a linear layer's q / k / v and every ``wo`` the
    draw itself."""
    cfg = get_config(name)
    key = jax.random.PRNGKey(2**31 + 48)
    got = dict(_flat(quant.init_params_quantized(cfg, key, jnp.bfloat16,
                                                 bits=bits)))
    seen = set()
    for n, (path, leaf) in enumerate(got.items(), 1):
        stack, _, leafname = path.rpartition("/")
        if leafname not in ("wq", "wk", "wv", "wo"):
            continue
        is_split = leafname != "wo" and stack in split
        assert is_split or leafname == "wo" or stack in plain, path
        seen.add(stack)
        shape = leaf["q"].shape
        if is_split:
            l, h, d, e = shape
            assert e == cfg.hidden_size
            want = {k: tf.split_heads(v, h) for k, v in _drawn(
                jax.random.fold_in(key, n), (l, e, h * d), bits).items()}
        else:
            assert len(shape) == 3
            want = _drawn(jax.random.fold_in(key, n), shape, bits)
        assert sorted(leaf) == sorted(want)
        for k in want:
            assert leaf[k].shape == want[k].shape, (path, k)
            assert np.array_equal(np.asarray(leaf[k].astype(jnp.float32)),
                                  np.asarray(want[k].astype(jnp.float32))), (
                path, k)
    assert seen == set(split + plain)


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_params_of_a_float_tree_agrees_with_the_stored_order(bits):
    """A float tree (its q / k / v already stored head-split) quantised
    leaf by leaf is the quantised LOGICAL leaf stored head-split: the scale
    an output channel (the groups along ``E``) is the same number either
    way.  The names alone do not decide: a linear layer's ``wq`` is a plain
    matmul."""
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    logical = {"wq": jax.random.normal(next(keys), (2, 256, 4 * 8)),
               "wk": jax.random.normal(next(keys), (2, 256, 2 * 8))}
    full = {"layers": {"wq": tf.split_heads(logical["wq"], 4),
                       "wk": tf.split_heads(logical["wk"], 2),
                       "wo": jax.random.normal(next(keys), (2, 32, 256))},
            "lin_layers": {"wq": jax.random.normal(next(keys), (2, 256, 32))}}
    got = quant.quantize_params(full, bits=bits)

    def rule(w):
        return (quant.quantize_tensor_int4(w) if bits == 4
                else quant.quantize_tensor(w, axis=-2))

    want = {"layers": {"wq": {k: tf.split_heads(v, 4)
                              for k, v in rule(logical["wq"]).items()},
                       "wk": {k: tf.split_heads(v, 2)
                              for k, v in rule(logical["wk"]).items()},
                       "wo": rule(full["layers"]["wo"])},
            "lin_layers": {"wq": rule(full["lin_layers"]["wq"])}}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert got["layers"]["wq"]["gs" if bits == 4 else "s"].shape == (
        (2, 4, 8, 2) if bits == 4 else (2, 4, 8, 1))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


@pytest.mark.parametrize("kind", ["float", "int8", "int4"])
def test_the_projection_reads_a_head_split_leaf_as_the_matmul_it_is(kind):
    """``_qkv`` on the stored leaves is ``x W`` of the logical ``[E, H x
    D]`` matrices split into heads, biases and all; a quantised leaf's
    ``dequantize`` is the leaf in its own shape."""
    cfg = get_config("tiny")            # qkv biases
    params = tf.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    lp = {k: v[1] for k, v in params["layers"].items()}
    for b in ("bq", "bk", "bv"):
        lp[b] = jnp.linspace(-1, 1, lp[b].size, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg.hidden_size))
    ref = lp
    if kind != "float":
        bits = int(kind[3:])
        lp = dict(lp, **quant.quantize_params(
            {k: params["layers"][k] for k in ("wq", "wk", "wv")}, bits=bits))
        lp = {k: (jax.tree.map(lambda a: a[1], v)
                  if k in ("wq", "wk", "wv") else v) for k, v in lp.items()}
        for k in ("wq", "wk", "wv"):
            deq = quant.dequantize(lp[k], jnp.float32)
            assert deq.shape == ref[k].shape
            assert _rel_err(deq, ref[k]) < (0.15 if bits == 4 else 0.01)
        ref = dict(ref, **{k: quant.dequantize(lp[k], jnp.float32)
                           for k in ("wq", "wk", "wv")})
    for got, w, b, heads in zip(
            tf._qkv(x, lp, cfg), ("wq", "wk", "wv"), ("bq", "bk", "bv"),
            (cfg.num_heads, cfg.num_kv_heads, cfg.num_kv_heads)):
        logical = ref[w].reshape(-1, cfg.hidden_size).T      # [E, H x D]
        want = (x @ logical + ref[b]).reshape(2, 5, heads, cfg.head_dim)
        assert got.shape == want.shape
        assert _rel_err(got, want) < 1e-5


def test_the_partition_specs_shard_the_heads_of_a_head_split_leaf():
    from jax.sharding import PartitionSpec as P
    cfg = get_config("tiny-gqa")
    specs = tf.param_pspecs(cfg, tp=2)["layers"]
    assert specs["wq"] == P(None, "model", None, None)
    assert specs["wk"] == specs["wv"] == P(None, "model", None, None)
    assert tf.param_pspecs(cfg, tp=8)["layers"]["wk"] == P(
        None, None, None, None)                   # 2 KV heads: replicated
    for bits, scale in ((8, "s"), (4, "gs")):
        q = quant.quantize_pspecs({"layers": specs}, bits)["layers"]
        assert q["wq"][scale] == q["wq"]["q"] == specs["wq"]
        assert q["wo"]["q"] == P(None, "model", None)
    assert quant.quantize_pspecs({"layers": specs}, 8)["layers"]["wo"][
        "s"] == P(None, None, None)               # [L, 1, E]


# ---------------------------------------------------------------------------
# The stored order of the latent block's wq_b / wkv_b (PR 57)
# ---------------------------------------------------------------------------

LATENT_PRESETS = ["tiny-mla-moe", "tiny-shortcut-mla-moe",
                  "tiny-latent-linear-moe"]


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("name", LATENT_PRESETS)
def test_seeded_latent_up_projections_are_the_drawn_ones_stored_head_split(
        name, bits):
    """``init_params_quantized`` against the rule a seed means, over every
    latent stack's attention leaves: ``wq_b`` / ``wkv_b`` are the LOGICAL
    draws ``[.., q_lora, H x (nope + rope)]`` / ``[.., kv_lora, H x (nope +
    v)]`` (what the reference families draw), quantised along the latent
    and then stored ``split_heads`` (values AND scales, bit for bit: a
    shortcut block's by sublayer, ``[L, 2, H, ..]``); ``wq_a`` / ``wkv_a``
    / ``wo`` are the draw itself."""
    cfg = get_config(name)
    key = jax.random.PRNGKey(2**31 + 57)
    got = dict(_flat(quant.init_params_quantized(cfg, key, jnp.bfloat16,
                                                 bits=bits)))
    h = cfg.num_heads
    width = {"wq_b": (cfg.head_dim, cfg.q_lora_rank),
             "wkv_b": (cfg.qk_nope_head_dim + cfg.v_head_dim,
                       cfg.kv_lora_rank)}
    seen = set()
    for n, (path, leaf) in enumerate(got.items(), 1):
        stack, _, leafname = path.rpartition("/")
        if stack + "/wq_a" not in got:
            continue                        # not a latent stack
        if leafname not in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"):
            continue
        shape = leaf["q"].shape
        if leafname in width:
            *lead, heads, d, k = shape
            assert (heads, d, k) == (h, *width[leafname]), path
            assert len(lead) == (2 if cfg.shortcut else 1), path
            seen.add(path)
            want = {m: tf.split_heads(v, h) for m, v in _drawn(
                jax.random.fold_in(key, n), (*lead, k, h * d), bits).items()}
        else:
            want = _drawn(jax.random.fold_in(key, n), shape, bits)
        assert sorted(leaf) == sorted(want)
        for m in want:
            assert leaf[m].shape == want[m].shape, (path, m)
            assert np.array_equal(np.asarray(leaf[m].astype(jnp.float32)),
                                  np.asarray(want[m].astype(jnp.float32))), (
                path, m)
    stacks = ["layers"] + ["dense_layers"] * (name == "tiny-mla-moe")
    assert seen == {f"{s}/{m}" for s in stacks for m in width}


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_params_of_a_float_latent_tree_agrees_with_the_stored_order(
        bits):
    """A float tree (its up projections already stored head-split, at rank
    4 and at the shortcut block's rank 5) quantised leaf by leaf is the
    quantised LOGICAL leaf stored head-split; ``wq_a`` stays a plain
    matmul."""
    keys = iter(jax.random.split(jax.random.PRNGKey(57), 8))
    logical = {"wq_b": jax.random.normal(next(keys), (2, 256, 4 * 24)),
               "wkv_b": jax.random.normal(next(keys), (2, 2, 256, 4 * 8))}
    full = {"layers": {"wq_b": tf.split_heads(logical["wq_b"], 4),
                       "wkv_b": tf.split_heads(logical["wkv_b"], 4),
                       "wq_a": jax.random.normal(next(keys), (2, 64, 256))}}
    assert full["layers"]["wkv_b"].shape == (2, 2, 4, 8, 256)
    got = quant.quantize_params(full, bits=bits)

    def rule(w):
        return (quant.quantize_tensor_int4(w) if bits == 4
                else quant.quantize_tensor(w, axis=-2))

    want = {"layers": {
        "wq_b": {k: tf.split_heads(v, 4)
                 for k, v in rule(logical["wq_b"]).items()},
        "wkv_b": {k: tf.split_heads(v, 4)
                  for k, v in rule(logical["wkv_b"]).items()},
        "wq_a": rule(full["layers"]["wq_a"])}}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    scale = "gs" if bits == 4 else "s"
    assert got["layers"]["wq_b"][scale].shape == (
        (2, 4, 24, 2) if bits == 4 else (2, 4, 24, 1))
    assert got["layers"]["wkv_b"][scale].shape == (
        (2, 2, 4, 8, 2) if bits == 4 else (2, 2, 4, 8, 1))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


@pytest.mark.parametrize("kind", ["float", "int8", "int4"])
@pytest.mark.parametrize("name", LATENT_PRESETS)
def test_the_latent_block_reads_its_up_projections_as_the_matmuls_they_are(
        name, kind):
    """``_mla_q`` / ``_mla_out`` on the stored leaves against the
    drawn-order einsums (the form the block had until PR 57: ``cq W_qb``
    reshaped into heads, ``q_nope W_uk^T`` and ``attn W_uv`` over ``W_kvb``
    reshaped ``[C, H, nope + v]``), on a layer (and sublayer) taken out of
    the stack; a quantised leaf's ``dequantize`` is the leaf in its own
    shape."""
    cfg = get_config(name)
    params = tf.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    stack = {k: params["layers"][k] for k in (
        "wq_a", "q_norm", "wq_b", "wkv_b", "wo") + ("wg",) * cfg.attn_out_gate}
    if kind != "float":
        bits = int(kind[3:])
        stack.update(quant.quantize_params(
            {k: stack[k] for k in ("wq_b", "wkv_b")}, bits=bits))
    at = (1, 1) if cfg.shortcut else (1,)
    lp = {k: jax.tree.map(lambda a: a[at], v) for k, v in stack.items()}
    h, nope, v, c = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    deq = {k: quant.dequantize(lp[k], jnp.float32) for k in ("wq_b", "wkv_b")}
    assert deq["wq_b"].shape == (h, cfg.head_dim, cfg.q_lora_rank)
    assert deq["wkv_b"].shape == (h, nope + v, c)
    if kind != "float":
        for k in deq:
            assert _rel_err(deq[k], params["layers"][k][at]) < (
                0.15 if bits == 4 else 0.01)
    wq_b, wkv_b = (harness.as_drawn(deq[k]) for k in ("wq_b", "wkv_b"))
    assert wq_b.shape == (cfg.q_lora_rank, cfg.q_dim)
    b, t = 2, 5
    x = jax.random.normal(jax.random.PRNGKey(6), (b, t, cfg.hidden_size))
    pos = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t) * 3

    cq = tf._norm(x @ lp["wq_a"], lp["q_norm"], cfg, cfg.mla_q_scale)
    q = (cq @ wq_b).reshape(b, t, h, cfg.head_dim)
    w = wkv_b.reshape(c, h, nope + v)
    want_q = jnp.concatenate([
        jnp.einsum("bthn,chn->bthc", q[..., :nope], w[..., :nope]),
        tf.apply_rope(q[..., nope:], pos, cfg.rope_theta, cfg.rope_yarn)],
        axis=-1)
    got_q = tf._mla_q(x, lp, cfg, pos)
    assert got_q.shape == want_q.shape == (b, t, h, cfg.latent_row)
    assert _rel_err(got_q, want_q) < 1e-5

    attn = jax.random.normal(jax.random.PRNGKey(7), (t, h, c))
    o = jnp.einsum("thc,chv->thv", attn, w[..., nope:]).reshape(t, h * v)
    if cfg.attn_out_gate:
        o = o * jax.nn.sigmoid(x[0] @ lp["wg"])
    got_o = tf._mla_out(attn, lp, cfg, x[0])
    assert got_o.shape == (t, cfg.hidden_size)
    assert _rel_err(got_o, o @ lp["wo"]) < 1e-5


@pytest.mark.parametrize("name,ndim,axis", [
    ("wq_b", 4, -1), ("wq_b", 5, -1), ("wq_b", 3, -1), ("wkv_b", 4, -1),
    ("wkv_b", 5, -1), ("wq_a", 3, -2), ("wq_a", 4, -2), ("wkv_a", 4, -2),
    ("wq", 4, -1), ("wq", 3, -2), ("wo", 3, -2), ("w_in", 3, -1)])
def test_the_contraction_axis_of_a_leaf_by_name_and_rank(name, ndim, axis):
    """The latent up projections are head-split at every rank (a layer's
    slice, a stack, a stack by sublayer: they have no plain form); a GQA
    stack's names at rank 4 alone."""
    assert quant.contraction_axis(name, ndim) == axis


def test_the_partition_specs_of_a_latent_up_projection_follow_its_heads():
    """The latent block has no sharding rules (``param_pspecs`` refuses it
    by name); what `quantize_pspecs` makes of a spec for its leaves follows
    the stored order all the same: the heads' entry kept, the scales' entry
    along the contraction dimension, the LAST, dropped (int8) or kept whole
    (int4: the groups tile it)."""
    from jax.sharding import PartitionSpec as P
    specs = {"layers": {"wq_b": P(None, "model", None, "data"),
                        "wkv_b": P(None, None, "model", None, "data"),
                        "wq_a": P(None, "data", "model")}}
    q8 = quant.quantize_pspecs(specs, 8)["layers"]
    assert q8["wq_b"] == {"q": specs["layers"]["wq_b"],
                          "s": P(None, "model", None, None)}
    assert q8["wkv_b"]["s"] == P(None, None, "model", None, None)
    assert q8["wq_a"]["s"] == P(None, None, "model")
    q4 = quant.quantize_pspecs(specs, 4)["layers"]
    for k, spec in specs["layers"].items():
        assert q4[k] == {"q": spec, "gs": spec}
    with pytest.raises(NotImplementedError, match="no sharding rules"):
        tf.param_pspecs(get_config("tiny-mla-moe"), tp=2)
