"""MoE block correctness: routing, decode/prefill agreement, expert
parallelism over the mesh.

Reference parity note: the reference serves MoE models only by naming them
in runtime container commands; the block itself (Mixtral / Qwen2-MoE
semantics) is native here and tested on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import get_config
from arks_tpu.models import moe
from arks_tpu.models import transformer as tf
from arks_tpu.parallel.mesh import make_mesh


def test_router_weights_topk_semantics():
    cfg = get_config("tiny-mixtral")  # top-2 of 4, normalized
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    w = np.asarray(moe.router_weights(logits, cfg))
    assert (w[0] > 0).sum() == 2            # exactly k nonzero
    assert w[0, 3] == 0 and w[0, 2] == 0    # lowest logits dropped
    np.testing.assert_allclose(w[0].sum(), 1.0, rtol=1e-6)  # renormalized

    cfg2 = get_config("tiny-moe")  # norm_topk_prob=False
    w2 = np.asarray(moe.router_weights(logits, cfg2))
    assert 0 < w2[0].sum() < 1.0  # global-softmax probs used as-is


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-mixtral"])
def test_moe_decode_matches_prefill(name):
    cfg = get_config(name)
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = [int(x) for x in
           jax.random.randint(jax.random.PRNGKey(1), (8,), 0, cfg.vocab_size)]

    # Oracle: full prefill over each prefix.
    ref = []
    for i in range(1, len(ids) + 1):
        toks = jnp.asarray([ids[:i]], jnp.int32)
        logits, _, _ = tf.prefill(params, cfg, toks, jnp.asarray([i], jnp.int32))
        ref.append(np.asarray(logits[0]))

    n_prefill = 3
    cache = tf.init_cache(cfg, num_slots=2, max_len=32, dtype=jnp.float32)
    toks = jnp.asarray([ids[:n_prefill]], jnp.int32)
    logits, ks, vs = tf.prefill(params, cfg, toks, jnp.asarray([n_prefill], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), ref[n_prefill - 1],
                               rtol=2e-4, atol=2e-4)
    cache = tf.insert(cache, ks, vs, jnp.asarray(0))
    lengths = jnp.zeros((2,), jnp.int32).at[0].set(n_prefill)
    tokens = jnp.zeros((2,), jnp.int32)
    for i in range(n_prefill, len(ids)):
        tokens = tokens.at[0].set(ids[i])
        logits, cache = tf.decode_step(params, cfg, cache, tokens, lengths)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[i],
                                   rtol=2e-4, atol=2e-4)
        lengths = lengths.at[0].set(i + 1)


@pytest.mark.parametrize("tp,dp", [(4, 1), (2, 2), (8, 1)])
def test_moe_expert_parallel_equivalence(tp, dp):
    """Experts sharded over the model axis must match single-device.
    tp=8 with 8 experts = one expert per device; tp also shards kv heads
    when divisible (tiny-moe has 4)."""
    cfg = get_config("tiny-moe")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, cfg.vocab_size)
    lengths = jnp.asarray([6, 6], jnp.int32)

    ref_logits, _, _ = tf.prefill(params, cfg, jnp.asarray(ids), lengths)
    mesh = make_mesh(tensor_parallel=tp, data_parallel=dp,
                     devices=jax.devices()[: tp * dp])
    params_s = tf.shard_params(params, cfg, mesh)
    got_logits, _, _ = tf.prefill(params_s, cfg, jnp.asarray(ids), lengths, mesh)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-mixtral"])
def test_moe_grouped_matches_dense(name):
    """The dropless grouped (sort + ragged_dot) dispatch is numerically
    equivalent to the dense all-expert dispatch."""
    cfg = get_config(name)
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree_util.tree_map(lambda t: t[0], mp)  # layer 0 slice
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 40, cfg.hidden_size),
                          jnp.float32)
    dense = moe.moe_ffn(x, lp, cfg, grouped=False)
    grouped = moe.moe_ffn(x, lp, cfg, grouped=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_moe_grouped_auto_threshold(monkeypatch):
    """Auto mode routes large unsharded [B, T, E] batches through the
    grouped path, decode-shaped [B, E] and small batches through dense —
    verified by counting actual grouped-path invocations."""
    cfg = get_config("tiny-moe")
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree_util.tree_map(lambda t: t[0], mp)
    calls = []
    real = moe.moe_ffn_grouped
    monkeypatch.setattr(moe, "moe_ffn_grouped",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))

    big = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.hidden_size))
    moe.moe_ffn(big, lp, cfg)
    assert len(calls) == 1  # large prefill → grouped
    moe.moe_ffn(big[:, :4], lp, cfg)
    assert len(calls) == 1  # small prefill → dense
    decode = jax.random.normal(jax.random.PRNGKey(2), (128, cfg.hidden_size))
    moe.moe_ffn(decode, lp, cfg)
    assert len(calls) == 1  # decode stays dense no matter the slot count
    moe.moe_ffn(big, lp, cfg, constrain=lambda t, d: t)
    assert len(calls) == 1  # sharded (constrained) → dense


def test_moe_grouped_grad():
    """Training uses the grouped path when unsharded — it must be
    differentiable (ragged_dot grads + scatter-add transpose)."""
    cfg = get_config("tiny-moe")
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    lp = jax.tree_util.tree_map(lambda t: t[0], mp)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))

    def loss(lp, grouped):
        return jnp.sum(moe.moe_ffn(x, lp, cfg, grouped=grouped) ** 2)

    g_dense = jax.grad(loss)(lp, False)
    g_grouped = jax.grad(loss)(lp, True)
    for k in g_dense:
        np.testing.assert_allclose(np.asarray(g_grouped[k]),
                                   np.asarray(g_dense[k]),
                                   rtol=5e-4, atol=5e-4, err_msg=k)


def test_moe_param_counts():
    assert 40e9 < get_config("mixtral-8x7b").num_params() < 50e9
    assert 50e9 < get_config("qwen2-57b-a14b").num_params() < 62e9


def test_moe_hf_config_roundtrip():
    from arks_tpu.models.config import ModelConfig
    d = {
        "architectures": ["MixtralForCausalLM"], "model_type": "mixtral",
        "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 4, "num_local_experts": 8,
        "num_experts_per_tok": 2, "eos_token_id": 2,
    }
    cfg = ModelConfig.from_hf_config(d)
    assert cfg.num_experts == 8 and cfg.num_experts_per_tok == 2
    assert cfg.norm_topk_prob and cfg.moe_intermediate_size == 128
    d2 = {
        "architectures": ["Qwen2MoeForCausalLM"], "model_type": "qwen2_moe",
        "vocab_size": 1000, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 4, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 48, "shared_expert_intermediate_size": 96,
        "norm_topk_prob": False,
    }
    cfg2 = ModelConfig.from_hf_config(d2)
    assert cfg2.qkv_bias and cfg2.num_experts == 16
    assert cfg2.shared_expert_intermediate_size == 96 and not cfg2.norm_topk_prob


# ---------------------------------------------------------------------------
# Block-sparse Pallas grouped matmul (ARKS_MOE_KERNEL=pallas)
# ---------------------------------------------------------------------------


def test_grouped_matmul_kernel_matches_ragged_dot():
    """pad_groups + grouped_matmul == ragged_dot on the same sorted rows,
    including the fused int8 dequant."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arks_tpu.models.quant import quantize_tensor
    from arks_tpu.ops.moe_kernel import grouped_ffn, grouped_matmul, pad_groups

    rng = np.random.default_rng(0)
    t, k, n, nx, bt = 37, 32, 48, 4, 8
    sorted_expert = jnp.asarray(np.sort(rng.integers(0, nx, t)), jnp.int32)
    group_sizes = jnp.bincount(sorted_expert, length=nx)
    xs = jnp.asarray(rng.standard_normal((t, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((nx, k, n)), jnp.float32)

    ref = jax.lax.ragged_dot(xs, w, group_sizes)
    xs_p, dest, bexp = pad_groups(xs, sorted_expert, group_sizes, bt)
    got = grouped_matmul(xs_p, w, bexp, block_t=bt, block_n=16,
                         interpret=True)[dest]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    # int8 fused dequant vs materialized dequant + ragged_dot.
    wq = quantize_tensor(w)
    from arks_tpu.models.quant import dequantize
    ref_q = jax.lax.ragged_dot(xs, dequantize(wq, jnp.float32), group_sizes)
    got_q = grouped_matmul(xs_p, wq["q"], bexp, wq["s"].astype(jnp.float32),
                           block_t=bt, block_n=16, interpret=True)[dest]
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(ref_q),
                               atol=1e-3, rtol=1e-3)

    # int4 groupwise fused dequant vs materialized dequant + ragged_dot.
    from arks_tpu.models.quant import quantize_tensor_int4
    w4 = quantize_tensor_int4(w, group=8)
    ref_4 = jax.lax.ragged_dot(xs, dequantize(w4, jnp.float32), group_sizes)
    got_4 = grouped_matmul(xs_p, w4["q"], bexp,
                           w_group_scale=w4["gs"].astype(jnp.float32),
                           block_t=bt, block_n=16, interpret=True)[dest]
    np.testing.assert_allclose(np.asarray(got_4), np.asarray(ref_4),
                               atol=1e-3, rtol=1e-3)


def test_moe_grouped_pallas_matches_xla_path(monkeypatch):
    """The full grouped MoE FFN through the Pallas kernel == the ragged_dot
    path, float and quantized."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arks_tpu.models import get_config
    from arks_tpu.models import transformer as tf
    from arks_tpu.models.moe import moe_ffn_grouped
    from arks_tpu.models.quant import quantize_params

    cfg = get_config("tiny-moe")
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mp = params["layers"]
    mp1 = jax.tree.map(lambda a: a[0], mp)  # layer 0 slice
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.hidden_size),
                          jnp.float32)

    monkeypatch.setenv("ARKS_MOE_KERNEL", "xla")
    ref = moe_ffn_grouped(x, mp1, cfg)
    monkeypatch.setenv("ARKS_MOE_KERNEL", "pallas")
    got = moe_ffn_grouped(x, mp1, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)

    qp = quantize_params(params)["layers"]
    qp1 = jax.tree.map(lambda a: a[0], qp)
    monkeypatch.setenv("ARKS_MOE_KERNEL", "xla")
    ref_q = moe_ffn_grouped(x, qp1, cfg)
    monkeypatch.setenv("ARKS_MOE_KERNEL", "pallas")
    got_q = moe_ffn_grouped(x, qp1, cfg)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(ref_q),
                               atol=2e-3, rtol=2e-3)

    # int4 (w4a16) experts: group-scale dequant fused in the kernel.
    q4 = quantize_params(params, bits=4)["layers"]
    q41 = jax.tree.map(lambda a: a[0], q4)
    monkeypatch.setenv("ARKS_MOE_KERNEL", "xla")
    ref_4 = moe_ffn_grouped(x, q41, cfg)
    monkeypatch.setenv("ARKS_MOE_KERNEL", "pallas")
    got_4 = moe_ffn_grouped(x, q41, cfg)
    np.testing.assert_allclose(np.asarray(got_4), np.asarray(ref_4),
                               atol=2e-3, rtol=2e-3)
