"""Gated-delta-rule linear layers beside gated latent-attention layers (the
``gigachat3_5`` block) on the CPU at ``tiny-latent-linear-moe`` size: the
configuration as ``from_hf_config`` reads it and what it refuses, the delta
rule's chunked scan against the one-step recurrence with a decay a head,
grouped key heads, the step program against the reference family's full
forward (a prompt cut at odd lengths through latent pages and state, decode
and prefill lanes in one batch, a slot another sequence just left), every
reading the published config leaves open held alike by program and
reference, a share of a routed layer against the uncut layer, the cache
tuple, the engine at pipeline depth 0 and 2, and what such a model refuses
by name.

The served-against-reference comparison (with the must-fail controls) is
``benchmarks/tests/test_reference_latent_linear_moe.py``, imported into
tier-1 by ``tests/test_contract_latent_linear_moe.py``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import moe, transformer as tf
from arks_tpu.models.config import ModelConfig, get_config

import harness
# The float64 recurrence (a ``[n, H, 1]`` log decay broadcasts in it as a
# ``[n, H, d]`` one) and the ragged layouts: the ``solar_open2`` block's.
from test_linear_layers import _LAYOUTS, _recurrence

GIGA = "gigachat3.5-432b-ep8-l5"
TINY = "tiny-latent-linear-moe"


def _published() -> dict:
    """GigaChat3.5-432B-A28B's published ``config.json``: the benchmark's
    file with what its ``reduced`` lists put back (40 layers behind 3 dense
    ones, a latent layer every fourth, 256 experts, the whole vocabulary,
    the two draft modules)."""
    return harness.published(
        GIGA, num_hidden_layers=40, first_k_dense_replace=3,
        full_attention_layers=list(range(3, 40, 4)),
        n_routed_experts=256, vocab_size=128256, num_nextn_predict_layers=2)


# ---------------------------------------------------------------------------
# The configuration
# ---------------------------------------------------------------------------


def test_from_hf_config_reads_the_published_file_key_for_key():
    cfg = ModelConfig.from_hf_config(_published(), name="giga")
    kinds = cfg.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "full"] \
        == list(range(3, 40, 4))
    assert kinds.count("linear") == 30 == cfg.num_linear_layers
    # 3 dense linear layers, a first period the prefix cut to its latent
    # layer, nine whole periods.
    assert (cfg.head_layers, cfg.short_period, cfg.num_periods,
            cfg.inner_tail) == (3, 0, 9, 0)
    assert cfg.linear_head and cfg.latent and cfg.linear
    assert cfg.num_full_layers == 10 and cfg.num_routed_layers == 37
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (7168, 18432, 2048)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.num_heads) \
        == (1536, 512, 128, 64, 128, 64)
    assert cfg.latent_row == 576 and cfg.head_dim == 192
    assert (cfg.linear_key_heads, cfg.linear_num_heads, cfg.linear_head_dim,
            cfg.linear_conv) == (32, 64, 128, 4)
    assert cfg.linear_conv_dim == 16384 and cfg.linear_dim == 8192
    assert cfg.linear_head_decay and not cfg.linear_neg_eigval
    assert cfg.linear_gate_scale == 2.0 and cfg.linear_norm_eps == 1e-6
    assert cfg.norm_gate == 2.0 and cfg.norm_post and cfg.attn_out_gate
    assert cfg.swiglu_limit == 10.0
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_shared_experts,
            cfg.routed_scaling_factor) == (256, 8, 1, 2.5)
    assert cfg.scoring_func == "sigmoid" and cfg.norm_topk_prob
    assert cfg.rope_yarn == (8.0, 32768.0, 32.0, 1.0, 1.0, 1.0)
    assert cfg.rope_theta == 100000.0
    # 192^-1/2 times YaRN's m^2, m = 0.1 ln 8 + 1.
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    assert cfg.max_position_embeddings == 262144
    # 432 B with the two draft modules, which are read and dropped.
    assert 425e9 < cfg.num_params() < 434e9


def test_the_draft_modules_are_read_and_dropped_not_refused():
    d = _published()
    with_, without = (ModelConfig.from_hf_config(
        dict(d, num_nextn_predict_layers=n), name="g") for n in (2, 0))
    assert with_ == without


def test_the_benchmark_configuration_is_a_dense_layer_a_period_and_a_share():
    deploy = harness.deploy(GIGA)
    share = deploy["share"]
    cfg = ModelConfig.from_hf_config(
        os.path.join(harness.CONFIGS, GIGA), name="g").with_expert_share(
        share["chips_per_layer"], share["index"])
    assert cfg.layer_kinds() == ("linear",) * 4 + ("full",)
    assert (cfg.head_layers, cfg.short_period, cfg.num_periods) == (1, -1, 1)
    assert (cfg.num_experts, cfg.router_width) == (32, 256)
    assert cfg.vocab_size * 8 == share["published"]["vocab_size"]
    assert 7.5e9 < cfg.num_params() < 7.8e9     # one byte a parameter
    assert deploy["state_dtype"] == "float32"
    pub, here = _published(), harness.published(GIGA)
    assert sorted(k for k in pub if pub[k] != here[k]) \
        == sorted(deploy["reduced"])
    # What a slot holds whatever the context, and a token's latent row.
    cache = jax.eval_shape(lambda: tf.init_paged_cache(
        cfg, 4, 256, jnp.bfloat16, state_slots=2))
    assert cache.lin.s.shape == (4, 2, 64, 128, 128)
    assert cache.lin.conv.shape == (4, 2, 3, 16384)
    assert cache.k.shape == (1, 4, 1, 256, 576) and cache.v is None


def test_the_tiny_preset_is_what_its_config_file_says():
    cfg = ModelConfig.from_hf_config(
        harness.published(TINY, n_routed_experts=16), name=TINY)
    assert cfg == get_config(TINY)
    assert cfg.layer_kinds() == ("linear", "linear", "linear", "full",
                                 "linear", "linear", "full", "linear")
    assert (cfg.head_layers, cfg.short_period, cfg.num_periods,
            cfg.inner_tail) == (2, 1, 1, 1)


@pytest.mark.parametrize("key, value", [
    ("full_attention_layers", [3, 7]), ("linear_num_key_heads", 2),
    ("linear_attention_type", "GigaChat35GatedDeltaNet"),
    ("gated_attention", True), ("norm_type", "ZeroCenteredGatedNorm"),
    ("layernorm_type", "pre_post"), ("swiglu_limit", 10)])
@pytest.mark.parametrize("model_type", ["qwen2", "deepseek_v3"])
def test_a_gigachat_key_under_another_model_type_is_refused_by_name(
        key, value, model_type):
    """Before, such a file went to the GQA reader or (with
    ``n_routed_experts``) to the latent reader, which dropped the key."""
    d = dict(model_type=model_type, vocab_size=512, hidden_size=64,
             intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, n_routed_experts=8, **{key: value})
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(d, name="m")


def test_the_usual_values_of_those_keys_refuse_nothing():
    d = dict(model_type="qwen2", vocab_size=512, hidden_size=64,
             intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, norm_type="RMSNorm",
             layernorm_type="pre", swiglu_limit=0, gated_attention=False)
    assert ModelConfig.from_hf_config(d, name="m").num_layers == 2


def test_a_checkpoint_raises_by_name_instead_of_being_mis_mapped(tmp_path):
    from arks_tpu.models import weights
    (tmp_path / "model.safetensors").write_bytes(b"")
    with pytest.raises(weights.LatentLinearCheckpointError,
                       match="sandwich-norm"):
        weights.load_params(get_config(TINY), str(tmp_path))


# ---------------------------------------------------------------------------
# The delta rule with a decay a head; grouped key heads
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_the_chunked_scan_is_the_one_step_recurrence_with_a_decay_a_head(
        layout):
    """``g [T, H, 1]``: one log decay a head, broadcast over the head's
    channels by the chunk form and by the one-step pass alike."""
    h, d, slots = 3, 8, 6
    lanes = _LAYOUTS[layout]
    t = max(s + n for s, n, _ in lanes) + 5
    rng = np.random.default_rng(len(layout))
    q, k, v = (rng.standard_normal((t, h, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.001, 0.9, (t, h, 1)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (t, h)).astype(np.float32)
    state = rng.standard_normal((slots, h, d, d)).astype(np.float32)
    q_start, q_len, pos = (np.asarray(x, np.int32) for x in zip(*lanes))
    fresh = (q_len > 0) & (pos == 0)
    stack = np.stack([state + 1, state, state - 1])
    o, new = jax.jit(tf._linear_state)(
        *(jnp.asarray(x) for x in (q, k, v, g, beta, stack, 1, q_start,
                                   q_len, fresh)))
    o, new = np.asarray(o), np.asarray(new)
    assert np.array_equal(new[0], stack[0]) and np.array_equal(new[2],
                                                               stack[2])
    for b, (s0, n, p) in enumerate(lanes):
        if not n:
            assert np.array_equal(new[1][b], state[b])
            continue
        rows = slice(s0, s0 + n)
        want_o, want_s = _recurrence(
            q[rows], k[rows], v[rows], g[rows], beta[rows],
            np.zeros_like(state[b]) if p == 0 else state[b])
        np.testing.assert_allclose(o[rows], want_o, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(new[1][b], want_s, rtol=2e-4, atol=2e-4)


def test_key_heads_are_shared_by_value_heads_behind_one_convolution():
    """2 key heads under 4 value heads: the convolution runs over the 2 x 2
    x 16 + 4 x 16 channels of q | k | v, a lane's first rows reach into its
    slot's carry, key head j comes back for value heads 2j and 2j + 1, and
    the decay is one number a head."""
    cfg = get_config(TINY)
    assert (cfg.linear_key_dim, cfg.linear_dim, cfg.linear_conv_dim) \
        == (32, 64, 128)
    lp = jax.tree.map(
        lambda a: a[0].astype(jnp.float32),
        tf.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)["lin_layers"])
    rng = np.random.default_rng(0)
    lanes = [(0, 1, 7), (1, 2, 0), (3, 9, 5), (0, 0, 0)]
    t = 14
    x = rng.standard_normal((t, cfg.hidden_size)).astype(np.float32)
    conv = rng.standard_normal((4, 3, 128)).astype(np.float32)
    q_start, q_len, pos = (np.asarray(v, np.int32) for v in zip(*lanes))
    fresh = (q_len > 0) & (pos == 0)
    q, k, v, g, beta, new = tf._linear_qkv(
        jnp.asarray(x), lp, cfg, jnp.asarray(conv), jnp.asarray(q_start),
        jnp.asarray(q_len), jnp.asarray(fresh))
    assert q.shape == k.shape == v.shape == (t, 4, 16)
    assert g.shape == (t, 4, 1) and beta.shape == (t, 4)
    pre = np.concatenate([x @ np.asarray(lp[n]) for n in ("wq", "wk", "wv")],
                         axis=-1)
    w = np.concatenate([np.asarray(lp[n]) for n in ("conv_q", "conv_k",
                                                    "conv_v")], axis=-1)
    for b, (s0, n, p) in enumerate(lanes):
        if not n:
            assert np.array_equal(np.asarray(new[b]), conv[b])
            continue
        line = np.concatenate([np.zeros_like(conv[b]) if p == 0 else conv[b],
                               pre[s0:s0 + n]])
        np.testing.assert_allclose(np.asarray(new[b]), line[-3:], rtol=1e-5,
                                   atol=1e-6)
        y = sum(line[i: i + n] * w[i] for i in range(4))        # [n, 128]
        y = y / (1 + np.exp(-y))
        yk = y[:, 32:64].reshape(n, 2, 16)
        unit = yk / np.sqrt((yk ** 2).sum(-1, keepdims=True) + 1e-6)
        got_k = np.asarray(k[s0:s0 + n])
        np.testing.assert_allclose(got_k[:, 0::2], unit, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(got_k[:, 1::2], unit, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(v[s0:s0 + n]),
                                   y[:, 64:].reshape(n, 4, 16), rtol=1e-4,
                                   atol=1e-6)
    rate = np.exp(np.asarray(lp["a_log"])) * np.log1p(np.exp(
        x @ np.asarray(lp["w_a"]) + np.asarray(lp["dt_bias"])))
    np.testing.assert_allclose(np.asarray(g)[..., 0], -rate, rtol=1e-4)
    assert float(g.max()) < 0 and 0 < float(beta.min()) \
        and float(beta.max()) < 1            # no negative eigenvalues


# ---------------------------------------------------------------------------
# The clamp
# ---------------------------------------------------------------------------


def test_the_clamp_is_the_stated_function():
    g = jnp.asarray([-3.0, 0.1, 0.5, 9.0])
    u = jnp.asarray([-9.0, 0.1, 0.5, 9.0])
    got = np.asarray(moe.swiglu(g, u, 0.2))
    gc, uc = np.minimum(np.asarray(g), 0.2), np.clip(np.asarray(u), -.2, .2)
    np.testing.assert_allclose(got, gc / (1 + np.exp(-gc)) * uc, rtol=1e-6)
    assert np.array_equal(np.asarray(moe.swiglu(g, u)),
                          np.asarray(jax.nn.silu(g) * u))


# ---------------------------------------------------------------------------
# The cache tuple, the engine
# ---------------------------------------------------------------------------


def test_the_pool_is_the_latent_layers_and_the_state_a_fixed_size_a_slot():
    cfg = get_config(TINY)
    small = tf.init_paged_cache(cfg, 8, 16, jnp.bfloat16, state_slots=3)
    big = tf.init_paged_cache(cfg, 64, 16, jnp.bfloat16, state_slots=3)
    assert small.k.shape == (cfg.num_full_layers, 8, 1, 16, 40)
    assert small.v is None and small.latent and small.k_scale is None
    assert small.token_bytes == 2 * 40 * 2          # two layers, one row each
    assert small.lin.s.shape == (6, 3, 4, 16, 16)
    assert small.lin.s.dtype == jnp.float32
    assert small.lin.conv.shape == (6, 3, 3, 128)
    per = 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    assert small.lin.slot_bytes == big.lin.slot_bytes == per
    with pytest.raises(ValueError, match="state_slots"):
        tf.init_paged_cache(cfg, 8, 16, jnp.bfloat16)
    with pytest.raises(ValueError, match="bf16 only"):
        tf.init_paged_cache(cfg, 8, 16, jnp.bfloat16, quantized=True,
                            state_slots=3)


def _counted(eng):
    m = eng.metrics
    return dict(
        starts=m.linear_state_starts_total.total(),
        state_steps=m.kv_held_byte_steps_total.get(kind="state"),
        page_steps=m.kv_held_byte_steps_total.get(kind="pages"),
        latent_rows=m.mixed_latent_rows_total.total(),
        hits=m.prefix_cache_hit_tokens_total.total())


@pytest.fixture(scope="module")
def depth0_streams():
    """One drain of one engine: its streams, and what its counters rose by
    across the drain."""
    seen = []
    with harness.fresh(TINY) as eng:
        labels = dict(eng.resolved_config)
        assert eng._cache.k.shape[0] == 2 and eng._cache.v is None
        assert eng._cache.lin is not None
        before = _counted(eng)
        toks, lps = harness.drain(
            eng, harness.requests(logprobs=1),
            lambda e: seen.append((e.metrics.linear_state_bytes.get(),
                                   e.metrics.kv_page_bytes.get())))
        stats = {k: v - before[k] for k, v in _counted(eng).items()}
        stats.update(labels=labels, slot_bytes=eng._lin_slot_bytes,
                     page_bytes=eng._page_bytes, seen=seen,
                     rendered=eng.metrics.registry.render())
    return toks, lps, stats


def test_the_engine_labels_and_counts_latent_pages_and_state(depth0_streams):
    toks, _, s = depth0_streams
    assert all(len(t) == 10 for t in toks.values())
    labels = s["labels"]
    assert labels["kv_page"] == "latent+state"
    assert labels["state_dtype"] == "float32"
    assert labels["kv_dtype"] == "bf16" and labels["kv_layout"] == "paged"
    assert labels["mixed_step"] == "true" and labels["pipeline_depth"] == "0"
    assert 'kv_page="latent+state"' in s["rendered"]
    assert s["starts"] == 3
    assert s["slot_bytes"] == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    # A page of the pool: the two latent layers' one row a token, bf16.
    page = 16
    assert s["page_bytes"] == 2 * page * 40 * 2
    assert max(live for live, _ in s["seen"]) == 2 * s["slot_bytes"]
    assert all(p % s["page_bytes"] == 0 for _, p in s["seen"])
    assert max(p for _, p in s["seen"]) >= 9 * s["page_bytes"]   # 133 tokens
    assert s["state_steps"] > 0 and s["page_steps"] > 0
    # Latent rows are written by the latent layers only (2 of 8).
    assert s["latent_rows"] == 2 * (70 + 9 + 133 + 3 * 9)
    assert s["hits"] == 0                    # no prefix is indexed or matched


def test_every_refused_argument_of_a_pod_is_named_together(monkeypatch):
    monkeypatch.setenv("ARKS_PREEMPT", "1")
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
    with pytest.raises(ValueError) as e:
        harness.engine(TINY, kv_cache_dtype="int8", draft_model="tiny-gqa")
    for word in ("kv_cache_dtype=int8", "speculative decoding",
                 "ARKS_PREFIX_HOST_MB", "ARKS_PREEMPT"):
        assert word in str(e.value)


# ---------------------------------------------------------------------------
# The benchmark's readers (benchmarks/kernels, layer_metrics)
# ---------------------------------------------------------------------------


_SCOPES = {"arks.linear_qkv": 0.2, "arks.linear_state": 0.5,
           "arks.linear_out": 0.1, "arks.moe_dot": 0.6,
           "arks.moe_shared": 0.1, "arks.mla_q": 0.04, "arks.mla_kv": 0.02,
           "arks.attn_kernel": 0.1, "arks.attn_layout": 0.02,
           "arks.mla_out": 0.02, "arks.mla_gate": 0.03,
           "arks.norm_post": 0.05, "arks.ffn": 0.1, None: 0.12}


def test_the_norm_and_gate_reader_reads_its_three_scopes():
    from benchmarks import manifest
    read = manifest.load_reader("norm_gate_share.tput")
    dev = {"ops": [1], "busy_s": 2.0, "xplane": "x",
           "scope_seconds": dict(_SCOPES)}
    assert read({"device": dev}) == pytest.approx(4.0)        # 0.08 of 2
    # solar's elementwise gate is the third scope.
    dev["scope_seconds"]["arks.attn_gate"] = 0.02
    assert read({"device": dev}) == pytest.approx(5.0)


def test_the_norm_and_gate_reader_finds_nothing_in_the_parents_program():
    """The driver lays this PR's benchmark files over the parent's
    checkout: there the reader returns None and does not raise."""
    from benchmarks import manifest
    read = manifest.load_reader("norm_gate_share.tput")
    ctx = {"device": {"ops": [], "busy_s": 1.0, "xplane": None,
                      "slice_monotonic": (0.0, 1.0)},
           "metrics_open": {}, "metrics_close": {}, "cell": {}, "run": {},
           "engine": None, "kind": "TPU v5 lite"}
    assert read(ctx) is None
    assert read({**ctx, "device": None}) is None
    other = {**ctx["device"], "xplane": "x", "ops": [1],
             "scope_seconds": {"arks.ffn": 0.5, "arks.mla_q": 0.2,
                               "arks.attn_gate": 0.1, None: 0.1}}
    assert read({**ctx, "device": other}) is None
    assert read({**ctx, "device": {**other, "busy_s": 0.0}}) is None


def test_the_accepted_readers_read_the_new_family_unedited():
    """``linear_attn_share``, ``mla_share``, ``moe_share`` by scope; the two
    rooflines through the family's ``kernel_shapes`` (one latent layer's
    576-wide row) and ``linear_kernel_shapes`` (the 64 VALUE heads)."""
    from benchmarks import manifest
    dev = {"ops": [1], "busy_s": 2.0, "xplane": "x",
           "slice_monotonic": (0.0, 1.0), "scope_seconds": dict(_SCOPES)}
    for name, want in (("linear_attn_share.tput", 40.0),
                       ("mla_share.tput", 10.0), ("moe_share.tput", 35.0)):
        assert manifest.load_reader(name)({"device": dev}) \
            == pytest.approx(want)
    ref, config = harness.reference(TINY, "latent_linear_moe",
                                    n_routed_experts=8)
    a = ref.arch(config)
    assert ref.kernel_shapes(a) == {"heads": 4, "row": 40, "value": 32,
                                    "layers": 2}
    assert ref.linear_kernel_shapes(a) == {
        "heads": 4, "head_dim": 16, "layers": 6, "state_bytes": 4}
    big = ref.arch(harness.published(GIGA))
    assert ref.kernel_shapes(big) == {"heads": 64, "row": 576, "value": 512,
                                      "layers": 1}
    assert ref.linear_kernel_shapes(big) == {
        "heads": 64, "head_dim": 128, "layers": 4, "state_bytes": 4}
    run = {"records": [{"frames": [(0.5, 1)], "prompt_tokens": 9,
                        "first": 0.1, "sent": 0.2}]}
    dev["scope_seconds"]["arks.linear_state"] = 1e-3
    got = manifest.load_reader("linear_state_roofline.tput")(
        {"device": dev, "cell": {"reference": ref, "config": config,
                                 "deploy": {}},
         "run": run, "kind": "TPU v5 lite"})
    assert 0 < got < 1
    state = 4 * 16 * 16
    assert dev["linear_state_roofline_detail"]["bytes"] == 6 * (
        2.0 * state * 4 + 4 * (3 * 16 * 2 + 16 * 4 + 4 + 16 * 4))
