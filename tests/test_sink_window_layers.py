"""The ``mimo_v2`` block on the CPU at ``tiny-swa-sink-moe`` size: window
layers with KV heads of their own and a sink logit a head, values narrower
than keys, a value scale, a rotary share in both kinds, a first period cut
short by the dense prefix, sigmoid-routed experts and no shared one.  The
configuration as ``from_hf_config`` reads it and what it and every other
reader refuse, the ragged launch with a value width and a sink against a
dense masked softmax, the row write of rows of two widths, a share of a
routed layer against the uncut layer, the two pools' shapes, labels and
byte counters, the weight-name mapping on seeded leaves.

The served-against-reference comparison (with the must-fail controls) is
``benchmarks/tests/test_reference_swa_sink_moe.py``, imported into tier-1 by
``tests/test_sink_window_reference.py``."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import transformer as tf, weights
from arks_tpu.models.config import ModelConfig, get_config
from arks_tpu.ops import paged_attention as pa
from arks_tpu.ops.attention import paged_mixed_update_and_attend

import harness

CONFIGS = harness.CONFIGS
TINY = "tiny-swa-sink-moe"
_config = harness.published


def _published() -> dict:
    """MiMo-V2.5's published ``config.json``: the benchmark's file with what
    its ``reduced`` lists put back (48 layers, 256 experts, the whole
    vocabulary; the first period cut to four window layers by the dense
    layer)."""
    return harness.published(
        "mimo-v2.5-ep16-l13", num_hidden_layers=48, n_routed_experts=256,
        vocab_size=152576, moe_layer_freq=[0] + [1] * 47,
        hybrid_layer_pattern=[0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7)


# ---------------------------------------------------------------------------
# The configuration
# ---------------------------------------------------------------------------


def test_from_hf_config_reads_the_published_file_key_for_key():
    cfg = ModelConfig.from_hf_config(_published(), name="mimo")
    assert cfg.windowed and not cfg.latent and not cfg.linear
    # 48 = 1 dense + (4 window + 1 full) + 7 x (5 window + 1 full).
    assert (cfg.num_layers, cfg.first_k_dense, cfg.short_period,
            cfg.window_period, cfg.num_periods, cfg.window_tail) == (
                48, 1, 4, 5, 7, 0)
    assert (cfg.num_full_layers, cfg.num_window_layers) == (9, 39)
    assert list(cfg.layer_kinds()) == [
        "window" if k else "full"
        for k in _published()["hybrid_layer_pattern"]]
    assert (cfg.num_heads, cfg.window_num_heads, cfg.num_kv_heads,
            cfg.window_kv_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.sliding_window) == (64, 64, 4, 8, 192, 128, 128)
    assert (cfg.kv_heads_of(False), cfg.kv_heads_of(True)) == (4, 8)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.partial_rotary_factor,
            cfg.window_partial_rotary_factor) == (1e7, 1e4, 0.334, 0.334)
    assert int(cfg.head_dim * cfg.partial_rotary_factor) == 64
    assert cfg.attn_sink == ("window",) and cfg.attn_value_scale == 0.707
    assert cfg.sink_of(True) and not cfg.sink_of(False)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.scoring_func,
            cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.n_shared_experts, cfg.shared_expert_intermediate_size,
            cfg.routed_scaling_factor, cfg.norm_topk_prob) == (
                256, 8, "sigmoid", 2048, 16384, 0, 0, 1.0, True)
    assert cfg.rms_norm_eps == 1e-5 and not cfg.rope_hf_yarn
    assert not cfg.attn_gate and not cfg.qkv_bias
    # 309B by shape (MiMo-V2-Flash 309B-A15B).
    assert 308e9 < cfg.num_params() < 310e9


def test_the_benchmark_configuration_is_whole_periods_and_a_share():
    cfg = ModelConfig.from_hf_config(
        os.path.join(CONFIGS, "mimo-v2.5-ep16-l13"), name="cut")
    assert cfg.layer_kinds() == ("full",) + (("window",) * 5 + ("full",)) * 2
    assert (cfg.short_period, cfg.num_periods, cfg.num_experts,
            cfg.vocab_size) == (-1, 2, 16, 19072)
    held = cfg.with_expert_share(16, 0)
    assert held.router_width == 256
    # int8 weights, a byte a parameter: the issue's 6.42 GB to within 2 %.
    assert abs(held.num_params() / 6.42e9 - 1) < 0.02


def test_the_tiny_preset_is_what_its_config_file_says():
    got = ModelConfig.from_hf_config(
        dict(_config("tiny-swa-sink-moe"), n_routed_experts=16),
        name="tiny-swa-sink-moe")
    assert got == get_config("tiny-swa-sink-moe")
    assert got.layer_kinds() == ("full", "window", "full", "window",
                                 "window", "full", "window", "window", "full")


_OTHERS = {
    "plain": {"model_type": "qwen2", "vocab_size": 512, "hidden_size": 64,
              "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 8},
    "deepseek_v3": "tiny-mla-moe", "laguna": "tiny-swa-moe",
    "solar_open2": "tiny-linear-moe", "gigachat3_5": "tiny-latent-linear-moe",
}


@pytest.mark.parametrize("reader", sorted(_OTHERS))
@pytest.mark.parametrize("key, value", [
    ("hybrid_layer_pattern", [0, 1]), ("swa_num_key_value_heads", 8),
    ("swa_rope_theta", 10000), ("add_swa_attention_sink_bias", True),
    ("add_full_attention_sink_bias", True), ("attention_value_scale", 0.707)])
def test_every_other_reader_refuses_the_blocks_keys_by_name(reader, key,
                                                            value):
    base = _OTHERS[reader]
    base = _config(base) if isinstance(base, str) else base
    ModelConfig.from_hf_config(dict(base), name="ok")       # reads as it is
    with pytest.raises(ValueError, match=f"{key}=.*only model_type "
                                         "'mimo_v2'"):
        ModelConfig.from_hf_config(dict(base, **{key: value}), name="t")
    # What says "the usual thing" is no refusal.
    usual = {"attention_value_scale": 1.0}.get(key, False)
    ModelConfig.from_hf_config(dict(base, **{key: usual}), name="t")


def test_a_plain_config_with_a_window_names_both_readers():
    with pytest.raises(ValueError, match="'laguna' and 'mimo_v2'"):
        ModelConfig.from_hf_config(dict(_OTHERS["plain"], sliding_window=64),
                                   name="t")


def test_a_checkpoint_raises_by_name_and_the_mapping_reads_seeded_leaves(
        tmp_path):
    """No checkpoint of the family is on the machine: ``params_from_hf``
    refuses by name.  The mapping itself is held to seeded leaves: the
    program's tree written out under the published names (``[out, in]``,
    a layer at a time, every expert of the router's width) comes back as
    the tree, a share keeping its own experts."""
    cfg = get_config("tiny-swa-sink-moe")
    with pytest.raises(weights.MimoCheckpointError, match="qkv_proj"):
        weights.params_from_hf(cfg, str(tmp_path))
    tree = jax.tree.map(np.asarray, tf.init_params(
        cfg, jax.random.PRNGKey(5), jnp.float32))
    at = dict.fromkeys(("dense_layers", "layers", "win_layers"), 0)
    t = {"model.embed_tokens.weight": tree["embed"],
         "model.norm.weight": tree["final_norm"],
         "lm_head.weight": tree["lm_head"].T}
    for i, kind in enumerate(cfg.layer_kinds()):
        name = ("dense_layers" if i < cfg.first_k_dense
                else "win_layers" if kind == "window" else "layers")
        lp = {k: v[at[name]] for k, v in tree[name].items()}
        at[name] += 1
        base = f"model.layers.{i}."
        t[base + "input_layernorm.weight"] = lp["attn_norm"]
        t[base + "post_attention_layernorm.weight"] = lp["mlp_norm"]
        # q / k / v are stored [H, D, E]: the published [out, in] matrix
        # with its rows split into heads, so x W^T is the step's dot.
        for leaf, hf in (("wq", "q_proj"), ("wk", "k_proj"),
                         ("wv", "v_proj")):
            w = lp[leaf].reshape(-1, lp[leaf].shape[-1])
            t[f"{base}self_attn.{hf}.weight"] = w
            x = np.linspace(-1, 1, w.shape[1], dtype=np.float32)
            assert np.allclose(
                (x @ w.T).reshape(lp[leaf].shape[:2]),
                np.einsum("e,hde->hd", x, lp[leaf]), atol=1e-5)
        t[base + "self_attn.o_proj.weight"] = lp["wo"].T
        if "attn_sink" in lp:
            t[base + "self_attn.attention_sink_bias"] = lp["attn_sink"]
        ffn = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
               ("w_down", "down_proj"))
        if name == "dense_layers":
            for leaf, hf in ffn:
                t[f"{base}mlp.{hf}.weight"] = lp[leaf].T
            continue
        t[base + "mlp.gate.weight"] = lp["router"].T
        t[base + "mlp.gate.e_score_correction_bias"] = lp["router_bias"]
        for e in range(cfg.num_experts):
            for leaf, hf in ffn:
                t[f"{base}mlp.experts.{e}.{hf}.weight"] = lp[leaf][e].T
    assert sum("attention_sink_bias" in k for k in t) == 5   # window layers
    got = weights.mimo_v2_tree(cfg, t, np.float32)
    assert jax.tree.structure(got) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        assert np.array_equal(a, b)
    half = weights.mimo_v2_tree(
        dataclasses.replace(cfg, num_experts=8).with_expert_share(2, 1), t,
        np.float32)
    assert np.array_equal(half["win_layers"]["w_up"],
                          tree["win_layers"]["w_up"][:, 8:])
    assert half["layers"]["router"].shape == (3, 64, 16)      # whole width
    with pytest.raises(weights.MimoCheckpointError, match="fuse"):
        weights.mimo_v2_tree(
            cfg, {"model.layers.0.self_attn.qkv_proj.weight": 0}, np.float32)


# ---------------------------------------------------------------------------
# The two pools and the tree
# ---------------------------------------------------------------------------


def test_the_pools_have_a_head_count_a_kind_and_a_width_for_keys_and_values():
    cfg = get_config("tiny-swa-sink-moe")
    cache = tf.init_paged_cache(cfg, 6, 128, quantized=True, win_pages=4)
    assert cache.k.shape == (4, 6, 2, 128, 24)      # full: 2 KV heads
    assert cache.v.shape == (4, 6, 2, 128, 16)
    assert cache.win.k.shape == (5, 4, 4, 128, 24)  # window: 4 KV heads
    assert cache.win.v.shape == (5, 4, 4, 128, 16)
    assert cache.win.k_scale.shape == (5, 4, 4, 128)
    # K + V rows and two float32 scales a head a token a layer.
    assert cache.token_bytes == 4 * 2 * (24 + 16 + 8)
    assert cache.win.token_bytes == 5 * 4 * (24 + 16 + 8)
    # Padded for the chip: keys to whole 128-lane tiles (192 -> 256 at the
    # published width; Mosaic refuses a 192-lane slice), values on their own.
    real = ModelConfig.from_hf_config(_published(), name="mimo")
    assert (tf.cache_head_dim(real, True), tf.cache_value_dim(real, True),
            tf.cache_head_dim(real), tf.cache_value_dim(real)) == (
                256, 128, 192, 128)
    # A model without a value width stores values as wide as its keys.
    plain = get_config("tiny-swa-moe")
    assert tf.cache_value_dim(plain) == tf.cache_head_dim(plain) == 16
    assert tf.cache_value_dim(plain, True) == 128
    lat = get_config("tiny-mla-moe")
    assert tf.cache_value_dim(lat) == tf.cache_head_dim(lat)


def test_the_tree_has_a_stack_a_kind_with_its_own_projections():
    cfg = get_config("tiny-swa-sink-moe")
    p = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                       jax.random.PRNGKey(0))
    # q / k / v head-split, the contraction dimension minor (init_params).
    assert p["dense_layers"]["wq"].shape == (1, 8, 24, 64)
    # The cut-short period's full layer stands ahead of the periods'.
    assert p["layers"]["wk"].shape == (3, 2, 24, 64)
    assert p["layers"]["wv"].shape == (3, 2, 16, 64)
    assert p["win_layers"]["wk"].shape == (5, 4, 24, 64)
    assert p["win_layers"]["wv"].shape == (5, 4, 16, 64)
    assert p["win_layers"]["wo"].shape == (5, 8 * 16, 64)
    assert p["win_layers"]["attn_sink"].shape == (5, 8)
    assert "attn_sink" not in p["layers"] and "attn_sink" not in \
        p["dense_layers"]
    assert "shared_up" not in p["layers"]           # no shared expert
    assert p["layers"]["router_bias"].shape == (3, 16)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(p))
    assert n == cfg.num_params()


# ---------------------------------------------------------------------------
# The launch with a value width and a sink; the row write of two widths
# ---------------------------------------------------------------------------


def _batch(kv: str, dk: int = 24, dv: int = 16, seed: int = 0):
    """Two decode lanes deep in their contexts and a chunk that starts a
    sequence, over pools whose keys are ``dk`` and values ``dv`` wide."""
    page = 128 if kv == "int8" else 16
    hkv, g, maxp, lanes = 2, 4, 6, 3
    rng = np.random.default_rng(seed)
    n_pages = lanes * maxp
    pos0 = np.asarray([page * 21 // 8, page * 37 // 8, 0], np.int32)
    qlen = np.asarray([1, 1, page * 11 // 8], np.int32)
    tables = np.arange(n_pages, dtype=np.int32).reshape(lanes, maxp)
    t = int(qlen.sum())
    token_slot = np.repeat(np.arange(lanes), qlen).astype(np.int32)
    token_pos = np.concatenate([pos0[s] + np.arange(qlen[s])
                                for s in range(lanes)]).astype(np.int32)
    kshape, vshape = (2, n_pages, hkv, page, dk), (2, n_pages, hkv, page, dv)
    if kv == "int8":
        k = rng.integers(-127, 128, kshape).astype(np.int8)
        v = rng.integers(-127, 128, vshape).astype(np.int8)
        ks = rng.uniform(0.005, 0.02, kshape[:-1]).astype(np.float32)
        vs = rng.uniform(0.005, 0.02, kshape[:-1]).astype(np.float32)
    else:
        k = rng.normal(size=kshape).astype(np.float32)
        v = rng.normal(size=vshape).astype(np.float32)
        ks = vs = None
    j = jnp.asarray
    return dict(
        q=j(rng.normal(size=(t, hkv * g, dk)).astype(np.float32)),
        k_new=j(rng.normal(size=(t, hkv, dk)).astype(np.float32)),
        v_new=j(rng.normal(size=(t, hkv, dv)).astype(np.float32)),
        k_pool=j(k), v_pool=j(v), tables=j(tables),
        token_slot=j(token_slot), token_pos=j(token_pos),
        seq_q_start=j((np.cumsum(qlen) - qlen).astype(np.int32)),
        seq_q_len=j(qlen), seq_pos_start=j(pos0), layer=1,
        k_scale=None if ks is None else j(ks),
        v_scale=None if vs is None else j(vs))


def _dense(b, got, window: int, sink):
    """A dense masked softmax, a row at a time, over the rows the pools
    hold AFTER the write (``got[1:]``): the sink as one extra column,
    dropped after the softmax."""
    kp, vp, ks, vs = (None if x is None else np.asarray(x, np.float32)
                      for x in got[1:])
    page = kp.shape[3]
    tables = np.asarray(b["tables"])
    q = np.asarray(b["q"])
    hkv, g = kp.shape[2], q.shape[1] // kp.shape[2]
    out = np.zeros((q.shape[0], q.shape[1], vp.shape[-1]), np.float32)
    for t, (slot, pos) in enumerate(zip(np.asarray(b["token_slot"]),
                                        np.asarray(b["token_pos"]))):
        lo = max(pos - window + 1, 0) if window else 0
        at = np.arange(lo, pos + 1)
        pg, off = tables[slot, at // page], at % page
        for h in range(hkv * g):
            kh = kp[1, pg, h // g, off]
            vh = vp[1, pg, h // g, off]
            if ks is not None:
                kh = kh * ks[1, pg, h // g, off, None]
                vh = vh * vs[1, pg, h // g, off, None]
            s = kh @ q[t, h] / np.sqrt(q.shape[-1])
            if sink is not None:
                s = np.append(s, sink[h])
            p = np.exp(s - s.max())
            p = (p / p.sum())[: len(at)]
            out[t, h] = p @ vh
    return out


@pytest.mark.parametrize("kv, window, sink", [
    ("float32", 5, True),       # a window of 5/16 of a page, with a sink
    ("float32", 0, False),      # values narrower than keys, nothing else
    ("int8", 40, True),         # window < page, int8 pages
    ("int8", 0, False),
])
def test_the_launch_with_a_value_width_and_a_sink_is_a_dense_masked_softmax(
        kv, window, sink):
    b = _batch(kv)
    sinks = np.linspace(-1.0, 3.0, 8).astype(np.float32) if sink else None
    more = dict(window=window, **({"sink": jnp.asarray(sinks)} if sink
                                  else {}))
    got = paged_mixed_update_and_attend(**b, impl="pallas", **more)
    want = paged_mixed_update_and_attend(**b, impl="xla", **more)
    assert got[0].shape == (b["q"].shape[0], 8, 16)       # [T, H, Dv]
    dense = _dense(b, want, window, sinks)
    np.testing.assert_allclose(np.asarray(want[0]), dense, rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(got[0]), dense, rtol=2e-3,
                               atol=2e-3)
    # The rows written: K and V rows of their own widths, byte for byte
    # what the oracle's scatter leaves (a quantised row's scale to an ulp).
    for a, c in zip(got[1:], want[1:]):
        if a is not None:
            assert a.shape == c.shape
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(c, np.float32),
                rtol=1e-6, atol=1 if a.dtype == jnp.int8 else 0)
    if sink:
        # The sink takes mass: without it the same rows read otherwise.
        bare = paged_mixed_update_and_attend(**b, impl="xla", window=window)
        assert np.abs(np.asarray(bare[0]) - dense).max() > 1e-2


def test_a_sink_far_below_every_score_is_no_sink_and_one_far_above_is_all():
    b = _batch("float32")
    bare = np.asarray(paged_mixed_update_and_attend(
        **b, impl="pallas", window=5)[0])
    for logit, want in ((-80.0, bare), (80.0, np.zeros_like(bare))):
        got = paged_mixed_update_and_attend(
            **b, impl="pallas", window=5,
            sink=jnp.full((8,), logit, jnp.float32))[0]
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_a_sink_is_refused_where_the_softmax_state_is_carried():
    q = jnp.zeros((1, 1, 2, 8, 16))
    pool = jnp.zeros((1, 2, 1, 16, 16))
    wl = pa.build_mixed_work_list(
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32), page=16,
        block_q=8, num_qb=1, max_pages=2)
    with pytest.raises(ValueError, match="sink logit"):
        pa._ragged_launch(
            q, pool, pool, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), wl, 0, None, None,
            carry_state=(q[..., :1], q[..., :1], q), compact=False,
            block_q=8, dma_depth=2, interpret=True, head_group=1,
            sink=jnp.zeros((1, 2)))


# ---------------------------------------------------------------------------
# The engine: labels, gauges, bytes a kind
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def oracle_run():
    """One engine on the XLA path, the three requests over both pools: what it labels itself, what its counters rose by, the
    log-probabilities it served."""
    def counted(eng):
        m = eng.metrics
        return dict(kv_full=m.mixed_kv_bytes_total.get(kind="full"),
                    kv_window=m.mixed_kv_bytes_total.get(kind="window"),
                    released=m.kv_window_pages_released_total.total())

    with harness.fresh(TINY) as eng:
        labels = dict(eng.resolved_config)
        before = counted(eng)
        _, lps = harness.serve(eng)
        stats = {k: v - before[k] for k, v in counted(eng).items()}
        m = eng.metrics
        stats.update(
            page_full=m.kv_pool_page_bytes.get(kind="full"),
            page_window=m.kv_pool_page_bytes.get(kind="window"),
            head_full=eng._page_head_bytes(),
            head_window=eng._page_head_bytes(eng._cache.win),
            free_after=eng._win.alloc.free_pages,
            win_pages=eng._win.alloc.num_pages)
    return labels, lps, stats


def test_the_engine_says_what_a_cell_must_expect(oracle_run):
    labels, _, stats = oracle_run
    assert labels["kv_page"] == "kv+window"
    assert labels["kv_heads"] == "2/4" and labels["attn_sink"] == "window"
    assert labels["expert_share"] == "0/1"
    # Every other block says one count and no sink.
    assert stats["released"] > 0 and stats["free_after"] == stats["win_pages"]


def test_each_pool_is_counted_at_its_own_heads_and_widths(oracle_run):
    _, _, s = oracle_run
    page = 16
    # bf16 pages: a (page, head) block moves K at 24 and V at 16 lanes.
    assert s["head_full"] == s["head_window"] == page * (24 + 16) * 2
    # A pool's page over all its layers: 4 full layers of 2 heads, 5 window
    # layers of 4.
    assert s["page_full"] == 4 * 2 * s["head_full"]
    assert s["page_window"] == 5 * 4 * s["head_window"]
    # The step's page stream, a kind: whole (page, head) blocks of the
    # kind's own head count.
    assert s["kv_full"] % (2 * s["head_full"]) == 0
    assert s["kv_window"] % (4 * s["head_window"]) == 0
    assert s["kv_full"] > 0 and s["kv_window"] > 0


def test_the_kernel_path_serves_the_oracle_paths_numbers(oracle_run,
                                                         monkeypatch):
    """The same engine through the ragged kernel (interpret mode here): the
    window launch with its sink over 4 KV heads, the full layers' over 2,
    rows of two widths written by the row-write kernel.  The first token's
    number is held (what a prompt's last row reads through both pools); a
    later one follows whichever token a tie of these tiny logits chose."""
    _, lps0, _ = oracle_run
    monkeypatch.setenv("ARKS_ATTN_IMPL", "pallas")
    with harness.fresh(TINY) as eng:
        assert eng.resolved_config["decode_impl"] == "pallas"
        _, lps = harness.drain(eng, harness.requests(2, logprobs=1)[:2])
        for rid in lps:
            np.testing.assert_allclose(lps[rid][0], lps0[rid][0], atol=1e-2)
