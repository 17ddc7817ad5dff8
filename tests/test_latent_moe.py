"""The latent-attention / sigmoid-routed block (DeepSeek-V3, kimi_k2) on the
CPU at ``tiny-mla-moe`` size: the configuration as ``from_hf_config`` reads
it and what it refuses, the router against a numpy loop, the share of a
layer against the uncut reference layer, the latent pool (one row a token,
stored once), the latent kernel against the XLA oracle, the served step
(chunks, then decode, through the pool) against the plain reference's full
forward, and what a latent model refuses by name.

The reference is ``benchmarks/references/mla_moe.py`` (the NON-absorbed,
published form, float32, no cache); it imports nothing of the program."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import moe, quant, transformer as tf
from arks_tpu.models.config import ModelConfig, get_config

import harness

KIMI = os.path.join(harness.CONFIGS, "kimi-k2.5-ep32-l9")


def _reference():
    from benchmarks import manifest
    return manifest.load_reference("mla_moe")


def _tiny_config(**over) -> dict:
    """The public-style ``config.json`` of the preset ``tiny-mla-moe``
    (benchmarks/configs/tiny-mla-moe holds 8 of its 16 experts)."""
    return harness.published("tiny-mla-moe",
                             **{"n_routed_experts": 16, **over})


# ---------------------------------------------------------------------------
# The configuration
# ---------------------------------------------------------------------------


def test_from_hf_config_reads_the_published_file_key_for_key():
    cfg = ModelConfig.from_hf_config(KIMI, name="kimi")
    assert cfg.latent and cfg.num_kv_heads == 1
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.head_dim, cfg.latent_row, cfg.attn_out_dim) == (192, 576, 8192)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_routed_layers) == \
        (9, 1, 8)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.n_shared_experts,
            cfg.moe_intermediate_size) == (12, 8, 1, 2048)
    assert cfg.scoring_func == "sigmoid" and cfg.norm_topk_prob
    assert cfg.routed_scaling_factor == 2.827
    assert cfg.rope_yarn == (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    # s = 192^-0.5 m^2 with m = 0.1 ln 64 + 1
    m = 0.1 * np.log(64) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    share = cfg.with_expert_share(32, 0)
    assert share.router_width == 384
    # 1 dense + 8 routed layers of this chip's share: the issue's 6.2 B.
    assert 6.1e9 < share.num_params() < 6.3e9


def test_the_tiny_preset_is_what_its_config_file_says_and_counts_its_leaves():
    preset = get_config("tiny-mla-moe")
    assert ModelConfig.from_hf_config(_tiny_config(),
                                      name="tiny-mla-moe") == preset
    params = tf.init_params(preset, jax.random.PRNGKey(0))
    assert sorted(params) == ["dense_layers", "embed", "final_norm",
                              "layers", "lm_head"]
    assert preset.num_params() == sum(
        x.size for x in jax.tree.leaves(params))
    half = preset.with_expert_share(2, 1)
    assert half.router_width == 32          # a share HOLDS num_experts


def test_a_gqa_config_with_a_rope_scaling_is_refused_not_served_plain():
    d = {"model_type": "llama", "vocab_size": 512, "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 8,
         "rope_scaling": {"type": "yarn", "factor": 4}}
    with pytest.raises(ValueError, match="rope_scaling"):
        ModelConfig.from_hf_config(d)
    assert ModelConfig.from_hf_config(dict(d, rope_scaling=None)).num_layers == 2


def test_a_latent_checkpoint_raises_by_name_instead_of_being_mis_mapped(
        tmp_path):
    from arks_tpu.models import weights
    (tmp_path / "model.safetensors").write_bytes(b"")
    cfg = get_config("tiny-mla-moe")
    with pytest.raises(weights.LatentCheckpointError, match="rotary"):
        weights.load_params(cfg, str(tmp_path))
    with pytest.raises(weights.LatentCheckpointError):
        weights.params_from_hf(cfg, str(tmp_path))


# ---------------------------------------------------------------------------
# The router, against a loop
# ---------------------------------------------------------------------------


def _route_loop(logits, bias, k, scaling, norm=True):
    vals = np.zeros((logits.shape[0], k))
    idx = np.zeros((logits.shape[0], k), int)
    for t, row in enumerate(logits):
        sigma = 1.0 / (1.0 + np.exp(-row.astype(np.float64)))
        chosen = np.argsort(-(sigma + bias), kind="stable")[:k]
        w = sigma[chosen]
        vals[t] = scaling * (w / w.sum() if norm else w)
        idx[t] = chosen
    return vals, idx


def test_router_sigmoid_bias_selects_and_unbiased_scores_weigh():
    cfg = get_config("tiny-mla-moe")
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    bias = rng.normal(size=(16,)).astype(np.float32) * 0.1
    vals, idx = moe.router_topk(jnp.asarray(logits), cfg, jnp.asarray(bias))
    want_v, want_i = _route_loop(logits, bias, 4, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    np.testing.assert_allclose(np.asarray(vals), want_v, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 2.5, rtol=1e-5)
    # The bias changes WHO is chosen ...
    _, idx0 = moe.router_topk(jnp.asarray(logits), cfg, jnp.zeros((16,)))
    assert (np.sort(np.asarray(idx0)) != np.sort(want_i)).any()
    # ... and never the weight a chosen expert gets: where both choose the
    # same set, the weights are equal whatever the bias.
    same = (np.sort(np.asarray(idx0)) == np.sort(want_i)).all(-1)
    v0, _ = moe.router_topk(jnp.asarray(logits), cfg, jnp.zeros((16,)))
    assert same.any()
    np.testing.assert_allclose(np.sort(np.asarray(v0)[same]),
                               np.sort(want_v[same]), rtol=1e-5)
    # Without normalisation the scaled scores themselves.
    import dataclasses
    raw = dataclasses.replace(cfg, norm_topk_prob=False)
    vals, _ = moe.router_topk(jnp.asarray(logits), raw, jnp.asarray(bias))
    np.testing.assert_allclose(
        np.asarray(vals), _route_loop(logits, bias, 4, 2.5, norm=False)[0],
        rtol=1e-5)


def test_softmax_routing_is_unchanged_by_the_new_argument():
    cfg = get_config("tiny-mixtral")
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(9, 4)),
                         jnp.float32)
    vals, idx = moe.router_topk(logits, cfg)
    probs = jax.nn.softmax(logits, -1)
    top, want = jax.lax.top_k(probs, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    np.testing.assert_allclose(np.asarray(vals),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# The share of a layer (guide model-configs, section 4)
# ---------------------------------------------------------------------------


def _routed_layer(weights, l=0, cast=None):
    """Layer ``l`` of the routed tree of a family's weights, as the
    program's layer params (bfloat16 leaves as jnp arrays)."""
    lw = {}
    for k, v in weights.items():
        if k.startswith("layers/"):
            leaf = v[l] if not isinstance(v, dict) else \
                {a: b[l] for a, b in v.items()}
            lw[k.split("/", 1)[1]] = jax.tree.map(
                lambda x: jnp.asarray(x, jnp.bfloat16)
                if x.dtype == np.float32 else jnp.asarray(x), leaf)
    return lw


@pytest.mark.parametrize("grouped", [True, False])
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference(grouped):
    """Four chips hold four experts each of the 16-expert layer; the parts
    their layers return, the shared expert (which every chip computes
    alike) counted once, add up to the reference's uncut layer."""
    ref = _reference()
    config = _tiny_config()
    a = ref.arch(config)
    w = ref.generate_weights(config, seed=5, weight_bits=0)
    fn = ref._jits(tuple(sorted(a.items())))
    lw = _routed_layer(w)
    cfg4 = ModelConfig.from_hf_config(dict(config, n_routed_experts=4),
                                      name="quarter")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64), jnp.float32)
    x = x.astype(jnp.bfloat16)

    # The reference, uncut, in float32 on the same bfloat16 input.
    hn = x[0].astype(jnp.float32)
    host = {k: np.asarray(v.astype(jnp.float32)) for k, v in lw.items()}
    gates = fn["route"](hn, host["router"], host["router_bias"])
    assert np.count_nonzero(np.asarray(gates), axis=-1).tolist() == [4] * 96
    want = sum(fn["ffn"](hn, *(host[k][e] for k in
                               ("w_gate", "w_up", "w_down")))
               * gates[:, e, None] for e in range(16))
    shared = fn["ffn"](hn, *(host[k] for k in (
        "shared_gate_proj", "shared_up", "shared_down")))
    want = np.asarray(want + shared)

    total = np.zeros_like(want)
    held_all = 0
    for rank in range(4):
        cfg = cfg4.with_expert_share(4, rank)
        part = dict(lw, **{k: lw[k][rank * 4:(rank + 1) * 4]
                           for k in ("w_gate", "w_up", "w_down")})
        out, held = moe.moe_ffn(x, part, cfg, grouped=grouped,
                                row_valid=jnp.ones((1, 96), bool))
        only_shared = moe._shared_expert(x, part, cfg)
        total += np.asarray((out - only_shared).astype(jnp.float32))[0]
        held_all += int(held[0])
    total += np.asarray(shared)
    assert held_all == 96 * 4            # every chosen pair lands on one chip
    # bfloat16 activations against float32: a few parts in a thousand of the
    # layer's output scale; a lost or doubled expert would be ~1/4 of it.
    scale = np.abs(want).max()
    assert np.abs(total - want).max() < 0.02 * scale


def test_an_expert_that_draws_more_rows_than_the_batch_holds_overflows_in_tiles(
        monkeypatch):
    """The share's grouped dispatch gives each held expert a fixed number
    of rows in one batch (four times its fair load); an expert that draws more
    sends the rest through overflow tiles, so nothing is dropped: the result is the dense
    dispatch's."""
    cfg8 = ModelConfig.from_hf_config(_tiny_config(n_routed_experts=2),
                                      name="eighth").with_expert_share(8, 3)
    # 256 tokens x 4 of 16 experts: 64 rows an expert if the router were
    # uniform, four times that in the batch, at most every token.
    assert moe._held_capacity(256, cfg8) == 256
    assert moe._held_capacity(1032, ModelConfig.from_hf_config(
        KIMI, name="kimi").with_expert_share(32, 0)) == 128
    ref = _reference()
    w = ref.generate_weights(_tiny_config(), seed=6, weight_bits=0)
    lw = _routed_layer(w)
    part = dict(lw, **{k: lw[k][6:8] for k in ("w_gate", "w_up", "w_down")})
    # A bias that sends EVERY token to the two held experts: 256 rows each.
    part["router_bias"] = jnp.zeros((16,), jnp.bfloat16).at[6:8].set(9.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 64), jnp.bfloat16)
    valid = jnp.ones((1, 256), bool)
    want, held_d = moe.moe_ffn(x, part, cfg8, grouped=False, row_valid=valid)
    assert held_d.tolist() == [256 * 2, 0, 0]
    # no overflow; one tile an expert and two spare ones, all dead; two tiles
    # an expert, the last ragged; five tiles an expert, six of them in the
    # loop behind the four spare ones
    for cap, tiles in ((256, 0), (200, 2), (96, 4), (48, 10)):
        monkeypatch.setattr(moe, "_held_capacity", lambda n, cfg: cap)
        got, held = moe.moe_ffn(x, part, cfg8, grouped=True, row_valid=valid)
        assert held.tolist() == [256 * 2, tiles,
                                 max(tiles - moe._SPARE_TILES, 0)]
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=0.02 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("drawn", [352, 1056], ids=["a third", "every row"])
def test_one_tile_an_expert_is_exact_when_an_expert_draws_far_more(drawn):
    """The capacity AS THE RULE GIVES IT, nothing patched, at the shape of
    laguna's whole-budget step (1,056 rows, top-10 of 256, 32 held: one
    tile of 128 rows an expert): a router that sends ``drawn`` rows to ONE
    held expert on top of what the seeded weights spread gives the dense
    dispatch's output, the tiles counted by hand from the router's own
    choice: a third of the rows is two tiles, inside the spare ones; every
    row is eight, of which the loop runs those past the spare ones."""
    n, star = 1056, 5
    cfg = ModelConfig.from_hf_config(
        _tiny_config(n_routed_experts=32, num_experts_per_tok=10),
        name="laguna-shaped").with_expert_share(8, 0)
    assert (cfg.router_width, cfg.num_experts) == (256, 32)
    assert moe._held_capacity(n, cfg) == 128
    lp = jax.tree.map(lambda a: a[0], moe.init_moe_params(
        cfg, jax.random.PRNGKey(3), jnp.float32, layers=1))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, n, cfg.hidden_size),
                          jnp.float32)
    # Feature 0 carries the choice: the star expert's score is ~1 on the
    # first ``drawn`` rows and ~0 on the others, whatever the bias adds.
    x = x.at[0, :, 0].set(jnp.where(jnp.arange(n) < drawn, 1.0, -1.0))
    lp["router"] = lp["router"].at[0, :].set(0.0).at[0, star].set(40.0)
    lp["router_bias"] = jnp.zeros_like(lp["router_bias"])
    valid = jnp.ones((1, n), bool)
    _, idx = moe.router_topk(jnp.einsum("te,ex->tx", x[0], lp["router"]),
                             cfg, lp["router_bias"])
    idx = np.asarray(idx)
    sizes = np.bincount(idx[idx < cfg.num_experts],
                        minlength=cfg.num_experts)
    assert sizes[star] == drawn and np.delete(sizes, star).max() <= 128
    needed = int(np.sum(-(-np.maximum(sizes - 128, 0) // 128)))
    assert needed == {352: 2, 1056: 8}[drawn]
    want, held_d = moe.moe_ffn(x, lp, cfg, grouped=False, row_valid=valid)
    got, held = moe.moe_ffn(x, lp, cfg, row_valid=valid)       # the auto rule
    assert held_d.tolist() == [int(sizes.sum()), 0, 0]
    assert held.tolist() == [int(sizes.sum()), needed,
                             max(needed - moe._SPARE_TILES, 0)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("leaves", ["float32", "int8"])
@pytest.mark.parametrize("router", ["one expert", "spread"])
def test_a_scanned_layers_overflow_loop_reads_its_own_layers_experts(
        router, leaves, monkeypatch):
    """Three routed layers with DIFFERENT weights under one scan, as a step
    program runs them: the layer's slice beside the stacked tree and its
    index in it.  A bias that sends every row to ONE held expert makes
    each layer run tiles far past the spare ones, in the loop that takes
    its expert out of the stack by (layer, expert): the scan gives what
    the dense dispatch gives layer after layer, the counts read the tiles
    the layers needed, and a loop handed ANOTHER layer's index reads that
    layer's expert and does not.  A router that overflows nowhere runs
    the spare tiles dead and no trip of the loop."""
    cfg = get_config("tiny-mla-moe").with_expert_share(2, 1)
    layers, n, cap = 3, 256, 32 if router == "one expert" else 128
    monkeypatch.setattr(moe, "_held_capacity", lambda n, cfg: cap)
    stack = moe.init_moe_params(cfg, jax.random.PRNGKey(3), jnp.float32,
                                layers=layers)
    # The scale of the preset's hidden states, so that a layer's output
    # moves the next layer's routing.
    stack = {k: v * 4 if k.startswith("w_") else v for k, v in stack.items()}
    bias = jnp.zeros((layers, cfg.router_width), jnp.float32)
    if router == "one expert":
        bias = bias.at[:, moe.held_first(cfg) + 5].set(9.0)
    stack["router_bias"] = bias
    if leaves == "int8":
        stack = quant.quantize_params(stack, bits=8)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, n, cfg.hidden_size),
                          jnp.float32)
    valid = (jnp.arange(n) < n - 6)[None]

    def layer(l):
        return jax.tree.map(lambda a: a[l], stack)

    # Layer after layer through the dense dispatch, and the tiles each
    # layer's router asks for, counted on the host.
    want, held, needed, extra = x, 0, 0, 0
    for l in range(layers):
        lp = layer(l)
        _, idx = moe.router_topk(
            jnp.einsum("te,ex->tx", want[0], lp["router"]), cfg,
            lp["router_bias"])
        local = np.asarray(idx)[:n - 6] - moe.held_first(cfg)
        sizes = np.bincount(local[(local >= 0) & (local < cfg.num_experts)],
                            minlength=cfg.num_experts)
        tiles = int(np.sum(-(-np.maximum(sizes - cap, 0) // cap)))
        needed, extra = needed + tiles, extra + max(
            tiles - moe._SPARE_TILES, 0)
        y, counts = moe.moe_ffn(want, lp, cfg, grouped=False,
                                row_valid=valid)
        want, held = want + y, held + int(counts[0])

    @jax.jit
    def scanned(x, shift):
        def body(h, xs):
            lp, at = xs
            y, counts = moe.moe_ffn(h, lp, cfg, grouped=True,
                                    row_valid=valid,
                                    stack=(stack, (at + shift) % layers))
            return h + y, counts
        h, counts = jax.lax.scan(
            body, x, (stack, jnp.arange(layers, dtype=jnp.int32)))
        return h, jnp.sum(counts, axis=0)

    got, counts = scanned(x, 0)
    assert counts.tolist() == [held, needed, extra]
    tol = 2e-4 * float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got[0, :n - 6]),
                               np.asarray(want[0, :n - 6]), atol=tol)
    if router == "spread":
        assert (needed, extra) == (0, 0)
        return
    # every row on one expert: 7 tiles a layer behind a batch of 32 rows
    assert needed >= 7 * layers and extra >= 3 * layers
    wrong, _ = scanned(x, 1)
    assert float(jnp.abs(wrong - want)[0, :n - 6].max()) > 100 * tol


def test_rows_that_carry_no_token_take_no_place_in_the_share_dispatch():
    """A step's padding rows are all alike, so they would all choose the
    same experts: the grouped dispatch leaves them out (only the shared
    expert sees them), and they count neither as held pairs nor towards
    an expert's rounds."""
    cfg8 = ModelConfig.from_hf_config(_tiny_config(n_routed_experts=2),
                                      name="eighth").with_expert_share(8, 3)
    ref = _reference()
    lw = _routed_layer(ref.generate_weights(_tiny_config(), seed=6,
                                            weight_bits=0))
    part = dict(lw, **{k: lw[k][6:8] for k in ("w_gate", "w_up", "w_down")})
    part["router_bias"] = jnp.zeros((16,), jnp.bfloat16).at[6:8].set(9.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 64), jnp.bfloat16)
    valid = (jnp.arange(256) < 100)[None]
    got, held = moe.moe_ffn(x, part, cfg8, grouped=True, row_valid=valid)
    want, held_d = moe.moe_ffn(x, part, cfg8, grouped=False, row_valid=valid)
    # 100 rows an expert past a batch of 256: no overflow tile
    assert held.tolist() == held_d.tolist() == [100 * 2, 0, 0]
    tol = 0.02 * float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got[0, :100], np.float32),
                               np.asarray(want[0, :100], np.float32), atol=tol)
    only_shared = moe._shared_expert(x, part, cfg8)
    np.testing.assert_array_equal(np.asarray(got[0, 100:], np.float32),
                                  np.asarray(only_shared[0, 100:], np.float32))


# ---------------------------------------------------------------------------
# The pool: one latent row a token, stored once
# ---------------------------------------------------------------------------


def test_the_latent_pool_holds_one_row_a_token_once():
    cfg = get_config("tiny-mla-moe")
    cache = tf.init_paged_cache(cfg, num_pages=6, page=16)
    assert cache.latent and cache.v is None and cache.k_scale is None
    assert cache.k.shape == (3, 6, 1, 16, 32 + 8)
    # bytes a token = layers x (latent + rotary lanes) x 2, nothing twice
    assert cache.token_bytes == 3 * 40 * 2
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(cache)) == 6 * 16 * 240
    # The published widths: 576 lanes, stored as five 128-lane tiles.
    kimi = ModelConfig.from_hf_config(KIMI, name="kimi")
    assert tf.cache_head_dim(kimi) == 576
    assert tf.cache_head_dim(kimi, pad_head=True) == 640
    # What GQA at the same widths would hold: K and V for 64 heads.
    assert 2 * 64 * 128 > 28 * 576
    with pytest.raises(ValueError, match="bf16 only"):
        tf.init_paged_cache(cfg, 6, 16, quantized=True)


# ---------------------------------------------------------------------------
# The latent kernel (interpret) against the XLA oracle
# ---------------------------------------------------------------------------

_BATCHES = {
    # (lane, rows, first position), packed in this order into lanes + 16 rows
    "flood": [(0, 1, 40), (1, 1, 9), (2, 1, 77), (3, 3, 0), (4, 3, 12),
              (5, 2, 30), (6, 3, 5), (7, 2, 2)],
    "open": [(0, 1, 40), (5, 1, 9), (2, 16, 16)],
    "pipe": [(s, 1, 3 + 11 * s) for s in range(8) if s != 3],
    "empty": [],
    "across_pages": [(1, 9, 60), (6, 7, 121)],
    "last_row": [(0, 1, 127), (7, 15, 113)],
}


@pytest.mark.parametrize("batch", sorted(_BATCHES))
def test_latent_kernel_matches_the_xla_oracle(batch):
    """paged_latent_update_and_attend through the Pallas kernels (the row
    write, the block-compacted query layout, the ragged grid with Hkv = 1
    and the heads as the query group, values from the key tile) against
    the XLA gather on the same flat batch: the written pool bit for bit,
    every real row within rounding, padding rows zero."""
    from arks_tpu.ops.attention import paged_latent_update_and_attend

    lanes, heads, r, dv, page, max_pages = 8, 4, 40, 32, 64, 2
    t_flat = lanes if batch == "pipe" else lanes + 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    pool = jax.random.normal(ks[0], (2, lanes * max_pages + 1, 1, page, r),
                             jnp.bfloat16)
    tables = jnp.arange(lanes * max_pages, dtype=jnp.int32).reshape(
        lanes, max_pages)
    q = jax.random.normal(ks[1], (t_flat, heads, r), jnp.bfloat16)
    rows = jax.random.normal(ks[2], (t_flat, r), jnp.bfloat16)
    token_slot = np.full((t_flat,), -1, np.int32)
    token_pos = np.zeros((t_flat,), np.int32)
    q_start, q_len, pos0 = (np.zeros((lanes,), np.int32) for _ in range(3))
    t = 0
    for lane, n, p0 in _BATCHES[batch]:
        token_slot[t:t + n] = lane
        token_pos[t:t + n] = p0 + np.arange(n)
        q_start[lane], q_len[lane], pos0[lane] = t, n, p0
        t += n

    def run(impl):
        out, new = paged_latent_update_and_attend(
            q, rows, pool, tables, jnp.asarray(token_slot),
            jnp.asarray(token_pos), jnp.asarray(q_start), jnp.asarray(q_len),
            jnp.asarray(pos0), 1, dv=dv, scale=0.2, impl=impl)
        return (np.asarray(out.astype(jnp.float32)),
                np.asarray(new.astype(jnp.float32)))

    (got, pool_k), (want, pool_x) = run("pallas"), run("xla")
    assert got.shape == (t_flat, heads, dv)
    np.testing.assert_array_equal(pool_k, pool_x)
    real = token_slot >= 0
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[~real], 0.0)
    if real.any():
        assert np.abs(got[real] - want[real]).max() \
            <= 0.02 * np.abs(want[real]).max()
        # The written rows are in the pool, and nothing else moved.
        before = np.asarray(pool.astype(jnp.float32))
        changed = np.argwhere((pool_k != before).any(axis=-1))
        assert len(changed) == real.sum() and (changed[:, 0] == 1).all()


def test_a_mixed_step_is_held_to_the_rows_whose_block_tables_fit_smem():
    """What a v5e refused at its first dispatch (16 slots + a 2048-token
    budget over 32-page tables) is refused by name at construction."""
    from arks_tpu.engine.paged import mixed_step_row_limit
    assert mixed_step_row_limit(32) == 2030          # 8192 tokens of 256
    assert mixed_step_row_limit(4) == 2030           # a row pads to 128 lanes
    assert mixed_step_row_limit(129) == 1019
    assert 16 + 2048 > mixed_step_row_limit(32) >= 32 + 1024
    assert 192 + 256 <= mixed_step_row_limit(4)      # the cells that were


def test_block_q_follows_the_query_group_so_64_heads_fit_a_work_item():
    from arks_tpu.ops.paged_attention import mixed_grid_plan
    plan = mixed_grid_plan(1025, hkv=1, g=64, d=640, page=256, kv="bfloat16",
                           lanes=16)
    assert plan["block_q"] == 8                 # 512 query rows a work item
    # The configurations the benchmark had keep their blocks.
    assert mixed_grid_plan(257, hkv=4, g=7, d=128, page=256, kv="int8",
                           lanes=8)["block_q"] == 32
    assert mixed_grid_plan(257, hkv=4, g=7, d=128, page=256, kv="int8",
                           lanes=192)["block_q"] == 8


# ---------------------------------------------------------------------------
# The served step against the reference's full forward
# ---------------------------------------------------------------------------


def _serve_logits(cfg, params, prompt, n_decode, *, chunk=16, page=16,
                  pool_dtype=jnp.bfloat16, mutate=None):
    """Prefill ``prompt`` in chunks of ``chunk`` through the latent pool,
    then decode ``n_decode`` tokens greedily, one mixed step each, on lane
    1 of a two-lane engine shape.  Returns (logits [n_decode + 1, V] at
    the sampled positions, the tokens chosen)."""
    max_pages = 8
    cache = tf.init_paged_cache(cfg, 2 * max_pages + 1, page,
                                dtype=pool_dtype)
    tables = (1 + jnp.arange(2 * max_pages, dtype=jnp.int32)).reshape(
        2, max_pages)
    t_flat = 2 + chunk
    step = jax.jit(lambda p, c, *a: tf.mixed_step(p, cfg, c, *a))

    def one(cache, toks, pos0):
        n = len(toks)
        tokens = np.zeros((t_flat,), np.int32)
        slot = np.full((t_flat,), -1, np.int32)
        pos = np.zeros((t_flat,), np.int32)
        tokens[:n], slot[:n], pos[:n] = toks, 1, pos0 + np.arange(n)
        logits, cache = step(
            params, cache, tables, jnp.asarray(tokens), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray([0, n - 1]), jnp.asarray([0, 0]),
            jnp.asarray([0, n]), jnp.asarray([0, pos0]))
        if mutate is not None:
            cache = mutate(cache)
        return np.asarray(logits[1]), cache

    out, chosen = [], []
    for c0 in range(0, len(prompt), chunk):
        logits, cache = one(cache, prompt[c0:c0 + chunk], c0)
    for i in range(n_decode + 1):
        out.append(logits)
        chosen.append(int(np.argmax(logits)))
        if i < n_decode:
            logits, cache = one(cache, [chosen[-1]], len(prompt) + i)
    return np.stack(out), chosen


# With weights of scale 0.02 at these widths every attention score is ~0.02
# and attention is a plain average: a lost rotary term or a coarser latent
# would hardly move a logit.  Both sides therefore widen ``W_qb``'s stored
# scales by SHARPEN (the same stored weights on both sides, bit for bit),
# which gives the scores a spread of ~1: the latent part and the rotary
# part of a score then matter alike.
SHARPEN = 50.0
SEED = 11


def _both_sides(seed=SEED, activations=jnp.bfloat16):
    """(cfg, the program's params, the reference's config and weights, a
    prompt) on the same seeded int8 weights; ``activations`` float32 widens
    the program's full-width leaves (norms, router, bias), which is exact,
    so that the whole step computes in float32."""
    cfg = get_config("tiny-mla-moe")
    params = quant.init_params_quantized(cfg, jax.random.PRNGKey(seed),
                                         jnp.bfloat16, bits=8)
    config = _tiny_config()
    weights = _reference().generate_weights(config, seed, 8)
    for tree in ("dense_layers", "layers"):
        params[tree]["wq_b"]["s"] = params[tree]["wq_b"]["s"] * SHARPEN
        weights[tree + "/wq_b"]["s"] = weights[tree + "/wq_b"]["s"] * SHARPEN
    params = jax.tree.map(
        lambda x: x.astype(activations) if x.dtype == jnp.bfloat16 else x,
        params)
    prompt = [int(t) for t in
              np.random.default_rng(seed).integers(2, 258, 53)]
    return cfg, params, config, weights, prompt


def _errors(config, weights, prompt, served, chosen):
    """Per sampled position: max |served - reference| over the whole
    vocabulary in units of the standard deviation of the reference's
    logits there, and whether the position's routing is clear (the 4th
    and 5th biased scores at least ROUTING_TIE apart in every layer: a
    position inside that may take either expert by a rounding upstream,
    and is set aside as the benchmark's comparison sets it aside)."""
    tokens = np.asarray([prompt + chosen[:-1]], np.int32)
    rows = (len(prompt) - 1 + np.arange(len(chosen)))[None].astype(np.int32)
    margins: list = []
    want = _reference().forward(config, weights, tokens, rows,
                                margins=margins)[0]
    err = np.abs(served - want).max(-1) / want.std(-1)
    return err, np.min(margins, axis=0)[0] >= ROUTING_TIE


# The three tolerances, each with what it was set between (CPU, seeds 11 to
# 13, the seven sampled positions of a 53-token prompt in chunks of 16 and
# six decode steps):
# - FLOAT32_TOL: activations and pool in float32 on the stored int8
#   weights.  The absorbed form through the paged pool, in chunks and then
#   token by token, then IS the reference's non-absorbed full forward up to
#   float32 rounding: it reads under 1e-5.  A pool rounded to bfloat16 (the
#   precision the configurations state) reads 0.002 to 0.010.
# - LATENT_TOL: float32 activations over the pool at the stated precision,
#   bfloat16: 0.002 to 0.010.  The nearest precision below, the rows
#   rounded to int8 with one scale a row: 0.022 to 0.042.
# - SERVED_TOL: the step as it is served, bfloat16 activations and pool:
#   0.017 to 0.034.  The rotary lanes of the query left out: 0.56 to 1.2.
FLOAT32_TOL = 1e-3
LATENT_TOL = 0.015
SERVED_TOL = 0.08
ROUTING_TIE = 0.001


def _round_rows(dtype):
    def mutate(cache):
        k = cache.k.astype(jnp.float32)
        if dtype == "int8":
            s = jnp.maximum(jnp.abs(k).max(-1, keepdims=True), 1e-8) / 127
            k = jnp.round(k / s) * s
        else:
            k = k.astype(dtype).astype(jnp.float32)
        return cache._replace(k=k.astype(cache.k.dtype))
    return mutate


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_absorbed_paged_step_is_the_reference_forward_in_float32(
        impl, monkeypatch):
    """Prefill in chunks, then decode, through the latent pool, against
    the reference's one full non-absorbed forward: logits, every sampled
    position, the whole vocabulary."""
    monkeypatch.setenv("ARKS_ATTN_IMPL", impl)
    cfg, params, config, weights, prompt = _both_sides(
        activations=jnp.float32)
    served, chosen = _serve_logits(cfg, params, prompt, 6,
                                   pool_dtype=jnp.float32)
    err, clear = _errors(config, weights, prompt, served, chosen)
    assert clear.sum() >= 4
    assert err[clear].max() < FLOAT32_TOL, err


@pytest.mark.parametrize("rows, passes", [(jnp.bfloat16, True),
                                          ("int8", False)])
def test_a_bf16_latent_row_passes_and_an_int8_one_fails(rows, passes,
                                                        monkeypatch):
    monkeypatch.setenv("ARKS_ATTN_IMPL", "xla")
    cfg, params, config, weights, prompt = _both_sides(
        activations=jnp.float32)
    served, chosen = _serve_logits(cfg, params, prompt, 6,
                                   pool_dtype=jnp.float32,
                                   mutate=_round_rows(rows))
    err, clear = _errors(config, weights, prompt, served, chosen)
    assert clear.sum() >= 4
    assert (err[clear].max() < LATENT_TOL) == passes, err
    assert err[clear].max() > FLOAT32_TOL      # the float32 limit sees both


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_step_as_served_matches_the_reference_forward(impl, monkeypatch):
    monkeypatch.setenv("ARKS_ATTN_IMPL", impl)
    cfg, params, config, weights, prompt = _both_sides()
    served, chosen = _serve_logits(cfg, params, prompt, 6)
    err, clear = _errors(config, weights, prompt, served, chosen)
    assert clear.sum() >= 4
    assert err[clear].max() < SERVED_TOL, err


def test_a_lost_rotary_term_fails_the_served_comparison(monkeypatch):
    monkeypatch.setenv("ARKS_ATTN_IMPL", "xla")
    cfg, params, config, weights, prompt = _both_sides()
    orig = tf._mla_q

    def no_rotary(x, lp, c, positions):
        return orig(x, lp, c, positions).at[..., c.kv_lora_rank:].set(0)
    monkeypatch.setattr(tf, "_mla_q", no_rotary)
    served, chosen = _serve_logits(cfg, params, prompt, 6)
    err, clear = _errors(config, weights, prompt, served, chosen)
    assert err[clear].min() > SERVED_TOL, err


def test_quantized_init_makes_both_stacks_and_the_share_leaf_by_leaf():
    cfg = get_config("tiny-mla-moe").with_expert_share(2, 1)
    import dataclasses
    cfg = dataclasses.replace(cfg, num_experts=8)
    p = quant.init_params_quantized(cfg, jax.random.PRNGKey(0), jnp.bfloat16,
                                    bits=8)
    assert p["dense_layers"]["w_gate"]["q"].shape == (1, 64, 128)
    assert p["layers"]["w_gate"]["q"].shape == (2, 8, 64, 32)
    assert p["layers"]["router"].shape == (2, 64, 16)       # whole width
    assert p["layers"]["router_bias"].dtype == jnp.bfloat16
    assert float(jnp.abs(p["layers"]["router_bias"]).max()) > 0
    assert p["layers"]["wkv_b"]["q"].dtype == jnp.int8
    assert p["layers"]["kv_norm"].shape == (2, 32)
    assert "shared_gate" not in p["layers"]                 # ungated
    p4 = quant.init_params_quantized(cfg, jax.random.PRNGKey(0),
                                     jnp.bfloat16, bits=4)
    assert "gs" in p4["layers"]["wkv_b"]


# ---------------------------------------------------------------------------
# The engine: labels, counters, the prefix cache, and what it refuses
# ---------------------------------------------------------------------------


# Every engine here is FRESH: the tests read counters from zero (the
# rendered registry has no sample of a counter nothing has moved), call
# ``_count_held`` by hand, or leave pages in the prefix index.
_SHAPE = harness.SHAPE["tiny-mla-moe"]


def test_engine_serves_a_latent_share_and_counts_what_it_holds():
    from arks_tpu.engine.types import Request, SamplingParams
    cfg = ModelConfig.from_hf_config(_tiny_config(n_routed_experts=8),
                                     name="tiny-mla-half")
    eng = harness.engine(cfg.with_expert_share(2, 1), **_SHAPE)
    try:
        labels = eng.resolved_config
        assert labels["kv_page"] == "latent"
        assert labels["expert_share"] == "1/2"
        assert labels["kv_layout"] == "paged" and labels["kv_dtype"] == "bf16"
        assert labels["mixed_step"] == "true"
        assert eng._cache.v is None
        assert eng._page_bytes == eng._cache.token_bytes * eng._page_size()
        prompt = list(range(2, 42))
        sp = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
        first = harness.drain(eng, [Request("a", prompt, sp)])[0]["a"]
        m = eng.metrics
        rows = m.mixed_latent_rows_total.get() / cfg.num_layers
        assert rows == len(prompt) + 4          # every row fed, once
        routed = m.moe_routed_pairs_total.get()
        assert routed == rows * 4 * 2           # top-4, two routed layers
        held = m.moe_held_pairs_total.get()
        assert 0 < held < routed                # this chip's half, roughly
        # A share's pod renders both kinds; these 16-row steps take the
        # dense dispatch, which has no overflow tile.
        tiles = "\n".join(m.moe_overflow_tiles_total.collect())
        assert 'moe_overflow_tiles_total{kind="needed"} 0' in tiles
        assert 'moe_overflow_tiles_total{kind="extra"} 0' in tiles
        # The device-tier prefix cache shares pages by id: it keeps
        # working over latent pages, and the stream does not change.
        hits0 = m.prefix_cache_hit_tokens_total.total()
        again = harness.drain(eng, [Request("b", prompt, sp)])[0]["b"]
        assert again == first
        assert m.prefix_cache_hit_tokens_total.total() - hits0 >= 32
    finally:
        eng.stop()


@pytest.mark.parametrize("share", [False, True])
def test_a_steps_four_counts_land_on_their_counters(share):
    """The four int32 behind a step's token ids (held pairs, overflow tiles
    needed, those the loop ran, valid rows): a share's pod counts the tiles
    by kind, a pod that holds every expert renders no sample of them."""
    cfg = get_config("tiny-mla-moe")
    eng = harness.engine(cfg.with_expert_share(2, 0) if share else cfg)
    try:
        eng._count_held(np.asarray([9, 9, 9, 40, 11, 3, 20], np.int32), 16)
        m = eng.metrics
        assert m.moe_held_pairs_total.get() == 40
        assert m.moe_routed_pairs_total.get() == 20 * 4 * 2
        assert m.mixed_latent_rows_total.get() == 20 * 3
        tiles = m.moe_overflow_tiles_total
        assert (tiles.get(kind="needed"), tiles.get(kind="extra")) == (
            (11, 3) if share else (0, 0))
        assert ("moe_overflow_tiles_total{" in m.registry.render()) == share
    finally:
        eng.stop()


def test_a_shares_pod_counts_the_rows_its_experts_computed():
    """``moe_batch_rows_total`` beside ``moe_held_pairs_total``, read off
    the rendered registry (what ``/metrics`` serves), the rows counted by
    hand.  The served steps of this tiny share are 2 + 16 rows, the dense
    dispatch: every row for each of the 16 held experts (of 32 scored), two
    routed layers a step.  A step of 2 + 1024 rows is the batched one: a
    batch of 512 rows an expert (three times the fair 129, in tiles of 128)
    and the spare tiles a layer, plus a tile for every trip of the loop.
    Held pairs over those rows is how full the experts' batches ran."""
    import re
    from arks_tpu.engine.types import Request, SamplingParams
    cfg = get_config("tiny-mla-moe").with_expert_share(2, 0)
    eng = harness.engine(cfg)

    def read(name):
        return float(re.search(rf"^{name} (\S+)$",
                               eng.metrics.registry.render(), re.M).group(1))

    try:
        sp = SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True)
        harness.drain(eng, [Request("a", list(range(2, 42)), sp)])
        steps = int(read("mixed_batch_tokens_count"))
        assert steps >= 3 + 4                  # three chunks, four decode steps
        rows = steps * (2 + 16) * 16 * 2
        assert read("moe_batch_rows_total") == rows
        held = read("moe_held_pairs_total")
        assert held == eng.metrics.moe_held_pairs_total.get()
        assert 0 < held / rows < 44 * 4 * 2 / rows
        # A whole-budget step's counts as the program hands them back: 9
        # tiles needed over its two layers, 3 of them in the loop.
        assert moe.share_rows(2 + 1024, cfg) == (
            (16 + moe._SPARE_TILES) * 512, 512)
        eng._count_held(np.asarray([7, 7, 900, 9, 3, 1000], np.int32),
                        2 + 1024)
        assert read("moe_batch_rows_total") == rows + (
            2 * (16 + moe._SPARE_TILES) + 3) * 512
    finally:
        eng.stop()
    # A pod that holds every expert renders no sample of it.
    whole = harness.engine("tiny-mla-moe")
    try:
        whole._count_held(np.asarray([7, 40, 0, 0, 20], np.int32), 18)
        assert re.search(r"^moe_batch_rows_total \S+$",
                         whole.metrics.registry.render(), re.M) is None
    finally:
        whole.stop()
