"""One family, every block with routed layers (ROADMAP D9): the chips of a
share hold a slice of a 16-expert layer each, and the routed parts their
layers return, the shared expert (which every chip computes alike) counted
once, add up to the layer held whole; so counted is the identity experts'
part of a block whose router scores such.  A block joins by a row."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import moe, transformer as tf
from arks_tpu.models.config import get_config

# (preset, chips, which dispatches): sigmoid scores and a selection bias in
# every block but ``tiny-swa-moe`` (softmax); ``tiny-swa-sink-moe`` has no
# shared expert to count once; ``tiny-shortcut-mla-moe`` has softmax scores
# WITH a selection bias, no shared expert, and 8 identity experts behind the
# 16 real ones, whose part is what every chip computes alike;
# ``tiny-ssm-moe`` experts of TWO matrices (relu^2, no gate matrix, the
# shared expert alike), top-2.
_BLOCKS = [
    ("tiny-ssm-moe", 8, (True, False)),
    ("tiny-shortcut-mla-moe", 4, (True, False)),
    ("tiny-linear-moe", 8, (True, False)),
    ("tiny-latent-linear-moe", 8, (True, False)),
    ("tiny-swa-moe", 4, (True, False)),
    ("tiny-swa-sink-moe", 2, (False,)),
]


@pytest.mark.parametrize("preset, chips, grouped", [
    pytest.param(p, c, g, id=f"{p}-{'grouped' if g else 'dense'}")
    for p, c, gs in _BLOCKS for g in gs])
def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(
        preset, chips, grouped):
    cfg = get_config(preset)
    mp = jax.tree.map(lambda a: a[0], moe.init_moe_params(
        cfg, jax.random.PRNGKey(7), jnp.float32, layers=1))
    assert ("router_bias" in mp) == cfg.select_bias
    assert ("shared_gate" in mp) == (preset == "tiny-swa-moe")   # a gated one
    assert ("shared_up" in mp) == (
        preset not in ("tiny-swa-sink-moe", "tiny-shortcut-mla-moe"))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64), jnp.float32)
    valid = jnp.ones((1, 96), bool)
    # An expert of two matrices has no gate matrix, nor has its shared one.
    assert ("w_gate" in mp) == ("w_up" in mp) == ("w_upt" not in mp) \
        == (preset != "tiny-ssm-moe")
    assert ("shared_gate_proj" in mp) == (
        "shared_up" in mp and preset != "tiny-ssm-moe")
    experts = [k for k in ("w_gate", "w_up", "w_upt", "w_down") if k in mp]
    top_k = cfg.num_experts_per_tok
    whole, pairs = moe.moe_ffn(x, mp, cfg, grouped=False, row_valid=valid)
    # top-k of every row; the pairs on an identity expert counted apart
    zero = int(pairs[3]) if cfg.zero_experts else 0
    assert pairs.tolist() == [96 * top_k - zero, 0, 0] + [zero] * bool(
        cfg.zero_experts)
    assert (zero > 60) == bool(cfg.zero_experts)      # a third, seeded
    if cfg.swiglu_limit:
        # Every SwiGLU clamped, and the clamp bites at this size.
        unclamped, _ = moe.moe_ffn(x, mp, dataclasses.replace(
            cfg, swiglu_limit=0.0), grouped=False, row_valid=valid)
        assert float(jnp.abs(whole - unclamped).max()) \
            > 0.05 * float(jnp.abs(whole).max())
    shared = moe._shared_expert(x, mp, cfg) if "shared_up" in mp else 0.0
    if cfg.zero_experts:
        shared, _ = moe._zero_experts(x, *moe.router_topk(
            jnp.einsum("...e,ex->...x", x, mp["router"]), cfg,
            mp["router_bias"]), cfg, None)
        assert float(jnp.abs(shared).max()) > 0.01 * float(
            jnp.abs(whole).max())
    held = 16 // chips
    part_cfg = dataclasses.replace(cfg, num_experts=held)
    total, held_all = jnp.zeros_like(whole), 0
    for rank in range(chips):
        part = dict(mp, **{k: mp[k][rank * held:(rank + 1) * held]
                           for k in experts})
        out, pairs = moe.moe_ffn(x, part,
                                 part_cfg.with_expert_share(chips, rank),
                                 grouped=grouped, row_valid=valid)
        total = total + out - shared
        held_all += int(pairs[0])
        # Every chip counts the identity pairs alike: routed = held here +
        # zero + absent, the absent ones held on the other chips.
        assert pairs[3:].tolist() == [zero] * bool(cfg.zero_experts)
    assert held_all + zero == 96 * top_k  # every chosen pair lands on a chip
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "dense"])
def test_a_row_sent_to_identity_experts_alone_gets_its_weights_times_itself(
        grouped):
    """A router whose four picks are identity experts for every row (a
    selection bias on four of them that no probability outweighs): the layer
    returns ``(sum g) x`` and nothing else, through no expert's weights, in
    both dispatches, held whole and as a share; ``g = 2.5 p`` with the
    UNBIASED softmax ``p``, not renormalised."""
    cfg = get_config("tiny-shortcut-mla-moe")
    mp = jax.tree.map(lambda a: a[0], moe.init_moe_params(
        cfg, jax.random.PRNGKey(3), jnp.float32, layers=1))
    mp["router_bias"] = jnp.zeros((24,)).at[jnp.array([17, 19, 20, 23])].set(
        2.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 80, 64), jnp.float32)
    p = jax.nn.softmax(jnp.einsum("bte,ex->btx", x, mp["router"]), axis=-1)
    want = x * 2.5 * (p[..., 17] + p[..., 19] + p[..., 20] + p[..., 23]
                      )[..., None]
    valid = jnp.ones((1, 80), bool).at[0, 70:].set(False)
    for share in (cfg, dataclasses.replace(cfg, num_experts=8)
                  .with_expert_share(2, 1)):
        part = dict(mp, **{k: mp[k][:share.num_experts]
                           for k in ("w_gate", "w_up", "w_down")})
        out, pairs = moe.moe_ffn(x, part, share, grouped=grouped,
                                 row_valid=valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)
        # held 0, no tile, and the VALID rows' pairs all on identity experts
        assert pairs.tolist() == [0, 0, 0, 70 * 4]


def test_a_softmax_router_with_a_bias_selects_by_it_and_weighs_without_it():
    """``router_topk``'s third rule: the top-k of ``p + bias``, weights the
    unbiased ``p`` times the scaling factor, nothing renormalised; without
    a bias the softmax rule is what it was."""
    cfg = get_config("tiny-shortcut-mla-moe")
    logits = jax.random.normal(jax.random.PRNGKey(5), (50, 24), jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(6), (24,), jnp.float32) * 0.05
    vals, idx = moe.router_topk(logits, cfg, bias)
    p = jax.nn.softmax(logits, axis=-1)
    _, want = jax.lax.top_k(p + bias, 4)
    assert np.array_equal(np.asarray(idx), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(vals), 2.5 * np.asarray(jnp.take_along_axis(p, idx, -1)),
        rtol=1e-6)
    assert float(vals.sum(-1).max()) < 2.5            # not renormalised
    _, plain = moe.router_topk(logits, cfg, None)
    assert not np.array_equal(np.asarray(plain), np.asarray(idx))


def test_the_seeded_selection_bias_is_of_the_order_of_a_probability():
    cfg = get_config("tiny-shortcut-mla-moe")
    mp = moe.init_moe_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    std = float(jnp.std(mp["router_bias"]))
    assert 0.3 / 24 < std < 0.7 / 24                  # half of 1 / W
    sig = moe.init_moe_params(get_config("tiny-mla-moe"),
                              jax.random.PRNGKey(7), jnp.float32)
    assert 0.015 < float(jnp.std(sig["router_bias"])) < 0.025


def test_the_latent_pool_holds_a_row_an_attention_sublayer():
    """2 layers of the shortcut block keep 4 rows a token, the other latent
    blocks one a latent layer; ``token_bytes`` and the router's width say
    so."""
    cfg = get_config("tiny-shortcut-mla-moe")
    assert (cfg.num_layers, cfg.num_attn_sublayers) == (2, 4)
    assert (cfg.router_width, cfg.num_real_experts) == (24, 16)
    share = dataclasses.replace(cfg, num_experts=8).with_expert_share(2, 1)
    assert (share.router_width, share.num_real_experts) == (24, 16)
    cache = tf.init_paged_cache(cfg, 8, 16, jnp.bfloat16)
    assert cache.k.shape == (4, 8, 1, 16, 40) and cache.v is None
    assert cache.token_bytes == 4 * 40 * 2
    for other in ("tiny-mla-moe", "tiny-latent-linear-moe"):
        c = get_config(other)
        assert c.num_attn_sublayers == c.num_full_layers
        assert tf.init_paged_cache(c, 8, 16, jnp.bfloat16,
                                   state_slots=2).k.shape[0] \
            == c.num_full_layers
