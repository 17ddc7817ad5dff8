"""One family, every block with routed layers (ROADMAP D9): the chips of a
share hold a slice of a 16-expert layer each, and the routed parts their
layers return, the shared expert (which every chip computes alike) counted
once, add up to the layer held whole.  A block joins by a row."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import moe
from arks_tpu.models.config import get_config

# (preset, chips, which dispatches): sigmoid scores and a selection bias in
# every block but ``tiny-swa-moe`` (softmax); ``tiny-swa-sink-moe`` has no
# shared expert to count once.
_BLOCKS = [
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
    assert ("router_bias" in mp) == (cfg.scoring_func == "sigmoid")
    assert ("shared_gate" in mp) == (preset == "tiny-swa-moe")   # a gated one
    assert ("shared_up" in mp) == (preset != "tiny-swa-sink-moe")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64), jnp.float32)
    valid = jnp.ones((1, 96), bool)
    whole, pairs = moe.moe_ffn(x, mp, cfg, grouped=False, row_valid=valid)
    assert pairs.tolist() == [96 * 4, 0, 0]          # top-4 of every row
    if cfg.swiglu_limit:
        # Every SwiGLU clamped, and the clamp bites at this size.
        unclamped, _ = moe.moe_ffn(x, mp, dataclasses.replace(
            cfg, swiglu_limit=0.0), grouped=False, row_valid=valid)
        assert float(jnp.abs(whole - unclamped).max()) \
            > 0.05 * float(jnp.abs(whole).max())
    shared = moe._shared_expert(x, mp, cfg) if "shared_up" in mp else 0.0
    held = 16 // chips
    part_cfg = dataclasses.replace(cfg, num_experts=held)
    total, held_all = jnp.zeros_like(whole), 0
    for rank in range(chips):
        part = dict(mp, **{k: mp[k][rank * held:(rank + 1) * held]
                           for k in ("w_gate", "w_up", "w_down")})
        out, pairs = moe.moe_ffn(x, part,
                                 part_cfg.with_expert_share(chips, rank),
                                 grouped=grouped, row_valid=valid)
        total = total + out - shared
        held_all += int(pairs[0])
    assert held_all == 96 * 4            # every chosen pair lands on one chip
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), rtol=2e-4, atol=2e-6)
