"""The ``decoder`` reference family against the program, on the CPU at tiny
size: the weights a seed means are the program's bit for bit, the served
logprobs agree with the plain forward, and the same engine in a lower
precision does not."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, manifest

reference = manifest.load_reference("decoder")
CONFIGS = ["tiny", "tiny-mixtral"]


def _config(name):
    with open(os.path.join(manifest.config_dir(name), "config.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_seeded_weights_are_the_programs_bit_for_bit(name):
    import jax
    import jax.numpy as jnp
    from arks_tpu.models import quant
    from arks_tpu.models.config import ModelConfig

    seed = 2**31 + 12345
    cfg = ModelConfig.from_hf_config(manifest.config_dir(name), name=name)
    prog = quant.init_params_quantized(cfg, jax.random.PRNGKey(seed),
                                       jnp.bfloat16, bits=8)
    ref = reference.generate_weights(_config(name), seed)

    def flat(t, pre=""):
        for k, v in t.items():
            if isinstance(v, dict) and "q" not in v:
                yield from flat(v, pre + k + "/")
            else:
                yield pre + k, v

    prog = dict(flat(prog))
    assert sorted(prog) == sorted(ref)
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), ref[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), ref[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)), ref[k]), k


# CPU readings at tiny size (PR 23, largest over the 12 positions, three
# seeds each): sound runs 0.0071..0.0100; int4 weights 0.211..0.266; int4
# KV 0.0405..0.085.  The limit for these two test sizes sits between.
TINY_LIMIT = 0.02


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("control", [None, "weight_int4", "kv_int4"])
def test_served_logprobs_against_the_reference(name, control):
    r = check_correct.read_one(name, seed=31 + len(name), control=control,
                               platform="cpu")
    assert r["clean_positions"] + r["tie_positions"] == 12
    if control is None:
        assert r["logprob_err"] < TINY_LIMIT, r
    else:
        assert r["logprob_err"] > TINY_LIMIT, r


def test_the_quantile_is_nearest_rank_and_the_verdict_wants_enough_positions():
    from benchmarks import correctness
    v = np.arange(1, 29, dtype=float)            # 28 positions: 2 left out
    assert correctness._at_quantile(v, 0.9) == 26.0
    assert correctness._at_quantile(v, 1.0) == 28.0
    assert correctness._at_quantile(v[:9], 0.9) == 9.0
    assert correctness._at_quantile(v[:0], 0.9) is None
    spec = {"limit": 0.7, "min_clean_positions": 12}
    ok = {"logprob_err": 0.26, "clean_positions": 22}
    assert correctness.verdict(ok, spec)
    assert not correctness.verdict(dict(ok, logprob_err=0.71), spec)
    assert not correctness.verdict(dict(ok, clean_positions=11), spec)
    assert not correctness.verdict(dict(ok, logprob_err=None), spec)


def test_the_routing_margin_is_small_where_two_experts_tie():
    """The reference's margin at a position is the gap between the last
    chosen expert's router logit and the first one left out."""
    cfg = _config("tiny-mixtral")
    w = reference.generate_weights(cfg, 5)
    tokens = np.arange(2, 22, dtype=np.int32)[None]
    rows = np.array([[3, 11, 19]], np.int32)
    margins: list = []
    reference.forward(cfg, w, tokens, rows, margins=margins)
    assert len(margins) == cfg["num_hidden_layers"]
    assert all(m.shape == (1, 3) and (m >= 0).all() for m in margins)
    dense: list = []
    reference.forward(_config("tiny"), reference.generate_weights(
        _config("tiny"), 5), tokens, rows, margins=dense)
    assert dense == []
