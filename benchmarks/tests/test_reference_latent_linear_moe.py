"""The ``latent_linear_moe`` reference family against the program, on the
CPU at ``tiny-latent-linear-moe`` size (8 of the preset's 16 experts held:
share 1 of 2; a chunk budget of 150 under pages of 256 and scan blocks of
64): the weights a seed means are the program's bit for bit, the three
stacks, the share's leaves, the zeroed gated norms and the shifted
``dt_bias``; the served log-probabilities (prefill in chunks through the
latent layers' pages and the linear layers' state, then decode) agree with
the plain forward whose delta rule runs one token at a time and whose
latent attention is not absorbed; the same reference with its state
forgotten, its post-norms dropped, its SwiGLU unclamped or its experts chosen
without the selection bias does not, nor does the same engine with int4
weights."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, correctness, manifest

NAME = "tiny-latent-linear-moe"
FAMILY = "latent_linear_moe"
SEED = 31 + len(NAME)


def _files():
    cdir = manifest.config_dir(NAME)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(cdir, "deploy.json")) as f:
        deploy = json.load(f)
    return config, deploy


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    ref = manifest.load_reference(FAMILY)
    for name in manifest.FAMILY_CONTRACT + ("kernel_shapes",
                                            "linear_kernel_shapes"):
        assert callable(getattr(ref, name))
    with open(manifest.reference_path(FAMILY)) as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "arks_tpu" not in code
    config, deploy = _files()
    a = ref.arch(manifest.with_share(config, deploy))
    assert (a["held"], a["first"], a["experts"]) == (8, 8, 16)
    assert a["kinds"] == ("linear", "linear", "linear", "full", "linear",
                          "linear", "full", "linear")
    assert [t for t, *_ in ref._layers(a)] == [
        "dense_layers", "dense_layers", "lin_layers", "layers", "lin_layers",
        "lin_layers", "layers", "lin_layers"]
    assert ref.kernel_shapes(a) == {"heads": 4, "row": 40, "value": 32,
                                    "layers": 2}
    assert ref.linear_kernel_shapes(a) == {
        "heads": 4, "head_dim": 16, "layers": 6, "state_bytes": 4}
    with pytest.raises(ValueError, match="do not make"):
        ref.arch(dict(config, share=dict(deploy["share"], chips_per_layer=4)))
    with pytest.raises(NotImplementedError, match="layernorm_type"):
        ref.arch(dict(config, layernorm_type="pre"))
    with pytest.raises(NotImplementedError, match="dense prefix"):
        ref.arch(dict(config, full_attention_layers=[1, 4, 7]))
    with pytest.raises(ValueError, match="reference_without"):
        ref.arch(dict(config, reference_without=["rope"]))


def test_seeded_weights_are_the_programs_bit_for_bit():
    import jax
    import jax.numpy as jnp
    from arks_tpu.models import quant
    from arks_tpu.models.config import ModelConfig

    seed = 2**31 + 12345
    config, deploy = _files()
    ref = manifest.load_reference(deploy["reference"])
    share = deploy["share"]
    cfg = ModelConfig.from_hf_config(manifest.config_dir(NAME), name=NAME) \
        .with_expert_share(share["chips_per_layer"], share["index"])
    prog = quant.init_params_quantized(cfg, jax.random.PRNGKey(seed),
                                       jnp.bfloat16, bits=8)
    want = ref.generate_weights(manifest.with_share(config, deploy), seed)

    def flat(t, pre=""):
        for k, v in t.items():
            if isinstance(v, dict) and "q" not in v:
                yield from flat(v, pre + k + "/")
            else:
                yield pre + k, v

    prog = dict(flat(prog))
    assert sorted(prog) == sorted(want)
    # A stack a kind: the dense linear prefix, the latent layers, the
    # routed linear layers.
    assert prog["dense_layers/w_gate"]["q"].shape == (2, 64, 128)
    assert prog["dense_layers/wq"]["q"].shape == (2, 64, 2 * 16)  # key heads
    assert prog["lin_layers/wv"]["q"].shape == (4, 64, 4 * 16)  # value heads
    assert prog["lin_layers/w_z"]["q"].shape == (4, 64, 4 * 16)   # full gate
    assert prog["lin_layers/conv_k"].shape == (4, 4, 32)
    assert prog["lin_layers/w_a"].shape == (4, 64, 4)         # a decay a head
    assert prog["lin_layers/dt_bias"].shape == (4, 4)
    assert prog["layers/wkv_a"]["q"].shape == (2, 64, 32 + 8)
    assert prog["layers/wg"]["q"].shape == (2, 64, 4 * 16)  # elementwise gate
    assert prog["layers/router"].shape == (2, 64, 16)           # whole width
    assert prog["lin_layers/w_gate"]["q"].shape == (4, 8, 64, 32)   # held
    # Four norms a layer, the gated ones zeros (a scale of 1), the linear
    # layers' per-head norm ones.
    for name in ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"):
        for tree in ("dense_layers", "layers", "lin_layers"):
            assert not np.asarray(prog[f"{tree}/{name}"], np.float32).any()
    assert not np.asarray(prog["final_norm"], np.float32).any()
    assert not np.asarray(prog["layers/q_norm"], np.float32).any()
    assert (np.asarray(prog["lin_layers/o_norm"], np.float32) == 1).all()
    assert abs(float(prog["lin_layers/dt_bias"].astype(jnp.float32).mean())
               + 4.0) < 0.05
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), want[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), want[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  want[k]), k


# CPU readings at this size (my runs, PR 40, seed 53: 12 positions, probes
# of 20 / 300 / 600 tokens x 4): sound median 0.087, largest 0.237 (on
# float32 activations the step program is the reference to 1e-5,
# tests/test_latent_linear_layers.py, so this is bfloat16 rounding: three
# times tiny-linear-moe's, because a post-norm brings every sublayer's
# output to unit size before the residual add, where the other presets'
# sublayers add a few hundredths to an embedding of unit size, and because
# no position is set aside as a tie at this size); the reference with its
# state forgotten at every token reads a median of 2.19, without its
# post-norms 2.29, without the SwiGLU's clamp 0.98, with experts chosen by
# the unbiased scores 0.63 (without the latent gate 0.20 and without the
# softmax scale's m^2 0.089: those two readings are held on float32
# activations, where they read 0.8 and 0.07 against 1e-5); int4 weights
# 1.19.  The limit is held on the MEDIAN position.
TINY_LIMIT = 0.13


@pytest.fixture(scope="module")
def latent_linear_served():
    """One pod, the probes served once through latent pages and state; what
    the reference is given varies."""
    from benchmarks import pod as podlib

    config, deploy = _files()
    cdir = manifest.config_dir(NAME)
    config = manifest.with_share(config, deploy)
    ref = manifest.load_reference(deploy["reference"])
    spec = deploy["correct"]
    weights = correctness.reference_weights(ref, config, deploy, SEED)
    pod = podlib.build(NAME, cdir, deploy, SEED, platform="cpu")
    try:
        pod.engine._pipe_warm_wait(900.0)
        prompts = correctness.probes(spec, SEED)
        got = correctness.serve(pod.engine, prompts, spec["decode_tokens"])
        m = pod.engine.metrics
        state = {"starts": m.linear_state_starts_total.total(),
                 "taken_after": pod.engine.ecfg.num_slots
                 - len(pod.engine._free),
                 "slot_bytes": pod.engine._lin_slot_bytes,
                 "state_dtype": pod.labels["state_dtype"],
                 "pool": pod.engine._cache.k.shape,
                 "pool_v": pod.engine._cache.v,
                 "kv_page": pod.labels["kv_page"],
                 "expert_share": pod.labels["expert_share"]}
    finally:
        pod.close()
    return ref, config, weights, prompts, got, spec, state


@pytest.mark.parametrize("without, passes", [
    ((), True), (("state",), False), (("post_norm",), False),
    (("swiglu_limit",), False), (("router_bias",), False)])
def test_served_logprobs_against_the_reference(latent_linear_served, without,
                                               passes):
    """Contexts of 20, 300 and 600 tokens in chunks of 150 rows (three scan
    blocks each, the last of 22 rows; a page boundary at 256 and 512), then
    four decode steps: the served numbers are the reference's; with the
    reference's state, post-norms, clamp or selection bias switched off
    they are not, so the comparison sees each mechanism."""
    ref, config, weights, prompts, got, spec, _ = latent_linear_served
    out = correctness.compare(
        ref, dict(config, reference_without=list(without)), weights, prompts,
        got, spec)
    assert out["clean_positions"] + out["tie_positions"] == 12
    if passes:
        assert out["logprob_err_median"] < TINY_LIMIT, out["per_position"]
        assert out["logprob_err_largest"] < 3 * TINY_LIMIT, \
            out["per_position"]
    else:
        assert out["logprob_err_median"] > 3 * TINY_LIMIT, out["per_position"]


def test_the_probes_went_through_latent_pages_and_state(latent_linear_served):
    *_, state = latent_linear_served
    assert state["kv_page"] == "latent+state"
    assert state["state_dtype"] == "float32"
    assert state["expert_share"] == "1/2"
    assert state["starts"] == 3 and state["taken_after"] == 0
    # The pool: the two latent layers, one 40-wide row a token, no V.
    assert state["pool"][0] == 2 and state["pool"][2] == 1
    assert state["pool_v"] is None
    assert state["slot_bytes"] == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 2)


def test_the_lower_precision_control_fails():
    r = check_correct.read_one(NAME, seed=SEED, control="weight_int4",
                               platform="cpu")
    assert r["logprob_err_median"] > 3 * TINY_LIMIT, r["per_position"]


def test_the_routing_margin_is_in_biased_score_units():
    config, deploy = _files()
    ref = manifest.load_reference(FAMILY)
    config = manifest.with_share(config, deploy)
    w = ref.generate_weights(config, 5)
    tokens = np.arange(2, 42, dtype=np.int32)[None]
    rows = np.array([[3, 21, 39]], np.int32)
    margins: list = []
    logits = ref.forward(config, w, tokens, rows, margins=margins)
    assert logits.shape == (1, 3, 512)
    assert len(margins) == 6                 # the routed layers of eight
    assert all(m.shape == (1, 3) and (m >= 0).all() and (m < 0.1).all()
               for m in margins)
    assert max(m.max() for m in margins) > 1e-4
