"""The ``linear_moe`` reference family against the program, on the CPU at
``tiny-linear-moe`` size (8 of the preset's 16 experts held: share 1 of 2;
a chunk budget of 150 under pages of 256 and scan blocks of 64): the weights
a seed means are the program's bit for bit, the three stacks, the share's
leaves and the shifted ``dt_bias``; the served log-probabilities (prefill in
chunks through the GQA layers' pages and the linear layers' state, then
decode) agree with the plain forward whose delta rule runs one token at a
time; the same reference with its state forgotten, its convolution's older
taps dropped or its GQA gate left out does not, nor does the same engine
with int4 weights or int4 pages."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, correctness, manifest

NAME = "tiny-linear-moe"
SEED = 31 + len(NAME)


def _files():
    cdir = manifest.config_dir(NAME)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(cdir, "deploy.json")) as f:
        deploy = json.load(f)
    return config, deploy


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    ref = manifest.load_reference("linear_moe")
    for name in manifest.FAMILY_CONTRACT + ("kernel_shapes",
                                            "linear_kernel_shapes"):
        assert callable(getattr(ref, name))
    with open(manifest.reference_path("linear_moe")) as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "arks_tpu" not in code
    config, deploy = _files()
    a = ref.arch(manifest.with_share(config, deploy))
    assert (a["held"], a["first"], a["experts"]) == (8, 8, 16)
    assert a["kinds"] == ("full", "linear", "linear") * 3
    assert ref.kernel_shapes(a) == {
        "heads": 4, "kv_heads": 2, "head_dim": 16, "layers": 3}
    assert ref.linear_kernel_shapes(a) == {
        "heads": 4, "head_dim": 16, "layers": 6, "state_bytes": 4}
    with pytest.raises(ValueError, match="do not make"):
        ref.arch(dict(config, share=dict(deploy["share"], chips_per_layer=4)))
    with pytest.raises(NotImplementedError, match="kda_use_full_proj"):
        ref.arch(dict(config, kda_use_full_proj=True))
    with pytest.raises(NotImplementedError, match="gqa_layers"):
        ref.arch(dict(config, gqa_layers=[0, 4, 8]))


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
    # A stack a kind: layer 0, a GQA layer a period, the linear layers.
    assert prog["head_layers/wq"]["q"].shape == (1, 64, 4 * 16)
    assert prog["layers/wg"]["q"].shape == (2, 64, 4 * 16)  # elementwise gate
    assert prog["lin_layers/wv"]["q"].shape == (6, 64, 4 * 16)
    assert prog["lin_layers/w_f2"]["q"].shape == (6, 16, 64)    # low rank
    assert prog["lin_layers/conv_k"].shape == (6, 4, 64)        # full width
    assert prog["lin_layers/a_log"].shape == (6, 4)
    assert prog["layers/router"].shape == (2, 64, 16)           # whole width
    assert prog["lin_layers/w_gate"]["q"].shape == (6, 8, 64, 32)   # held
    # The shifted leaf: near -4, so that softplus reads 0.018.
    assert abs(float(prog["lin_layers/dt_bias"].astype(jnp.float32).mean())
               + 4.0) < 0.05
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), want[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), want[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  want[k]), k


# CPU readings at this size (my runs, PR 36, seed 46: 12 positions, probes
# of 20 / 300 / 600 tokens x 4): sound median 0.025 (largest 0.034; on
# float32 activations the step program is the reference to 5e-6,
# tests/test_linear_layers.py, so this is bfloat16 rounding under logits of
# std 0.16); the reference with its state forgotten at every token 1.66,
# with only the newest tap of its convolution 1.94, without its GQA gate
# 0.30; int4 pages a median of 0.042 and a largest of 0.131 (three of the
# nine layers keep pages, and a context of 20 tokens feels them most), int4
# weights more.  The limit is held on the LARGEST position.
TINY_LIMIT = 0.05


@pytest.fixture(scope="module")
def linear_served():
    """One pod, the probes served once through pages and state; what the
    reference is given varies."""
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
                 "pool_layers": pod.engine._cache.k.shape[0],
                 "kv_page": pod.labels["kv_page"]}
    finally:
        pod.close()
    return ref, config, weights, prompts, got, spec, state


@pytest.mark.parametrize("without, passes", [
    ((), True), (("state",), False), (("conv",), False), (("gate",), False)])
def test_served_logprobs_against_the_reference(linear_served, without,
                                               passes):
    """Contexts of 20, 300 and 600 tokens in chunks of 150 rows (three scan
    blocks each, the last of 22 rows; a page boundary at 256 and 512), then
    four decode steps: the served numbers are the reference's; with the
    reference's state, convolution or gate switched off they are not, so
    the comparison sees each mechanism."""
    ref, config, weights, prompts, got, spec, _ = linear_served
    out = correctness.compare(
        ref, dict(config, reference_without=list(without)), weights, prompts,
        got, spec)
    assert out["clean_positions"] + out["tie_positions"] == 12
    if passes:
        assert out["logprob_err_largest"] < TINY_LIMIT, out["per_position"]
    else:
        assert out["logprob_err_median"] > 4 * TINY_LIMIT, out["per_position"]


def test_the_probes_went_through_pages_and_state(linear_served):
    *_, state = linear_served
    assert state["kv_page"] == "kv+state"
    assert state["starts"] == 3 and state["taken_after"] == 0
    assert state["pool_layers"] == 3
    assert state["slot_bytes"] == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)


@pytest.mark.parametrize("control", ["weight_int4", "kv_int4"])
def test_the_lower_precision_controls_fail(control):
    r = check_correct.read_one(NAME, seed=SEED, control=control,
                               platform="cpu")
    assert r["logprob_err_largest"] > 2 * TINY_LIMIT, r["per_position"]


def test_the_routing_margin_is_in_biased_score_units():
    config, deploy = _files()
    ref = manifest.load_reference("linear_moe")
    config = manifest.with_share(config, deploy)
    w = ref.generate_weights(config, 5)
    tokens = np.arange(2, 42, dtype=np.int32)[None]
    rows = np.array([[3, 21, 39]], np.int32)
    margins: list = []
    logits = ref.forward(config, w, tokens, rows, margins=margins)
    assert logits.shape == (1, 3, 512)
    assert len(margins) == 9                 # every layer is routed
    # sigmoid(~0) + a bias of normal * 0.02: a few hundredths apart at most.
    assert all(m.shape == (1, 3) and (m >= 0).all() and (m < 0.1).all()
               for m in margins)
    assert max(m.max() for m in margins) > 1e-4
