"""The ``ssm_moe`` reference family against the program, on the CPU at
``tiny-ssm-moe`` size (8 of the preset's 16 experts held: share 1 of 2; a
chunk budget of 150 under pages of 256 and scan blocks of 64): the weights a
seed means are the program's bit for bit, the three stacks of sublayers, the
share's leaves, the shifted ``dt_bias`` and the scaled taps; the served
log-probabilities (prefill in chunks through the GQA layers' pages, the
mixers' carry and their chunked scan, then decode through the one-step
kernel) agree with the plain forward whose selective scan runs one token at
a time; the same reference with one published term dropped does not, nor
does the same engine with int4 weights or int4 pages."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, correctness, manifest

NAME = "tiny-ssm-moe"
SEED = 31 + len(NAME)


def _files():
    cdir = manifest.config_dir(NAME)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(cdir, "deploy.json")) as f:
        deploy = json.load(f)
    return config, deploy


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    ref = manifest.load_reference("ssm_moe")
    for name in manifest.FAMILY_CONTRACT + ("kernel_shapes",
                                            "ssm_kernel_shapes"):
        assert callable(getattr(ref, name))
    with open(manifest.reference_path("ssm_moe")) as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "arks_tpu" not in code
    config, deploy = _files()
    a = ref.arch(manifest.with_share(config, deploy))
    assert (a["held"], a["first"], a["experts"]) == (8, 8, 16)
    assert a["pattern"] == "MEM*EMEM*EM*EME"
    assert ref.kernel_shapes(a) == {
        "heads": 4, "kv_heads": 2, "head_dim": 16, "layers": 3}
    assert ref.ssm_kernel_shapes(a) == {
        "heads": 8, "head_dim": 8, "state": 16, "groups": 2, "layers": 6,
        "state_bytes": 4}
    with pytest.raises(ValueError, match="do not make"):
        ref.arch(dict(config, share=dict(deploy["share"], chips_per_layer=4)))
    with pytest.raises(NotImplementedError, match="mlp_hidden_act"):
        ref.arch(dict(config, mlp_hidden_act="silu"))
    with pytest.raises(NotImplementedError, match="hybrid_override_pattern"):
        ref.arch(dict(config, hybrid_override_pattern="MEM*EMEM*EM*EM-"))


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

    def drawn(name, leaf):
        """A head-split projection ``[L, H, D, E]`` and the transposed
        ``w_in [L, Q, E]`` in the order they are drawn in, ``[L, E, H x D]``
        (the same numbers, transposed)."""
        if name in quant.TRANSPOSED_KEYS:
            return jax.tree.map(lambda a: a.swapaxes(-1, -2), leaf)
        if name in quant.HEAD_SPLIT_KEYS and jax.tree.leaves(leaf)[0].ndim == 4:
            return jax.tree.map(lambda a: a.reshape(
                a.shape[0], -1, a.shape[-1]).swapaxes(-1, -2), leaf)
        return leaf

    def flat(t, pre=""):
        for k, v in t.items():
            if isinstance(v, dict) and "q" not in v:
                yield from flat(v, pre + k + "/")
            else:
                yield pre + k, drawn(k, v)

    prog = dict(flat(prog))
    assert sorted(prog) == sorted(want)
    # A stack a kind of SUBLAYER: six mixers, three GQA layers, six FFNs.
    assert prog["ssm_layers/w_in"]["q"].shape == (6, 64, 2 * 64 + 2 * 32 + 8)
    assert prog["ssm_layers/conv_w"].shape == (6, 4, 64 + 2 * 32)
    assert prog["ssm_layers/conv_b"].shape == (6, 128)
    assert prog["ssm_layers/d_skip"].shape == (6, 8)
    assert prog["layers/wq"]["q"].shape == (3, 64, 4 * 16)
    assert "layers/mlp_norm" not in prog and "ssm_layers/mlp_norm" not in prog
    assert prog["moe_layers/router"].shape == (6, 64, 16)       # whole width
    assert prog["moe_layers/w_upt"]["q"].shape == (6, 8, 64, 32)    # held
    assert prog["moe_layers/shared_up"]["q"].shape == (6, 64, 48)
    # An expert is two matrices: no gate matrix, the shared one neither.
    assert not [k for k in prog
                if k.endswith(("w_gate", "w_up", "shared_gate_proj"))]
    # The shifted leaf: near -4, so that softplus reads 0.018; the taps at
    # 25 x 0.02; the skip, the decay's rate and both biases drawn, not set.
    assert abs(float(prog["ssm_layers/dt_bias"].astype(jnp.float32).mean())
               + 4.0) < 0.05
    assert 0.4 < float(prog["ssm_layers/conv_w"].astype(jnp.float32).std()) \
        < 0.6
    for k in ("ssm_layers/a_log", "ssm_layers/d_skip", "ssm_layers/conv_b",
              "moe_layers/router_bias"):
        assert 0.01 < float(prog[k].astype(jnp.float32).std()) < 0.03, k
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), want[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), want[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  want[k]), k


# CPU readings at this size (my runs, PR 56, seed 43: 12 positions, probes
# of 20 / 300 / 600 tokens x 4): sound 0.007-0.018 a position, but where a
# router's 2nd and 3rd biased scores lie within 0.001 (five positions of
# the twelve; ``tie_margin`` sets them aside): bfloat16 resolves a sigmoid
# near 0.5 to 0.004, and one such position reads 0.114 through the
# sequential program and 0.010 through the pipelined one.  The reference
# with its convolution cut to the newest tap reads a median of 1.5 and
# more, without the gate or the skip as much; int4 pages (three of the
# fifteen layers keep pages) a median of 0.04 and a largest of 0.064, int4
# weights more.  The limit is held on the LARGEST position that counts,
# the controls on their median.
TINY_LIMIT = 0.03


@pytest.fixture(scope="module")
def ssm_served():
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
                 "rows": {p: m.ssm_rows_total.get(path=p)
                          for p in ("step", "scan")},
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
    ((), True), (("conv",), False), (("gate",), False), (("d_skip",), False)])
def test_served_logprobs_against_the_reference(ssm_served, without, passes):
    """Contexts of 20, 300 and 600 tokens in chunks of 150 rows (three scan
    blocks each, the last of 22 rows; a page boundary at 256 and 512), then
    four decode steps: the served numbers are the reference's; with the
    reference's convolution cut to its newest tap, its gate or its skip
    left out they are not, so the comparison sees each mechanism.  (At this
    width, 64, x | B | C leave the convolution at 0.08 and the skip
    outweighs the state eight to one, where at the published width they
    leave it at 0.5 and the state outweighs the skip: what a forgotten or a
    rounded state, a dropped rate, bias or grouping does to the logits is
    read on float32 activations, ``tests/test_ssm_layers.py``.)"""
    ref, config, weights, prompts, got, spec, _ = ssm_served
    out = correctness.compare(
        ref, dict(config, reference_without=list(without)), weights, prompts,
        got, spec)
    assert out["clean_positions"] + out["tie_positions"] == 12
    if passes:
        assert out["logprob_err_largest"] < TINY_LIMIT, out["per_position"]
    else:
        assert out["logprob_err_median"] > 4 * TINY_LIMIT, out["per_position"]


def test_the_probes_went_through_pages_and_state(ssm_served):
    *_, state = ssm_served
    assert state["kv_page"] == "kv+state" and state["state_dtype"] == "float32"
    assert state["starts"] == 3 and state["taken_after"] == 0
    assert state["pool_layers"] == 3
    # Six mixers: 8 heads x [8, 16] float32 and 3 rows of 128 channels.
    assert state["slot_bytes"] == 6 * (8 * 8 * 16 * 4 + 3 * 128 * 2)
    # The 920 prompt rows through the scan (a probe's last prompt token
    # rides its last chunk), the decode rows through the one-step kernel.
    assert state["rows"]["scan"] == 920
    assert state["rows"]["step"] >= 3 * (
        _files()[1]["correct"]["decode_tokens"] - 1)


@pytest.mark.parametrize("control", ["weight_int4", "kv_int4"])
def test_the_lower_precision_controls_fail(control):
    r = check_correct.read_one(NAME, seed=SEED, control=control,
                               platform="cpu")
    assert r["logprob_err_median"] > TINY_LIMIT, r["per_position"]


def test_the_routing_margin_is_in_biased_score_units():
    config, deploy = _files()
    ref = manifest.load_reference("ssm_moe")
    config = manifest.with_share(config, deploy)
    w = ref.generate_weights(config, 5)
    tokens = np.arange(2, 42, dtype=np.int32)[None]
    rows = np.array([[3, 21, 39]], np.int32)
    margins: list = []
    logits = ref.forward(config, w, tokens, rows, margins=margins)
    assert logits.shape == (1, 3, 512)
    assert len(margins) == 6                 # the E layers, no other
    # sigmoid(~0) + a bias of normal * 0.02: a few hundredths apart at most.
    assert all(m.shape == (1, 3) and (m >= 0).all() and (m < 0.1).all()
               for m in margins)
    assert max(m.max() for m in margins) > 1e-4
