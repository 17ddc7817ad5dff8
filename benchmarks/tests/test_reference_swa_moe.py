"""The ``swa_moe`` reference family against the program, on the CPU at
``tiny-swa-moe`` size (8 of the preset's 16 experts held: share 1 of 2; a
window of 16 under pages of 256, a chunk budget of 40): the weights a seed
means are the program's bit for bit, the three stacks and the share's
leaves; the served log-probabilities (prefill in chunks through BOTH page
pools, then decode, window pages released on the way) agree with the plain
masked-softmax forward; the same reference with its window or its gate
switched off does not, nor does the same engine with int4 weights or int4
pages."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, correctness, manifest

NAME = "tiny-swa-moe"
SEED = 31 + len(NAME)


def _files():
    cdir = manifest.config_dir(NAME)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(cdir, "deploy.json")) as f:
        deploy = json.load(f)
    return config, deploy


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    ref = manifest.load_reference("swa_moe")
    for name in manifest.FAMILY_CONTRACT + ("kernel_shapes",):
        assert callable(getattr(ref, name))
    with open(manifest.reference_path("swa_moe")) as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "arks_tpu" not in code
    config, deploy = _files()
    a = ref.arch(manifest.with_share(config, deploy))
    assert (a["held"], a["first"], a["experts"]) == (8, 8, 16)
    assert a["kinds"] == ("full", "window", "window", "full", "window",
                          "window", "full")
    assert (a["heads_full"], a["heads_window"], a["window"]) == (4, 6, 16)
    assert ref.kernel_shapes(a) == {
        "heads": 4, "kv_heads": 2, "head_dim": 16, "layers": 3}
    assert ref.window_kernel_shapes(a) == {
        "heads": 6, "kv_heads": 2, "head_dim": 16, "layers": 4, "window": 16}
    with pytest.raises(ValueError, match="do not make"):
        ref.arch(dict(config, share=dict(deploy["share"], chips_per_layer=4)))
    with pytest.raises(NotImplementedError, match="soft-cap"):
        ref.arch(dict(config, moe_router_logit_softcapping=30.0))


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
    # A stack a kind: the dense full layer, a full layer a period, the
    # window layers; each with projections of its own head count.
    assert prog["dense_layers/wq"]["q"].shape == (1, 64, 4 * 16)
    assert prog["layers/wq"]["q"].shape == (2, 64, 4 * 16)
    assert prog["win_layers/wq"]["q"].shape == (4, 64, 6 * 16)
    assert prog["win_layers/attn_gate"].shape == (4, 64, 6)     # full width
    assert prog["layers/router"].shape == (2, 64, 16)           # whole width
    assert prog["win_layers/w_gate"]["q"].shape == (4, 8, 64, 32)   # held
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), want[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), want[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  want[k]), k


# CPU readings at this size (my runs, PR 32, seed 43: 12 positions, probes
# of 20 / 300 / 600 tokens x 4; the smallest routing margin over six routed
# layers is a few thousandths of a logit at most here, so the limit is
# held on the MEDIAN position and the largest is reported): sound median
# 0.007 (largest 0.053, a tie); the reference without its window 0.28
# (0.12-0.63); without its gate 0.77 (0.51-1.05); int4 weights and int4
# pages read below.
TINY_LIMIT = 0.03


@pytest.fixture(scope="module")
def served():
    """One pod, the probes served once through both pools; what the
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
        pages = {"released": m.kv_window_pages_released_total.total(),
                 "per_slot": pod.engine._win.per_slot,
                 "in_use_after": pod.engine._win.pages_in_use,
                 "kv_page": pod.labels["kv_page"]}
    finally:
        pod.close()
    return ref, config, weights, prompts, got, spec, pages


@pytest.mark.parametrize("without, passes", [
    ((), True), (("window",), False), (("gate",), False)])
def test_served_logprobs_against_the_reference(served, without, passes):
    """Contexts of 20, 300 and 600 tokens over a window of 16 (37 windows),
    chunk boundaries every 40 rows (inside windows), a page boundary at
    256 and 512: the served numbers are the reference's; with the
    reference's window or gate switched off they are not, so the
    comparison sees each mechanism."""
    ref, config, weights, prompts, got, spec, pages = served
    out = correctness.compare(
        ref, dict(config, reference_without=list(without)), weights, prompts,
        got, spec)
    assert out["clean_positions"] + out["tie_positions"] == 12
    if passes:
        assert out["logprob_err_median"] < TINY_LIMIT, out["per_position"]
    else:
        assert out["logprob_err_median"] > 3 * TINY_LIMIT, out["per_position"]
        # Every position past the first window reads off, not a few.
        errs = np.asarray([e for e, _ in out["per_position"]])
        assert (errs > TINY_LIMIT).sum() >= 8, out["per_position"]


def test_the_probes_went_through_both_pools_and_released_window_pages(served):
    *_, pages = served
    assert pages["kv_page"] == "kv+window"
    # 300 tokens pass one page boundary + the window, 600 pass two.
    assert pages["released"] == 3
    assert pages["per_slot"] == 2          # ceil((15 + 40) / 256) + 1
    assert pages["in_use_after"] == 0


@pytest.mark.parametrize("control", ["weight_int4", "kv_int4"])
def test_the_lower_precision_controls_fail(control):
    r = check_correct.read_one(NAME, seed=SEED, control=control,
                               platform="cpu")
    assert r["logprob_err_median"] > TINY_LIMIT, r["per_position"]


def test_the_routing_margin_is_in_router_logit_units():
    config, deploy = _files()
    ref = manifest.load_reference("swa_moe")
    config = manifest.with_share(config, deploy)
    w = ref.generate_weights(config, 5)
    tokens = np.arange(2, 42, dtype=np.int32)[None]
    rows = np.array([[3, 21, 39]], np.int32)
    margins: list = []
    logits = ref.forward(config, w, tokens, rows, margins=margins)
    assert logits.shape == (1, 3, 512)
    assert len(margins) == 6                 # the routed layers only
    # Logits of a 64-wide normed input through normal * 0.02 weights: a
    # few hundredths apart at the most.
    assert all(m.shape == (1, 3) and (m >= 0).all() and (m < 0.2).all()
               for m in margins)
    assert max(m.max() for m in margins) > 1e-3
