"""The ``shortcut_mla_moe`` reference family against the program, on the CPU
at ``tiny-shortcut-mla-moe`` size (8 of the preset's 16 real experts held:
share 1 of 2, beside its 8 identity experts): the weights a seed means are
the program's bit for bit, the leaves stacked by sublayer and the share's;
the served log-probabilities agree with the plain non-absorbed forward; the
same engine with int4 weights does not."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, manifest

NAME = "tiny-shortcut-mla-moe"
FAMILY = "shortcut_mla_moe"


def _files():
    cdir = manifest.config_dir(NAME)
    with open(os.path.join(cdir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(cdir, "deploy.json")) as f:
        deploy = json.load(f)
    return config, deploy


def test_the_family_keeps_the_contract_and_imports_nothing_of_the_program():
    ref = manifest.load_reference(FAMILY)
    for name in manifest.FAMILY_CONTRACT + ("kernel_shapes",):
        assert callable(getattr(ref, name))
    with open(manifest.reference_path(FAMILY)) as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "arks_tpu" not in code
    config, deploy = _files()
    a = ref.arch(manifest.with_share(config, deploy))
    assert (a["held"], a["first"], a["experts"], a["zero"]) == (8, 8, 16, 8)
    assert a["q_scale"] == (64 / 48) ** 0.5 and a["kv_scale"] == 2 ** 0.5
    # The kernel runs once an attention SUBLAYER: 2 layers, 4 calls a token.
    assert ref.kernel_shapes(a) == {"heads": 4, "row": 40, "value": 32,
                                    "layers": 4}
    with pytest.raises(ValueError, match="do not make"):
        ref.arch(dict(config, share=dict(deploy["share"], chips_per_layer=4)))
    with pytest.raises(NotImplementedError, match="zero_expert_type"):
        ref.arch(dict(config, zero_expert_type="copy"))


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
    assert prog["layers/router"].shape == (2, 64, 24)   # 16 real + 8 identity
    assert prog["layers/router_bias"].shape == (2, 24)
    assert prog["layers/w_gate"]["q"].shape == (2, 8, 64, 32)   # held
    assert prog["layers/wq_a"]["q"].shape == (2, 2, 64, 48)     # by sublayer
    assert prog["layers/ffn_gate"]["q"].shape == (2, 2, 64, 128)
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), want[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), want[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  want[k]), k


# CPU readings at this size (my runs, PR 54, three seeds: the largest over
# the positions whose routing margin is 0.0005 or more, 10 or 11 of 12):
# sound 0.0053..0.0100; int4 weights 0.180..0.266.
TINY_LIMIT = 0.03
TIE_MARGIN = 0.0005


@pytest.mark.parametrize("control", [None, "weight_int4"])
def test_served_logprobs_against_the_reference(control):
    r = check_correct.read_one(NAME, seed=31 + len(NAME), control=control,
                               platform="cpu")
    assert r["clean_positions"] + r["tie_positions"] == 12
    err = np.asarray([e for e, _ in r["per_position"]])
    margin = np.asarray([m for _, m in r["per_position"]])
    clear = margin >= TIE_MARGIN
    assert clear.sum() >= 6, margin
    if control is None:
        assert err[clear].max() < TINY_LIMIT, r["per_position"]
    else:
        assert err[clear].max() > TINY_LIMIT, r["per_position"]


def test_the_routing_margin_is_in_biased_probability_units():
    config, deploy = _files()
    ref = manifest.load_reference(FAMILY)
    config = manifest.with_share(config, deploy)
    w = ref.generate_weights(config, 5)
    tokens = np.arange(2, 22, dtype=np.int32)[None]
    rows = np.array([[3, 11, 19]], np.int32)
    margins: list = []
    logits = ref.forward(config, w, tokens, rows, margins=margins)
    assert logits.shape == (1, 3, 512)
    assert len(margins) == 2                 # one routed layer a layer
    # p + b over 24 columns: a probability's distance, well under 1.
    assert all(m.shape == (1, 3) and (m >= 0).all() and (m < 0.2).all()
               for m in margins)
