"""The ``swa_sink_moe`` reference family against the program, on the CPU at
``tiny-swa-sink-moe`` size (8 of the preset's 16 experts held: share 1 of
2; a window of 16 under pages of 256, a chunk budget of 40; 4 KV heads in a
window layer and 2 in a full one, keys 24 and values 16 wide): the weights
a seed means are the program's bit for bit, the three stacks, the sink
logits and the share's leaves; the served log-probabilities (prefill in
chunks through BOTH page pools, then decode, window pages released on the
way) agree with the plain masked-softmax forward; the same reference with
its sink, its value scale, its rotary share or its window switched off does
not, nor does the same engine with int4 pages (int4 weights: this file's
own run, not tier-1's).

Seeded leaves are ``normal x 0.02``: a sink logit near zero holds a
seventeenth of a window's mass here and under a hundredth of a window of
128, and scores of a few thousandths make every softmax flat, so that WHERE
a head rotates hardly shows.  So the pod and the reference are handed the
same leaves set LARGE before anything is served: the sink logits 2..4 a
head, the scales of ``wq`` and ``wk`` times 8 (scores times 64)."""

import json
import os

import numpy as np
import pytest

from benchmarks import check_correct, correctness, manifest

NAME = "tiny-swa-sink-moe"
FAMILY = "swa_sink_moe"
SEED = 31 + len(NAME)
TREES = ("dense_layers", "layers", "win_layers")


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
                                            "window_kernel_shapes"):
        assert callable(getattr(ref, name))
    with open(manifest.reference_path(FAMILY)) as f:
        code = f.read().split('"""', 2)[2]          # past the docstring
    assert "arks_tpu" not in code
    config, deploy = _files()
    a = ref.arch(manifest.with_share(config, deploy))
    assert (a["held"], a["first"], a["experts"]) == (8, 8, 16)
    assert a["kinds"] == ("full", "window", "full", "window", "window",
                          "full", "window", "window", "full")
    assert (a["kv_heads_full"], a["kv_heads_window"], a["head_dim"],
            a["v_head_dim"], a["window"]) == (2, 4, 24, 16, 16)
    assert a["sink_window"] and not a["sink_full"]
    # Keys 24 and values 16 wide as the one width the work functions take.
    assert ref.kernel_shapes(a) == {
        "heads": 8, "kv_heads": 2, "head_dim": 20, "layers": 4}
    assert ref.window_kernel_shapes(a) == {
        "heads": 8, "kv_heads": 4, "head_dim": 20, "layers": 5, "window": 16}
    with pytest.raises(ValueError, match="do not make"):
        ref.arch(dict(config, share=dict(deploy["share"], chips_per_layer=4)))
    with pytest.raises(NotImplementedError, match="shared expert"):
        ref.arch(dict(config, n_shared_experts=1))


def test_the_work_functions_count_keys_and_values_at_their_own_widths():
    """At the cell's widths the two work functions, handed ``head_dim`` =
    (192 + 128) / 2, count exactly the launch's useful work: 2 x pairs x
    heads x (192 + 128) operations, and a key's 192 + 128 int8 lanes and two
    float32 scales a KV head."""
    import importlib
    ref = manifest.load_reference(FAMILY)
    with open(os.path.join(manifest.config_dir("mimo-v2.5-ep16-l13"),
                           "config.json")) as f:
        a = ref.arch(json.load(f))
    full, win = ref.kernel_shapes(a), ref.window_kernel_shapes(a)
    assert (full["kv_heads"], full["layers"], full["head_dim"]) == (4, 3, 160)
    assert (win["kv_heads"], win["layers"], win["window"]) == (8, 10, 128)
    mixed = importlib.import_module("benchmarks.kernels.paged_mixed_attention")
    w = mixed.work(heads=full["heads"], kv_heads=full["kv_heads"],
                   head_dim=full["head_dim"], layers=1, kv_bytes=1,
                   kv_scale_bytes=4, calls=[(1, 3000)])
    assert w["flops"] == 2 * 3000 * 64 * (192 + 128)
    # The cached rows, and the query read at 192 and the output written at
    # 128 lanes in bfloat16.
    assert w["bytes"] == 3000 * 4 * (192 + 128 + 8) + 64 * (192 + 128) * 2


def test_the_window_read_share_weighs_a_kinds_bytes_by_its_layers():
    """``mixed_kv_bytes_total{kind}`` counts ONE layer's launch a kind; the
    reader weighs each by the family's layers of that kind (5 window, 4 full
    at this size).  A program without the counter (the parent), or a family
    that states no layers a kind, leaves nothing to read."""
    read = manifest.load_reader("kv_window_read_share.tput")
    config, deploy = _files()
    cell = {"reference": manifest.load_reference(FAMILY),
            "config": manifest.with_share(config, deploy)}
    name = "mixed_kv_bytes_total"
    opened = {name: [({"kind": "full"}, 10.0), ({"kind": "window"}, 4.0)]}
    closed = {name: [({"kind": "full"}, 40.0), ({"kind": "window"}, 14.0)]}
    ctx = {"cell": cell, "metrics_open": opened, "metrics_close": closed}
    assert read(ctx) == pytest.approx(100 * 5 * 10 / (5 * 10 + 4 * 30))
    assert read(dict(ctx, metrics_open={}, metrics_close={})) is None
    assert read(dict(ctx, cell=dict(
        cell, reference=manifest.load_reference("decoder")))) is None
    assert read(dict(ctx, metrics_close=opened)) is None    # nothing moved


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
    # A stack a kind, each with projections of its own head counts and
    # widths; the cut-short period's full layer is the first of ``layers``.
    assert prog["dense_layers/wk"]["q"].shape == (1, 64, 2 * 24)
    assert prog["layers/wv"]["q"].shape == (3, 64, 2 * 16)
    assert prog["win_layers/wk"]["q"].shape == (5, 64, 4 * 24)
    assert prog["win_layers/wo"]["q"].shape == (5, 8 * 16, 64)
    assert prog["win_layers/attn_sink"].shape == (5, 8)
    assert "layers/attn_sink" not in prog and "layers/shared_up" not in prog
    assert prog["layers/router"].shape == (3, 64, 16)           # whole width
    assert prog["win_layers/w_gate"]["q"].shape == (5, 8, 64, 32)   # held
    for k, a in prog.items():
        if isinstance(a, dict):
            assert np.array_equal(np.asarray(a["q"]), want[k]["q"]), k
            assert np.array_equal(np.asarray(a["s"]), want[k]["s"]), k
        else:
            assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                                  want[k]), k


# CPU readings at this size (my runs, PR 47, seed 48: 12 positions, probes
# of 20 / 300 / 600 tokens x 4, the limit held on the MEDIAN position as
# ``swa_moe``'s).  With the leaves set large: sound median 0.0125 (largest
# 0.030); the reference without its sink 0.42 (0.21-0.98), without its
# value scale 0.27 (0.13-0.54), the whole head rotated 0.71 (0.30-1.07),
# without its window 0.51 (0.16-1.76).  At the seeded leaves as drawn:
# sound 0.0091, no sink 0.067, no value scale 0.25, the whole head rotated
# 0.0118 (NOT seen), no window 0.92.
TINY_LIMIT = 0.03
SINK_LOGITS = (2.0, 4.0)
QK_BOOST = 8.0


def _set_large(params: dict, weights: dict) -> dict:
    """The pod's tree and the reference's leaves with the same large sink
    logits and query / key scales."""
    import jax.numpy as jnp
    params = dict(params)
    for tree in TREES:
        t = dict(params[tree])
        for leaf in ("wq", "wk"):
            t[leaf] = dict(t[leaf], s=t[leaf]["s"] * QK_BOOST)
            w = weights[f"{tree}/{leaf}"]
            weights[f"{tree}/{leaf}"] = dict(w, s=w["s"] * QK_BOOST)
        if "attn_sink" in t:
            sink = t["attn_sink"]
            t["attn_sink"] = jnp.linspace(
                *SINK_LOGITS, sink.size).reshape(sink.shape).astype(
                    sink.dtype)
            weights[f"{tree}/attn_sink"] = np.asarray(
                t["attn_sink"].astype(jnp.float32))
        params[tree] = t
    return params


@pytest.fixture(scope="module")
def sink_served():
    """One pod, its sink logits and query / key scales set large, the
    probes served once through both pools; what the reference (handed the
    same leaves) switches off varies."""
    from benchmarks import pod as podlib

    config, deploy = _files()
    cdir = manifest.config_dir(NAME)
    config = manifest.with_share(config, deploy)
    ref = manifest.load_reference(deploy["reference"])
    spec = deploy["correct"]
    weights = correctness.reference_weights(ref, config, deploy, SEED)
    pod = podlib.build(NAME, cdir, deploy, SEED, platform="cpu")
    try:
        pod.engine.params = _set_large(pod.engine.params, weights)
        pod.engine._pipe_warm_wait(900.0)
        prompts = correctness.probes(spec, SEED)
        got = correctness.serve(pod.engine, prompts, spec["decode_tokens"])
        m = pod.engine.metrics
        pages = {"released": m.kv_window_pages_released_total.total(),
                 "per_slot": pod.engine._win.per_slot,
                 "in_use_after": pod.engine._win.pages_in_use,
                 "labels": dict(pod.labels)}
    finally:
        pod.close()
    return ref, config, weights, prompts, got, spec, pages


@pytest.mark.parametrize("without, passes", [
    ((), True), (("sink",), False), (("value_scale",), False),
    (("rotary",), False), (("window",), False)])
def test_served_logprobs_against_the_reference(sink_served, without, passes):
    """Contexts of 20, 300 and 600 tokens over a window of 16 (a sixteenth
    of a page), chunk boundaries every 40 rows (inside windows), a page
    boundary at 256 and 512: the served numbers are the reference's; with
    the reference's sink, value scale, rotary share or window switched off
    they are not, so the comparison sees each mechanism."""
    ref, config, weights, prompts, got, spec, _ = sink_served
    out = correctness.compare(
        ref, dict(config, reference_without=list(without)), weights, prompts,
        got, spec)
    assert out["clean_positions"] + out["tie_positions"] == 12
    if passes:
        assert out["logprob_err_median"] < TINY_LIMIT, out["per_position"]
    else:
        assert out["logprob_err_median"] > 3 * TINY_LIMIT, out["per_position"]
        errs = np.asarray([e for e, _ in out["per_position"]])
        assert (errs > TINY_LIMIT).sum() >= 10, out["per_position"]


def test_the_probes_went_through_both_pools_and_released_window_pages(
        sink_served):
    *_, pages = sink_served
    labels = pages["labels"]
    assert labels["kv_page"] == "kv+window"
    assert labels["kv_heads"] == "2/4" and labels["attn_sink"] == "window"
    assert labels["expert_share"] == "1/2"
    # 300 tokens pass one page boundary + the window, 600 pass two.
    assert pages["released"] == 3
    assert pages["per_slot"] == 2          # ceil((15 + 40) / 256) + 1
    assert pages["in_use_after"] == 0


@pytest.mark.parametrize("control", ["kv_int4", "weight_int4"])
def test_the_lower_precision_controls_fail(control):
    r = check_correct.read_one(NAME, seed=SEED, control=control,
                               platform="cpu")
    assert r["logprob_err_median"] > TINY_LIMIT, r["per_position"]


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
    assert len(margins) == 8                 # the routed layers only
    # Sigmoids of logits of a few hundredths plus a bias of normal x 0.02:
    # a few hundredths apart at the most, and not all ties.
    assert all(m.shape == (1, 3) and (m >= 0).all() and (m < 0.2).all()
               for m in margins)
    assert max(m.max() for m in margins) > 1e-3
