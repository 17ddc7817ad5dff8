import copy
import filecmp
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import manifest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("rehearsal", [False, True])
def test_manifest_and_every_file_it_names(rehearsal):
    man = manifest.load(rehearsal=rehearsal)
    assert manifest.validate(man) == []
    for w in man["workloads"]:
        cell = manifest.cell(man, w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(manifest.load_reader(m["name"]))


def _broken(edit):
    man = copy.deepcopy(manifest.load())
    edit(man)
    return manifest.validate(man)


@pytest.mark.parametrize("edit, word", [
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["workloads"][0].update(name="a b"), "name"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda m: m["per_layer"][0].update(moves="setup_s"), "moves"),
    (lambda m: m["per_layer"][0].update(workloads=["qwen2.5-7b.chat.flood"]),
     "does not report"),
    (lambda m: m["per_layer"][3].update(name="no_such_metric"), "no benchmarks"),
    (lambda m: m["workloads"][0].update(traffic="no.such.mix"), "traffic file"),
    (lambda m: m["configs"][1].update(reduced=["hidden_size"]), "width"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["end_to_end"][1].update(why="x"), "keys"),
    (lambda m: m.update(run_seconds=90), "run_seconds"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")),
     "twice"),
])
def test_validator_names_the_fault(edit, word):
    assert any(word in e for e in _broken(edit)), _broken(edit)


def _checkout(tmp_path):
    """A copy of the benchmark's files and the manifest to add to."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(manifest.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root, manifest.load()


def test_a_new_cell_loads_from_added_files_alone(tmp_path):
    """The dry run the issue asks for: a configuration, a mix, a cell and a
    per-layer metric added as files and entries, no existing file edited."""
    root, man = _checkout(tmp_path)
    cfg = root / "benchmarks" / "configs" / "newmodel"
    shutil.copytree(root / "benchmarks" / "configs" / "mixtral-8x7b-l4", cfg)
    mix = json.loads((root / "benchmarks" / "traffic"
                      / "chat.closed.json").read_text())
    mix["load_share"] = 2.0
    (root / "benchmarks" / "traffic" / "chat.crowd.json").write_text(
        json.dumps(mix))
    (root / "benchmarks" / "knees" / "newmodel.chat.crowd.json").write_text(
        json.dumps({"knee": 40, "found": "dry run"}))
    lm = root / "benchmarks" / "layer_metrics"
    (lm / "new_metric.json").write_text(json.dumps({"name": "new_metric"}))
    (lm / "new_metric.py").write_text("def read(ctx):\n    return 1.0\n")
    man["configs"].append(dict(man["configs"][1], name="newmodel",
                               file="benchmarks/configs/newmodel/config.json"))
    man["workloads"].append({"name": "newmodel.chat.crowd",
                             "config": "newmodel", "traffic": "chat.crowd",
                             "chips": 1, "why": "dry run"})
    man["per_layer"].append({"name": "new_metric", "unit": "rows",
                             "better": "higher", "source": "program_counter",
                             "layer": "step loop", "moves": "output_tok_s",
                             "workloads": ["newmodel.chat.crowd"]})
    # the new cell enters the list of each end-to-end metric it reports
    next(m for m in man["end_to_end"] if m["name"] == "output_tok_s")[
        "workloads"].append("newmodel.chat.crowd")
    assert manifest.validate(man, str(root)) == []
    cell = manifest.cell(man, "newmodel.chat.crowd", str(root))
    assert cell["load"] == 80.0
    assert "new_metric" in [m["name"] for m in cell["per_layer"]]
    assert manifest.load_reader("new_metric", str(root))({}) == 1.0


TOY_CONFIG = {"model_type": "toy_latent", "hidden_size": 32,
              "num_hidden_layers": 2, "num_attention_heads": 4, "head_dim": 8,
              "kv_lora_rank": 8, "moe_intermediate_size": 16,
              "n_routed_experts": 8, "num_experts_per_tok": 2,
              "vocab_size": 320, "rms_norm_eps": 1e-6, "rope_theta": 10000.0}
TOY_SHARE = {"chips_per_layer": 2, "index": 1,
             "published": {"n_routed_experts": 16, "vocab_size": 2560}}


def _add_toy_family(root, man, share=TOY_SHARE, family="toy_latent",
                    config=TOY_CONFIG):
    """A family, a configuration that names it and holds a share, a mix, a
    knee and a cell, as files and entries."""
    b = root / "benchmarks"
    shutil.copy(os.path.join(HERE, "data", "toy_latent.py"),
                b / "references" / "toy_latent.py")
    cfg = b / "configs" / "toy"
    cfg.mkdir()
    (cfg / "config.json").write_text(json.dumps(config))
    deploy = json.loads((b / "configs" / "tiny" / "deploy.json").read_text())
    deploy.update(reference=family, **({"share": share} if share else {}))
    (cfg / "deploy.json").write_text(json.dumps(deploy))
    shutil.copy(b / "traffic" / "rehearsal.closed.json",
                b / "traffic" / "toy.closed.json")
    (b / "knees" / "toy.toy.closed.json").write_text(
        json.dumps({"knee": 4, "found": "dry run"}))
    man["configs"].append({
        "name": "toy", "source": "none: a dry run",
        "file": "benchmarks/configs/toy/config.json",
        "reduced": ["n_routed_experts", "vocab_size"], "why": "dry run"})
    man["workloads"].append({"name": "toy.toy.closed", "config": "toy",
                             "traffic": "toy.closed", "chips": 1,
                             "why": "dry run"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("output_tok_s", "rows_per_step_mean",
                         "attn_roofline.tput"):
            m["workloads"].append("toy.toy.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _only_added(old: str, new: str) -> list[str]:
    """Files of ``old`` that ``new`` lacks or holds with other bytes."""
    d = filecmp.dircmp(old, new, ignore=["__pycache__", "tests"])
    out = [os.path.join(old, f) for f in d.left_only + d.diff_files
           + d.funny_files]
    for sub in d.common_dirs:
        out += _only_added(os.path.join(old, sub), os.path.join(new, sub))
    return out


def test_a_new_family_loads_from_added_files_alone(tmp_path):
    """A reference family the decoder family cannot express (a low-rank
    key/value projection, a share of the experts, a margin in sigmoid
    units) comes in as files and entries: ``validate`` is clean, the
    comparison runs through it, and no file that existed changed."""
    from benchmarks import correctness

    root, man = _checkout(tmp_path)
    before = copy.deepcopy(man)
    _add_toy_family(root, man)
    assert manifest.validate(man, str(root)) == []
    assert _only_added(os.path.join(manifest.ROOT, "benchmarks"),
                       str(root / "benchmarks")) == []
    for key, old in before.items():        # entries added, none edited
        new = copy.deepcopy(man[key])
        if isinstance(old, list) and old and isinstance(old[0], dict):
            for m in new:
                if "workloads" in m:
                    m["workloads"] = [w for w in m["workloads"]
                                      if w != "toy.toy.closed"]
            new = new[:len(old)]
        assert new == old, key

    cell = manifest.cell(man, "toy.toy.closed", str(root))
    ref, config, deploy = cell["reference"], cell["config"], cell["deploy"]
    assert ref.__file__ == str(root / "benchmarks/references/toy_latent.py")
    a = ref.arch(config)
    assert (a["held"], a["first"], a["experts"]) == (8, 8, 16)
    assert manifest.load_reader("attn_roofline.tput", str(root))(
        {"cell": cell, "device": {"ops": []}}) is None   # no kernel_shapes

    weights = correctness.reference_weights(ref, config, deploy, seed=7)
    assert weights["layers/w_kv_a"]["q"].shape == (2, 32, 8)
    assert weights["layers/router"].shape == (2, 32, 16)
    spec = dict(deploy["correct"], tie_margin=0.01)
    prompts = correctness.probes(spec, 7)
    k = spec["decode_tokens"]
    rng = np.random.default_rng(7)
    served = [{"tokens": [int(t) for t in rng.integers(2, 258, k)]}
              for _ in prompts]
    tokens, rows = correctness.layout(prompts, served)
    margins: list = []
    logits = ref.forward(config, weights, tokens, rows, margins=margins)
    assert logits.shape == (len(prompts), k, 320) and len(margins) == 2
    assert all(m.shape == rows.shape and (m >= 0).all() and (m < 1).all()
               for m in margins)           # sigmoid-score units
    lp = correctness.log_softmax(logits)
    for i, s in enumerate(served):
        s["top_ids"] = [np.argsort(lp[i, j])[-8:].tolist() for j in range(k)]
        s["top_lps"] = [lp[i, j, ids].tolist()
                        for j, ids in enumerate(s["top_ids"])]
    cmp_ = correctness.compare(ref, config, weights, prompts, served, spec)
    assert cmp_["logprob_err_largest"] == 0.0 and cmp_["logprob_err_mean"] == 0.0
    assert cmp_["clean_positions"] + cmp_["tie_positions"] == rows.size
    assert cmp_["tie_positions"] == int(
        (np.min(margins, axis=0) < 0.01).sum())
    assert correctness.verdict(cmp_, spec)


@pytest.mark.parametrize("change, word", [
    (dict(family="no_such_family"), "no benchmarks/references"),
    (dict(family="_common"), "lacks"),
    (dict(family="a b"), "names no reference family"),
    (dict(share=None), "has to state the share"),
    (dict(share=dict(TOY_SHARE, index=2)), "index"),
    (dict(share=dict(TOY_SHARE, published={"vocab_size": 2560})),
     "published counts"),
    (dict(share=dict(TOY_SHARE, published={"n_routed_experts": 32,
                                           "vocab_size": 2560})),
     "do not hold every expert"),
    (dict(config=dict(TOY_CONFIG, n_routed_experts=4),
          share=dict(TOY_SHARE, chips_per_layer=4, index=1)),
     "under 8 routed experts"),
    (dict(share=dict(TOY_SHARE, published={"n_routed_experts": 16,
                                           "vocab_size": 2561})),
     "under an eighth"),
    (dict(config=dict(TOY_CONFIG, vocab_size=257),
          share=dict(TOY_SHARE, published={"n_routed_experts": 16,
                                           "vocab_size": 514})),
     "the 258 ids"),
])
def test_validator_names_a_fault_of_a_family_or_a_share(tmp_path, change,
                                                        word):
    root, man = _checkout(tmp_path)
    _add_toy_family(root, man, **change)
    bad = manifest.validate(man, str(root))
    assert any(word in e for e in bad) and all("'toy'" in e for e in bad), bad
