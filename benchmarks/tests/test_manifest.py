import copy

import pytest

from benchmarks import manifest


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


def test_a_new_cell_loads_from_added_files_alone(tmp_path):
    """The dry run the issue asks for: a configuration, a mix, a cell and a
    per-layer metric added as files and entries, no existing file edited."""
    import json
    import os
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(manifest.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = manifest.load()
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
