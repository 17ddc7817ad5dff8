"""What PR 56 added to the instrument for the one-sublayer block, from the
added files alone: the three per-layer readers (``ssm_share.tput``,
``ssm_state_roofline.tput``, ``ssm_scan_row_share.tput``) on made-up inputs
and on what the program's own registry renders, the selective scan's work
from shapes, and the manifest's entries of the configuration
``nemotron-3-nano-30b-ep8``, its family and its cell."""

import json
import os

import pytest

from benchmarks import manifest
from benchmarks import pod as podlib
from benchmarks.kernels import ssm_state_update as work

CONFIG = "nemotron-3-nano-30b-ep8"
CELL = CONFIG + ".reason.closed"
NEW = ("ssm_share.tput", "ssm_state_roofline.tput", "ssm_scan_row_share.tput")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_state_space_layers(
        name):
    """The driver lays this PR's benchmark files over the parent's checkout:
    there the readers return None and do not raise."""
    read = manifest.load_reader(name)
    ctx = {"device": {"ops": [], "busy_s": 1.0, "xplane": None,
                      "slice_monotonic": (0.0, 1.0)},
           "metrics_open": {}, "metrics_close": {}, "cell": {}, "run": {},
           "engine": None, "kind": "TPU v5 lite"}
    assert read(ctx) is None
    assert read({**ctx, "device": None}) is None
    # A trace whose ops carry other scopes only (solar's program), and a
    # scrape with the delta rule's counters only.
    other = {**ctx["device"], "xplane": "x", "ops": [1],
             "scope_seconds": {"arks.ffn": 0.5, "arks.linear_state": 0.2,
                               "arks.linear_qkv": 0.1, None: 0.1}}
    lanes = {"linear_state_lane_steps_total": [({"path": "step"}, 9.0)]}
    assert read({**ctx, "device": other, "metrics_open": lanes,
                 "metrics_close": lanes}) is None


def test_the_share_sums_its_three_scopes_over_the_busy_time():
    dev = {"ops": [1], "busy_s": 2.0, "xplane": "x",
           "scope_seconds": {"arks.ssm_in": 0.2, "arks.ssm_state": 0.5,
                             "arks.ssm_out": 0.1, "arks.moe_dot": 0.8,
                             "arks.attn_kernel": 0.1}}
    assert manifest.load_reader("ssm_share.tput")({"device": dev}) \
        == pytest.approx(40.0)


def _scrape(step, scan):
    return podlib.parse_metrics("".join(
        f'ssm_rows_total{{path="{p}"}} {v!r}\n'
        for p, v in (("step", step), ("scan", scan)) if v is not None))


def test_the_scan_row_share_is_a_ratio_of_two_deltas():
    read = manifest.load_reader("ssm_scan_row_share.tput")
    ctx = {"metrics_open": _scrape(100.0, 50.0),
           "metrics_close": _scrape(1000.0, 150.0)}
    assert read(ctx) == pytest.approx(10.0)
    same = _scrape(4.0, 1.0)
    assert read({"metrics_open": same, "metrics_close": same}) is None


def test_the_counter_the_reader_names_is_the_one_the_registry_renders():
    from arks_tpu.engine.engine import EngineMetrics
    m = EngineMetrics()
    m.ssm_rows_total.inc(30, path="step")
    m.ssm_rows_total.inc(10, path="scan")
    closed = podlib.parse_metrics(m.registry.render())
    assert manifest.load_reader("ssm_scan_row_share.tput")(
        {"metrics_open": {}, "metrics_close": closed}) == pytest.approx(25.0)


def test_the_selective_scans_work_is_counted_from_shapes():
    """A decode token of Nemotron-3-Nano: 23 layers x (a 2.1 MB state read
    and written, the rows that drive it) and five operations a state
    element; one lane is bound by memory, and 64 of them by 6.2 GB a step:
    7.6 ms at the chip's peak."""
    shapes = dict(heads=64, head_dim=64, state=128, groups=8, layers=23,
                  state_bytes=4)
    one = work.work(**shapes, calls=[(1, 900)])
    state = 64 * 64 * 128
    assert one["flops"] == 23 * 5.0 * state
    assert one["bytes"] == 23 * (2.0 * state * 4
                                 + 4 * (2 * 4096 + 2 * 1024 + 64))
    least, bound = work.least_seconds(one, PEAK)
    assert bound == "memory" and 115e-6 < least < 120e-6
    # A prompt chunk reads and writes the state once, whatever its rows.
    chunk = work.work(**shapes, calls=[(256, 256)])
    assert chunk["bytes"] - one["bytes"] == 23 * 255 * 4 * (
        2 * 4096 + 2 * 1024 + 64)
    assert chunk["flops"] == 256 * one["flops"]


def test_the_roofline_reads_its_scope_and_the_familys_shapes():
    import sys
    sys.path.insert(0, os.path.join(manifest.ROOT, "tests"))
    import harness
    ref, config = harness.reference("tiny-ssm-moe", "ssm_moe")
    dev = {"ops": [1], "busy_s": 2.0, "xplane": "x",
           "slice_monotonic": (0.0, 1.0),
           "scope_seconds": {"arks.ssm_state": 1e-3, "arks.ssm_in": 1.0}}
    run = {"records": [{"frames": [(0.5, 1)], "prompt_tokens": 9,
                        "first": 0.1, "sent": 0.2}]}
    got = manifest.load_reader("ssm_state_roofline.tput")(
        {"device": dev, "cell": {"reference": ref, "config": config,
                                 "deploy": {}},
         "run": run, "kind": "TPU v5 lite"})
    state = 8 * 8 * 16
    assert dev["ssm_state_roofline_detail"]["bytes"] == 6 * (
        2.0 * state * 4 + 4 * (2 * 64 + 2 * 32 + 8))
    assert 0 < got < 1
    # A family without the shapes (solar's) leaves nothing to read.
    other, oconfig = harness.reference("tiny-linear-moe", "linear_moe")
    assert manifest.load_reader("ssm_state_roofline.tput")(
        {"device": dev, "cell": {"reference": other, "config": oconfig,
                                 "deploy": {}},
         "run": run, "kind": "TPU v5 lite"}) is None


def test_the_new_configuration_family_cell_and_metrics_load_and_validate():
    m = manifest.load()
    assert manifest.validate(m) == []
    cell = manifest.cell(m, CELL)
    assert (cell["chips"], cell["config_name"], cell["traffic_name"]) \
        == (1, CONFIG, "reason.closed")
    assert cell["load"] == cell["knee"]["knee"] \
        == cell["deploy"]["server_args"]["num-slots"]
    assert [e["name"] for e in cell["end_to_end"]] \
        == ["output_tok_s", "setup_s"]
    names = [x["name"] for x in cell["per_layer"]]
    assert set(NEW) <= set(names)
    assert {"moe_share.tput", "attn_roofline.tput",
            "kv_state_resident_share"} <= set(names)
    # The delta rule's metrics are not this cell's, nor the step clock's
    # percentile that the ledger's notes are about.
    assert not {"pipe_step_ms_p50", "linear_attn_share.tput",
                "linear_state_roofline.tput", "gqa_attn_share.tput"} \
        & set(names)
    for n in NEW:
        entry = next(x for x in m["per_layer"] if x["name"] == n)
        assert entry["workloads"] == [CELL] and entry["moves"] \
            == "output_tok_s"
        js, _ = manifest.metric_paths(n)
        with open(js) as f:
            assert json.load(f)["name"] == n
        assert callable(manifest.load_reader(n))
    # The configuration: the catalog row's file with TWO keys cut, every
    # width, the whole pattern and the router's 128 columns as published.
    config, deploy = cell["config"], cell["deploy"]
    assert cell["config_entry"]["reduced"] == deploy["reduced"] \
        == ["n_routed_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (52, 16, 16384)
    published = dict(
        hidden_size=2688, intermediate_size=1856, moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, mamba_num_heads=64,
        mamba_head_dim=64, ssm_state_size=128, n_groups=8, conv_kernel=4,
        num_experts_per_tok=6, routed_scaling_factor=2.5, n_shared_experts=1,
        mlp_hidden_act="relu2", layer_norm_epsilon=1e-05, chunk_size=128,
        max_position_embeddings=262144, use_conv_bias=True,
        hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM"
                                "*EMEMEMEME")
    assert {k: config[k] for k in published} == published
    assert deploy["share"] == {
        "chips_per_layer": 8, "index": 0,
        "published": {"n_routed_experts": 128, "vocab_size": 131072}}
    assert deploy["state_dtype"] == "float32" \
        == deploy["expect_labels"]["state_dtype"]
    assert deploy["expect_labels"]["kv_page"] == "kv+state"
    ref = cell["reference"]
    a = ref.arch(config)
    assert (a["held"], a["experts"], a["top_k"], len(a["pattern"])) \
        == (16, 128, 6, 52)
    assert ref.kernel_shapes(a) == {"heads": 32, "kv_heads": 2,
                                    "head_dim": 128, "layers": 6}
    assert ref.ssm_kernel_shapes(a) == {
        "heads": 64, "head_dim": 64, "state": 128, "groups": 8, "layers": 23,
        "state_bytes": 4}
    assert os.path.isfile(manifest.knee_path(CONFIG, "reason.closed"))


def test_the_catalog_rows_numbers_are_the_files():
    """Every key of the catalog row's ``config`` stands in the file under
    the same key with the same value, but the two that ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog in this installation")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    with open(os.path.join(manifest.config_dir(CONFIG), "config.json")) as f:
        config = json.load(f)
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == ["n_routed_experts", "vocab_size"]
    entry = next(c for c in manifest.load()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]


def test_the_program_reads_the_configurations_file_as_its_family_does():
    from arks_tpu.models.config import ModelConfig
    cfg = ModelConfig.from_hf_config(
        manifest.config_dir(CONFIG), name="nemotron").with_expert_share(8, 0)
    assert (cfg.num_layers, cfg.num_linear_layers, cfg.num_full_layers,
            cfg.num_routed_layers) == (52, 23, 6, 23)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.expert_act) == (16, 128, 6, "relu2")
    assert cfg.ssm and cfg.recurrent and not cfg.linear and not cfg.use_rope
