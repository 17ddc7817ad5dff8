"""What PR 54 added to the instrument for the shortcut block, from the added
files alone: the three per-layer readers (``shortcut_moe_share.tput``,
``dense_ffn_share.tput``, ``moe_zero_pair_share.tput``) on made-up inputs
and on what the program's own registry renders, and the manifest's entries
of the configuration ``longcat-flash-ep32-l6``, its family and its cell."""

import json
import os

import pytest

from benchmarks import manifest
from benchmarks import pod as podlib

CELL = "longcat-flash-ep32-l6.reason.closed"
NEW = ("shortcut_moe_share.tput", "dense_ffn_share.tput",
       "moe_zero_pair_share.tput")


def _device(scopes):
    """A reduced device trace whose scope seconds are already summed
    (``_scopes.by_scope`` keeps them under ``scope_seconds``)."""
    return {"device": {"xplane": "x", "ops": [("op", 0.0, 1.0)],
                       "busy_s": 20.0, "scope_seconds": scopes}}


def test_the_routed_share_sums_its_four_scopes_and_wants_the_identity_part():
    read = manifest.load_reader("shortcut_moe_share.tput")
    got = {"arks.moe_route": 1.0, "arks.moe_dot": 6.0, "arks.moe_zero": 0.5,
           "arks.ffn": 8.0, "arks.moe_shared": 2.0, None: 2.5}
    # route + dot + zero (no dequant op in this trace), not the shared one
    assert read(_device(got)) == pytest.approx(100 * 7.5 / 20.0)
    # a routed block WITHOUT identity experts (kimi's): nothing to read
    assert read(_device({k: v for k, v in got.items()
                         if k != "arks.moe_zero"})) is None
    assert read({"device": None}) is None
    # and the reader that wants a shared expert's scope reads nothing of a
    # block that has none
    assert manifest.load_reader("moe_share.tput")(_device(
        {k: v for k, v in got.items() if k != "arks.moe_shared"})) is None


def test_the_dense_share_is_the_ffn_scope_over_the_busy_time():
    read = manifest.load_reader("dense_ffn_share.tput")
    assert read(_device({"arks.ffn": 8.0, "arks.moe_dot": 6.0})) \
        == pytest.approx(40.0)
    assert read(_device({"arks.moe_dot": 6.0})) is None
    assert read(_device(None)) is None
    assert read({"device": None}) is None


def _scrape(zero, routed):
    lines = []
    if zero is not None:
        lines.append(f"moe_zero_pairs_total {zero!r}")
    if routed is not None:
        lines.append(f"moe_routed_pairs_total {routed!r}")
    return podlib.parse_metrics("\n".join(lines) + "\n")


def test_the_zero_pair_share_is_a_ratio_of_two_deltas():
    read = manifest.load_reader("moe_zero_pair_share.tput")
    ctx = {"metrics_open": _scrape(100.0, 400.0),
           "metrics_close": _scrape(1100.0, 3400.0)}
    assert read(ctx) == pytest.approx(100 * 1000.0 / 3000.0)
    # the parent's program has no such counter; a window that routed nothing
    parent = _scrape(None, 400.0)
    assert read({"metrics_open": parent, "metrics_close": parent}) is None
    assert read({"metrics_open": _scrape(1.0, 4.0),
                 "metrics_close": _scrape(1.0, 4.0)}) is None
    assert read({"metrics_open": {}, "metrics_close": {}}) is None


def test_the_counter_the_reader_names_is_the_one_the_registry_renders():
    from arks_tpu.engine.engine import EngineMetrics
    m = EngineMetrics()
    m.moe_zero_pairs_total.inc(12)
    m.moe_routed_pairs_total.inc(36)
    closed = podlib.parse_metrics(m.registry.render())
    got = manifest.load_reader("moe_zero_pair_share.tput")(
        {"metrics_open": {}, "metrics_close": closed})
    assert got == pytest.approx(100 / 3)


def test_the_new_configuration_family_cell_and_metrics_load_and_validate():
    m = manifest.load()
    assert manifest.validate(m) == []
    cell = manifest.cell(m, CELL)
    assert (cell["chips"], cell["config_name"], cell["traffic_name"]) \
        == (1, "longcat-flash-ep32-l6", "reason.closed")
    assert cell["load"] == cell["knee"]["knee"] == 64
    assert [e["name"] for e in cell["end_to_end"]] \
        == ["output_tok_s", "setup_s"]
    names = [x["name"] for x in cell["per_layer"]]
    assert set(NEW) <= set(names)
    assert {"mla_share.tput", "latent_attn_roofline.tput"} <= set(names)
    assert "pipe_step_ms_p50" not in names and "moe_share.tput" not in names
    for n in NEW:
        entry = next(x for x in m["per_layer"] if x["name"] == n)
        assert entry["workloads"] == [CELL] and entry["moves"] \
            == "output_tok_s"
        js, py = manifest.metric_paths(n)
        with open(js) as f:
            assert json.load(f)["name"] == n
        assert callable(manifest.load_reader(n))
    # The configuration: the catalog row's file with three keys cut, every
    # width, the router's 768 columns and its 12 a token as published.
    config, deploy = cell["config"], cell["deploy"]
    assert cell["config_entry"]["reduced"] == deploy["reduced"] \
        == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 16, 16384)
    published = dict(
        hidden_size=6144, ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
        num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
        qk_rope_head_dim=64, qk_nope_head_dim=128, v_head_dim=128,
        routed_scaling_factor=6, max_position_embeddings=131072,
        rms_norm_eps=1e-05, rope_theta=10000000, zero_expert_num=256,
        moe_topk=12, mla_scale_q_lora=True, mla_scale_kv_lora=True,
        attention_bias=False, attention_method="MLA",
        zero_expert_type="identity")
    assert {k: config[k] for k in published} == published
    assert deploy["share"] == {
        "chips_per_layer": 32, "index": 0,
        "published": {"n_routed_experts": 512, "vocab_size": 131072}}
    ref = cell["reference"]
    a = ref.arch(config)
    assert (a["held"], a["experts"], a["zero"], a["top_k"]) \
        == (16, 512, 256, 12)
    assert ref.kernel_shapes(a)["layers"] == 12       # attention sublayers
    assert os.path.isfile(manifest.knee_path("longcat-flash-ep32-l6",
                                             "reason.closed"))


def test_the_program_reads_the_configurations_file_as_its_family_does():
    from arks_tpu.models.config import ModelConfig
    cfg = ModelConfig.from_hf_config(
        manifest.config_dir("longcat-flash-ep32-l6"),
        name="longcat").with_expert_share(32, 0)
    assert (cfg.num_layers, cfg.num_attn_sublayers, cfg.num_experts,
            cfg.zero_experts, cfg.router_width, cfg.num_experts_per_tok) \
        == (6, 12, 16, 256, 768, 12)
    assert cfg.mla_q_scale == 2.0 and cfg.mla_kv_scale == 12 ** 0.5
    assert (cfg.shortcut, cfg.router_select_bias, cfg.norm_topk_prob,
            cfg.rope_yarn, cfg.first_k_dense) == (True, True, False, (), 0)
