"""Three families, every block (ROADMAP D9): what ``from_hf_config`` refuses
of a block's file, what the engine's preflight refuses of a block's pod by
name, and that a block refuses a mesh and disaggregation.  A block joins by
a table under its preset's name; no case here builds an engine that serves."""

import jax
import pytest

from arks_tpu.models import transformer as tf
from arks_tpu.models.config import ModelConfig, get_config

import harness

# ---------------------------------------------------------------------------
# The configuration reader: (a key of the preset's file changed, a word of
# the refusal).  The file is the preset's with its sixteen experts back.
# ---------------------------------------------------------------------------

_FILE = {
    "tiny-linear-moe": dict(n_routed_experts=16),
    "tiny-latent-linear-moe": dict(n_routed_experts=16),
    "tiny-swa-moe": dict(num_experts=16),
    "tiny-mla-moe": dict(n_routed_experts=16),
    "tiny-swa-sink-moe": {},
    "tiny-shortcut-mla-moe": dict(n_routed_experts=16),
    "tiny-ssm-moe": dict(n_routed_experts=16),
}
_UNREADABLE = {
    "tiny-ssm-moe": [
        (dict(hybrid_override_pattern="MEM*EMEM*EM*EM-"), "a '-' layer"),
        (dict(hybrid_override_pattern="MEM*EMEM*EM*EMA"), "one of M, E,"),
        (dict(hybrid_override_pattern="MEM*EMEM*EM*EM"), "15 layers"),
        (dict(hybrid_override_pattern="MEMEEMEMEEMEEME"), "at least one M"),
        (dict(hybrid_override_pattern="*E**E*E**E**E*E"), "at least one M"),
        (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
        (dict(mamba_hidden_act="gelu"), "mamba_hidden_act"),
        (dict(use_conv_bias=False), "use_conv_bias"),
        (dict(mamba_proj_bias=True), "mamba_proj_bias"),
        (dict(attention_bias=True), "attention_bias"),
        (dict(mlp_bias=True), "mlp_bias"),
        (dict(n_group=4, topk_group=2), "group-limited"),
        (dict(n_shared_experts=2), "n_shared_experts"),
        (dict(n_groups=3), "mamba_num_heads=8 over n_groups=3"),
        (dict(num_key_value_heads=3), "KV heads"),
        (dict(sliding_window=64), "sliding_window"),
        (dict(ssm_state_size=None), "without ssm_state_size"),
        (dict(conv_kernel=0), "without conv_kernel"),
        # The other readers refuse what only this one reads.
        (dict(model_type="solar_open2"), "only model_type 'nemotron_h'"),
        (dict(model_type="llama", hybrid_override_pattern=None,
              mamba_num_heads=None, mamba_head_dim=None,
              mamba_hidden_act=None), "ssm_state_size"),
    ],
    "tiny-shortcut-mla-moe": [
        (dict(zero_expert_type="copy"), "zero_expert_type"),
        (dict(router_bias=True), "router_bias"),
        (dict(num_nextn_predict_layers=1), "multi-token"),
        (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
        (dict(attention_bias=True), "attention_bias"),
        (dict(norm_topk_prob=True), "norm_topk_prob"),
        (dict(hidden_act="gelu"), "hidden_act"),
        (dict(attention_method="MHA"), "attention_method"),
        (dict(n_shared_experts=1), "n_shared_experts"),
        (dict(n_group=8, topk_group=4), "group-limited"),
        (dict(moe_topk=None), "without moe_topk"),
        (dict(expert_ffn_hidden_size=0), "without expert_ffn_hidden_size"),
        (dict(kv_lora_rank=0), "without kv_lora_rank"),
        # The other readers refuse what only this one reads.
        (dict(model_type="kimi_k2"), "only model_type 'longcat_flash'"),
        (dict(model_type="deepseek_v3", zero_expert_num=0,
              mla_scale_q_lora=False), "mla_scale_kv_lora"),
    ],
    "tiny-linear-moe": [
        (dict(gqa_layers=[0, 3, 7]), "gqa_layers"),
        (dict(gqa_interval=0), "gqa_layers"),
        (dict(kda_use_full_proj=True), "kda_use_full_proj"),
        (dict(use_rope=True), "use_rope"),
        (dict(first_k_dense_replace=1), "first_k_dense_replace"),
        (dict(scoring_func="softmax"), "scoring_func"),
        (dict(n_group=4, topk_group=2), "group-limited"),
        (dict(linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                      num_heads=4, num_kv_heads=2)),
         "num_kv_heads"),
        (dict(linear_attn_config=dict(head_dim=16, num_heads=4)),
         "short_conv_kernel_size"),
        (dict(partial_rotary_factor=0.5), "partial_rotary_factor"),
    ],
    "tiny-latent-linear-moe": [
        (dict(full_attention_layers=[3, 5]), "full_attention_layers"),
        (dict(full_attention_layers=[1, 4, 7]), "full_attention_layers"),
        (dict(full_attention_layers=[]), "full_attention_layers"),
        (dict(linear_attention_type="KimiDeltaAttention"),
         "linear_attention_type"),
        (dict(linear_gating_type="swish"), "linear_gating_type"),
        (dict(linear_value_head_dim=32), "linear_value_head_dim"),
        (dict(linear_num_key_heads=3), "linear_num_value_heads"),
        (dict(linear_conv_kernel_dim=None), "linear_conv_kernel_dim"),
        (dict(norm_type="LayerNorm"), "norm_type"),
        (dict(layernorm_type="post"), "layernorm_type"),
        (dict(layernorm_gating_weight=0), "layernorm_gating_weight"),
        (dict(hidden_act="gelu"), "hidden_act"),
        (dict(scoring_func="softmax"), "scoring_func"),
        (dict(topk_method="greedy"), "topk_method"),
        (dict(n_group=4, topk_group=2), "group-limited"),
        (dict(attention_bias=True), "attention_bias"),
        (dict(use_shared_expert_sigmoid=True), "use_shared_expert_sigmoid"),
        (dict(use_mla_scaling_factor=False), "use_mla_scaling_factor"),
        (dict(num_key_value_heads=2), "num_key_value_heads"),
        (dict(qk_head_dim=32), "qk_head_dim"),
        (dict(kv_lora_rank=0), "kv_lora_rank"),
        (dict(rope_scaling=dict(type="linear", factor=2)), "rope_scaling"),
    ],
    "tiny-swa-moe": [
        (dict(moe_router_logit_softcapping=30.0), "softcapping"),
        (dict(moe_apply_router_weight_on_input=True),
         "moe_apply_router_weight_on_input"),
        (dict(gating_types=["per_head"] * 6 + ["per_layer"]), "gating_types"),
        (dict(gating="elementwise"), "gating="),
        (dict(layer_types=["full_attention"] * 7), "layer_types"),
        (dict(layer_types=["full_attention", "sliding_attention",
                           "full_attention"] + ["sliding_attention"] * 4),
         "layer_types"),
        (dict(num_attention_heads_per_layer=[4, 6, 8, 4, 6, 6, 4]),
         "one head count"),
        (dict(mlp_layer_types=["sparse", "dense"] + ["sparse"] * 5),
         "dense layers must be a prefix"),
        (dict(sliding_window=None), "sliding_window"),
        (dict(attention_bias=True), "attention_bias"),
        (dict(rope_scaling={"rope_type": "linear", "factor": 2.0}),
         "rope_scaling"),
    ],
    "tiny-mla-moe": [
        (dict(scoring_func="softmax"), "scoring_func"),
        (dict(topk_method="group_limited_greedy"), "topk_method"),
        (dict(n_group=8), "group-limited"),
        (dict(topk_group=4), "group-limited"),
        (dict(rope_scaling={"type": "linear", "factor": 4}), "rope_scaling"),
        (dict(num_nextn_predict_layers=1), "multi-token"),
    ],
    "tiny-swa-sink-moe": [
        ({"scoring_func": "softmax"}, "only sigmoid"),
        ({"topk_method": "greedy"}, "only noaux_tc"),
        ({"n_group": 8, "topk_group": 4}, "group-limited"),
        ({"hybrid_block_size": 4}, "hybrid_block_size"),
        ({"attention_chunk_size": 64}, "attention_chunk_size=64"),
        ({"sliding_window_size": 32}, "sliding_window_size=32"),
        ({"swa_head_dim": 32}, "swa_head_dim"),
        ({"swa_v_head_dim": 8}, "swa_v_head_dim"),
        ({"rope_scaling": {"rope_type": "yarn", "factor": 4}},
         "rope_scaling"),
        ({"attention_bias": True}, "attention_bias"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"hybrid_layer_pattern": [0, 1, 0, 1, 1, 1, 0, 1, 0]},
         "a full layer every so many"),
        ({"hybrid_layer_pattern": [1, 1, 0, 1, 1, 0, 1, 1, 0]},
         "inside the dense prefix"),
        ({"moe_layer_freq": [0, 1, 1, 0, 1, 1, 1, 1, 1]}, "must be a prefix"),
        ({"swa_num_key_value_heads": 3}, "KV heads"),
        ({"sliding_window": 0}, "without sliding_window"),
    ],
}


def _rows(tables):
    """A table a preset as flat cases, each under an id that names its
    preset, its row and the row's word."""
    return [pytest.param(preset, *row, id=f"{preset}-{i}-{row[-1]}")
            for preset, rows in tables.items() for i, row in enumerate(rows)]


@pytest.mark.parametrize("preset, change, word", _rows(_UNREADABLE))
def test_from_hf_config_refuses_what_the_block_cannot_express(preset, change,
                                                              word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(
            harness.published(preset, **{**_FILE[preset], **change}),
            name="m")


# ---------------------------------------------------------------------------
# The engine's arguments: (EngineConfig fields, environment, a word of the
# refusal, whether the refused feature moves K and V blocks between places).
# Nine arguments pack every layer's pages into one pool of K and V and are
# refused of every block; a latent pool refuses narrower rows too, and only
# a block with window layers reserves pages.
# ---------------------------------------------------------------------------

_ONE_POOL = [
    (dict(kv_layout="slot"), {}, "slot layout", False),
    (dict(prefill_chunk=None), {}, "chunked prefill", False),
    (dict(draft_model="tiny-gqa"), {}, "speculative", True),
    ({}, {"ARKS_PREFIX_HOST_MB": "64"}, "host spill tier", True),
    ({}, {"ARKS_PREFIX_DISK_MB": "64"}, "disk spill tier", True),
    ({}, {"ARKS_RESIDENCY_WINDOW_PAGES": "6"}, "windowed residency", True),
    ({}, {"ARKS_PREEMPT": "1"}, "KV swap", True),
    ({}, {"ARKS_PEER_ADDRS": "10.0.0.1:8080"}, "peer fetch", True),
    ({}, {"ARKS_MIXED_STEP": "0"}, "legacy scheduler", False),
]


def _narrow_rows(word):
    return [(dict(kv_cache_dtype=d), {}, word, False)
            for d in ("int8", "int4")]


_UNSERVABLE = {
    "tiny-linear-moe": _ONE_POOL + [
        (dict(kv_pool_pages=16), {}, "kv_pool_pages", False)],
    "tiny-swa-moe": _ONE_POOL,
    "tiny-ssm-moe": _ONE_POOL + [
        (dict(kv_pool_pages=16), {}, "kv_pool_pages", False)],
    "tiny-latent-linear-moe": _narrow_rows("int8 / int4 latent row")
    + _ONE_POOL,
    "tiny-mla-moe": _narrow_rows("bf16 only") + _ONE_POOL,
    "tiny-shortcut-mla-moe": _narrow_rows("bf16 only") + _ONE_POOL,
}
# ONE preflight a block (``engine.py::_BLOCKS``): its sentence says what the
# model is once, in these words, and names each refused argument; and what it
# says of a feature that moves blocks, where the block says more than the word.
_IS = {
    "tiny-linear-moe": ("linear-attention layers with a fixed state a slot "
                        "beside GQA layers over pages", ""),
    "tiny-swa-moe": ("window and full attention layers over two page pools",
                     ""),
    "tiny-ssm-moe": ("state-space layers with a fixed state a slot beside "
                     "GQA layers over pages", ""),
    "tiny-latent-linear-moe": (
        "linear-attention layers with a fixed state a slot beside "
        "latent-attention layers over one latent row a token",
        "neither a latent row nor a recurrent state"),
    "tiny-mla-moe": ("latent attention, one latent row a token", ""),
    "tiny-shortcut-mla-moe": ("latent attention, one latent row a token", ""),
}
# The one refusal of the tables that is no preflight's.
_OWN_SENTENCE = {"kv_pool_pages": "kv_pool_pages=16: an admission that "
                                  "reserves pages exists for"}


@pytest.mark.parametrize("preset, over, env, word, moves",
                         _rows(_UNSERVABLE))
def test_a_block_refuses_by_name_what_packs_every_layers_pages_in_one_pool(
        preset, over, env, word, moves, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    with pytest.raises(ValueError, match=word) as e:
        harness.engine(preset, **over)
    what, of_moved = _IS[preset]
    assert str(e.value).startswith(_OWN_SENTENCE.get(
        word, f"model {preset!r} ({what}) cannot be served with: "))
    assert (of_moved if moves else "") in str(e.value)


# ---------------------------------------------------------------------------
# A mesh and disaggregation: (a word of the mesh's refusal, a word of
# ``param_pspecs``', further server arguments and the share they make, a word of
# ``build_server``'s refusal, a word of ``prefill_detached``'s where the
# engine itself refuses to hand its pages over)
# ---------------------------------------------------------------------------

_UNSHARDED = {
    "tiny-linear-moe": ("their state have no sharding rules", "their state",
                        [], "0/1", "recurrent state", None),
    "tiny-swa-moe": ("layers of two head counts", "two head counts",
                     [], "0/1", "two pools", None),
    "tiny-ssm-moe": ("state-space layers and their state have no sharding",
                     "a share of the heads is not built",
                     ["--expert-parallel-size", "2",
                      "--expert-parallel-rank", "1"], "1/2",
                     "state of such a model's state-space layers", None),
    "tiny-latent-linear-moe": (
        "neither the latent block nor the linear layers' state",
        "sharding rules", [], "0/1", "nor the recurrent state",
        "kv_transfer"),
    "tiny-mla-moe": ("the latent block has no sharding rules",
                     "the latent block has no sharding rules",
                     ["--expert-parallel-size", "2",
                      "--expert-parallel-rank", "1"], "1/2", "kv_transfer",
                     "kv_transfer"),
    "tiny-shortcut-mla-moe": ("the latent block has no sharding rules",
                              "the latent block has no sharding rules",
                              ["--expert-parallel-size", "2",
                               "--expert-parallel-rank", "0"], "0/2",
                              "kv_transfer", "kv_transfer"),
}


@pytest.mark.parametrize("preset", sorted(_UNSHARDED))
def test_a_block_refuses_a_mesh_and_disaggregation(preset):
    from arks_tpu.engine.engine import EngineConfig, InferenceEngine
    from arks_tpu.engine.tokenizer import ByteTokenizer
    from arks_tpu.parallel.mesh import make_mesh
    from arks_tpu.server.__main__ import build_engine, build_server, parse_args
    meshed, pspecs, more, share, word, detached = _UNSHARDED[preset]
    cfg = get_config(preset)
    mesh = make_mesh(tensor_parallel=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"device mesh.*{meshed}"):
        InferenceEngine(cfg, EngineConfig(
            model=cfg.name, num_slots=2, max_cache_len=64,
            prefill_buckets=(16,), prefill_chunk=16, tensor_parallel=2),
            ByteTokenizer(), mesh=mesh)
    with pytest.raises(NotImplementedError, match=pspecs):
        tf.param_pspecs(cfg, 2)
    ns = parse_args(["--model", preset, "--platform", "cpu",
                     "--num-slots", "2", "--max-model-len", "64",
                     "--tensor-parallel-size", "1",
                     "--disaggregation-mode", "prefill", *more])
    eng = build_engine(ns)
    try:
        assert eng.resolved_config["expert_share"] == share
        with pytest.raises(ValueError, match=word):
            build_server(ns, eng)
        if detached:
            with pytest.raises(ValueError, match=detached):
                eng.prefill_detached([2, 3, 4], None)
    finally:
        eng.stop()


def test_a_share_names_a_rank_out_of_range_and_a_model_without_experts():
    with pytest.raises(ValueError, match="rank"):
        get_config("tiny-mla-moe").with_expert_share(2, 2)
    with pytest.raises(ValueError, match="no routed experts"):
        get_config("tiny").with_expert_share(2, 0)


def test_a_checkpoint_of_the_one_sublayer_block_is_refused_by_name(tmp_path):
    from arks_tpu.models import weights
    (tmp_path / "model.safetensors").write_bytes(b"")
    with pytest.raises(weights.SsmCheckpointError,
                       match="ssm_layers.*seeded random weights only"):
        weights.load_params(get_config("tiny-ssm-moe"), str(tmp_path))


def test_a_checkpoint_of_the_shortcut_block_is_refused_by_name(tmp_path):
    from arks_tpu.models import weights
    (tmp_path / "model.safetensors").write_bytes(b"")
    with pytest.raises(weights.ShortcutCheckpointError,
                       match="stacked by sublayer"):
        weights.load_params(get_config("tiny-shortcut-mla-moe"),
                            str(tmp_path))
