"""Weight loading: HF safetensors -> params (dense + MoE) and Orbax
sharded checkpoint roundtrips.

Covers the model-cache path the reference only half-owns (it downloads raw
HF snapshots — scripts/download.py — and leaves parsing to the runtimes);
here conversion and sharded loading are native.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from safetensors.numpy import save_file

import harness
from arks_tpu.models import get_config
from arks_tpu.models import transformer as tf
from arks_tpu.models import weights as w
from arks_tpu.parallel.mesh import make_mesh


def _rng_tensors(cfg):
    """Synthesize an HF-style checkpoint for a tiny config."""
    rng = np.random.RandomState(0)
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    t = {
        "model.embed_tokens.weight": rng.randn(v, e).astype(np.float32),
        "model.norm.weight": np.ones((e,), np.float32),
    }
    if not cfg.tie_word_embeddings:
        t["lm_head.weight"] = rng.randn(v, e).astype(np.float32)
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}"
        t[f"{p}.input_layernorm.weight"] = np.ones((e,), np.float32)
        t[f"{p}.post_attention_layernorm.weight"] = np.ones((e,), np.float32)
        t[f"{p}.self_attn.q_proj.weight"] = rng.randn(cfg.q_dim, e).astype(np.float32)
        t[f"{p}.self_attn.k_proj.weight"] = rng.randn(cfg.kv_dim, e).astype(np.float32)
        t[f"{p}.self_attn.v_proj.weight"] = rng.randn(cfg.kv_dim, e).astype(np.float32)
        t[f"{p}.self_attn.o_proj.weight"] = rng.randn(e, cfg.q_dim).astype(np.float32)
        if cfg.qkv_bias:
            t[f"{p}.self_attn.q_proj.bias"] = rng.randn(cfg.q_dim).astype(np.float32)
            t[f"{p}.self_attn.k_proj.bias"] = rng.randn(cfg.kv_dim).astype(np.float32)
            t[f"{p}.self_attn.v_proj.bias"] = rng.randn(cfg.kv_dim).astype(np.float32)
        if cfg.num_experts:
            fm = cfg.moe_intermediate_size
            if cfg.shared_expert_intermediate_size:  # qwen2-moe naming
                t[f"{p}.mlp.gate.weight"] = rng.randn(cfg.num_experts, e).astype(np.float32)
                for x in range(cfg.num_experts):
                    t[f"{p}.mlp.experts.{x}.gate_proj.weight"] = rng.randn(fm, e).astype(np.float32)
                    t[f"{p}.mlp.experts.{x}.up_proj.weight"] = rng.randn(fm, e).astype(np.float32)
                    t[f"{p}.mlp.experts.{x}.down_proj.weight"] = rng.randn(e, fm).astype(np.float32)
                fs = cfg.shared_expert_intermediate_size
                t[f"{p}.mlp.shared_expert.gate_proj.weight"] = rng.randn(fs, e).astype(np.float32)
                t[f"{p}.mlp.shared_expert.up_proj.weight"] = rng.randn(fs, e).astype(np.float32)
                t[f"{p}.mlp.shared_expert.down_proj.weight"] = rng.randn(e, fs).astype(np.float32)
                t[f"{p}.mlp.shared_expert_gate.weight"] = rng.randn(1, e).astype(np.float32)
            else:  # mixtral naming
                t[f"{p}.block_sparse_moe.gate.weight"] = rng.randn(cfg.num_experts, e).astype(np.float32)
                for x in range(cfg.num_experts):
                    t[f"{p}.block_sparse_moe.experts.{x}.w1.weight"] = rng.randn(fm, e).astype(np.float32)
                    t[f"{p}.block_sparse_moe.experts.{x}.w3.weight"] = rng.randn(fm, e).astype(np.float32)
                    t[f"{p}.block_sparse_moe.experts.{x}.w2.weight"] = rng.randn(e, fm).astype(np.float32)
        else:
            t[f"{p}.mlp.gate_proj.weight"] = rng.randn(f, e).astype(np.float32)
            t[f"{p}.mlp.up_proj.weight"] = rng.randn(f, e).astype(np.float32)
            t[f"{p}.mlp.down_proj.weight"] = rng.randn(e, f).astype(np.float32)
    return t


@pytest.mark.parametrize("name", ["tiny", "tiny-moe", "tiny-mixtral"])
def test_params_from_hf_shapes_and_forward(tmp_path, name):
    cfg = get_config(name)
    save_file(_rng_tensors(cfg), str(tmp_path / "model.safetensors"))
    params = w.params_from_hf(cfg, str(tmp_path), jnp.float32)

    # Pytree structure must match init_params exactly.
    ref = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.structure(params) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        assert a.shape == b.shape, (a.shape, b.shape)

    # And the model must run with the loaded weights.
    logits, _, _ = tf.prefill(params, cfg, jnp.zeros((1, 4), jnp.int32),
                              jnp.asarray([4], jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("name", ["tiny", "tiny-mixtral"])
def test_params_from_hf_keeps_q_k_v_as_published_heads_split(tmp_path, name):
    """A published ``[out, in]`` projection IS the stored order but for the
    head split: no transpose on the way in, the published matrix back by a
    reshape, and the step's dot is ``x W^T`` (``wo`` and the FFN leaves are
    ``[in, out]``, transposed as they always were)."""
    cfg = get_config(name)
    t = _rng_tensors(cfg)
    save_file(t, str(tmp_path / "model.safetensors"))
    layers = w.params_from_hf(cfg, str(tmp_path), jnp.float32)["layers"]
    x = np.linspace(-1, 1, cfg.hidden_size, dtype=np.float32)
    for leaf, hf, heads in (("wq", "q_proj", cfg.num_heads),
                            ("wk", "k_proj", cfg.num_kv_heads),
                            ("wv", "v_proj", cfg.num_kv_heads)):
        got = np.asarray(layers[leaf])
        assert got.shape == (cfg.num_layers, heads, cfg.head_dim,
                             cfg.hidden_size)
        for i in range(cfg.num_layers):
            pub = t[f"model.layers.{i}.self_attn.{hf}.weight"]
            assert np.array_equal(got[i].reshape(pub.shape), pub)
            assert np.allclose(np.einsum("e,hde->hd", x, got[i]).ravel(),
                               x @ pub.T, atol=1e-4)
    assert np.array_equal(np.asarray(layers["wo"][0]),
                          t["model.layers.0.self_attn.o_proj.weight"].T)


def test_an_orbax_checkpoint_in_another_stored_order_is_refused_by_leaf(
        tmp_path):
    """Orbax restores a leaf in the shape it was saved in, whatever the
    template: a tree saved with ``[L, E, H x D]`` projections is named and
    refused instead of failing in the first step's einsum."""
    cfg = get_config("tiny-gqa")
    params = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    old = dict(params, layers=dict(params["layers"], wq=params["layers"][
        "wq"].reshape(cfg.num_layers, cfg.q_dim, -1).swapaxes(-1, -2)))
    w.save_orbax(old, str(tmp_path))
    with pytest.raises(ValueError, match=r"wq.*convert the checkpoint"):
        w.load_orbax(cfg, str(tmp_path), None, jnp.float32)


@pytest.mark.parametrize("leaf", ["wq_b", "wkv_b"])
@pytest.mark.parametrize("name", ["tiny-mla-moe", "tiny-shortcut-mla-moe"])
def test_an_orbax_checkpoint_of_drawn_order_latent_leaves_is_refused_by_leaf(
        tmp_path, name, leaf):
    """The latent block's up projections were ``[L, K, H x D]`` until they
    became ``[L, H, D, K]`` (PR 57; ``[L, 2, ..]`` by sublayer in the
    shortcut block): a tree saved the old way is refused by that leaf's
    name, a tree saved as stored comes back."""
    cfg = get_config(name)
    params = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    new = params["layers"][leaf]
    old = dict(params, layers=dict(params["layers"],
                                   **{leaf: harness.as_drawn(new)}))
    w.save_orbax(old, str(tmp_path / "old"))
    with pytest.raises(ValueError,
                       match=rf"'{leaf}'.*convert the checkpoint"):
        w.load_orbax(cfg, str(tmp_path / "old"), None, jnp.float32)
    w.save_orbax(params, str(tmp_path / "new"))
    back = w.load_orbax(cfg, str(tmp_path / "new"), None, jnp.float32)
    assert np.array_equal(np.asarray(back["layers"][leaf]), np.asarray(new))


def test_orbax_roundtrip_sharded(tmp_path):
    cfg = get_config("tiny-gqa")
    params = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    w.save_orbax(params, str(tmp_path))
    mesh = make_mesh(tensor_parallel=4, data_parallel=2)
    restored = w.load_orbax(cfg, str(tmp_path), mesh, jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Restored leaves carry the mesh sharding (each host reads its shards).
    wq = restored["layers"]["wq"]
    assert wq.sharding.mesh.shape["model"] == 4


def test_load_params_fallback_chain(tmp_path):
    cfg = get_config("tiny")
    # Nothing on disk -> random init, no crash.
    p = w.load_params(cfg, str(tmp_path / "missing"))
    assert p["embed"].shape[0] == cfg.vocab_size
    assert not w.has_real_weights(str(tmp_path / "missing"))


def test_load_params_int8_from_safetensors(tmp_path):
    """--weight-dtype int8 quantizes during load (leaf-by-leaf, so a 7B
    checkpoint never materializes full-width on a 16GB chip) and matches
    the full-width model within quantization error."""
    from arks_tpu.models import quant
    cfg = get_config("tiny")
    save_file(_rng_tensors(cfg), str(tmp_path / "model.safetensors"))
    full = w.load_params(cfg, str(tmp_path), dtype=jnp.float32)
    q = w.load_params(cfg, str(tmp_path), dtype=jnp.float32,
                      weight_dtype="int8")
    assert quant.is_quantized(q["layers"]["wq"])
    assert quant.is_quantized(q["embed"])
    toks = jnp.zeros((1, 4), jnp.int32).at[0, 1].set(7)
    lens = jnp.asarray([4], jnp.int32)
    ref, _, _ = tf.prefill(full, cfg, toks, lens)
    got, _, _ = tf.prefill(q, cfg, toks, lens)
    assert np.argmax(np.asarray(got)) == np.argmax(np.asarray(ref))


def test_load_orbax_int8_single_chip(tmp_path):
    """Orbax + int8 with no mesh restores via host memory, then quantizes
    leaf-by-leaf onto the device."""
    from arks_tpu.models import quant
    cfg = get_config("tiny")
    params = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    w.save_orbax(params, str(tmp_path))
    q = w.load_params(cfg, str(tmp_path), dtype=jnp.float32,
                      weight_dtype="int8")
    assert quant.is_quantized(q["layers"]["wq"])
    deq = quant.dequantize(q["layers"]["wq"], jnp.float32)
    err = np.abs(np.asarray(deq) - np.asarray(params["layers"]["wq"])).max()
    assert err < np.abs(np.asarray(params["layers"]["wq"])).max() / 100


def test_weights_kind_single_directory_read(tmp_path, monkeypatch):
    """Classification costs exactly ONE opendir.  has_real_weights and
    load_params used to stat the Orbax subdir AND list the directory —
    on a network filesystem that doubled the metadata reads on every
    model switch."""
    (tmp_path / w.ORBAX_SUBDIR).mkdir()
    (tmp_path / "model.safetensors").write_bytes(b"")
    calls = []
    real = w.os.scandir
    monkeypatch.setattr(w.os, "scandir",
                        lambda p: (calls.append(p), real(p))[1])

    assert w.weights_kind(str(tmp_path)) == "orbax"
    assert len(calls) == 1
    calls.clear()
    assert w.has_real_weights(str(tmp_path)) is True
    assert len(calls) == 1
    calls.clear()
    assert w.weights_kind(str(tmp_path / "missing")) is None
    assert len(calls) == 1


def test_weights_kind_prefers_orbax_over_safetensors(tmp_path):
    assert w.weights_kind(None) is None
    assert w.weights_kind(str(tmp_path)) is None  # empty dir
    (tmp_path / "model.safetensors").write_bytes(b"")
    assert w.weights_kind(str(tmp_path)) == "safetensors"
    (tmp_path / w.ORBAX_SUBDIR).mkdir()
    assert w.weights_kind(str(tmp_path)) == "orbax"


def test_load_params_classifies_once(tmp_path, monkeypatch):
    """load_params branches on one weights_kind call instead of probing
    the directory per format."""
    cfg = get_config("tiny")
    save_file(_rng_tensors(cfg), str(tmp_path / "model.safetensors"))
    n = {"calls": 0}
    real = w.weights_kind

    def counting(p):
        n["calls"] += 1
        return real(p)

    monkeypatch.setattr(w, "weights_kind", counting)
    p = w.load_params(cfg, str(tmp_path), dtype=jnp.float32)
    assert n["calls"] == 1
    assert p["embed"].shape[0] == cfg.vocab_size


@pytest.mark.parametrize("kind", ["safetensors", "orbax"])
def test_load_params_streaming_matches_blocking(tmp_path, kind):
    """The async per-leaf streaming loader (live model switches) must
    produce the exact tree the blocking loader does."""
    cfg = get_config("tiny")
    if kind == "safetensors":
        save_file(_rng_tensors(cfg), str(tmp_path / "model.safetensors"))
    else:
        params = tf.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
        w.save_orbax(params, str(tmp_path))
    ref = w.load_params(cfg, str(tmp_path), dtype=jnp.float32)
    got = w.load_params_streaming(cfg, str(tmp_path), dtype=jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
