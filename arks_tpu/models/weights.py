"""Weight loading: HF safetensors -> arks params; Orbax sharded checkpoints.

Parity anchor: the reference's ArksModel controller downloads a raw HF
snapshot into a PVC (/root/reference/internal/controller/
arksmodel_controller.go:218-354, scripts/download.py).  The TPU-native twist
(BASELINE.json north star) is a conversion step that writes **Orbax** sharded
checkpoints so every host in a multi-host slice reads only its own shards;
``arks_tpu.control.model`` drives that conversion after download.

Layout conventions: all projection matrices are stored [in, out] (JAX
convention; HF/torch stores [out, in]) and per-layer weights are stacked with
a leading [L] dim for the scan-based forward pass.
"""

from __future__ import annotations

import logging
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from arks_tpu.models.config import ModelConfig
from arks_tpu.models import transformer as tf
from arks_tpu.models.quant import weight_bits as _weight_bits

log = logging.getLogger("arks_tpu.weights")

ORBAX_SUBDIR = "arks_orbax"


# ---------------------------------------------------------------------------
# HF safetensors -> params
# ---------------------------------------------------------------------------

def _hf_tensors(path: str) -> dict[str, np.ndarray]:
    """Load all tensors from the safetensors shards in ``path``."""
    from safetensors import safe_open

    files = sorted(f for f in os.listdir(path) if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    out: dict[str, np.ndarray] = {}
    for fname in files:
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for key in f.keys():
                out[key] = f.get_tensor(key)
    return out


class LatentCheckpointError(NotImplementedError):
    """A latent-attention (DeepSeek-V3 / kimi_k2) checkpoint: the name
    mapping (``q_a_proj`` / ``kv_a_proj_with_mqa`` / ``kv_b_proj``, the
    expert and ``e_score_correction_bias`` tensors) and the permutation of
    the rotary columns from the published interleaved form to this repo's
    rotate-half form are not built; such a model is served from seeded
    random weights only.  Raised instead of mapping its tensors onto the
    GQA block's names."""


class WindowedCheckpointError(NotImplementedError):
    """A checkpoint of a model with window and full attention layers
    (``laguna``): the name mapping of its per-kind projections (a head count
    a kind of layer), its per-head gate and its expert tensors onto the
    three stacked trees (``dense_layers`` / ``layers`` / ``win_layers``)
    is not built; such a model is served from seeded random weights only.
    Raised instead of stacking layers of two shapes into one tree."""


class MimoCheckpointError(NotImplementedError):
    """A ``mimo_v2`` checkpoint (window layers with a sink logit a head and
    KV heads of their own, values narrower than keys): its name mapping
    onto the three stacked trees is written (:func:`mimo_v2_tree`) and held
    to seeded leaves, but no checkpoint of the family has been on this
    machine, and the published files fuse q | k | v into one ``qkv_proj``
    (``attention_projection_layout: fused_qkv``) whose row order nothing
    here has been checked against; such a model is served from seeded
    random weights only.  Raised instead of loading tensors nobody has
    compared."""


class LinearCheckpointError(NotImplementedError):
    """A checkpoint of a model with linear-attention layers
    (``solar_open2``): the name mapping of its delta-rule projections,
    convolutions, low-rank pairs and expert tensors onto the three stacked
    trees (``head_layers`` / ``layers`` / ``lin_layers``) is not built; such
    a model is served from seeded random weights only.  Raised instead of
    stacking layers of two kinds into one tree."""


class LatentLinearCheckpointError(NotImplementedError):
    """A checkpoint of a model with gated-delta-rule linear layers beside
    gated latent-attention layers (``gigachat3_5``): the name mapping of its
    fused q | k | v projection and convolution, its decay, gate and
    sandwich-norm tensors, its latent projections and its expert tensors
    onto the three stacked trees (``dense_layers`` / ``layers`` /
    ``lin_layers``) is not built; such a model is served from seeded random
    weights only.  Raised instead of stacking layers of two kinds into one
    tree."""


class ShortcutCheckpointError(NotImplementedError):
    """A checkpoint of a model with two latent-attention sublayers a layer
    and a routed layer on a shortcut (``longcat_flash``): the name mapping
    of its paired attention, norm and dense-FFN tensors (``self_attn.0`` /
    ``.1``, ``mlps.0`` / ``.1``) onto leaves stacked by sublayer, of its
    router (``classifier``, ``e_score_correction_bias``) and expert tensors,
    and the permutation of the rotary columns to this repo's rotate-half
    form are not built; such a model is served from seeded random weights
    only.  Raised instead of mapping its tensors onto the latent block's
    names."""


class SsmCheckpointError(NotImplementedError):
    """A checkpoint of a model with layers of one sublayer, Mamba-2
    state-space mixers among them (``nemotron_h``): the name mapping of its
    ``backbone.layers.N.mixer`` tensors (a mixer's ``in_proj``, ``conv1d``,
    ``A_log``, ``D``, ``dt_bias``, grouped ``norm`` and ``out_proj``; an
    attention layer's projections; a routed layer's ``gate``,
    ``e_score_correction_bias``, two-matrix experts and shared expert) onto
    the three stacked trees (``ssm_layers`` / ``layers`` / ``moe_layers``)
    is not built; such a model is served from seeded random weights only.
    Raised instead of stacking layers of three kinds into one tree."""


def _refuse_latent(cfg: ModelConfig, path: str) -> None:
    for is_kind, err in ((cfg.ssm, SsmCheckpointError),
                         (cfg.latent and cfg.linear,
                          LatentLinearCheckpointError),
                         (cfg.shortcut, ShortcutCheckpointError),
                         (cfg.latent, LatentCheckpointError),
                         (cfg.windowed and cfg.scoring_func == "sigmoid",
                          MimoCheckpointError),
                         (cfg.windowed, WindowedCheckpointError),
                         (cfg.linear, LinearCheckpointError)):
        if is_kind:
            raise err(
                f"model {cfg.name!r}: cannot load the checkpoint at {path}: "
                + " ".join(err.__doc__.split()))


def mimo_v2_tree(cfg: ModelConfig, t: dict[str, np.ndarray],
                 dtype: Any) -> tf.Params:
    """The ``mimo_v2`` name mapping: published tensor names (``[out, in]``
    projections, a layer at a time) onto ``tf.init_params``'s three stacked
    trees, on the host: ``dense_layers`` (the dense prefix), ``layers`` (the
    routed full layers) and ``win_layers`` (the window layers), each in
    model order.  ``self_attn.{q,k,v,o}_proj`` are a kind's projections (a
    fused ``qkv_proj`` is refused: :class:`MimoCheckpointError`),
    ``self_attn.attention_sink_bias`` the sink logit a head of the kinds
    that have one, ``mlp.gate.weight`` the router at its whole width and
    ``mlp.gate.e_score_correction_bias`` its selection bias,
    ``mlp.experts.{e}.*`` the experts, of which a share keeps its own."""
    if any(".qkv_proj." in k for k in t):
        raise MimoCheckpointError(
            f"model {cfg.name!r}: " + " ".join(
                MimoCheckpointError.__doc__.split()))
    from arks_tpu.models.moe import held_first
    first = held_first(cfg)

    def mat(name: str) -> np.ndarray:
        return np.asarray(t[name].T, dtype)

    def attn(i: int, window: bool) -> dict:
        base = f"model.layers.{i}."
        out = {"attn_norm": np.asarray(t[base + "input_layernorm.weight"],
                                       dtype),
               "mlp_norm": np.asarray(
                   t[base + "post_attention_layernorm.weight"], dtype)}
        # q / k / v stay [out, in] as published, heads split: the stored
        # order (tf.init_params).
        for leaf, name, heads in (
                ("wq", "q_proj", cfg.heads_of(window)),
                ("wk", "k_proj", cfg.kv_heads_of(window)),
                ("wv", "v_proj", cfg.kv_heads_of(window))):
            w = np.asarray(t[f"{base}self_attn.{name}.weight"], dtype)
            out[leaf] = w.reshape(heads, -1, w.shape[-1])
        out["wo"] = mat(base + "self_attn.o_proj.weight")
        if cfg.sink_of(window):
            out["attn_sink"] = np.asarray(
                t[base + "self_attn.attention_sink_bias"], dtype)
        return out

    def ffn(base: str) -> dict:
        return {leaf: mat(f"{base}{name}.weight") for leaf, name in (
            ("w_gate", "gate_proj"), ("w_up", "up_proj"),
            ("w_down", "down_proj"))}

    trees: dict[str, list] = {"dense_layers": [], "layers": [],
                              "win_layers": []}
    for i, kind in enumerate(cfg.layer_kinds()):
        lp = attn(i, kind == "window")
        mlp = f"model.layers.{i}.mlp."
        if i < cfg.first_k_dense:
            trees["dense_layers"].append(dict(lp, **ffn(mlp)))
            continue
        experts = [ffn(f"{mlp}experts.{first + e}.")
                   for e in range(cfg.num_experts)]
        lp.update({k: np.stack([x[k] for x in experts])
                   for k in ("w_gate", "w_up", "w_down")},
                  router=mat(mlp + "gate.weight"),
                  router_bias=np.asarray(
                      t[mlp + "gate.e_score_correction_bias"], dtype))
        trees["win_layers" if kind == "window" else "layers"].append(lp)
    params: tf.Params = {
        name: {k: np.stack([lp[k] for lp in rows]) for k in rows[0]}
        for name, rows in trees.items() if rows}
    params.update(embed=np.asarray(t["model.embed_tokens.weight"], dtype),
                  final_norm=np.asarray(t["model.norm.weight"], dtype),
                  lm_head=mat("lm_head.weight"))
    return params


def params_from_hf(cfg: ModelConfig, path: str, dtype: Any = None,
                   weight_dtype: str = "bf16", shards: int = 1) -> tf.Params:
    """Convert a HuggingFace Qwen2/Llama checkpoint directory to arks params.

    Leaves are assembled on the HOST (numpy) and moved to device one at a
    time; with ``weight_dtype='int8'`` each matmul leaf is quantized on
    arrival (models.quant w8a16) so peak device memory is the int8 tree plus
    ONE full-width leaf — the only way a ~15GB bf16 7B checkpoint reaches a
    16GB chip.
    """
    _refuse_latent(cfg, path)
    dtype = jnp.dtype(dtype or cfg.dtype)
    t = _hf_tensors(path)
    l = cfg.num_layers

    def get(name: str, transpose: bool = False) -> np.ndarray:
        x = t[name]
        x = x.T if transpose else x
        return np.asarray(x, dtype)

    def stack(fmt: str, transpose: bool = False) -> np.ndarray:
        return _stack_layers(t, l, dtype, fmt, transpose)

    def heads(fmt: str, n: int) -> np.ndarray:
        # [out, in] as published, heads split: the stored order
        # (tf.init_params).
        return stack(fmt).reshape(l, n, cfg.head_dim, -1)

    layers: tf.Params = {
        "attn_norm": stack("model.layers.{}.input_layernorm.weight"),
        "wq": heads("model.layers.{}.self_attn.q_proj.weight", cfg.num_heads),
        "wk": heads("model.layers.{}.self_attn.k_proj.weight",
                    cfg.num_kv_heads),
        "wv": heads("model.layers.{}.self_attn.v_proj.weight",
                    cfg.num_kv_heads),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight", True),
        "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight"),
    }
    if cfg.num_experts:
        layers.update(_moe_from_hf(cfg, t, dtype))
    else:
        layers.update({
            "w_gate": stack("model.layers.{}.mlp.gate_proj.weight", True),
            "w_up": stack("model.layers.{}.mlp.up_proj.weight", True),
            "w_down": stack("model.layers.{}.mlp.down_proj.weight", True),
        })
    if cfg.qkv_bias:
        layers["bq"] = stack("model.layers.{}.self_attn.q_proj.bias")
        layers["bk"] = stack("model.layers.{}.self_attn.k_proj.bias")
        layers["bv"] = stack("model.layers.{}.self_attn.v_proj.bias")
    params: tf.Params = {
        "embed": get("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": get("model.norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight", True)
    return _leaves_to_device(params, _weight_bits(weight_dtype),
                             shards=shards)


def _quantize_leaf(leaf, axis: int, bits: int = 8, shards: int = 1):
    import functools

    from arks_tpu.models.quant import quantize_tensor, quantize_tensor_int4

    x = jnp.asarray(leaf)
    # donate: the full-width device copy is freed as soon as the quantized
    # outputs exist, bounding the transient to one leaf.
    if bits == 4:
        fn = jax.jit(functools.partial(quantize_tensor_int4, shards=shards,
                                       axis=axis), donate_argnums=(0,))
    else:
        fn = jax.jit(functools.partial(quantize_tensor, axis=axis),
                     donate_argnums=(0,))
    return fn(x)


def _leaves_to_device(host_params: dict, bits: int,
                      shards: int = 1) -> tf.Params:
    """Move a host-side (numpy) params tree to device leaf-by-leaf,
    quantizing matmul leaves on arrival when requested (``bits`` =
    0 = no quantization | 8 | 4).  ``shards`` = mesh model-axis size
    (int4 groups align to shards)."""
    from arks_tpu.models.quant import MATMUL_KEYS, contraction_axis

    def walk(sub: dict) -> dict:
        out = {}
        for name, leaf in sub.items():
            if isinstance(leaf, dict):
                out[name] = walk(leaf)
            elif bits and name == "embed":
                out[name] = _quantize_leaf(leaf, -1)  # int8 either way
            elif bits and name in MATMUL_KEYS:
                out[name] = _quantize_leaf(
                    leaf, contraction_axis(name, leaf.ndim), bits, shards)
            else:
                out[name] = jnp.asarray(leaf)
        return out

    return walk(host_params)


def _stack_layers(t: dict[str, np.ndarray], l: int, dtype: Any, fmt: str,
                  transpose: bool = False) -> np.ndarray:
    """Stack one per-layer tensor family into the leading-[L] convention
    (host-side; device transfer happens in _leaves_to_device)."""
    xs = [t[fmt.format(i)] for i in range(l)]
    if transpose:
        xs = [x.T for x in xs]
    return np.stack(xs).astype(dtype)


def _moe_from_hf(cfg: ModelConfig, t: dict[str, np.ndarray],
                 dtype: Any) -> tf.Params:
    """Expert weights for Mixtral (`block_sparse_moe.experts.{e}.w1/w3/w2`)
    and Qwen2-MoE (`mlp.experts.{e}.gate_proj/up_proj/down_proj` + shared
    expert) checkpoints, stacked [L, X, ..]."""
    l, x = cfg.num_layers, cfg.num_experts
    mixtral = any(".block_sparse_moe." in k for k in t)
    if mixtral:
        base = "model.layers.{}.block_sparse_moe"
        router = base + ".gate.weight"
        gate, up, down = (base + ".experts.{}.w1.weight",
                          base + ".experts.{}.w3.weight",
                          base + ".experts.{}.w2.weight")
    else:
        base = "model.layers.{}.mlp"
        router = base + ".gate.weight"
        gate, up, down = (base + ".experts.{}.gate_proj.weight",
                          base + ".experts.{}.up_proj.weight",
                          base + ".experts.{}.down_proj.weight")

    def estack(fmt: str) -> np.ndarray:
        return np.stack([
            np.stack([t[fmt.format(i, e)].T for e in range(x)])
            for i in range(l)]).astype(dtype)

    p: tf.Params = {
        "router": _stack_layers(t, l, dtype, router, True),
        "w_gate": estack(gate),
        "w_up": estack(up),
        "w_down": estack(down),
    }
    if cfg.shared_expert_intermediate_size:
        sh = "model.layers.{}.mlp.shared_expert"
        p["shared_gate_proj"] = _stack_layers(t, l, dtype, sh + ".gate_proj.weight", True)
        p["shared_up"] = _stack_layers(t, l, dtype, sh + ".up_proj.weight", True)
        p["shared_down"] = _stack_layers(t, l, dtype, sh + ".down_proj.weight", True)
        p["shared_gate"] = np.stack(
            [t["model.layers.{}.mlp.shared_expert_gate.weight".format(i)].reshape(-1)
             for i in range(l)]).astype(dtype)
    return p


# ---------------------------------------------------------------------------
# Orbax sharded checkpoints
# ---------------------------------------------------------------------------

def orbax_path(model_path: str) -> str:
    return os.path.join(model_path, ORBAX_SUBDIR)


def save_orbax(params: tf.Params, model_path: str) -> str:
    import orbax.checkpoint as ocp

    path = orbax_path(model_path)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(path), params, force=True)
    ckptr.wait_until_finished()
    return path


def check_restored_shapes(restored, template, path: str) -> None:
    """Orbax hands back a leaf in the shape it was SAVED in, whatever the
    template says: a checkpoint written with another stored order (the
    q / k / v leaves were [L, E, H x D] until they became [L, H, D, E],
    the latent block's ``wq_b`` / ``wkv_b`` [L, K, H x D] until [L, H, D,
    K]: `tf.init_params`) is refused here, by leaf, and not by an einsum
    deep in the first step."""
    flat = jax.tree_util.tree_flatten_with_path(template)[0]
    for (keys, want), got in zip(flat, jax.tree.leaves(restored)):
        if tuple(got.shape) != tuple(want.shape):
            raise ValueError(
                f"checkpoint at {path}: leaf {jax.tree_util.keystr(keys)} "
                f"has shape {tuple(got.shape)}, this build stores "
                f"{tuple(want.shape)} (docs/model-usage.md, how the weight "
                "tree is stored): convert the checkpoint again")


def load_orbax(cfg: ModelConfig, model_path: str, mesh=None,
               dtype: Any = None, weight_dtype: str = "bf16") -> tf.Params:
    """Load an Orbax checkpoint, sharded directly to the mesh when given —
    each host reads only the shards it owns (multi-host friendly).

    With ``weight_dtype='int8'`` and no mesh, the checkpoint is restored to
    HOST memory and quantized onto the device leaf-by-leaf (bounded peak —
    the single-chip 7B path).  With a mesh, the full-width restore is
    already spread across devices, so the tree-level quantize follows it.
    """
    import orbax.checkpoint as ocp

    dtype = jnp.dtype(dtype or cfg.dtype)
    quantize = _weight_bits(weight_dtype)
    path = os.path.abspath(orbax_path(model_path))
    template = jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0), dtype))
    if mesh is not None:
        tp = mesh.shape.get(tf.AXIS_MODEL, 1)
        specs = tf.param_pspecs(cfg, tp)
        template = jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.NamedSharding(mesh, spec)),
            template, specs)
    elif quantize:
        cpu = jax.devices("cpu")[0]
        template = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.SingleDeviceSharding(cpu)),
            template)
    ckptr = ocp.StandardCheckpointer()
    params = ckptr.restore(path, template)
    check_restored_shapes(params, template, path)
    if quantize:
        shards = mesh.shape.get(tf.AXIS_MODEL, 1) if mesh is not None else 1
        if mesh is not None:
            from arks_tpu.models.quant import quantize_params
            return quantize_params(params, bits=quantize, shards=shards)
        return _leaves_to_device(
            jax.tree.map(np.asarray, params), quantize)
    return params


def _shard_put_fns(cfg: ModelConfig, template, mesh=None):
    """Per-leaf H2D placement fns (the make_shard_and_gather_fns idiom):
    one closure per param leaf that converts the host value to the leaf's
    dtype and issues a NON-BLOCKING ``jax.device_put`` — sharded onto the
    mesh when given, whole-array otherwise.  Because each put is async,
    walking the tree overlaps the host read/convert of leaf N+1 with the
    device transfer of leaf N."""
    if mesh is not None:
        tp = mesh.shape.get(tf.AXIS_MODEL, 1)
        specs = tf.param_pspecs(cfg, tp)

        def make(s, spec):
            sh = jax.sharding.NamedSharding(mesh, spec)
            return lambda x: jax.device_put(jnp.asarray(x, s.dtype), sh)

        return jax.tree.map(make, template, specs)

    def make_local(s):
        return lambda x: jax.device_put(jnp.asarray(x, s.dtype))

    return jax.tree.map(make_local, template)


def stream_params_to_device(cfg: ModelConfig, host_params, mesh=None,
                            dtype: Any = None) -> tf.Params:
    """Stream a host-resident params tree to device leaf-by-leaf with
    async H2D puts (no blocking between leaves, no tree-level barrier).
    The returned arrays are in flight; the caller's first dispatch — an
    ordinary stream op, exactly the restore mechanics — orders after them,
    so a live engine keeps issuing pipelined decode for the CURRENT model
    while the NEXT model's weights fly."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    template = jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0), dtype))
    fns = _shard_put_fns(cfg, template, mesh)
    return jax.tree.map(lambda fn, x: fn(x), fns, host_params)


def reshard_plan(cfg: ModelConfig, params, mesh=None):
    """Per-leaf placement fns for a LIVE topology resize: one closure per
    current param leaf that issues a non-blocking ``jax.device_put`` onto
    the NEW mesh's sharding (or whole-array onto the default device when
    the new shape is single-chip).  Same make_shard_and_gather_fns idiom
    as ``_shard_put_fns``, but the source leaves are already on device —
    each put is a device-to-device reshard dispatch, so walking the tree
    overlaps leaf N+1's issue with leaf N's transfer and the drained
    engine never blocks the host.  Quantized trees get quantize-aware
    pspecs (the ``shard_params`` discipline)."""
    if mesh is None:
        dev = jax.devices()[0]
        return jax.tree.map(
            lambda _: (lambda x: jax.device_put(x, dev)), params)
    tp = mesh.shape.get(tf.AXIS_MODEL, 1)
    specs = tf.param_pspecs(cfg, tp)
    from arks_tpu.models.quant import is_quantized, quantize_pspecs
    wq = params["layers"].get("wq")
    if is_quantized(wq):
        specs = quantize_pspecs(specs, bits=4 if "gs" in wq else 8)

    def make(spec):
        sh = jax.sharding.NamedSharding(mesh, spec)
        return lambda x: jax.device_put(x, sh)

    return jax.tree.map(make, specs)


def reshard_params_to_mesh(cfg: ModelConfig, params, mesh=None) -> tf.Params:
    """Migrate a live params tree to a new mesh shape with per-leaf async
    ``device_put`` (the resize half of ``stream_params_to_device``): the
    returned arrays are in flight and the first dispatch at the new shape
    orders after them."""
    fns = reshard_plan(cfg, params, mesh)
    return jax.tree.map(lambda fn, x: fn(x), fns, params)


def load_orbax_streaming(cfg: ModelConfig, model_path: str, mesh=None,
                         dtype: Any = None,
                         weight_dtype: str = "bf16") -> tf.Params:
    """Shard-streaming Orbax load for live model switches: restore the
    checkpoint to HOST memory, then scatter it to device with per-leaf
    async puts (``stream_params_to_device``).  Unlike ``load_orbax`` —
    which restores directly into device shardings and synchronizes the
    restore — every device-facing op here is an async stream dispatch, so
    it is safe to run from the model-pool loader thread while the engine
    keeps full pipeline depth on the resident model.

    Quantized loads fall back to ``load_orbax`` (its bounded-peak
    leaf-quantize path is already host-staged)."""
    import orbax.checkpoint as ocp

    dtype = jnp.dtype(dtype or cfg.dtype)
    if _weight_bits(weight_dtype):
        return load_orbax(cfg, model_path, mesh, dtype, weight_dtype)
    path = os.path.abspath(orbax_path(model_path))
    template = jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0), dtype))
    cpu = jax.devices("cpu")[0]
    host_template = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=jax.sharding.SingleDeviceSharding(cpu)),
        template)
    ckptr = ocp.StandardCheckpointer()
    host_params = ckptr.restore(path, host_template)
    return stream_params_to_device(
        cfg, jax.tree.map(np.asarray, host_params), mesh, dtype)


def convert_hf_to_orbax(cfg: ModelConfig, model_path: str,
                        dtype: Any = None) -> str:
    """One-shot conversion after model download (the ArksModel 'Loading'
    phase extension). Idempotent: skips when the Orbax dir already exists."""
    path = orbax_path(model_path)
    if os.path.isdir(path) and os.listdir(path):
        return path
    params = params_from_hf(cfg, model_path, dtype)
    return save_orbax(params, model_path)


# ---------------------------------------------------------------------------
# Entry point used by the serving pod
# ---------------------------------------------------------------------------

def weights_kind(model_path: str | None) -> str | None:
    """Classify what ``load_params`` would load with ONE directory scan:
    ``"orbax"`` > ``"safetensors"`` > ``None`` (random init).

    This is the model-switch hot path: ``has_real_weights`` and
    ``load_params`` both used to stat the Orbax subdir AND list the
    directory, doubling the filesystem reads per switch.  ``os.scandir``
    gives entry types from the directory read itself (no per-entry stat
    on mainstream filesystems), so classification costs one opendir."""
    if not model_path:
        return None
    kind = None
    try:
        with os.scandir(model_path) as it:
            for e in it:
                if e.name == ORBAX_SUBDIR and e.is_dir():
                    return "orbax"
                if e.name.endswith(".safetensors"):
                    kind = "safetensors"
    except (FileNotFoundError, NotADirectoryError):
        return None
    return kind


def has_real_weights(model_path: str | None) -> bool:
    """True when ``load_params`` would load actual weights (Orbax or
    safetensors) rather than falling back to random init."""
    return weights_kind(model_path) is not None


def load_params(cfg: ModelConfig, model_path: str | None, mesh=None,
                dtype: Any = None, weight_dtype: str = "bf16") -> tf.Params:
    """Best available weights: Orbax (sharded) > safetensors > random init.

    ``weight_dtype='int8'`` quantizes during load with bounded peak memory
    (see params_from_hf / load_orbax) — quantizing after a full-width load
    would OOM exactly the HBM-limited configs the flag exists for."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    quantize = _weight_bits(weight_dtype)
    if model_path:
        kind = weights_kind(model_path)
        if kind:
            _refuse_latent(cfg, model_path)
        if kind == "orbax":
            log.info("loading Orbax checkpoint from %s", orbax_path(model_path))
            return load_orbax(cfg, model_path, mesh, dtype, weight_dtype)
        if kind == "safetensors":
            log.info("loading HF safetensors from %s", model_path)
            params = params_from_hf(
                cfg, model_path, dtype, weight_dtype,
                shards=mesh.shape.get(tf.AXIS_MODEL, 1)
                if mesh is not None else 1)
            if mesh is not None:
                params = tf.shard_params(params, cfg, mesh)
            return params
        log.warning("no weights found under %s; using random init", model_path)
    if quantize:
        from arks_tpu.models.quant import init_params_quantized
        params = init_params_quantized(
            cfg, jax.random.PRNGKey(0), dtype, bits=quantize,
            shards=mesh.shape.get(tf.AXIS_MODEL, 1) if mesh is not None else 1)
    else:
        params = tf.init_params(cfg, jax.random.PRNGKey(0), dtype)
    if mesh is not None:
        params = tf.shard_params(params, cfg, mesh)
    return params


def load_params_streaming(cfg: ModelConfig, model_path: str | None, mesh=None,
                          dtype: Any = None,
                          weight_dtype: str = "bf16") -> tf.Params:
    """``load_params`` for LIVE model switches: every device-facing op is
    an async stream dispatch (per-leaf puts), never a blocking restore —
    the model-pool loader thread can run this under a serving engine
    without stalling its pipelined decode.  Same weight preference order
    as ``load_params`` (Orbax > safetensors > random init), same single
    directory scan."""
    kind = weights_kind(model_path)
    if kind == "orbax":
        log.info("streaming Orbax checkpoint from %s", orbax_path(model_path))
        return load_orbax_streaming(cfg, model_path, mesh, dtype, weight_dtype)
    # params_from_hf already streams leaf-by-leaf via _leaves_to_device;
    # the random-init fallback is device-side and cheap.
    return load_params(cfg, model_path, mesh, dtype, weight_dtype)
