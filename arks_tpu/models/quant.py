"""Weight-only quantization for serving: int8 (w8a16) and int4 (w4a16).

Why: a 7B-class model in bf16 (~15 GB) does not fit a single v5e chip's
16 GB HBM next to its KV cache — and decode is HBM-bandwidth-bound, so
shrinking the bytes read per step is also the single biggest
decode-throughput lever.  Activations stay bf16 in both modes; MXU FLOPs
are unchanged.

- **int8**: per-output-channel float scales.  The dequant is expressed as
  ``int8 -> bf16 convert feeding the einsum`` plus a per-channel scale on
  the OUTPUT (valid because the scale is constant along the contraction
  dim), so XLA fuses the convert into the matmul's operand read and the
  full-width weight never materializes in HBM.
- **int4**: per-(128-row group x output channel) scales — per-channel
  int4 loses too much fidelity, groupwise is the standard recipe (GPTQ/
  AWQ-style).  Scales vary ALONG the contraction dim, so the dequant is
  an elementwise producer of the dot's weight operand (int4 -> bf16
  convert * broadcast group scale); XLA fuses elementwise producers into
  the dot read, so HBM still sees ~K*N/2 bytes + K/128*N scale bytes.
  The embedding table stays int8 in int4 mode (row-gathered, small, and
  quality-critical).

The reference has no quantization of its own (it forwards dtype flags to
vLLM/SGLang via runtimeCommonArgs, /root/reference/api/v1/
arksapplication_types.go:292); this module is the TPU-native counterpart.

A quantized leaf is a pytree-compatible dict: int8 = ``{"q": int8,
"s": f32}`` with s = [.., 1, N] for matmul weights [.., K, N] (the
embedding [V, E] carries s = [V, 1]); int4 = ``{"q": int4 [.., K, N],
"gs": f32 [.., K/G, N]}``.  The GQA blocks' q / k / v projections are
stored head-split with the contraction dimension minor, ``[L, H, D, E]``
(`transformer.init_params` says why): their scales are ``[L, H, D, 1]`` /
``[L, H, D, E/G]``, the same numbers as the ``[L, E, H x D]`` leaf's
(:func:`contraction_axis` tells the two apart).  So are the latent
block's ``wq_b`` / ``wkv_b``, ``[.., H, D, K]``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from arks_tpu.utils import knobs

INT4_GROUP = 128


def _int4_group(group: int | None) -> int:
    """Resolve the int4 group size: explicit arg > ARKS_INT4_GROUP env >
    128.  Sharded deployments need the group to divide each shard of the
    contraction dim (e.g. q_dim 3584 at tp=8 -> local K 448 -> group 64);
    the env knob avoids replumbing every load path for that case."""
    if group is not None:
        return group
    return knobs.get_int("ARKS_INT4_GROUP")

# Weights quantized per-output-channel along reduction dim -2 ([.., K, N]).
MATMUL_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
    "shared_gate_proj", "shared_up", "shared_down",
    # The latent block (transformer.py): query down / up, the latent's
    # down-projection, and [W_uk | W_uv], which the step absorbs (the two
    # up projections head-split: LATENT_SPLIT_KEYS).
    "wq_a", "wq_b", "wkv_a", "wkv_b",
    # The ``solar_open2`` block: a GQA layer's elementwise output gate, a
    # linear layer's low-rank decay and gate pairs.
    "wg", "w_f1", "w_f2", "w_g1", "w_g2",
    # The ``gigachat3_5`` block: a linear layer's full output gate (its
    # latent layers' gate is ``wg``).
    "w_z",
    # The ``longcat_flash`` block: the dense SwiGLU behind each of a
    # layer's two attention sublayers, [L, 2, K, N] (``w_gate`` / ``w_up`` /
    # ``w_down`` are the layer's routed experts').
    "ffn_gate", "ffn_up", "ffn_down",
    # The ``nemotron_h`` block: a Mamba-2 mixer's input projection (z | x B
    # C | dt) and its output projection.
    "w_in", "w_out",
    # ... and a two-matrix expert's input projection, [L, X, Fm, E].
    "w_upt",
})
# The leaves a GQA stack stores head-split, ``[L, H, D, E]``
# (`transformer.split_heads`).  The linear layers' leaves of the same names
# are plain ``[L, E, H x d]`` matmuls: the rank says which.
HEAD_SPLIT_KEYS = frozenset({"wq", "wk", "wv"})
# The leaves stored TRANSPOSED, ``[L, N, K]``, contraction dimension minor
# (a Mamba-2 mixer's input projection: `transformer._init_ssm_params`; a
# two-matrix expert's: `moe.init_moe_params`).
TRANSPOSED_KEYS = frozenset({"w_in", "w_upt"})
# The latent block's two up projections, head-split as a GQA stack's are,
# ``[L, H, nope + rope, q_lora]`` / ``[L, H, nope + v, kv_lora]`` (``[L, 2,
# H, ..]`` in the shortcut block: `transformer._init_latent_params`).  No
# other leaf has these names: head-split at every rank.
LATENT_SPLIT_KEYS = frozenset({"wq_b", "wkv_b"})


def contraction_axis(name: str, ndim: int) -> int:
    """The contraction dimension of a STACKED matmul leaf: -2 (``[.., K,
    N]``), and -1 for a head-split projection: ``[L, H, D, E]``, the only
    leaf of rank 4 under a GQA stack's names, a latent up projection at
    any rank; and for a transposed one."""
    return -1 if name in TRANSPOSED_KEYS or name in LATENT_SPLIT_KEYS or (
        name in HEAD_SPLIT_KEYS and ndim == 4) else -2


# Router logits feed a softmax over experts — tiny and precision-sensitive,
# so it stays full width, as do norms, biases and the scalar shared gate.
SKIP_KEYS = frozenset({
    "attn_norm", "mlp_norm", "final_norm", "bq", "bk", "bv", "router",
    "shared_gate", "q_norm", "kv_norm", "router_bias",
    # The per-head output gate [E, H]: a sigmoid's input, tiny; the sink
    # logit a head [H] of a softmax's denominator.
    "attn_gate", "attn_sink",
    # A linear layer's small leaves: the convolutions' taps [K, H x d], the
    # decay's bias and per-head rate, the step size [E, H], the output's
    # per-head norm.
    "conv_q", "conv_k", "conv_v", "dt_bias", "a_log", "w_b", "o_norm",
    # The decay's projection a head [E, H] (a softplus's input, tiny) and
    # the two post-norms of a layer with sandwich norms.
    "w_a", "attn_post_norm", "mlp_post_norm",
    # A Mamba-2 mixer's small leaves: the convolution's taps [K, C] and
    # bias [C], the skip a head [H], the grouped norm's weight [H x P].
    "conv_w", "conv_b", "d_skip", "ssm_norm",
})
NORM_KEYS = frozenset({"attn_norm", "mlp_norm", "final_norm", "q_norm",
                       "kv_norm", "o_norm", "attn_post_norm",
                       "mlp_post_norm", "ssm_norm"})


def weight_bits(weight_dtype: str) -> int:
    """'bf16' -> 0 (no quantization), 'int8' -> 8, 'int4' -> 4 — the ONE
    mapping every weight_dtype consumer shares."""
    try:
        return {"bf16": 0, "int8": 8, "int4": 4}[weight_dtype]
    except KeyError:
        raise ValueError(f"weight_dtype={weight_dtype!r}") from None


def is_quantized(w) -> bool:
    return isinstance(w, dict) and "q" in w and ("s" in w or "gs" in w)


def quantize_tensor(w: jnp.ndarray, axis: int = -2) -> dict:
    """Symmetric int8 quantization with a shared scale along ``axis``."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def quantize_tensor_int4(w: jnp.ndarray, group: int | None = None,
                         shards: int = 1, axis: int = -2) -> dict:
    """Symmetric int4 quantization of a matmul weight [.., K, N] with one
    scale per (``group`` reduction rows x output channel); ``axis`` -1: of
    a weight [.., N, K], group scales [.., N, K/G].

    ``shards``: the mesh's model-axis size.  A row-parallel leaf shards
    its contraction dim K, and group scales shard with it, so the group
    must divide K/shards (whole groups per shard).  The group clamps down
    to the largest divisor that fits — also covers small test-sized
    weights (group <= K).
    """
    if axis == -1:
        out = quantize_tensor_int4(jnp.swapaxes(w, -1, -2), group, shards)
        return {k: jnp.swapaxes(v, -1, -2) for k, v in out.items()}
    w32 = w.astype(jnp.float32)
    k = w32.shape[-2]
    local = max(k // max(shards, 1), 1)
    group = min(_int4_group(group), local)
    while local % group:
        group -= 1
    if k % group:
        raise ValueError(
            f"int4 reduction dim {k} not a multiple of group {group}")
    grp = w32.reshape(*w32.shape[:-2], k // group, group, w32.shape[-1])
    amax = jnp.max(jnp.abs(grp), axis=-2, keepdims=True)  # [.., K/G, 1, N]
    s = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(grp / s), -7, 7).astype(jnp.int4)
    return {"q": q.reshape(w32.shape), "gs": jnp.squeeze(s, -2)}


def _dequant_int4(w, dtype: jnp.dtype) -> jnp.ndarray:
    """The groups tile the contraction dimension: the one dimension in
    which the scales are fewer than the values (none: groups of one)."""
    q, gs = w["q"], w["gs"]
    axis = next((i for i, (a, b) in enumerate(zip(q.shape, gs.shape))
                 if a != b), q.ndim - 2)
    grp = q.astype(dtype).reshape(
        *q.shape[:axis], gs.shape[axis], q.shape[axis] // gs.shape[axis],
        *q.shape[axis + 1:])
    return (grp * jnp.expand_dims(gs, axis + 1).astype(dtype)).reshape(q.shape)


def qeinsum(eq: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """``jnp.einsum`` where ``w`` may be a quantized leaf.

    int8: the convert int8->x.dtype fuses into the dot's operand read; the
    per-output-channel scale applies to the OUTPUT (valid because the scale
    is constant along the contraction dim), broadcasting over trailing dims.
    int4: groupwise scales vary along the contraction dim, so the dequant
    is an elementwise producer of the weight operand (fused by XLA).
    The contraction dim is read off ``eq``: the one letter of ``w``'s that
    the output lacks (``"...e,eq->...q"``: -2; ``"...e,hde->...hd"``: -1);
    the output ends in ``w``'s other dims, in ``w``'s order.
    """
    if not is_quantized(w):
        return jnp.einsum(eq, x, w)
    if "gs" in w:
        return jnp.einsum(eq, x, _dequant_int4(w, x.dtype))
    ins, out = eq.split("->")
    spec = ins.split(",")[1]
    (axis,) = (i - len(spec) for i, c in enumerate(spec) if c not in out)
    y = jnp.einsum(eq, x, w["q"].astype(x.dtype))
    return y * jnp.squeeze(w["s"], axis=axis).astype(y.dtype)


def dequantize(w, dtype: jnp.dtype) -> jnp.ndarray:
    """Materialize the full-width weight.  No expert stack comes here
    since PR 33 (`models/moe.py` reads quantised experts in the
    contraction: dequantise + ``ragged_dot`` cost 46-58 ms a Mixtral step
    where the fused forms take 8-14, PERF.md); the one caller left in a
    step program is the latent block's absorbed ``wkv_b``
    (`transformer._wkv_b`, a few MB).  Everywhere else use qeinsum so the
    dequant stays fused."""
    if not is_quantized(w):
        return w
    if "gs" in w:
        return _dequant_int4(w, dtype)
    return (w["q"].astype(dtype) * w["s"].astype(dtype))


def embed_lookup(embed, tokens: jnp.ndarray, dtype: jnp.dtype) -> jnp.ndarray:
    """Row gather from a possibly-quantized [V, E] table — gathers int8 rows
    and their scales, never the dequantized table."""
    if not is_quantized(embed):
        return jnp.take(embed, tokens, axis=0)
    rows = jnp.take(embed["q"], tokens, axis=0).astype(dtype)
    scales = jnp.take(embed["s"], tokens, axis=0).astype(dtype)
    return rows * scales


def unembed_logits(h: jnp.ndarray, table, tied: bool) -> jnp.ndarray:
    """[B, E] @ unembed table -> [B, V] float32, scale applied post-dot."""
    if not is_quantized(table):
        t = table.T if tied else table
        return jnp.einsum("be,ev->bv", h, t).astype(jnp.float32)
    if "gs" in table:  # int4 lm_head [E, V] (the embedding stays int8)
        return jnp.einsum("be,ev->bv", h,
                          _dequant_int4(table, h.dtype)).astype(jnp.float32)
    if tied:  # table [V, E], s [V, 1]
        logits = jnp.einsum("be,ve->bv", h, table["q"].astype(h.dtype))
        return logits.astype(jnp.float32) * jnp.squeeze(table["s"], -1)
    # lm_head [E, V], s [1, V]
    logits = jnp.einsum("be,ev->bv", h, table["q"].astype(h.dtype))
    return logits.astype(jnp.float32) * jnp.squeeze(table["s"], -2)


def quantize_params(params: dict, bits: int = 8,
                    group: int | None = None, shards: int = 1) -> dict:
    """Quantize an already-materialized transformer Params tree.

    NOTE: the caller's full-width tree stays alive while this runs, so peak
    device memory is full tree + quantized tree.  Fine for small models and
    trees already sharded across a mesh; for HBM-limited single-chip loads
    use the bounded-peak paths instead — init_params_quantized (random
    init) or weights.params_from_hf(weight_dtype='int8'|'int4')
    (checkpoints), both of which quantize leaf-by-leaf as leaves are
    created.  ``bits=4`` stores matmul weights int4 groupwise; the
    embedding stays int8 either way.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits={bits}")
    out: dict = {}
    for name, leaf in params.items():
        if isinstance(leaf, dict):
            out[name] = quantize_params(leaf, bits, group, shards)
        elif name == "embed":
            out[name] = quantize_tensor(leaf, axis=-1)
        elif name in MATMUL_KEYS:
            axis = contraction_axis(name, leaf.ndim)
            out[name] = (quantize_tensor_int4(leaf, group, shards, axis)
                         if bits == 4
                         else quantize_tensor(leaf, axis=axis))
        else:
            assert name in SKIP_KEYS, (
                f"param leaf {name!r} is in neither MATMUL_KEYS nor "
                "SKIP_KEYS — classify it so quantization coverage can't "
                "silently drift")
            out[name] = leaf
    return out


def init_params_quantized(cfg, key, dtype=jnp.bfloat16, bits: int = 8,
                          shards: int = 1) -> dict:
    """Random-init a transformer Params tree directly in quantized form.

    Mirrors transformer.init_params' distributions (normal*0.02 weights,
    ones norms, zeros biases) but generates + quantizes each leaf inside its
    own jit, so peak device memory is the quantized tree plus ONE
    full-width leaf — a bf16 init of a 7B model (~15 GB) would not even fit
    the chip that the quantized model is for.  Used by the server's
    random-weight path (the benchmark's configurations) and anywhere
    random weights of an HBM-limited model are needed.
    ``bits=4`` = w4a16 (matmul weights int4 groupwise, embedding int8).
    """
    import functools

    from arks_tpu.models import moe, transformer as tf

    if bits not in (4, 8):
        raise ValueError(f"bits={bits}")
    shapes = jax.eval_shape(
        functools.partial(tf.init_params, cfg, dtype=dtype), key)

    @functools.partial(jax.jit, static_argnames=("shape", "kind", "axis"))
    def gen(k, shape, kind, axis):
        if kind == "ones":
            return jnp.ones(shape, dtype)
        if kind == "zeros":
            return jnp.zeros(shape, dtype)
        w = jax.random.normal(k, shape, jnp.float32) * 0.02
        if kind == "quant":
            if bits == 4 and axis == -2:  # matmul weights; embed stays int8
                return quantize_tensor_int4(w.astype(dtype), shards=shards)
            return quantize_tensor(w.astype(dtype), axis=axis)
        if kind == "dt_bias":
            return tf.shift_dt_bias(w.astype(dtype))
        if kind == "select_bias":
            return moe.seeded_select_bias(w.astype(dtype))
        if kind == "conv_taps":
            return tf.scale_conv_taps(w.astype(dtype))
        return w.astype(dtype)

    # A head-split projection (a GQA stack's q / k / v, the latent block's
    # two up projections) is drawn and quantised as the [.., E, H x D]
    # matmul it is, by the program every other matmul leaf takes, and
    # stored [.., H, D, E] by a program of its own: fused into ONE, the
    # chip's compiler divides ``w / s`` another way and 3.8 % of the int8
    # values come out one step off the values the same draw gives in the
    # drawn order (PERF.md section 6, PR 48), which is what a seed means
    # (benchmarks/references/_common.py).  What stands beside the stored
    # leaf for a moment is its int8 copy, a quarter of the float32 draw.
    # (``heads`` 0: a transposed leaf, [L, K, N] stored [L, N, K].)
    @functools.partial(jax.jit, static_argnames=("heads",))
    def stored(leaf, heads):
        return {n: tf.split_heads(a, heads) if heads
                else a.swapaxes(-1, -2) for n, a in leaf.items()}

    counter = [0]

    def build(subtree):
        out = {}
        for name, leaf in subtree.items():
            if isinstance(leaf, dict):
                out[name] = build(leaf)
                continue
            counter[0] += 1
            sub = jax.random.fold_in(key, counter[0])
            if name in NORM_KEYS:
                # A gated norm's scale is 1 at a weight of zero; the
                # linear layers' per-head norm is a plain one.
                kind, axis = ("zeros" if cfg.norm_gate and name != "o_norm"
                              else "ones"), 0
            elif name in ("bq", "bk", "bv"):
                kind, axis = "zeros", 0
            elif name == "embed":
                kind, axis = "quant", -1
            elif name in MATMUL_KEYS:
                kind, axis = "quant", -2
                if name in TRANSPOSED_KEYS:
                    *lead, n, k = leaf.shape
                    out[name] = stored(gen(sub, (*lead, k, n), kind, axis), 0)
                    continue
                if contraction_axis(name, leaf.ndim) == -1:
                    *lead, h, d, e = leaf.shape
                    out[name] = stored(
                        gen(sub, (*lead, e, h * d), kind, axis), h)
                    continue
            elif name == "dt_bias":
                kind, axis = "dt_bias", 0
            elif name == "router_bias" and cfg.router_select_bias:
                kind, axis = "select_bias", 0
            elif name == "conv_w":
                kind, axis = "conv_taps", 0
            else:
                kind, axis = "full", 0
            out[name] = gen(sub, tuple(leaf.shape), kind, axis)
        return out

    return build(shapes)


def quantize_pspecs(specs: dict, bits: int = 8) -> dict:
    """PartitionSpec tree matching quantize_params' output structure: the
    quantized payload keeps the original spec.  int8 scales keep the spec
    with the reduced dim's axis dropped (scales are [.., 1, N] there);
    int4 group scales [.., K/G, N] keep the FULL spec — the group dim
    shards exactly like the contraction dim it tiles (whole groups per
    shard, since shard sizes are multiples of the group)."""
    from jax.sharding import PartitionSpec as P

    out: dict = {}
    for name, leaf in specs.items():
        if isinstance(leaf, dict):
            out[name] = quantize_pspecs(leaf, bits)
        elif name == "embed":
            out[name] = {"q": leaf, "s": P(leaf[0], None)}
        elif name in MATMUL_KEYS:
            if bits == 4:
                out[name] = {"q": leaf, "gs": leaf}
                continue
            # All matmul specs are full-rank (param_pspecs/moe_pspecs emit
            # one entry per dim), so the scale spec is the weight spec with
            # the contraction dim replicated.
            s_entries = list(leaf)
            s_entries[contraction_axis(name, len(leaf))] = None
            out[name] = {"q": leaf, "s": P(*s_entries)}
        else:
            out[name] = leaf
    return out
