"""Functional decoder-only transformer (Qwen2 / Llama families) for serving.

Design notes (TPU-first, not a port — the reference has no model code at all;
it shells out to vLLM/SGLang containers):

- Layers are **stacked**: every per-layer weight carries a leading [L] dim and
  the forward pass is one ``lax.scan`` over layers.  One trace + one compile
  regardless of depth, and uniform sharding per leaf.
- Serving follows the slot model (JetStream-style): a decode batch of B slots,
  each slot owning a [S] stretch of KV cache.  ``prefill`` runs a prompt
  through the model producing its KV; ``insert`` drops that KV into a free
  slot; ``decode_step`` advances every slot by one token.
- Tensor parallelism is Megatron-pattern via weight PartitionSpecs over the
  ``model`` mesh axis (column-parallel qkv/gate/up, row-parallel o/down); XLA
  inserts the psums over ICI.  Batch parallelism rides the ``data`` axis.
- KV heads shard over ``model`` when divisible; otherwise KV projections and
  cache are replicated (cheap: GQA KV dims are small) — this keeps e.g.
  Qwen2.5-7B (4 KV heads) correct on an 8-way TP mesh.

Reference parity anchor: this module + arks_tpu.engine replace the runtime
containers listed in /root/reference/api/v1/arksapplication_types.go:46-49.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from arks_tpu.models.config import ModelConfig
from arks_tpu.models.quant import embed_lookup, qeinsum, unembed_logits
from arks_tpu.ops.attention import decode_update_and_attend, prefill_attention
from arks_tpu.ops.linear_state import linear_state_step
from arks_tpu.ops.ssm_state import heads_a_tile, ssm_state_step
from arks_tpu.ops.norms import rms_norm
from arks_tpu.ops.rope import apply_rope

AXIS_DATA = "data"
AXIS_MODEL = "model"

Params = dict[str, Any]



class KVCache(NamedTuple):
    """Decode KV cache: [num_layers, num_slots, num_kv_heads, max_len, head_dim].

    Head-major layout: each (slot, kv-head) sequence is a contiguous [S, D]
    stripe, so the ragged Pallas decode kernel's block reads are dense DMAs
    (arks_tpu.ops.pallas_attention).

    Quantized (int8) caches carry per-token scales
    [L, B, Hkv, S] float32; ``k_scale is None`` means full-width storage.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]


class LinearState(NamedTuple):
    """What the recurrent layers of a model keep of a sequence
    (``cfg.recurrent``): a fixed number of bytes a slot, whatever the
    context.

    Linear-attention layers (``cfg.linear``): ``s`` ``[Ll, num_slots, H, d,
    d]`` float32: the delta rule's state a
    (value) head (keys down, values across), layer ``l`` of the model's
    linear layers in model order.  ``conv`` ``[Ll, num_slots, K - 1, C]``:
    the last ``K - 1`` rows of the q | k | v projections ahead of the short
    convolution, oldest first (C = 3 x H x d; with fewer key heads Hk,
    2 x Hk x d + H x d).  Mamba-2 mixers (``cfg.ssm``): ``s`` ``[Lm,
    num_slots, H / r, N, r P]`` float32, the selective scan's state ``[P,
    N]`` a head as its one-step kernel reads it (the state's width down, the
    widths of r heads of a group across: `ops/ssm_state.pack_state`), and
    ``conv`` the last
    ``K - 1`` rows of x | B | C ahead of their convolution (C = H x P + 2
    x G x N).  A slot's rows are whatever its last
    sequence left there; a sequence that starts at position 0 reads them as
    zeros (:func:`_carry_conv`, :func:`_linear_state`, :func:`_ssm_state`),
    so nothing zeroes a slot between two steps."""

    s: jnp.ndarray
    conv: jnp.ndarray

    @property
    def slot_bytes(self) -> int:
        """Bytes one slot holds, all linear layers."""
        return sum(x.size * x.dtype.itemsize for x in self) // self.s.shape[1]


class PagedKVCache(NamedTuple):
    """Paged decode cache: pool [num_layers, num_pages, Hkv, page, head_dim].

    A page is a (layer, kv-head)-major stripe of ``page`` consecutive
    positions of ONE sequence; per-slot block tables [B, MaxP] (owned by
    the engine, passed as dispatch args) map position p of slot b to pool
    page tables[b, p // page].  Two tables pointing at one page = zero-copy
    prefix sharing (arks_tpu.ops.paged_attention).  int8 pools carry
    per-token scales [L, N, Hkv, page] float32.  int4 pools pack token
    pairs into nibble bytes along the page axis ([L, N, Hkv, page//2, D]
    int8) while the scale stripes keep full token resolution — which is
    also how int4-ness is detected (pool page rows != scale page).

    A LATENT pool (latent attention, ``cfg.latent``): ``k`` is ``[L,
    num_pages, 1, page, R]``, one row a token, the normed latent and the
    rotary key lanes, which is key AND value of every head; it is stored
    once: ``v`` is None, and so are the scales (bf16 only).  ``L`` counts
    attention SUBLAYERS (``cfg.num_attn_sublayers``: two a layer of the
    shortcut block).

    A model with WINDOW layers (``cfg.windowed``) has two pools with page
    counts and lifetimes of their own: the four arrays above are the
    FULL-attention layers' (``L`` = ``cfg.num_full_layers``, a layer's
    index its rank among them), and ``win`` is the window layers' pool,
    a ``PagedKVCache`` of the same page size over ``cfg.num_window_layers``
    layers, addressed through block tables of its own whose entries
    behind a slot's window the engine has released.

    A model with LINEAR-attention layers (``cfg.linear``) or Mamba-2
    mixers (``cfg.ssm``) keeps pages for
    its GQA layers only (``L`` = ``cfg.num_full_layers``); ``lin`` is what
    its recurrent layers keep, a :class:`LinearState` indexed by slot.  Where
    its other layers are LATENT layers (``cfg.latent`` too), the pool is
    the latent pool of those layers, ``v`` None, beside ``lin``.
    """

    k: jnp.ndarray
    v: jnp.ndarray | None
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None
    win: "PagedKVCache | None" = None
    lin: LinearState | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page(self) -> int:
        """Tokens per page (POSITION math everywhere uses this; the int4
        pool's byte rows are page // 2)."""
        if self.k_scale is not None:
            return self.k_scale.shape[3]
        return self.k.shape[3]

    @property
    def kv_bits(self) -> int:
        if self.k_scale is None:
            return self.k.dtype.itemsize * 8
        return 4 if self.k.shape[3] != self.k_scale.shape[3] else 8

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def token_bytes(self) -> int:
        """Bytes the pool holds for one token, all its layers (a window
        pool counts its own)."""
        return sum(x.size * x.dtype.itemsize
                   for x in self[:4] if x is not None) // (
            self.num_pages * self.page)


# ---------------------------------------------------------------------------
# Parameter init + sharding specs
# ---------------------------------------------------------------------------


def split_heads(w, heads: int):
    """A GQA projection ``[.., E, H x D]`` as it is STORED: ``[.., H, D,
    E]``, heads split and the contraction dimension minor (a jax or a numpy
    array; :func:`init_params` says why).  A published ``[H x D, E]``
    weight takes the reshape alone."""
    w = w.swapaxes(-1, -2)
    return w.reshape(*w.shape[:-2], heads, w.shape[-2] // heads, w.shape[-1])


def _init_latent_params(cfg: ModelConfig, key: jax.Array,
                        dtype: jnp.dtype) -> Params:
    """The DeepSeek-V3 / kimi_k2 tree: ``dense_layers`` (the first
    ``first_k_dense`` layers, SwiGLU of ``intermediate_size``) and
    ``layers`` (the routed ones), each stacked, both with the latent
    attention's leaves: ``wq_a`` [E, q_lora], ``q_norm``, ``wq_b`` [H,
    nope + rope, q_lora], ``wkv_a`` [E, kv_lora + rope], ``kv_norm``,
    ``wkv_b`` [H, nope + v, kv_lora] (per head ``[W_uk | W_uv]``, as
    ``kv_b_proj`` lays them out), ``wo`` [H x v, E].  The two up
    projections are stored head-split, contraction dimension minor
    (:func:`split_heads`; :func:`init_params` says why), and are the
    published ``[out, in]`` ``q_b_proj`` / ``kv_b_proj`` but for a reshape;
    their numbers are those of the ``[q_lora, H x (nope + rope)]`` /
    ``[kv_lora, H x (nope + v)]`` draws."""
    e, f, v, h = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_heads)
    keys = iter(jax.random.split(key, 24))

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def attn(l: int) -> Params:
        return {
            "attn_norm": jnp.ones((l, e), dtype),
            "wq_a": w((l, e, cfg.q_lora_rank)),
            "q_norm": jnp.ones((l, cfg.q_lora_rank), dtype),
            "wq_b": split_heads(w((l, cfg.q_lora_rank, cfg.q_dim)), h),
            "wkv_a": w((l, e, cfg.latent_row)),
            "kv_norm": jnp.ones((l, cfg.kv_lora_rank), dtype),
            "wkv_b": split_heads(w((l, cfg.kv_lora_rank, h * (
                cfg.qk_nope_head_dim + cfg.v_head_dim))), h),
            "wo": w((l, cfg.attn_out_dim, e)),
            "mlp_norm": jnp.ones((l, e), dtype),
        }

    from arks_tpu.models import moe
    routed = attn(cfg.num_routed_layers)
    routed.update(moe.init_moe_params(cfg, next(keys), dtype))
    params: Params = {
        "embed": w((v, e)),
        "layers": routed,
        "final_norm": jnp.ones((e,), dtype),
    }
    if cfg.first_k_dense:
        ld = cfg.first_k_dense
        params["dense_layers"] = dict(
            attn(ld), w_gate=w((ld, e, f)), w_up=w((ld, e, f)),
            w_down=w((ld, f, e)))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((e, v))
    return params


def _init_shortcut_params(cfg: ModelConfig, key: jax.Array,
                          dtype: jnp.dtype) -> Params:
    """The ``longcat_flash`` tree: ONE stacked tree ``layers``.  A layer's
    two attention sublayers and the dense FFN behind each are stacked once
    more, ``[L, 2, ..]``, sublayer ``j`` at ``[:, j]``:
    :func:`_init_latent_params`'s attention leaves and norms (``attn_norm``
    ahead of an attention, ``mlp_norm`` behind it) and the dense SwiGLU
    ``ffn_gate`` / ``ffn_up`` [L, 2, E, F], ``ffn_down`` [L, 2, F, E]; the
    layer's one routed FFN has `moe.init_moe_params`'s leaves ([L, ..]: the
    router ``cfg.router_width`` wide, identity experts included, with its
    selection bias, and the held experts)."""
    e, f, v, h = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_heads)
    l = cfg.num_layers
    keys = iter(jax.random.split(key, 16))

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    from arks_tpu.models import moe
    layers = {
        "attn_norm": jnp.ones((l, 2, e), dtype),
        "wq_a": w((l, 2, e, cfg.q_lora_rank)),
        "q_norm": jnp.ones((l, 2, cfg.q_lora_rank), dtype),
        "wq_b": split_heads(w((l, 2, cfg.q_lora_rank, cfg.q_dim)), h),
        "wkv_a": w((l, 2, e, cfg.latent_row)),
        "kv_norm": jnp.ones((l, 2, cfg.kv_lora_rank), dtype),
        "wkv_b": split_heads(w((l, 2, cfg.kv_lora_rank, h * (
            cfg.qk_nope_head_dim + cfg.v_head_dim))), h),
        "wo": w((l, 2, cfg.attn_out_dim, e)),
        "mlp_norm": jnp.ones((l, 2, e), dtype),
        "ffn_gate": w((l, 2, e, f)), "ffn_up": w((l, 2, e, f)),
        "ffn_down": w((l, 2, f, e)),
    }
    layers.update(moe.init_moe_params(cfg, next(keys), dtype))
    params: Params = {"embed": w((v, e)), "layers": layers,
                      "final_norm": jnp.ones((e,), dtype)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((e, v))
    return params


# A shortcut layer's leaves that are stacked by sublayer, ``[2, ..]``.
_SUBLAYER_LEAVES = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
                    "kv_norm", "wkv_b", "wo", "mlp_norm", "ffn_gate",
                    "ffn_up", "ffn_down")


def _init_windowed_params(cfg: ModelConfig, key: jax.Array,
                          dtype: jnp.dtype) -> Params:
    """The ``laguna`` / ``mimo_v2`` tree, a stacked tree a kind of layer:
    ``dense_layers`` (the ``first_k_dense`` leading layers: full attention,
    SwiGLU of ``intermediate_size``), ``layers`` (the full-attention routed
    layers: one a period, a cut-short first period's ahead of them) and
    ``win_layers`` (the window layers, in model order), each with
    projections of its own head counts: ``wq`` [H, D, E], ``wk`` [Hkv, D,
    E], ``wv`` [Hkv, Dv, E] (head-split: :func:`init_params`), ``wo`` [H x
    Dv, E], the per-head gate
    ``attn_gate`` [E, H] and, where the kind has one, the sink logit a head
    ``attn_sink`` [H]."""
    e, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    keys = iter(jax.random.split(key, 32))

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def attn(l: int, window: bool) -> Params:
        heads, kv = cfg.heads_of(window), cfg.kv_heads_of(window)
        out = {
            "attn_norm": jnp.ones((l, e), dtype),
            "wq": split_heads(w((l, e, heads * cfg.head_dim)), heads),
            "wk": split_heads(w((l, e, kv * cfg.head_dim)), kv),
            "wv": split_heads(w((l, e, kv * cfg.value_dim)), kv),
            "wo": w((l, heads * cfg.value_dim, e)),
            "mlp_norm": jnp.ones((l, e), dtype),
        }
        if cfg.attn_gate:
            out["attn_gate"] = w((l, e, heads))
        if cfg.sink_of(window):
            out["attn_sink"] = w((l, heads))
        return out

    from arks_tpu.models import moe
    params: Params = {"embed": w((v, e)),
                      "final_norm": jnp.ones((e,), dtype)}
    for name, l, window in (
            ("layers", cfg.num_full_layers - cfg.first_k_dense, False),
            ("win_layers", cfg.num_window_layers, True)):
        params[name] = dict(attn(l, window), **moe.init_moe_params(
            cfg, next(keys), dtype, layers=l))
    if cfg.first_k_dense:
        ld = cfg.first_k_dense
        params["dense_layers"] = dict(
            attn(ld, False), w_gate=w((ld, e, f)),
            w_up=w((ld, e, f)), w_down=w((ld, f, e)))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((e, v))
    return params


# A seeded ``dt_bias`` is normal * 0.02 plus this (a choice of the seeded
# weights, not of the mathematics): softplus(0) = 0.69 would halve the state
# a step, where a trained model of the family starts its step sizes in
# [0.001, 0.1] and forgets over hundreds of tokens.  softplus(-4) = 0.018: a
# decay of 0.98 a step.  ``quant.init_params_quantized`` and the reference
# family (benchmarks/references/linear_moe.py) apply the same shift.
LINEAR_DT_BIAS_SHIFT = -4.0


def shift_dt_bias(w: jnp.ndarray) -> jnp.ndarray:
    return (w.astype(jnp.float32) + LINEAR_DT_BIAS_SHIFT).astype(w.dtype)


# A seeded ``conv_w`` (a Mamba-2 mixer's taps) is normal * 0.02 TIMES this (a
# choice of the seeded weights, not of the mathematics): std 0.5, the order
# of a trained mixer's taps (their initialiser is uniform in +-K^-1/2).  At
# 0.02 x | B | C come out of the convolution at a few hundredths, the state
# ``sum dt x (x) B`` read out by C is their third power, and nine tenths and
# more of what a mixer returns is the skip ``D x``: neither the state nor the
# precision it is kept in would reach the logits, and a state update that
# wrote the wrong slot would pass the comparison (a linear layer's q and k
# are L2-normed, so its state is of order one whatever the taps).
# ``quant.init_params_quantized`` and the reference family
# (benchmarks/references/ssm_moe.py) apply the same factor.
SSM_CONV_SCALE = 25.0


def scale_conv_taps(w: jnp.ndarray) -> jnp.ndarray:
    return (w.astype(jnp.float32) * SSM_CONV_SCALE).astype(w.dtype)


def _init_linear_params(cfg: ModelConfig, key: jax.Array,
                        dtype: jnp.dtype) -> Params:
    """The ``solar_open2`` tree, a stacked tree a kind of layer, every layer
    routed: ``head_layers`` (layer 0, a GQA layer), ``layers`` (one GQA
    layer a period) and ``lin_layers`` (``linear_period`` linear layers a
    period and the tail, in model order).  A GQA layer: ``wq`` / ``wk`` /
    ``wv`` (head-split: :func:`init_params`) / ``wo`` and the elementwise
    output gate ``wg`` [E, H x D].  A
    linear layer, H heads of d: ``wq`` / ``wk`` / ``wv`` [E, H x d],
    ``conv_q`` / ``conv_k`` / ``conv_v`` [K, H x d] (oldest tap first),
    the decay ``w_f1`` [E, d] ``w_f2`` [d, H x d] ``dt_bias`` [H x d]
    ``a_log`` [H], the step size ``w_b`` [E, H], the output's per-head
    norm ``o_norm`` [d] and gate ``w_g1`` [E, d] ``w_g2`` [d, H x d],
    ``wo`` [H x d, E]."""
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = iter(jax.random.split(key, 48))

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def gqa(l: int) -> Params:
        out = {
            "attn_norm": jnp.ones((l, e), dtype),
            "wq": split_heads(w((l, e, cfg.q_dim)), cfg.num_heads),
            "wk": split_heads(w((l, e, cfg.kv_dim)), cfg.num_kv_heads),
            "wv": split_heads(w((l, e, cfg.kv_dim)), cfg.num_kv_heads),
            "wo": w((l, cfg.q_dim, e)),
            "mlp_norm": jnp.ones((l, e), dtype),
        }
        if cfg.attn_out_gate:
            out["wg"] = w((l, e, cfg.q_dim))
        return out

    def lin(l: int) -> Params:
        ld, r, k = cfg.linear_dim, cfg.linear_head_dim, cfg.linear_conv
        return {
            "attn_norm": jnp.ones((l, e), dtype),
            "wq": w((l, e, ld)), "wk": w((l, e, ld)), "wv": w((l, e, ld)),
            "conv_q": w((l, k, ld)), "conv_k": w((l, k, ld)),
            "conv_v": w((l, k, ld)),
            "w_f1": w((l, e, r)), "w_f2": w((l, r, ld)),
            "dt_bias": shift_dt_bias(w((l, ld))),
            "a_log": w((l, cfg.linear_num_heads)),
            "w_b": w((l, e, cfg.linear_num_heads)),
            "o_norm": jnp.ones((l, r), dtype),
            "w_g1": w((l, e, r)), "w_g2": w((l, r, ld)),
            "wo": w((l, ld, e)),
            "mlp_norm": jnp.ones((l, e), dtype),
        }

    from arks_tpu.models import moe
    params: Params = {"embed": w((v, e)),
                      "final_norm": jnp.ones((e,), dtype)}
    for name, l, attn in (("head_layers", 1, gqa),
                          ("layers", cfg.num_periods, gqa),
                          ("lin_layers", cfg.num_linear_layers, lin)):
        params[name] = dict(attn(l), **moe.init_moe_params(
            cfg, next(keys), dtype, layers=l))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((e, v))
    return params


def _init_latent_linear_params(cfg: ModelConfig, key: jax.Array,
                               dtype: jnp.dtype) -> Params:
    """The ``gigachat3_5`` tree, a stacked tree a kind of layer:
    ``dense_layers`` (the ``first_k_dense`` leading layers: LINEAR layers
    with a SwiGLU of ``intermediate_size``), ``lin_layers`` (the routed
    linear layers, in model order) and ``layers`` (the routed latent
    layers).  A linear layer in the Gated DeltaNet form, Hk key heads under
    H value heads of d: ``wq`` / ``wk`` [E, Hk x d], ``wv`` [E, H x d] and
    ``conv_q`` / ``conv_k`` / ``conv_v`` (the columns of the one q | k | v
    projection and of its one depthwise convolution, a leaf a part), the
    decay a head ``w_a`` [E, H] ``dt_bias`` [H] ``a_log`` [H], the step
    size ``w_b`` [E, H], the gate ``w_z`` [E, H x d], the per-head norm
    ``o_norm`` [d], ``wo`` [H x d, E].  A latent layer:
    :func:`_init_latent_params`'s leaves and the gate ``wg`` [E, H x v].
    Every layer four norms (``cfg.norm_post``); a gated norm's weight is
    zeros where a plain one's is ones (``cfg.norm_gate``: a scale of 1)."""
    e, f, v, h = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_heads)
    keys = iter(jax.random.split(key, 64))
    unit = jnp.zeros if cfg.norm_gate else jnp.ones

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def norms(l: int) -> Params:
        names = ("attn_norm", "mlp_norm") + (
            ("attn_post_norm", "mlp_post_norm") if cfg.norm_post else ())
        return {n: unit((l, e), dtype) for n in names}

    def lin(l: int) -> Params:
        kd, ld, lh, k = (cfg.linear_key_dim, cfg.linear_dim,
                         cfg.linear_num_heads, cfg.linear_conv)
        return dict(norms(l), **{
            "wq": w((l, e, kd)), "wk": w((l, e, kd)), "wv": w((l, e, ld)),
            "conv_q": w((l, k, kd)), "conv_k": w((l, k, kd)),
            "conv_v": w((l, k, ld)),
            "w_a": w((l, e, lh)), "dt_bias": shift_dt_bias(w((l, lh))),
            "a_log": w((l, lh)), "w_b": w((l, e, lh)),
            "w_z": w((l, e, ld)),
            "o_norm": jnp.ones((l, cfg.linear_head_dim), dtype),
            "wo": w((l, ld, e))})

    def latent(l: int) -> Params:
        out = dict(norms(l), **{
            "wq_a": w((l, e, cfg.q_lora_rank)),
            "q_norm": unit((l, cfg.q_lora_rank), dtype),
            "wq_b": split_heads(w((l, cfg.q_lora_rank, cfg.q_dim)), h),
            "wkv_a": w((l, e, cfg.latent_row)),
            "kv_norm": unit((l, cfg.kv_lora_rank), dtype),
            "wkv_b": split_heads(w((l, cfg.kv_lora_rank, h * (
                cfg.qk_nope_head_dim + cfg.v_head_dim))), h),
            "wo": w((l, cfg.attn_out_dim, e))})
        if cfg.attn_out_gate:
            out["wg"] = w((l, e, cfg.attn_out_dim))
        return out

    from arks_tpu.models import moe
    params: Params = {"embed": w((v, e)), "final_norm": unit((e,), dtype)}
    for name, l, attn in (
            ("layers", cfg.num_full_layers, latent),
            ("lin_layers", cfg.num_linear_layers - cfg.first_k_dense, lin)):
        params[name] = dict(attn(l), **moe.init_moe_params(
            cfg, next(keys), dtype, layers=l))
    if cfg.first_k_dense:
        ld = cfg.first_k_dense
        params["dense_layers"] = dict(
            lin(ld), w_gate=w((ld, e, f)), w_up=w((ld, e, f)),
            w_down=w((ld, f, e)))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((e, v))
    return params


def _init_ssm_params(cfg: ModelConfig, key: jax.Array,
                     dtype: jnp.dtype) -> Params:
    """The ``nemotron_h`` tree, a stacked tree a kind of SUBLAYER, each
    layer with its one norm: ``ssm_layers`` (the ``M`` layers in model
    order: ``attn_norm``, the input projection ``w_in`` [z | x B C | dt, E]
    = [2 H P + 2 G N + H, E], stored TRANSPOSED, contraction dimension
    minor, as :func:`init_params` stores a GQA stack's projections and for
    its reason: handed ``[Lm, E, Q]`` the chip's compiler gave the step
    programs' loops two layouts of the stack and copied it whole, 637 MB,
    every step; the numbers are those of the ``[Lm, E, Q]`` draw.  The
    convolution's taps ``conv_w`` [K, H P + 2 G N] (oldest first; seeded
    times ``SSM_CONV_SCALE``) and bias ``conv_b``, the step size's
    ``dt_bias`` [H], the decay's ``a_log`` [H], the skip ``d_skip`` [H], the
    grouped norm's ``ssm_norm`` [H P], ``w_out`` [H P, E]), ``layers`` (the ``*``
    layers: ``attn_norm`` and the head-split ``wq`` / ``wk`` / ``wv``
    (:func:`init_params`) / ``wo``) and ``moe_layers`` (the ``E`` layers:
    ``mlp_norm`` and `moe.init_moe_params`'s leaves, two matrices an
    expert)."""
    e, v = cfg.hidden_size, cfg.vocab_size
    keys = iter(jax.random.split(key, 24))

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    from arks_tpu.models import moe
    lm, la, le = (cfg.num_linear_layers, cfg.num_full_layers,
                  cfg.num_routed_layers)
    d_in, c, h = cfg.ssm_dim, cfg.ssm_conv_dim, cfg.ssm_num_heads
    params: Params = {
        "embed": w((v, e)),
        "final_norm": jnp.ones((e,), dtype),
        "ssm_layers": {
            "attn_norm": jnp.ones((lm, e), dtype),
            "w_in": w((lm, e, d_in + c + h)).swapaxes(-1, -2),
            "conv_w": scale_conv_taps(w((lm, cfg.ssm_conv, c))),
            "conv_b": w((lm, c)),
            "dt_bias": shift_dt_bias(w((lm, h))), "a_log": w((lm, h)),
            "d_skip": w((lm, h)),
            "ssm_norm": jnp.ones((lm, d_in), dtype),
            "w_out": w((lm, d_in, e))},
        "layers": {
            "attn_norm": jnp.ones((la, e), dtype),
            "wq": split_heads(w((la, e, cfg.q_dim)), cfg.num_heads),
            "wk": split_heads(w((la, e, cfg.kv_dim)), cfg.num_kv_heads),
            "wv": split_heads(w((la, e, cfg.kv_dim)), cfg.num_kv_heads),
            "wo": w((la, cfg.q_dim, e))},
        "moe_layers": dict(
            moe.init_moe_params(cfg, next(keys), dtype, layers=le),
            mlp_norm=jnp.ones((le, e), dtype)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((e, v))
    return params


def init_params(cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype | None = None) -> Params:
    """The parameter tree, layers stacked (leading ``[L]``), a stack a kind
    of layer; a matmul leaf is ``[.., K, N]``, contraction dimension first.

    But for the q / k / v projections of every GQA stack (dense, routed,
    window and full kinds, ``solar_open2``'s GQA layers): ``wq`` [L, H, D,
    E], ``wk`` [L, Hkv, D, E], ``wv`` [L, Hkv, Dv, E], that kind's heads
    split and the contraction dimension MINOR (:func:`split_heads`; the
    numbers are those of the ``[L, E, H x D]`` draw).  That is the order the
    step programs' dots read them in: the compiler emits the projection
    with its output already head-major and its weight contraction-minor, so
    an ``[L, E, H x D]`` leaf was transposed whole before the layer loop,
    every step, and a layer's slice written out of that copy again (13.5 %
    of mimo's device time; PERF.md section 6, PR 48).  Head-split, the dot's
    fusion takes the stacked leaf and the layer index and reads the bytes
    once.  One order for all three leaves of every GQA stack.  The latent
    block's two up projections are stored so too, for the same reason
    (their copies and slices were 7 % of longcat's device time; PERF.md
    section 6, PR 57): ``wq_b`` [L, H, nope + rope, q_lora] and ``wkv_b``
    [L, H, nope + v, kv_lora] (``[L, 2, H, ..]`` in the shortcut block),
    one order for every latent stack.  The latent block's ``wq_a`` /
    ``wkv_a`` and the linear layers' ``wq`` / ``wk`` / ``wv`` (another
    consumer: no head-major dot) are plain matmuls; a quantised leaf's
    scales follow its leaf (`quant.contraction_axis`).  Biases ``bq`` /
    ``bk`` / ``bv`` stay [L, H x D]."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.ssm:
        return _init_ssm_params(cfg, key, dtype)
    if cfg.latent and cfg.linear:
        return _init_latent_linear_params(cfg, key, dtype)
    if cfg.shortcut:
        return _init_shortcut_params(cfg, key, dtype)
    if cfg.latent:
        return _init_latent_params(cfg, key, dtype)
    if cfg.windowed:
        return _init_windowed_params(cfg, key, dtype)
    if cfg.linear:
        return _init_linear_params(cfg, key, dtype)
    if cfg.first_k_dense:
        raise NotImplementedError(
            f"model {cfg.name!r}: a dense prefix (first_k_dense="
            f"{cfg.first_k_dense}) is built for the latent block only")
    l, e, f, v = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    qd, kvd = cfg.q_dim, cfg.kv_dim
    keys = iter(jax.random.split(key, 16))

    def w(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers: Params = {
        "attn_norm": jnp.ones((l, e), dtype),
        "wq": split_heads(w(next(keys), (l, e, qd)), cfg.num_heads),
        "wk": split_heads(w(next(keys), (l, e, kvd)), cfg.num_kv_heads),
        "wv": split_heads(w(next(keys), (l, e, kvd)), cfg.num_kv_heads),
        "wo": w(next(keys), (l, qd, e)),
        "mlp_norm": jnp.ones((l, e), dtype),
    }
    if cfg.num_experts:
        from arks_tpu.models import moe
        layers.update(moe.init_moe_params(cfg, next(keys), dtype))
    else:
        layers.update({
            "w_gate": w(next(keys), (l, e, f)),
            "w_up": w(next(keys), (l, e, f)),
            "w_down": w(next(keys), (l, f, e)),
        })
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((l, qd), dtype)
        layers["bk"] = jnp.zeros((l, kvd), dtype)
        layers["bv"] = jnp.zeros((l, kvd), dtype)
    params: Params = {
        "embed": w(next(keys), (v, e)),
        "layers": layers,
        "final_norm": jnp.ones((e,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (e, v))
    return params


def shard_kv_heads(cfg: ModelConfig, tp: int) -> bool:
    return tp > 1 and cfg.num_kv_heads % tp == 0


def param_pspecs(cfg: ModelConfig, tp: int = 1) -> Params:
    """PartitionSpec pytree matching ``init_params`` (leading [L] dim on layers)."""
    if cfg.latent:
        raise NotImplementedError(
            f"model {cfg.name!r}: the latent block has no sharding rules "
            "(tensor / data / pipeline parallelism are not supported)")
    if cfg.windowed:
        raise NotImplementedError(
            f"model {cfg.name!r}: layers of two head counts have no "
            "sharding rules (tensor / data / pipeline parallelism are not "
            "supported)")
    if cfg.linear:
        raise NotImplementedError(
            f"model {cfg.name!r}: linear-attention layers and their state "
            "have no sharding rules (tensor / data / pipeline parallelism "
            "are not supported)")
    if cfg.ssm:
        raise NotImplementedError(
            f"model {cfg.name!r}: state-space layers and their state have "
            "no sharding rules: a share of the heads is not built (tensor "
            "/ data / pipeline parallelism are not supported)")
    kv = P(None, AXIS_MODEL if shard_kv_heads(cfg, tp) else None, None, None)
    kvb = P(None, AXIS_MODEL) if shard_kv_heads(cfg, tp) else P(None, None)
    layers: Params = {
        "attn_norm": P(None, None),
        "wq": P(None, AXIS_MODEL, None, None),
        "wk": kv,
        "wv": kv,
        "wo": P(None, AXIS_MODEL, None),
        "mlp_norm": P(None, None),
    }
    if cfg.num_experts:
        from arks_tpu.models import moe
        layers.update(moe.moe_pspecs(cfg, AXIS_MODEL, moe.shard_experts(cfg, tp)))
    else:
        layers.update({
            "w_gate": P(None, None, AXIS_MODEL),
            "w_up": P(None, None, AXIS_MODEL),
            "w_down": P(None, AXIS_MODEL, None),
        })
    if cfg.qkv_bias:
        layers["bq"] = P(None, AXIS_MODEL)
        layers["bk"] = kvb
        layers["bv"] = kvb
    specs: Params = {
        "embed": P(AXIS_MODEL, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, AXIS_MODEL)
    return specs


def cache_head_dim(cfg: ModelConfig, pad_head: bool = False) -> int:
    """Stored head dim: padded up to the 128-lane tile when requested, so
    models with head_dim < 128 (qwen2.5-0.5b, tiny test configs) ride the
    compiled Pallas decode kernels instead of the XLA fallback.  Zero
    padding is EXACT: padded K lanes add 0 to every q.k score and padded V
    lanes produce output columns the caller slices off.

    A latent model stores one row a token, ``kv_lora_rank +
    qk_rope_head_dim`` wide (576 -> 640 padded: the rotary lanes' tile is
    half zeros; the value lanes, the first ``kv_lora_rank``, are whole
    tiles)."""
    return _lane_padded(cfg.latent_row if cfg.latent else cfg.head_dim,
                        pad_head)


def _lane_padded(d: int, pad_head: bool) -> int:
    return -(-d // 128) * 128 if pad_head and d % 128 else d


def cache_value_dim(cfg: ModelConfig, pad_head: bool = False) -> int:
    """Stored width of a head's VALUES, padded as :func:`cache_head_dim`
    pads the keys: the same width, but for a GQA model whose values are
    narrower than its keys (``cfg.v_head_dim``: keys 192 stored as 256
    lanes, values 128 as 128)."""
    if cfg.latent or not cfg.v_head_dim:
        return cache_head_dim(cfg, pad_head)
    return _lane_padded(cfg.v_head_dim, pad_head)


def pad_heads(x: jnp.ndarray, d_store: int) -> jnp.ndarray:
    """Zero-pad the trailing head dim to the cache's stored width (ONE
    implementation — the attention ops' _pad_last)."""
    from arks_tpu.ops.attention import _pad_last
    return _pad_last(x, d_store)


def init_cache(cfg: ModelConfig, num_slots: int, max_len: int,
               dtype: jnp.dtype | None = None,
               quantized: bool = False, pad_head: bool = False) -> KVCache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, num_slots, cfg.num_kv_heads, max_len,
             cache_head_dim(cfg, pad_head))
    if quantized:
        return KVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32))
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def batch_axis_for(mesh: "Mesh | None"):
    """The mesh axes a batch dimension shards over: ``("slice", "data")``
    on a multi-slice mesh (dp rides DCN across slices AND ICI within),
    ``"data"``/``"slice"`` when only one is populated, None otherwise.
    PartitionSpec entries accept the tuple directly."""
    if mesh is None:
        return None
    from arks_tpu.parallel.mesh import AXIS_SLICE
    axes = [a for a in (AXIS_SLICE, AXIS_DATA) if mesh.shape.get(a, 1) > 1]
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def cache_pspecs(cfg: ModelConfig, tp: int = 1, dp: int = 1,
                 quantized: bool = False, batch=None) -> KVCache:
    batch = batch if batch is not None else (AXIS_DATA if dp > 1 else None)
    heads = AXIS_MODEL if shard_kv_heads(cfg, tp) else None
    spec = P(None, batch, heads, None, None)
    sspec = P(None, batch, heads, None) if quantized else None
    return KVCache(k=spec, v=spec, k_scale=sspec, v_scale=sspec)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page: int,
                     dtype: jnp.dtype | None = None,
                     quantized: bool = False,
                     pad_head: bool = False,
                     kv_bits: int = 8, win_pages: int = 0,
                     state_slots: int = 0) -> PagedKVCache:
    """``win_pages`` (a model with window layers): the pages of the window
    layers' pool; ``num_pages`` are then the full-attention layers'.
    ``state_slots`` (a model with recurrent layers): the slots its
    :class:`LinearState` holds; the pool is its GQA layers'."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.recurrent:
        if state_slots < 1:
            raise ValueError(f"model {cfg.name!r}: {cfg.recurrent_kind} "
                             "layers keep a state a slot (state_slots)")
        import dataclasses
        pool = init_paged_cache(
            dataclasses.replace(cfg, linear_period=0, layer_pattern="",
                                num_layers=cfg.num_full_layers),
            num_pages, page, dtype, quantized, pad_head, kv_bits)
        ll = cfg.num_linear_layers
        if cfg.ssm:
            # As the one-step kernel reads it (`ops/ssm_state.pack_state`).
            r = heads_a_tile(cfg.ssm_head_dim,
                             cfg.ssm_num_heads // cfg.ssm_groups)
            state = (cfg.ssm_num_heads // r, cfg.ssm_state_size,
                     r * cfg.ssm_head_dim)
            taps, channels = cfg.ssm_conv, cfg.ssm_conv_dim
        else:
            state = (cfg.linear_num_heads, cfg.linear_head_dim,
                     cfg.linear_head_dim)
            taps, channels = cfg.linear_conv, cfg.linear_conv_dim
        return pool._replace(lin=LinearState(
            s=jnp.zeros((ll, state_slots, *state), jnp.float32),
            conv=jnp.zeros((ll, state_slots, taps - 1, channels), dtype)))
    if cfg.windowed:
        if win_pages < 1:
            raise ValueError(f"model {cfg.name!r}: window layers keep a "
                             "pool of their own (win_pages)")
        import dataclasses
        pools = [init_paged_cache(
            dataclasses.replace(cfg, sliding_window=0, num_layers=layers,
                                num_kv_heads=kv),
            n, page, dtype, quantized, pad_head, kv_bits)
            for layers, n, kv in (
                (cfg.num_full_layers, num_pages, cfg.num_kv_heads),
                (cfg.num_window_layers, win_pages, cfg.kv_heads_of(True)))]
        return pools[0]._replace(win=pools[1])
    # (A pool row an attention SUBLAYER: a shortcut layer holds two.)
    shape = (cfg.num_attn_sublayers, num_pages, cfg.num_kv_heads, page,
             cache_head_dim(cfg, pad_head))
    # (The values' pool: as wide as the keys' but where the model's values
    # are narrower than its keys.)
    vwidth = cache_value_dim(cfg, pad_head)
    if cfg.latent:
        if quantized:
            raise ValueError(
                f"model {cfg.name!r}: a latent page is bf16 only (an "
                "int8 / int4 latent row is not built)")
        return PagedKVCache(k=jnp.zeros(shape, dtype), v=None)
    if quantized:
        if kv_bits not in (4, 8):
            raise ValueError(f"quantized kv_bits must be 4 or 8, got {kv_bits}")
        if kv_bits == 4 and page % 2:
            raise ValueError(f"int4 page size {page} must be even")
        rows = page // 2 if kv_bits == 4 else page
        return PagedKVCache(
            k=jnp.zeros(shape[:3] + (rows, shape[4]), jnp.int8),
            v=jnp.zeros(shape[:3] + (rows, vwidth), jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32))
    return PagedKVCache(k=jnp.zeros(shape, dtype),
                        v=jnp.zeros(shape[:4] + (vwidth,), dtype))


def paged_cache_pspecs(cfg: ModelConfig, tp: int = 1,
                       quantized: bool = False) -> PagedKVCache:
    """Pool sharding: kv heads over ``model`` when divisible (pages are
    whole-sequence stripes, so neither N nor P can shard without breaking
    page locality)."""
    heads = AXIS_MODEL if shard_kv_heads(cfg, tp) else None
    spec = P(None, None, heads, None, None)
    sspec = P(None, None, heads, None) if quantized else None
    return PagedKVCache(k=spec, v=spec, k_scale=sspec, v_scale=sspec)


def shard_paged_cache(cache: PagedKVCache, cfg: ModelConfig,
                      mesh: Mesh) -> PagedKVCache:
    tp = mesh.shape.get(AXIS_MODEL, 1)
    specs = paged_cache_pspecs(cfg, tp, quantized=cache.quantized)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), cache, specs)


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    tp = mesh.shape.get(AXIS_MODEL, 1)
    specs = param_pspecs(cfg, tp)
    from arks_tpu.models.quant import is_quantized, quantize_pspecs
    wq = params["layers"].get("wq")
    if is_quantized(wq):
        specs = quantize_pspecs(specs, bits=4 if "gs" in wq else 8)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def shard_cache(cache: KVCache, cfg: ModelConfig, mesh: Mesh) -> KVCache:
    tp = mesh.shape.get(AXIS_MODEL, 1)
    specs = cache_pspecs(cfg, tp, quantized=cache.quantized,
                         batch=batch_axis_for(mesh))
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), cache, specs)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


# Named scopes (``arks.<part>``) on the parts of the step a profile is read
# by: they travel in each op's metadata, so a trace's device time can be
# summed per part whatever the compiler numbers its fusions
# (docs/monitoring.md lists them; benchmarks/layer_metrics/_scopes.py reads
# them).  Scopes are metadata only: the compiled program is unchanged.
_scope = jax.named_scope


def _constrain(x: jnp.ndarray, mesh: Mesh | None, *spec) -> jnp.ndarray:
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _norm(x: jnp.ndarray, w: jnp.ndarray, cfg: ModelConfig,
          scale: float = 1.0) -> jnp.ndarray:
    """The model's norm: RMS norm times the learnt ``w``, or
    (``cfg.norm_gate`` g) times ``g sigmoid(w)``, which is 1 at ``w`` = 0;
    times ``scale`` in the same float32 product (a latent's
    ``cfg.mla_q_scale`` / ``mla_kv_scale``)."""
    if cfg.norm_gate:
        w = cfg.norm_gate * jax.nn.sigmoid(w.astype(jnp.float32))
    if scale != 1.0:
        w = w.astype(jnp.float32) * scale
    return rms_norm(x, w, cfg.rms_norm_eps)


def _post_norm(y: jnp.ndarray, lp: Params, name: str,
               cfg: ModelConfig) -> jnp.ndarray:
    """A sublayer's output on its way to the residual add: normed again
    where the model has sandwich norms (``cfg.norm_post``)."""
    if not cfg.norm_post:
        return y
    with _scope("arks.norm_post"):
        return _norm(y, lp[name], cfg)


def _qkv(h: jnp.ndarray, lp: Params, cfg: ModelConfig):
    """Normed ``h`` [.., E] -> q [.., H, D], k [.., Hkv, D], v [.., Hkv,
    Dv], the head counts the layer's leaves have (:func:`init_params`)."""
    q = qeinsum("...e,hde->...hd", h, lp["wq"])
    k = qeinsum("...e,hde->...hd", h, lp["wk"])
    v = qeinsum("...e,hde->...hd", h, lp["wv"])
    if cfg.qkv_bias:
        q = q + lp["bq"].reshape(q.shape[-2:])
        k = k + lp["bk"].reshape(k.shape[-2:])
        v = v + lp["bv"].reshape(v.shape[-2:])
    return q, k, v


@_scope("arks.attn_qkv")
def _block_qkv(h: jnp.ndarray, lp: Params, cfg: ModelConfig,
               positions: jnp.ndarray):
    """Pre-norm + qkv projection + head split + rope for a [B, T, E] block —
    shared by one-shot and chunked prefill so their math can never diverge."""
    x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv(x, lp, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _block_tail(h: jnp.ndarray, attn: jnp.ndarray, lp: Params,
                cfg: ModelConfig, mesh: Mesh | None, batch_axis: str | None,
                seq_axis: str | None = None) -> jnp.ndarray:
    """Output projection residual + MLP residual (post-attention half of the
    block) — the other shared piece of the prefill paths."""
    with _scope("arks.attn_out"):
        h = h + qeinsum("...q,qe->...e", attn, lp["wo"])
    h = h + _mlp(h, lp, cfg, mesh, batch_axis, seq_axis)
    return h


# A routed model's parts carry scopes of their own inside moe.py
# (arks.moe_route / moe_dot / moe_shared); the innermost scope names an op,
# so arks.ffn is what is left: the norm, and a dense FFN whole.
@_scope("arks.ffn")
def _mlp(h: jnp.ndarray, lp: Params, cfg: ModelConfig, mesh: Mesh | None,
         batch_axis: str | None, seq_axis: str | None = None,
         row_valid: jnp.ndarray | None = None, stack: tuple | None = None):
    """The FFN half of a block on normed ``h``: routed where the layer
    tree has a router (a model's dense prefix has none).  With
    ``row_valid`` a routed layer returns ``(out, counts)``; ``stack`` =
    ``(tree, index)`` is the stacked tree ``lp`` was taken out of and
    where (both :func:`moe.moe_ffn`'s)."""
    x = _norm(h, lp["mlp_norm"], cfg)

    def _int_spec(ndim: int, sharded_dim: int) -> list:
        # Intermediate spec: keep batch and (under context parallelism) the
        # T dim sharded — a None dim means REPLICATED to the constraint, and
        # regathering T across the seq axis would undo CP exactly where the
        # wide intermediates make it matter.
        spec = [None] * ndim
        spec[0] = batch_axis
        if ndim >= 3:
            spec[1] = seq_axis
        spec[sharded_dim] = AXIS_MODEL
        return spec

    if cfg.num_experts and "router" in lp:
        from arks_tpu.models import moe
        tp = mesh.shape.get(AXIS_MODEL, 1) if mesh is not None else 1

        def constrain(t, dim):
            # Pin the expert (or shared-F) dim of MoE intermediates to the
            # model axis so partial-expert outputs psum instead of regather.
            if not moe.shard_experts(cfg, tp) and t.ndim - dim == 2:
                return t  # expert dim replicated in this regime
            return _constrain(t, mesh, *_int_spec(t.ndim, dim))

        return moe.moe_ffn(x, lp, cfg, constrain if mesh is not None else None,
                           row_valid=row_valid, stack=stack)
    gate = qeinsum("...e,ef->...f", x, lp["w_gate"])
    up = qeinsum("...e,ef->...f", x, lp["w_up"])
    from arks_tpu.models.moe import swiglu
    act = swiglu(gate, up, cfg.swiglu_limit)
    act = _constrain(act, mesh, *_int_spec(act.ndim, act.ndim - 1))
    return qeinsum("...f,fe->...e", act, lp["w_down"])


@_scope("arks.lm_head")
def _unembed(h_last: jnp.ndarray, params: Params, cfg: ModelConfig,
             mesh: Mesh | None, batch_axis: str | None) -> jnp.ndarray:
    h_last = _norm(h_last, params["final_norm"], cfg)
    tied = cfg.tie_word_embeddings
    table = params["embed"] if tied else params["lm_head"]
    logits = unembed_logits(h_last, table, tied)
    return _constrain(logits, mesh, batch_axis, None)


def _wkv_b(lp: Params, cfg: ModelConfig, dtype) -> tuple[jnp.ndarray,
                                                         jnp.ndarray]:
    """``kv_b_proj`` as the absorbed form uses it: (W_uk [H, nope, C],
    W_uv [H, v, C]) with C = kv_lora_rank, widened to ``dtype``: the stored
    order (:func:`_init_latent_params`), no reshape."""
    from arks_tpu.models.quant import dequantize
    w = dequantize(lp["wkv_b"], dtype)
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


@_scope("arks.mla_q")
def _mla_q(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
           positions: jnp.ndarray) -> jnp.ndarray:
    """Normed ``x`` [B, T, E] -> the ABSORBED queries [B, T, H, C + rope]:
    down, norm, up, RoPE on the rotary lanes, and ``q_nope W_uk^T`` so that
    a head's score against a cached row is one dot over the row.  The
    query latent is scaled in its norm (``cfg.mla_q_scale``; the up
    projection is linear, so every lane of every head is)."""
    cq = _norm(qeinsum("...e,er->...r", x, lp["wq_a"]), lp["q_norm"], cfg,
               cfg.mla_q_scale)
    q = qeinsum("...r,hdr->...hd", cq, lp["wq_b"])
    q_nope, q_rope = (q[..., :cfg.qk_nope_head_dim],
                      q[..., cfg.qk_nope_head_dim:])
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_yarn)
    w_uk, _ = _wkv_b(lp, cfg, x.dtype)
    q_abs = jnp.einsum("bthn,hnc->bthc", q_nope, w_uk)
    return jnp.concatenate([q_abs, q_rope], axis=-1)


@_scope("arks.mla_kv")
def _mla_kv(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
            positions: jnp.ndarray) -> jnp.ndarray:
    """Normed ``x`` [B, T, E] -> the row a token caches [B, T, C + rope]:
    the normed latent (times ``cfg.mla_kv_scale``, which so reaches the
    no-rope keys and the values through ``W_kvb``) and the rotary key lanes
    all heads share."""
    kv = qeinsum("...e,er->...r", x, lp["wkv_a"])
    c = _norm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg,
              cfg.mla_kv_scale)
    k_r = apply_rope(kv[..., None, cfg.kv_lora_rank:], positions,
                     cfg.rope_theta, cfg.rope_yarn)[..., 0, :]
    return jnp.concatenate([c, k_r], axis=-1)


@_scope("arks.mla_out")
def _mla_out(attn: jnp.ndarray, lp: Params, cfg: ModelConfig,
             x: jnp.ndarray | None = None) -> jnp.ndarray:
    """``attn`` [T, H, C] (probabilities times the latent rows) -> [T, E]:
    un-absorb ``W_uv`` per head, then (``cfg.attn_out_gate``) times
    ``sigmoid(x Wg)`` elementwise over the H x v outputs, from the
    sublayer's normed input ``x`` [T, E], then the output projection."""
    _, w_uv = _wkv_b(lp, cfg, attn.dtype)
    o = jnp.einsum("thc,hvc->thv", attn, w_uv)
    o = o.reshape(o.shape[0], cfg.attn_out_dim)
    if cfg.attn_out_gate:
        with _scope("arks.mla_gate"):
            o = o * jax.nn.sigmoid(qeinsum(
                "te,eq->tq", x, lp["wg"]).astype(jnp.float32)).astype(o.dtype)
    return qeinsum("...q,qe->...e", o, lp["wo"])


def _kind_qkv(h: jnp.ndarray, lp: Params, cfg: ModelConfig,
              positions: jnp.ndarray, window: bool):
    """:func:`_block_qkv` for a layer of one kind of a model with window
    and full layers: the kind's head counts and RoPE (window: the first
    ``window_partial_rotary_factor`` of a head at ``window_rope_theta``;
    full: the first ``partial_rotary_factor`` of a head under
    ``rope_hf_yarn``); the values ``cfg.value_dim`` wide and times
    ``cfg.attn_value_scale``, as they are cached.  Also
    returns the per-head gate ``sigmoid(x Wg)`` [B, T, H] from the same
    normed input, or (``cfg.attn_out_gate``) the elementwise one [B, T, H,
    D] (None where the model has none).  ``cfg.use_rope`` False: no
    rotation of either kind."""
    with _scope("arks.attn_win_qkv" if window else "arks.attn_qkv"):
        x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(x, lp, cfg)
        if cfg.attn_value_scale != 1.0:
            v = (v.astype(jnp.float32) * cfg.attn_value_scale).astype(v.dtype)
        if not cfg.use_rope:
            def rope(t):
                return t
        elif window:
            rot = int(cfg.head_dim * cfg.window_partial_rotary_factor)
            rope = functools.partial(
                apply_rope, positions=positions, theta=cfg.window_rope_theta,
                rotary_dim=None if rot == cfg.head_dim else rot)
        else:
            rot = int(cfg.head_dim * cfg.partial_rotary_factor)
            rope = functools.partial(
                apply_rope, positions=positions, theta=cfg.rope_theta,
                yarn=cfg.rope_hf_yarn[:4],
                rotary_dim=None if rot == cfg.head_dim else rot,
                attention_factor=(cfg.rope_hf_yarn[4] if cfg.rope_hf_yarn
                                  else None))
        q, k = rope(q), rope(k)
    gate = None
    if cfg.attn_gate:
        with _scope("arks.attn_gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "...e,eh->...h", x, lp["attn_gate"]).astype(jnp.float32))
    elif cfg.attn_out_gate:
        with _scope("arks.attn_gate"):
            gate = jax.nn.sigmoid(qeinsum(
                "...e,eq->...q", x, lp["wg"]).astype(jnp.float32)).reshape(
                    q.shape)
    return q, k, v, gate


# Rows a block of the linear layers' chunked scan (the delta rule in its
# chunk form: one triangular solve and a few matrix products a block a
# head).  Within a block the decays enter as exp(G) on one side of a
# product and exp(-G) on the other, G the log decay summed from the block's
# start, so a block whose summed log decay passes float32's range (-87: a
# mean decay under 0.26 a step over 64 rows) is out of this form's reach.
LINEAR_CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _lane_rows(x: jnp.ndarray, start: jnp.ndarray, offsets: jnp.ndarray):
    """Rows ``start[b] + offsets[b, j]`` of the flat ``x [T, ..]``: ``[B, J,
    ..]`` (indices clipped; the caller masks what lies outside a lane)."""
    idx = jnp.clip(start[:, None] + offsets, 0, x.shape[0] - 1)
    return jnp.take(x, idx, axis=0)


def _carry_conv(pre: jnp.ndarray, w: jnp.ndarray, conv: jnp.ndarray,
                seq_q_start: jnp.ndarray, seq_q_len: jnp.ndarray,
                fresh: jnp.ndarray):
    """The causal depthwise convolution over the last K positions OF THE
    ROW'S SEQUENCE on the flat ``pre [T, C]`` with taps ``w [K, C]`` float32
    (oldest first): a lane's rows are contiguous from ``seq_q_start``; its
    first K - 1 rows reach into the slot's carry ``conv [B, K - 1, C]``
    (the last K - 1 rows ahead of the convolution, oldest first), read as
    zeros where ``fresh``.  Returns (``y [T, C]`` float32, the carry the
    lanes leave: the last K - 1 rows of (carry, a lane's rows))."""
    t, kk = pre.shape[0], w.shape[0]
    # Every row against the K - 1 flat rows before it ...
    y = sum(jnp.roll(pre, i, axis=0).astype(jnp.float32) * w[kk - 1 - i]
            for i in range(kk))
    # ... and a lane's first K - 1 rows again, against its carry.
    carry = jnp.where(fresh[:, None, None], 0, conv)               # [B, K-1, C]
    first = jnp.arange(kk - 1, dtype=jnp.int32)
    head = _lane_rows(pre, seq_q_start, jnp.broadcast_to(
        first, (conv.shape[0], kk - 1)))
    in_lane = first[None, :] < seq_q_len[:, None]                  # [B, K-1]
    line = jnp.concatenate(
        [carry, jnp.where(in_lane[..., None], head, 0)], axis=1)   # [B, 2K-2, C]
    fix = sum(line[:, i: i + kk - 1].astype(jnp.float32) * w[i]
              for i in range(kk))                                  # [B, K-1, C]
    y = y.at[jnp.where(in_lane, seq_q_start[:, None] + first, t)].set(
        fix, mode="drop")
    # The carry a lane leaves: the last K - 1 rows of (carry, its rows).
    back = seq_q_len[:, None] - (kk - 1) + first                   # [B, K-1]
    tail = _lane_rows(pre, seq_q_start, back)
    old = jnp.take_along_axis(
        carry, jnp.clip(back + kk - 1, 0, kk - 2)[..., None], axis=1)
    new_conv = jnp.where((seq_q_len > 0)[:, None, None],
                         jnp.where((back >= 0)[..., None], tail, old), conv)
    return y, new_conv


@_scope("arks.linear_qkv")
def _linear_qkv(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
                conv: jnp.ndarray, seq_q_start: jnp.ndarray,
                seq_q_len: jnp.ndarray, fresh: jnp.ndarray):
    """A linear layer's per-token half on the normed flat batch ``x [T,
    E]``: the q | k | v projections, the causal depthwise convolution over
    the last K positions OF THE ROW'S SEQUENCE (a lane's rows are contiguous
    from ``seq_q_start``; its first K - 1 rows reach into the slot's carry
    ``conv [B, K - 1, 3 H d]``, read as zeros where ``fresh``), SiLU, the
    L2 norm of q and k a head and q's scale; the log decay a channel and
    the step size a head.  Returns (q, k, v [T, H, d], g [T, H, d] f32 <= 0,
    beta [T, H] f32, the slots' new carry).  The Gated DeltaNet form
    (``cfg.linear_head_decay``): q and k have ``cfg.linear_key_heads``
    heads, key head j repeated for the value heads it serves; ONE log decay
    a head, ``g [T, H, 1]``, which :func:`_linear_state` broadcasts over
    the head's channels."""
    t = x.shape[0]
    h, d, kk = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv
    hk = cfg.linear_key_heads or h
    pre = jnp.concatenate([qeinsum("te,eq->tq", x, lp[n])
                           for n in ("wq", "wk", "wv")], axis=-1)  # [T, 3Hd]
    w = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]],
                        axis=-1).astype(jnp.float32)               # [K, 3Hd]
    y, new_conv = _carry_conv(pre, w, conv, seq_q_start, seq_q_len, fresh)
    y = jax.nn.silu(y)
    if hk == h:
        y = y.reshape(t, 3, h, d)

        def part(i):
            return y[:, i]
    else:
        edges = (0, hk * d, 2 * hk * d, y.shape[1])

        def part(i):
            return y[:, edges[i]: edges[i + 1]].reshape(t, -1, d)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(part(0)) * d ** -0.5
    k, v = unit(part(1)), part(2)
    # (The bias in float32: near -4 a bfloat16 sum would round the rate
    # itself by a few percent, and the decay compounds over the context.)
    if cfg.linear_head_decay:
        f = jnp.einsum("te,eh->th", x, lp["w_a"])
        g = (-jnp.exp(lp["a_log"].astype(jnp.float32))[None]
             * jax.nn.softplus(f.astype(jnp.float32)
                               + lp["dt_bias"].astype(jnp.float32))
             )[..., None]
    else:
        f = qeinsum("tr,rq->tq", qeinsum("te,er->tr", x, lp["w_f1"]),
                    lp["w_f2"])
        g = -jnp.exp(lp["a_log"].astype(jnp.float32))[None, :, None] \
            * jax.nn.softplus(f.astype(jnp.float32)
                              + lp["dt_bias"].astype(jnp.float32)
                              ).reshape(t, h, d)
    beta = jax.nn.sigmoid(jnp.einsum("te,eh->th", x, lp["w_b"]
                                     ).astype(jnp.float32))
    if cfg.linear_neg_eigval:
        beta = 2.0 * beta
    if hk != h:
        q, k = (jnp.repeat(a, h // hk, axis=1) for a in (q, k))
    return q, k, v, g, beta, new_conv.astype(conv.dtype)


def _delta_chunk(q, k, v, g, beta, s0):
    """One block of the gated delta rule in chunk form, a head at a time in
    parallel: rows ``q, k, v [H, C, d]``, log decay ``g [H, C, d]``, step
    size ``beta [H, C]`` (a row that carries no token: k = 0, g = 0, beta
    = 0), state ``s0 [H, d, d]`` before the block.  Returns (o [H, C, d],
    the state after the block).  With G the log decay summed from the
    block's start, K+ = k exp(G), K- = k exp(-G), Q+ = q exp(G): the rows'
    corrections solve ``(I + diag(beta) tril(K+ K-^T, -1)) U = diag(beta)
    (V - K+ S0)``; ``O = Q+ S0 + tril(Q+ K-^T) U``; ``S = exp(G_C) S0 + (k
    exp(G_C - G))^T U``.  Float32, every product at the highest precision:
    the state is kept in float32 and a bfloat16 pass over it would undo
    that."""
    c = q.shape[1]
    gs = jnp.cumsum(g, axis=1)
    kp, km, qp = k * jnp.exp(gs), k * jnp.exp(-gs), q * jnp.exp(gs)

    def mm(eq, a, b):
        return jnp.einsum(eq, a, b, precision=_HIGHEST)

    row = jnp.arange(c)
    below = (row[:, None] > row[None, :])
    a = jnp.where(below, mm("hid,hjd->hij", kp, km), 0.0)
    tri = jnp.eye(c, dtype=jnp.float32) + beta[..., None] * a
    rhs = beta[..., None] * (v - mm("hid,hdv->hiv", kp, s0))
    u = jax.lax.linalg.triangular_solve(
        tri, rhs, left_side=True, lower=True, unit_diagonal=True)
    qk = jnp.where(row[:, None] >= row[None, :],
                   mm("hid,hjd->hij", qp, km), 0.0)
    o = mm("hid,hdv->hiv", qp, s0) + mm("hij,hjv->hiv", qk, u)
    last = gs[:, -1]                                               # [H, d]
    s1 = jnp.exp(last)[..., None] * s0 + mm(
        "hjd,hjv->hdv", k * jnp.exp(last[:, None] - gs), u)
    return o, s1


@_scope("arks.linear_state")
def _linear_state(q, k, v, g, beta, s_all: jnp.ndarray, layer,
                  seq_q_start: jnp.ndarray, seq_q_len: jnp.ndarray,
                  fresh: jnp.ndarray):
    """The gated delta rule over a step's ragged flat batch: ``S' =
    diag(a_t) S``, ``S <- S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t =
    S^T q_t``, ``a_t = exp(g_t)``, lane b's rows in order from its slot's
    state ``s_all[layer, b]`` (``s_all [Ll, B, H, d, d]`` float32, rewritten
    in place; read as zeros where ``fresh``).  A lane
    of ONE row (a decode lane, a prompt's last token) takes one recurrence
    step, all such lanes in one kernel over their slots' states
    (:func:`arks_tpu.ops.linear_state.linear_state_step`: a state is read
    once and written once, no other slot's is touched, and the lanes' rows
    go from the flat batch into the kernel and back by their index); a
    lane of more rows is walked in blocks of ``LINEAR_CHUNK`` rows by
    :func:`_delta_chunk` (:func:`_walk_blocks`).  Returns (o [T, H, d] f32,
    ``s_all``)."""
    t, h, d = q.shape
    f32 = jnp.float32
    c = LINEAR_CHUNK
    # -- the lanes of one row: one kernel over their slots' states --------
    one = seq_q_len == 1
    out, s_all = linear_state_step(
        q, k, v, g, beta, s_all, layer,
        jnp.flatnonzero(one, size=one.shape[0], fill_value=0), jnp.sum(one),
        fresh, jnp.clip(seq_q_start, 0, t - 1), pad=c,
        interpret=jax.default_backend() != "tpu")

    # -- the lanes of more rows: blocks of C rows, in order ---------------
    out, s_all = _walk_blocks(_delta_chunk, (q, k, v, g, beta), out, s_all,
                              layer, seq_q_start, seq_q_len, fresh, c)
    return out[:t], s_all


def _walk_blocks(chunk, rows_in, out, s_all, layer, seq_q_start, seq_q_len,
                 fresh, c: int):
    """The lanes of MORE than one row of a recurrent layer's flat batch,
    walked in blocks of ``c`` rows, block after block and lane after lane,
    as many trips as the batch holds blocks: ``chunk(*rows, s0) -> (o [H,
    c, ..], s1)`` takes a block's rows of each of ``rows_in`` (``[T, ..]``,
    handed over head-major ``[.., c, ..]``, a row that carries no token
    zeros) and the state before the block (the slot's of ``s_all[layer]``,
    zeros where ``fresh`` at the lane's first block), and its results go
    into ``out [T + c, H, ..]`` and back into ``s_all``."""
    f32 = jnp.float32
    blocks = jnp.where(seq_q_len > 1, -(-seq_q_len // c), 0)       # [B]
    ends = jnp.cumsum(blocks)
    padded = [jnp.pad(x.astype(f32), ((0, c),) + ((0, 0),) * (x.ndim - 1))
              for x in rows_in]
    state = s_all.shape[2:]

    def block(i, carry):
        out, s_all = carry
        lane = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                           ends.shape[0] - 1).astype(jnp.int32)
        nth = i - (ends[lane] - blocks[lane])
        row0 = seq_q_start[lane] + nth * c
        live = jnp.arange(c) < seq_q_len[lane] - nth * c           # [C]

        def rows(x):
            r = jax.lax.dynamic_slice_in_dim(x, row0, c, axis=0)
            return jnp.swapaxes(jnp.where(
                live.reshape((c,) + (1,) * (r.ndim - 1)), r, 0), 0, 1)

        s0 = jax.lax.dynamic_slice(
            s_all, (layer, lane, 0, 0, 0), (1, 1, *state))[0, 0].astype(f32)
        s0 = jnp.where(fresh[lane] & (nth == 0), 0.0, s0)
        o, s1 = chunk(*(rows(x) for x in padded), s0)
        old = jax.lax.dynamic_slice_in_dim(out, row0, c, axis=0)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live[:, None, None], jnp.swapaxes(o, 0, 1), old),
            row0, axis=0)
        s_all = jax.lax.dynamic_update_slice(
            s_all, s1[None, None].astype(s_all.dtype),
            (layer, lane, 0, 0, 0))
        return out, s_all

    return jax.lax.fori_loop(0, ends[-1], block, (out, s_all))


@_scope("arks.linear_out")
def _linear_out(o: jnp.ndarray, x: jnp.ndarray, lp: Params,
                cfg: ModelConfig) -> jnp.ndarray:
    """``o [T, H, d]`` f32 -> [T, E]: RMS norm a head, times the low-rank
    gate ``sigmoid(x W_g1 W_g2)`` of the sublayer's normed input ``x`` (the
    Gated DeltaNet form: ``cfg.linear_gate_scale sigmoid(x W_z)``, a full
    projection), then the output projection."""
    t = o.shape[0]
    if cfg.linear_head_decay:
        gate = cfg.linear_gate_scale * jax.nn.sigmoid(qeinsum(
            "te,eq->tq", x, lp["w_z"]).astype(jnp.float32)).reshape(o.shape)
    else:
        gate = jax.nn.sigmoid(qeinsum(
            "tr,rq->tq", qeinsum("te,er->tr", x, lp["w_g1"]), lp["w_g2"]
        ).astype(jnp.float32)).reshape(o.shape)
    y = rms_norm(o, lp["o_norm"],
                 cfg.linear_norm_eps or cfg.rms_norm_eps) * gate
    return qeinsum("tq,qe->te", y.astype(x.dtype).reshape(t, cfg.linear_dim),
                   lp["wo"])


@_scope("arks.ssm_in")
def _ssm_in(x: jnp.ndarray, lp: Params, cfg: ModelConfig, conv: jnp.ndarray,
            seq_q_start: jnp.ndarray, seq_q_len: jnp.ndarray,
            fresh: jnp.ndarray):
    """A Mamba-2 mixer's per-token half on the normed flat batch ``x [T,
    E]``: the one input projection ``[z | x B C | dt]``, the causal
    depthwise convolution with bias over the last K positions of the row's
    sequence (:func:`_carry_conv`, the slot's carry ``conv [B, K - 1, H P +
    2 G N]``), SiLU, the step size ``dt = softplus(dt + dt_bias)`` and the
    log decay ``-exp(A_log) dt``, ONE a head.  Returns (z [T, H P], the
    head's input ``xs [T, H, P]`` f32, ``b, c [T, G, N]`` f32, ``dt, g [T,
    H]`` f32, the slots' new carry)."""
    t = x.shape[0]
    h, p, g, n = (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state_size)
    d_in, c = cfg.ssm_dim, cfg.ssm_conv_dim
    zxbcdt = qeinsum("te,qe->tq", x, lp["w_in"])
    z, pre, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in: d_in + c],
                  zxbcdt[:, d_in + c:])
    y, new_conv = _carry_conv(pre, lp["conv_w"].astype(jnp.float32), conv,
                              seq_q_start, seq_q_len, fresh)
    y = jax.nn.silu(y + lp["conv_b"].astype(jnp.float32))
    xs = y[:, :d_in].reshape(t, h, p)
    b = y[:, d_in: d_in + g * n].reshape(t, g, n)
    c = y[:, d_in + g * n:].reshape(t, g, n)
    # (The bias in float32, as a linear layer's: the decay compounds.)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    log_decay = -jnp.exp(lp["a_log"].astype(jnp.float32))[None] * dt
    return z, xs, b, c, dt, log_decay, new_conv.astype(conv.dtype)


def _ssd_chunk(x, b, c, g, s0):
    """One block of the selective scan in Mamba-2's chunk (SSD) form, the
    heads in parallel: rows ``x [H, C, P]`` (a head's input times its step
    size), ``b, c [G, C, N]`` (head h reads group ``h // (H / G)``), log
    decay ``g [H, C]`` (a row that carries no token: x = 0, g = 0), the
    state before the block ``s0 [H / r, N, r P]`` AS IT IS STORED
    (`ops/ssm_state.pack_state`: a head's ``[P, N]`` with the state's width
    down and r heads of a group side by side; its lanes are split into (r,
    P) here and nothing is transposed, so that the slots' states keep one
    layout through the step).  Returns (y [H, C, P], the state after the
    block, stored likewise).  With L the log decay summed from the block's
    start: ``y_t = exp(L_t) S0 C_t + sum_{s <= t} exp(L_t - L_s) (C_t .
    B_s) x_s``; ``S = exp(L_C) S0 + sum_s exp(L_C - L_s) x_s (x) B_s``;
    every exponent is <= 0.  Float32, every product at the highest
    precision, as :func:`_delta_chunk`."""
    h, rows, p = x.shape
    groups, n = b.shape[0], b.shape[2]
    r = s0.shape[-1] // p
    tiles = h // groups // r                       # a group's
    heads = (groups, tiles, r)
    gs = jnp.cumsum(g, axis=1).reshape(*heads, rows)
    x = x.reshape(*heads, rows, p)
    # The block's state in the layout the slots' states are STORED in, said
    # to the compiler in as many words: the products below contract over N,
    # and left to itself it lays every slot's state out N-minor for them
    # and copies all of it, 3 GB, to and from the kernel's layout around
    # each one-step call (compiled for a described v5e, PR 56).
    stored = Layout(major_to_minor=(0, 1, 2))
    s0 = with_layout_constraint(s0, stored).reshape(groups, tiles, n, r, p)

    def mm(eq, *a):
        return jnp.einsum(eq, *a, precision=_HIGHEST)

    row = jnp.arange(rows)
    m = jnp.exp(jnp.where(row[:, None] >= row[None, :],
                          gs[..., :, None] - gs[..., None, :], -jnp.inf))
    y = mm("gkjts,gkjsp->gkjtp",
           mm("gtn,gsn->gts", c, b)[:, None, None] * m, x) \
        + jnp.exp(gs)[..., None] * mm("gtn,gknjp->gkjtp", c, s0)
    last = gs[..., -1:]                                      # [G, k, r, 1]
    s1 = jnp.exp(last)[:, :, None] * s0 + mm(
        "gsn,gkjsp->gknjp", b, x * jnp.exp(last - gs)[..., None])
    return y.reshape(h, rows, p), with_layout_constraint(
        s1.reshape(groups * tiles, n, r * p), stored)


@_scope("arks.ssm_state")
def _ssm_state(xs, b, c, dt, g, s_all: jnp.ndarray, layer,
               seq_q_start: jnp.ndarray, seq_q_len: jnp.ndarray,
               fresh: jnp.ndarray):
    """The selective scan over a step's ragged flat batch: ``S_t = a_t
    S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t``, ``a_t = exp(g_t)``,
    lane b's rows in order from its slot's state ``s_all[layer, b]``
    (``s_all [Lm, B, H / r, N, r P]``: `ops/ssm_state.pack_state`; rewritten
    in place; read as zeros where ``fresh``).  As :func:`_linear_state`: a
    lane of ONE row takes one
    recurrence step, all such lanes in one kernel over their slots' states
    (:func:`arks_tpu.ops.ssm_state.ssm_state_step`); a lane of more rows is
    walked in blocks of ``LINEAR_CHUNK`` rows by :func:`_ssd_chunk`
    (:func:`_walk_blocks`).  Returns (y [T, H, P] f32, ``s_all``); the skip
    ``D x_t`` is :func:`_ssm_out`'s."""
    t = xs.shape[0]
    xdt = xs * dt[..., None]
    one = seq_q_len == 1
    out, s_all = ssm_state_step(
        xdt, b, c, g, s_all, layer,
        jnp.flatnonzero(one, size=one.shape[0], fill_value=0), jnp.sum(one),
        fresh, jnp.clip(seq_q_start, 0, t - 1), pad=LINEAR_CHUNK,
        interpret=jax.default_backend() != "tpu")
    out, s_all = _walk_blocks(_ssd_chunk, (xdt, b, c, g), out, s_all, layer,
                              seq_q_start, seq_q_len, fresh, LINEAR_CHUNK)
    return out[:t], s_all


@_scope("arks.ssm_out")
def _ssm_out(y: jnp.ndarray, xs: jnp.ndarray, z: jnp.ndarray, lp: Params,
             cfg: ModelConfig) -> jnp.ndarray:
    """``y [T, H, P]`` f32 (the state read out) -> [T, E]: plus the skip ``D
    x_t`` a head, times ``silu(z)``, the gate BEFORE the norm, an RMS norm
    over each of ``cfg.ssm_groups`` groups of channels times the learnt
    ``ssm_norm`` [H P], then the output projection."""
    t = y.shape[0]
    f32 = jnp.float32
    y = (y + lp["d_skip"].astype(f32)[None, :, None] * xs).reshape(
        t, cfg.ssm_dim) * jax.nn.silu(z.astype(f32))
    grouped = y.reshape(t, cfg.ssm_groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.rms_norm_eps)
    y = grouped.reshape(t, cfg.ssm_dim) * lp["ssm_norm"].astype(f32)
    return qeinsum("tq,qe->te", y.astype(z.dtype), lp["w_out"])


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    tables: jnp.ndarray,       # [B, MaxP] int32 — lane b == slot b
    tokens: jnp.ndarray,       # [T] int32 flat mixed token batch
    token_slot: jnp.ndarray,   # [T] int32 slot per token (-1 = padding)
    token_pos: jnp.ndarray,    # [T] int32 global position per token
    sample_src: jnp.ndarray,   # [B] int32 — flat index each lane samples from
    seq_q_start: jnp.ndarray,  # [B] int32 — lane's first flat-token index
    seq_q_len: jnp.ndarray,    # [B] int32 — lane's token count (0 inactive)
    seq_pos_start: jnp.ndarray,  # [B] int32 — lane's first global position
    mesh: Mesh | None = None,
    with_held: bool = False,
    win_tables: jnp.ndarray | None = None,  # [B, MaxP] — window layers'
) -> tuple[jnp.ndarray, PagedKVCache]:
    """One unified mixed prefill+decode forward: a flat ``[T]`` token batch
    carrying every decoding slot's next token PLUS one or more sequences'
    prefill-chunk tokens runs the model ONCE, writing all KV rows into the
    paged pool in place (write-then-attend, causal within each chunk) and
    returning logits only at ``sample_src`` — the last valid position of
    each lane that samples this step (decode lanes, and prefill lanes that
    just finished their prompt).  Returns (logits [B, V] f32, cache).

    This is the single-dispatch continuous-batching step: it replaces the
    chunk_step × decode_loop (× bucketed admit) program family for paged
    engines, so N prefills make progress per scheduler iteration without
    stalling decode.  Padding tokens (token_slot < 0) drop their writes and
    attend nothing; their activations are garbage no sample_src points at.
    Numerically equivalent to the legacy paths (same math, blockwise — only
    fp reassociation differs across chunk boundaries).

    ONE forward for every block, as ``cfg.layer_kinds()`` describes it: the
    head's stack (``cfg.head_layers`` layers: a dense prefix, or layer 0;
    full-attention layers, or linear ones where ``cfg.linear_head``), then a
    first period cut short by the prefix where the model has one
    (``cfg.short_period``), then a scan over the periods, each the period's
    INNER layers (an inner scan that takes them out of their stack by
    index) and its full layer, then the tail of inner layers behind the
    last whole period.  A block without inner layers (``cfg.inner_period``
    0: every layer a period) traces neither the inner scan nor its carry.
    The inner kind is the model's: window layers
    (``cfg.windowed``), which write and read the window pool (``cache.win``)
    through ``win_tables``, the same ragged launch told the window; or
    linear layers (``cfg.linear``), which read and write the slots' state
    (``cache.lin``) and no page.  The full kind is the model's too: GQA
    layers, or latent layers (``cfg.latent``) over the latent pool, one
    attention path, the absorbed one, for chunks and decode lanes alike,
    or shortcut layers (``cfg.shortcut``: two latent sublayers a layer over
    pool rows ``2 i`` and ``2 i + 1``, a dense FFN behind each, the routed
    layer's result carried from the first sublayer to the layer's end);
    all write and read the full pool through ``tables``.  The ONE-SUBLAYER
    block (``cfg.ssm``) does not fit a period of (inner layers, full layer)
    pairs of mixer AND FFN: each of its layers is a Mamba-2 mixer, a GQA
    layer's attention or a routed FFN ALONE, under one norm and one
    residual, from a stack of its own kind, and its periods are of unequal
    length; ``walk_pattern`` walks ``cfg.pattern_walk()`` instead, the same
    layer functions cut at the sublayer (``attend``, ``ffn``) and
    ``ssm_layer``, which reads and writes the slots' state (``cache.lin``)
    as a linear layer does, a run of equal periods one scan.  A layer
    function's
    ``src`` is ``(stack, index)``: the stacked tree of ``params`` its ``lp``
    was taken out of and where, for the routed FFN's overflow loop
    (:func:`moe.moe_ffn`).

    ``with_held``: a routed layer is handed the mask of the valid rows and
    ``src``, and the step returns a third result, counts: that function's
    three (four where the routers score identity experts) summed over the
    routed layers.  Without it a routed layer is
    handed neither (its padding rows are routed like any other; no valid
    row's output depends on them)."""
    from arks_tpu.ops.attention import (paged_latent_update_and_attend,
                                        paged_mixed_update_and_attend)
    t_flat = tokens.shape[0]
    cover = tables.shape[1] * cache.page
    # RoPE positions must be real for valid tokens; padding rows only need
    # a value the cache ops drop (their write_idx is routed past coverage).
    rope_pos = jnp.minimum(token_pos, cover - 1)[None]           # [1, T]
    valid = (token_slot >= 0)[None]
    first = cfg.head_layers
    head_name = "dense_layers" if cfg.first_k_dense else "head_layers"
    head = params[head_name] if first else params["layers"]
    with _scope("arks.embed"):
        h = embed_lookup(params["embed"], tokens[None],
                         head["attn_norm"].dtype)                # [1, T, E]
    kv_sharded = mesh is not None and shard_kv_heads(
        cfg, mesh.shape.get(AXIS_MODEL, 1))
    # ``moe.moe_ffn``'s counts of a layer that has no router (a fourth where
    # the model's routers score identity experts).
    no_counts = np.zeros((4 if cfg.zero_experts else 3,), np.int32)

    def ffn(h, lp, src):
        # Without ``with_held`` every layer's counts are the constant, and
        # what is summed of them below is dead code the lowering drops.
        if with_held and "router" in lp:
            y, held = _mlp(h, lp, cfg, mesh, None, row_valid=valid,
                           stack=src)
        else:
            y, held = _mlp(h, lp, cfg, mesh, None), no_counts
        return h + _post_norm(y, lp, "mlp_post_norm", cfg), held

    def latent_layer(h, lp, src, pool, tbl, index, window):
        del window
        x = _norm(h, lp["attn_norm"], cfg)
        attn, k = paged_latent_update_and_attend(
            _mla_q(x, lp, cfg, rope_pos)[0], _mla_kv(x, lp, cfg, rope_pos)[0],
            pool[0], tbl, token_slot, token_pos, seq_q_start, seq_q_len,
            seq_pos_start, index, dv=cfg.kv_lora_rank,
            scale=cfg.softmax_scale)
        y = _mla_out(attn, lp, cfg, x[0])[None]
        h, held = ffn(h + _post_norm(y, lp, "attn_post_norm", cfg), lp, src)
        return h, (k,) + tuple(pool[1:]), held

    def shortcut_layer(h, lp, src, pool, tbl, index, window):
        """Two latent sublayers, each with its dense FFN, pool rows ``2 i``
        and ``2 i + 1``; the routed layer reads the first sublayer's normed
        output and joins the stream at the layer's end."""
        del window
        from arks_tpu.models import moe
        k = pool[0]
        stack, at = src
        for j in range(cfg.attn_sublayers):
            # A sublayer's leaves come out of the STACKED tree by (layer,
            # sublayer), one slice a use: ``lp``'s ``[2, ..]`` slice of a
            # leaf has both sublayers' dots for users, so the compiler
            # writes it out a layer a step before either reads it.
            sub = {n: jax.tree.map(
                lambda a: jax.lax.dynamic_slice(
                    a, (at, j) + (0,) * (a.ndim - 2),
                    (1, 1) + a.shape[2:]).reshape(a.shape[2:]),
                stack[n]) for n in _SUBLAYER_LEAVES}
            x = _norm(h, sub["attn_norm"], cfg)
            attn, k = paged_latent_update_and_attend(
                _mla_q(x, sub, cfg, rope_pos)[0],
                _mla_kv(x, sub, cfg, rope_pos)[0], k, tbl, token_slot,
                token_pos, seq_q_start, seq_q_len, seq_pos_start,
                cfg.attn_sublayers * index + j, dv=cfg.kv_lora_rank,
                scale=cfg.softmax_scale)
            h = h + _mla_out(attn, sub, cfg, x[0])[None]
            with _scope("arks.ffn"):
                x = _norm(h, sub["mlp_norm"], cfg)
            if j == 0:
                if with_held:
                    shortcut, held = moe.moe_ffn(x, lp, cfg, row_valid=valid,
                                                 stack=src)
                else:
                    shortcut, held = moe.moe_ffn(x, lp, cfg), no_counts
            with _scope("arks.ffn"):
                act = moe.swiglu(
                    qeinsum("...e,ef->...f", x, sub["ffn_gate"]),
                    qeinsum("...e,ef->...f", x, sub["ffn_up"]))
                h = h + qeinsum("...f,fe->...e", act, sub["ffn_down"])
        return h + shortcut, (k,) + tuple(pool[1:]), held

    def attend(h, lp, pool, tbl, index, window: bool):
        """A GQA layer's attention sublayer on the residual stream."""
        q, k, v, gate = _kind_qkv(h, lp, cfg, rope_pos, window)
        attn, *pool = paged_mixed_update_and_attend(
            q[0], k[0], v[0], pool[0], pool[1], tbl, token_slot, token_pos,
            seq_q_start, seq_q_len, seq_pos_start, index, mesh, kv_sharded,
            model_axis=AXIS_MODEL, k_scale=pool[2], v_scale=pool[3],
            window=cfg.sliding_window if window else 0,
            sink=lp["attn_sink"] if cfg.sink_of(window) else None)
        if gate is not None:
            with _scope("arks.attn_gate"):
                gate = gate[0] if gate.ndim == 4 else gate[0][..., None]
                attn = attn * gate.astype(attn.dtype)
        attn = attn.reshape(1, t_flat, cfg.heads_of(window) * cfg.value_dim)
        attn = _constrain(attn, mesh, None, None, AXIS_MODEL)
        with _scope("arks.attn_win_out" if window else "arks.attn_out"):
            h = h + qeinsum("...q,qe->...e", attn, lp["wo"])
        return h, tuple(pool)

    def layer(h, lp, src, pool, tbl, index, window: bool):
        h, pool = attend(h, lp, pool, tbl, index, window)
        h, held = ffn(h, lp, src)
        return h, pool, held

    # A sequence that starts in this step starts from nothing, whatever
    # its slot's last sequence left in the state.
    fresh = (seq_q_len > 0) & (seq_pos_start == 0)

    def linear_layer(h, lp, src, lin, tbl, index, window):
        del tbl, window
        s_all, conv_all = lin
        x = _norm(h[0], lp["attn_norm"], cfg)
        q, k, v, g, beta, conv = _linear_qkv(
            x, lp, cfg, jax.lax.dynamic_index_in_dim(
                conv_all, index, 0, keepdims=False),
            seq_q_start, seq_q_len, fresh)
        o, s_all = _linear_state(q, k, v, g, beta, s_all, index,
                                 seq_q_start, seq_q_len, fresh)
        h = h + _post_norm(_linear_out(o, x, lp, cfg)[None], lp,
                           "attn_post_norm", cfg)
        lin = (s_all,
               jax.lax.dynamic_update_index_in_dim(conv_all, conv, index, 0))
        h, held = ffn(h, lp, src)
        return h, lin, held

    def ssm_layer(h, lp, lin, index):
        """A Mamba-2 mixer alone: one norm, one residual."""
        s_all, conv_all = lin
        x = _norm(h[0], lp["attn_norm"], cfg)
        z, xs, b, c, dt, g, conv = _ssm_in(
            x, lp, cfg, jax.lax.dynamic_index_in_dim(
                conv_all, index, 0, keepdims=False),
            seq_q_start, seq_q_len, fresh)
        y, s_all = _ssm_state(xs, b, c, dt, g, s_all, index, seq_q_start,
                              seq_q_len, fresh)
        h = h + _ssm_out(y, xs, z, lp, cfg)[None]
        return h, (s_all, jax.lax.dynamic_update_index_in_dim(
            conv_all, conv, index, 0))

    def walk_pattern(h):
        """The one-sublayer block: ``cfg.pattern_walk()``'s nested runs in
        model order, a run of equal things ONE traced body under a scan
        (the five equal periods; the ``ME`` pairs inside a period), every
        layer taken out of its kind's stack by its index there."""
        stacks = {"M": params["ssm_layers"], "*": params["layers"],
                  "E": params["moe_layers"]}

        def counts(node) -> dict:
            if isinstance(node, str):
                return {k: node.count(k) for k in stacks}
            return {k: sum(n * counts(item)[k] for item, n in node)
                    for k in stacks}

        def sublayer(kind, carry, at):
            h, full, lin, held = carry
            at = jnp.asarray(at, jnp.int32)
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, at, 0, keepdims=False), stacks[kind])
            if kind == "M":
                h, lin = ssm_layer(h, lp, lin, at)
            elif kind == "*":
                h, full = attend(h, lp, full, tables, at, False)
            else:
                h, n = ffn(h, lp, (stacks[kind], at))
                held = held + n
            return h, full, lin, held

        def run(node, carry, base):
            """``node`` from the stacks' indices ``base``: a string of
            layers, or runs ``((node, times), ..)`` one after the other."""
            if isinstance(node, str):
                seen = dict.fromkeys(stacks, 0)
                for kind in node:
                    carry = sublayer(kind, carry, base[kind] + seen[kind])
                    seen[kind] += 1
                return carry
            for item, times in node:
                per = counts(item)
                if times == 1:
                    carry = run(item, carry, base)
                else:
                    carry, _ = jax.lax.scan(
                        lambda c, i, item=item, per=per, base=base: (run(
                            item, c, {k: base[k] + i * per[k]
                                      for k in stacks}), None),
                        carry, jnp.arange(times, dtype=jnp.int32))
                base = {k: base[k] + times * per[k] for k in stacks}
            return carry

        return run(cfg.pattern_walk(), (
            h, tuple(cache[:4]), tuple(cache.lin), jnp.asarray(no_counts)),
            dict.fromkeys(stacks, 0))

    if cfg.ssm:
        h, full, inner, held = walk_pattern(h)
        return _mixed_tail(h, full, inner, held, params, cfg, sample_src,
                           mesh, with_held)
    full = tuple(cache[:4])
    per = cfg.inner_period
    if cfg.linear:
        inner, inner_layer = tuple(cache.lin), linear_layer
        inner_stack, inner_tbl = params["lin_layers"], None
    elif per:
        inner, inner_layer = tuple(cache.win[:4]), layer
        inner_stack, inner_tbl = params["win_layers"], win_tables
    else:
        inner = ()         # every layer a full layer: no inner kind at all
    full_layer = (shortcut_layer if cfg.shortcut
                  else latent_layer if cfg.latent else layer)
    held = no_counts
    # Where the head's layers are linear layers, the stacked inner layers'
    # state sits behind theirs, and the full pool starts at the first
    # period's layer.  (An offset of zero is left out of the traced index
    # arithmetic below, so that the older blocks lower the text they did.)
    inner_base = first if cfg.linear_head else 0
    full_base = 0 if cfg.linear_head else first
    if first:
        # (A linear layer takes neither the tables nor the flag.)
        head_layer = linear_layer if cfg.linear_head else full_layer

        def head_body(carry, xs):
            h, kept, n = head_layer(carry[0], xs[0], (head, xs[1]), carry[1],
                                    tables, xs[1], False)
            return (h, kept), n

        (h, kept), n = jax.lax.scan(
            head_body, (h, inner if cfg.linear_head else full),
            (head, jnp.arange(first, dtype=jnp.int32)))
        full, inner = (full, kept) if cfg.linear_head else (kept, inner)
        held = held + jnp.sum(n, axis=0)

    def inner_layers(h, inner, start, count: int):
        """``count`` inner layers from index ``start`` of the flat stack,
        each taken out by index, one slice a layer (a scan over the stack
        cut into periods would copy a period's layers out, then each of
        them again)."""
        def inner_body(c, j):
            h, inner = c
            at = start + j
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, at, 0, keepdims=False), inner_stack)
            h, inner, n = inner_layer(
                h, lp, (inner_stack, at), inner, inner_tbl,
                at + inner_base if inner_base else at, True)
            return (h, inner), n

        (h, inner), n = jax.lax.scan(
            inner_body, (h, inner), jnp.arange(count, dtype=jnp.int32))
        return h, inner, jnp.sum(n, axis=0)

    # A first period cut short by the dense prefix: its inner layers, then
    # the first of the stacked full layers; the scan takes the rest.
    lead, periods = cfg.lead_layers, params["layers"]
    if lead:
        if cfg.short_period:
            h, inner, n = inner_layers(h, inner, 0, cfg.short_period)
            held = held + n
        h, full, n = full_layer(
            h, jax.tree.map(lambda a: a[0], periods),
            (params["layers"], jnp.int32(0)), full, tables, full_base, False)
        held = held + n
        periods = jax.tree.map(lambda a: a[1:], periods)
        full_base += 1
    inner0 = max(cfg.short_period, 0)

    def period_body(carry, xs):
        h, full, inner = carry
        flp, i = xs
        if per:
            h, inner, n = inner_layers(
                h, inner, i * per + inner0 if inner0 else i * per, per)
        # The index is into the tree the program was handed, not into
        # what ``lead`` left of it: that slice would be a buffer to make.
        h, full, m = full_layer(
            h, flp, (params["layers"], i + 1 if lead else i), full, tables,
            full_base + i if full_base else i, False)
        return (h, full, inner), n + m if per else m

    (h, full, inner), n = jax.lax.scan(
        period_body, (h, full, inner),
        (periods, jnp.arange(cfg.num_periods, dtype=jnp.int32)))
    held = held + jnp.sum(n, axis=0)
    if cfg.inner_tail:
        h, inner, n = inner_layers(h, inner, inner0 + cfg.num_periods * per,
                                   cfg.inner_tail)
        held = held + n
    return _mixed_tail(h, full, inner, held, params, cfg, sample_src, mesh,
                       with_held)


def _mixed_tail(h, full, inner, held, params: Params, cfg: ModelConfig,
                sample_src, mesh, with_held: bool):
    """What :func:`mixed_step` hands back behind its last layer: the logits
    of the rows that sample, and the cache put together again."""
    with _scope("arks.lm_head"):
        h_sel = jnp.take(h[0], sample_src.astype(jnp.int32), axis=0)  # [B, E]
    logits = _unembed(h_sel, params, cfg, mesh, None)
    cache = PagedKVCache(*full,
                         win=PagedKVCache(*inner) if cfg.windowed else None,
                         lin=LinearState(*inner) if cfg.recurrent else None)
    return (logits, cache, held) if with_held else (logits, cache)


def prefill_layer(
    h: jnp.ndarray,       # [B, T, E]
    lp: Params,
    cfg: ModelConfig,
    positions: jnp.ndarray,  # [B, T]
    mesh: Mesh | None = None,
    batch_axis: str | None = None,
    seq_axis: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer block over a full sequence. Returns (h, k, v) — the
    single layer body shared by serving prefill and the training forward
    (train discards k/v; XLA dead-code-eliminates them there).

    With ``seq_axis`` set (context parallelism), T is sharded over that mesh
    axis and attention runs as a ring (arks_tpu.parallel.ring); every other
    op in the block is pointwise over T, so XLA partitions it for free.
    """
    b, t = h.shape[:2]
    q, k, v = _block_qkv(h, lp, cfg, positions)
    if seq_axis is not None and mesh is not None and mesh.shape.get(seq_axis, 1) > 1:
        from arks_tpu.parallel.ring import ring_prefill_attention
        heads_sharded = shard_kv_heads(cfg, mesh.shape.get(AXIS_MODEL, 1)) \
            and cfg.num_heads % mesh.shape.get(AXIS_MODEL, 1) == 0
        attn = ring_prefill_attention(q, k, v, mesh, seq_axis, batch_axis,
                                      heads_sharded=heads_sharded,
                                      model_axis=AXIS_MODEL)
        attn = attn.reshape(b, t, cfg.q_dim)
        attn = _constrain(attn, mesh, batch_axis, seq_axis, AXIS_MODEL)
    else:
        attn = prefill_attention(q, k, v).reshape(b, t, cfg.q_dim)
        attn = _constrain(attn, mesh, batch_axis, None, AXIS_MODEL)
    h = _block_tail(h, attn, lp, cfg, mesh, batch_axis, seq_axis)
    return h, k, v


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,   # [B, T] int32, padded to bucket length T
    lengths: jnp.ndarray,  # [B] int32 true lengths (<= T)
    mesh: Mesh | None = None,
    seq_axis: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run full prompts. Returns (last-token logits [B, V] float32,
    k [L, B, T, Hkv, D], v [L, B, T, Hkv, D]) for cache insertion.

    ``seq_axis`` turns on context parallelism: T shards over that mesh axis
    and attention runs as a ring (long-context prefill — prompts bigger than
    one chip's budget).  Padded positions sit at the END of the sequence, so
    under the global causal mask no valid query ever attends to them."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    h = embed_lookup(params["embed"], tokens,
                     params["layers"]["attn_norm"].dtype)
    h = _constrain(h, mesh, None, seq_axis, None)

    def body(h, lp):
        h, k, v = prefill_layer(h, lp, cfg, positions, mesh, None, seq_axis)
        return h, (k, v)

    h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    h_last = jnp.take_along_axis(
        h, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = _unembed(h_last, params, cfg, mesh, None)
    return logits, ks, vs


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    cache: KVCache,
    slot: jnp.ndarray,     # () int32 — cache slot being filled
    tokens: jnp.ndarray,   # [C] int32 — chunk tokens (padded on the last chunk)
    start: jnp.ndarray,    # () int32 — global position of tokens[0]
    valid: jnp.ndarray,    # () int32 — true token count in this chunk (<= C)
    mesh: Mesh | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """One chunk of an incremental (chunked) prefill for a single slot.

    Writes the chunk's KV into the cache at [start, start+C) and attends
    each query over the full cached prefix [0, start+i] — so a long prompt
    is processed as a sequence of bounded dispatches that interleave with
    decode steps instead of one monolithic prefill that stalls every
    decoding slot.  Returns (logits [1, V] f32 for the chunk's LAST VALID
    token — only meaningful on the final chunk — and the updated cache).

    Numerically equivalent to one-shot prefill (same math, blockwise):
    chunk-boundary differences are pure fp reassociation.  Padding rows on
    the final chunk write garbage KV beyond the prompt length; every read
    path masks by position, and decode overwrites them as generation
    proceeds (same invariant as decode's slot-0 garbage writes).
    """
    c = tokens.shape[0]
    positions = (start + jnp.arange(c, dtype=jnp.int32))[None]  # [1, C]
    h = embed_lookup(params["embed"], tokens[None],
                     params["layers"]["attn_norm"].dtype)       # [1, C, E]
    quantized = cache.quantized

    def body(carry, xs):
        h, kc, vc, ksc, vsc = carry
        lp, layer = xs
        q, k, v = _block_qkv(h, lp, cfg, positions)

        # Write the chunk's KV rows (head-major cache layout).
        kt = pad_heads(jnp.swapaxes(k[0], 0, 1), kc.shape[-1])
        vt = pad_heads(jnp.swapaxes(v[0], 0, 1), kc.shape[-1])
        at = (layer, slot.astype(jnp.int32), 0, start.astype(jnp.int32), 0)
        if quantized:
            from arks_tpu.ops.pallas_attention import quantize_kv
            kq, ks = quantize_kv(kt)
            vq, vs = quantize_kv(vt)
            kc = jax.lax.dynamic_update_slice(kc, kq[None, None], at)
            vc = jax.lax.dynamic_update_slice(vc, vq[None, None], at)
            ksc = jax.lax.dynamic_update_slice(ksc, ks[None, None], at[:-1])
            vsc = jax.lax.dynamic_update_slice(vsc, vs[None, None], at[:-1])
        else:
            kc = jax.lax.dynamic_update_slice(kc, kt[None, None].astype(kc.dtype), at)
            vc = jax.lax.dynamic_update_slice(vc, vt[None, None].astype(vc.dtype), at)

        # Attend over this slot's cache prefix (chunk rows included).
        kc_l = jax.lax.dynamic_index_in_dim(kc, layer, 0, keepdims=False)
        vc_l = jax.lax.dynamic_index_in_dim(vc, layer, 0, keepdims=False)
        kc_s = jax.lax.dynamic_index_in_dim(kc_l, slot, 0, keepdims=False)
        vc_s = jax.lax.dynamic_index_in_dim(vc_l, slot, 0, keepdims=False)
        ks_s = vs_s = None
        if quantized:
            ks_s = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(ksc, layer, 0, keepdims=False),
                slot, 0, keepdims=False)
            vs_s = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(vsc, layer, 0, keepdims=False),
                slot, 0, keepdims=False)
        g = cfg.num_heads // cfg.num_kv_heads
        qg = jnp.transpose(
            q[0].reshape(c, cfg.num_kv_heads, g, cfg.head_dim), (1, 2, 0, 3))
        d_store = kc.shape[-1]
        if d_store != cfg.head_dim:
            # Lane-padded cache: pad q (prescaled so the op's 1/sqrt(stored
            # d) nets to 1/sqrt(head_dim)); the padded V columns slice off.
            qg = pad_heads(qg, d_store) * ((d_store / cfg.head_dim) ** 0.5)
        from arks_tpu.ops.attention import chunk_attention_xla
        attn = chunk_attention_xla(qg, kc_s, vc_s, start, ks_s, vs_s)
        attn = jnp.transpose(attn[..., : cfg.head_dim],
                             (2, 0, 1, 3)).reshape(1, c, cfg.q_dim)
        attn = _constrain(attn, mesh, None, None, AXIS_MODEL)
        h = _block_tail(h, attn, lp, cfg, mesh, None)
        return (h, kc, vc, ksc, vsc), None

    (h, kc, vc, ksc, vsc), _ = jax.lax.scan(
        body, (h, cache.k, cache.v, cache.k_scale, cache.v_scale),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h_last = jax.lax.dynamic_index_in_dim(h[0], valid - 1, 0, keepdims=True)
    logits = _unembed(h_last, params, cfg, mesh, None)
    return logits, KVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def insert(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray,
           slot: jnp.ndarray) -> KVCache:
    """Insert prefill KV ([L, 1, T, Hkv, D]) into decode cache at ``slot``.

    T must be <= cache max_len; entries beyond the true length are masked by
    the per-slot length at decode time and overwritten as decoding proceeds.
    Prefill emits time-major KV; the cache is head-major, so transpose here
    (once per prompt — decode never pays for it).  Quantized caches get the
    rows quantized to int8 + per-token scales here.
    """
    start = (0, slot.astype(jnp.int32), 0, 0, 0)
    k_new = pad_heads(jnp.swapaxes(k_new, 2, 3), cache.k.shape[-1])
    v_new = pad_heads(jnp.swapaxes(v_new, 2, 3), cache.v.shape[-1])
    if cache.quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        kq, ks = quantize_kv(k_new)  # int8 [L,1,Hkv,T,D], f32 [L,1,Hkv,T]
        vq, vs = quantize_kv(v_new)
        sstart = (0, slot.astype(jnp.int32), 0, 0)
        return KVCache(
            k=jax.lax.dynamic_update_slice(cache.k, kq, start),
            v=jax.lax.dynamic_update_slice(cache.v, vq, start),
            k_scale=jax.lax.dynamic_update_slice(cache.k_scale, ks, sstart),
            v_scale=jax.lax.dynamic_update_slice(cache.v_scale, vs, sstart),
        )
    return KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), start),
        v=jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), start),
    )


def insert_pages(cache: PagedKVCache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 pages: jnp.ndarray, n_pages: jnp.ndarray) -> PagedKVCache:
    """Insert prefill KV ([L, 1, T, Hkv, D] time-major) into the first
    ``n_pages`` pool pages listed in ``pages`` ([T/page] int32, padded).

    The paged counterpart of ``insert``: page j gets positions
    [j*page, (j+1)*page); the last valid page's tail rows beyond the true
    prompt length are garbage that every read path masks by length (same
    invariant as bucket padding in the slot cache).  Pages listed beyond
    ``n_pages`` are never touched — the engine only allocates what the
    prompt needs."""
    page = cache.page
    int4 = cache.kv_bits == 4
    rows = page // 2 if int4 else page
    kt = pad_heads(jnp.swapaxes(k_new, 2, 3), cache.k.shape[-1])
    vt = pad_heads(jnp.swapaxes(v_new, 2, 3), cache.v.shape[-1])
    quantized = cache.quantized
    if quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        qm = 7 if int4 else 127
        kt, ks = quantize_kv(kt, qmax=qm)   # int8 + [L, 1, Hkv, T] f32
        vt, vs = quantize_kv(vt, qmax=qm)
        if int4:
            from arks_tpu.ops.paged_attention import pack_int4
            kt = pack_int4(kt, axis=3)
            vt = pack_int4(vt, axis=3)
    else:
        kt = kt.astype(cache.k.dtype)
        vt = vt.astype(cache.v.dtype)

    def body(j, c):
        kc, vc, ksc, vsc = c
        pg = pages[j]
        kb = jax.lax.dynamic_slice(
            kt, (0, 0, 0, j * rows, 0), kt.shape[:3] + (rows, kt.shape[4]))
        vb = jax.lax.dynamic_slice(
            vt, (0, 0, 0, j * rows, 0), vt.shape[:3] + (rows, vt.shape[4]))
        at = (0, pg, 0, 0, 0)
        kc = jax.lax.dynamic_update_slice(kc, kb, at)
        vc = jax.lax.dynamic_update_slice(vc, vb, at)
        if quantized:
            ksb = jax.lax.dynamic_slice(
                ks, (0, 0, 0, j * page), ks.shape[:3] + (page,))
            vsb = jax.lax.dynamic_slice(
                vs, (0, 0, 0, j * page), vs.shape[:3] + (page,))
            ksc = jax.lax.dynamic_update_slice(ksc, ksb, at[:-1])
            vsc = jax.lax.dynamic_update_slice(vsc, vsb, at[:-1])
        return (kc, vc, ksc, vsc)

    kc, vc, ksc, vsc = jax.lax.fori_loop(
        0, n_pages.astype(jnp.int32),
        body, (cache.k, cache.v, cache.k_scale, cache.v_scale))
    return PagedKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def insert_batch(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 slots: jnp.ndarray) -> KVCache:
    """Insert M prompts' prefill KV ([L, M, T, Hkv, D] time-major) into M
    slots — the batched-admission counterpart of ``insert`` (M is small
    and static, so the per-slot writes unroll)."""
    m = k_new.shape[1]
    kt = pad_heads(jnp.swapaxes(k_new, 2, 3), cache.k.shape[-1])
    vt = pad_heads(jnp.swapaxes(v_new, 2, 3), cache.v.shape[-1])
    if cache.quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        kt, ksn = quantize_kv(kt)
        vt, vsn = quantize_kv(vt)
    else:
        kt = kt.astype(cache.k.dtype)
        vt = vt.astype(cache.v.dtype)
    kc, vc, ksc, vsc = cache.k, cache.v, cache.k_scale, cache.v_scale
    for i in range(m):
        at = (0, slots[i], 0, 0, 0)
        kc = jax.lax.dynamic_update_slice(
            kc, jax.lax.dynamic_slice_in_dim(kt, i, 1, axis=1), at)
        vc = jax.lax.dynamic_update_slice(
            vc, jax.lax.dynamic_slice_in_dim(vt, i, 1, axis=1), at)
        if cache.quantized:
            ksc = jax.lax.dynamic_update_slice(
                ksc, jax.lax.dynamic_slice_in_dim(ksn, i, 1, axis=1), at[:-1])
            vsc = jax.lax.dynamic_update_slice(
                vsc, jax.lax.dynamic_slice_in_dim(vsn, i, 1, axis=1), at[:-1])
    return KVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def insert_pages_batch(cache: PagedKVCache, k_new: jnp.ndarray,
                       v_new: jnp.ndarray, pages: jnp.ndarray,
                       n_pages: jnp.ndarray) -> PagedKVCache:
    """Batched ``insert_pages``: M prompts ([L, M, T, Hkv, D], T a page
    multiple) into their page lists ([M, T/page] int32, first n_pages[i]
    valid per prompt)."""
    page = cache.page
    int4 = cache.kv_bits == 4
    rows = page // 2 if int4 else page
    m = k_new.shape[1]
    kt = pad_heads(jnp.swapaxes(k_new, 2, 3), cache.k.shape[-1])
    vt = pad_heads(jnp.swapaxes(v_new, 2, 3), cache.v.shape[-1])
    quantized = cache.quantized
    if quantized:
        from arks_tpu.ops.pallas_attention import quantize_kv
        qm = 7 if int4 else 127
        kt, ksn = quantize_kv(kt, qmax=qm)
        vt, vsn = quantize_kv(vt, qmax=qm)
        if int4:
            from arks_tpu.ops.paged_attention import pack_int4
            kt = pack_int4(kt, axis=3)
            vt = pack_int4(vt, axis=3)
    else:
        kt = kt.astype(cache.k.dtype)
        vt = vt.astype(cache.v.dtype)
    kc, vc, ksc, vsc = cache.k, cache.v, cache.k_scale, cache.v_scale

    for i in range(m):
        kti = jax.lax.dynamic_slice_in_dim(kt, i, 1, axis=1)  # [L,1,Hkv,T,D]
        vti = jax.lax.dynamic_slice_in_dim(vt, i, 1, axis=1)
        if quantized:
            ksi = jax.lax.dynamic_slice_in_dim(ksn, i, 1, axis=1)
            vsi = jax.lax.dynamic_slice_in_dim(vsn, i, 1, axis=1)

        def body(j, c, i=i, kti=kti, vti=vti,
                 ksi=ksi if quantized else None,
                 vsi=vsi if quantized else None):
            kc, vc, ksc, vsc = c
            pg = pages[i, j]
            at = (0, pg, 0, 0, 0)
            kb = jax.lax.dynamic_slice(
                kti, (0, 0, 0, j * rows, 0),
                kti.shape[:3] + (rows, kti.shape[4]))
            vb = jax.lax.dynamic_slice(
                vti, (0, 0, 0, j * rows, 0),
                vti.shape[:3] + (rows, vti.shape[4]))
            kc = jax.lax.dynamic_update_slice(kc, kb, at)
            vc = jax.lax.dynamic_update_slice(vc, vb, at)
            if quantized:
                ksb = jax.lax.dynamic_slice(
                    ksi, (0, 0, 0, j * page), ksi.shape[:3] + (page,))
                vsb = jax.lax.dynamic_slice(
                    vsi, (0, 0, 0, j * page), vsi.shape[:3] + (page,))
                ksc = jax.lax.dynamic_update_slice(ksc, ksb, at[:-1])
                vsc = jax.lax.dynamic_update_slice(vsc, vsb, at[:-1])
            return (kc, vc, ksc, vsc)

        kc, vc, ksc, vsc = jax.lax.fori_loop(
            0, n_pages[i].astype(jnp.int32), body, (kc, vc, ksc, vsc))
    return PagedKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def gather_pool_pages(cache: PagedKVCache, pages: jnp.ndarray):
    """Whole pool pages as contiguous pool-NATIVE staging blocks for the
    host prefix tier's spill path: ``(k, v, k_scale, v_scale)``, each
    ``[L, G, Hkv, P, D]`` (scales ``[L, G, Hkv, P]``; None when the pool
    is not kv-quantized).  Raw pool bytes — int8 stays int8 — so a later
    scatter_pool_pages restore reproduces the device state bit-exactly."""
    from arks_tpu.ops.paged_attention import paged_pool_gather
    k = paged_pool_gather(cache.k, pages)
    v = paged_pool_gather(cache.v, pages)
    if cache.quantized:
        return (k, v, paged_pool_gather(cache.k_scale, pages),
                paged_pool_gather(cache.v_scale, pages))
    return k, v, None, None


def scatter_pool_pages(cache: PagedKVCache, k_blocks: jnp.ndarray,
                       v_blocks: jnp.ndarray, pages: jnp.ndarray,
                       n_valid: jnp.ndarray, k_scale=None,
                       v_scale=None) -> PagedKVCache:
    """Restore pool-native page blocks (the inverse of gather_pool_pages)
    into the first ``n_valid`` pages listed in ``pages`` — the host
    prefix tier's H2D scatter.  Blocks arrive already in pool layout and
    dtype (incl. kv-quantized int8 + per-token scales), so no transpose
    or re-quantization happens on device: the written pages are byte
    copies of what the original prefill wrote."""
    from arks_tpu.ops.paged_attention import paged_pool_scatter
    kc = paged_pool_scatter(cache.k, k_blocks, pages, n_valid)
    vc = paged_pool_scatter(cache.v, v_blocks, pages, n_valid)
    ksc, vsc = cache.k_scale, cache.v_scale
    if cache.quantized:
        ksc = paged_pool_scatter(ksc, k_scale, pages, n_valid)
        vsc = paged_pool_scatter(vsc, v_scale, pages, n_valid)
    return PagedKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def gather_pages(cache: PagedKVCache, tables_row: jnp.ndarray,
                 layer: jnp.ndarray):
    """One slot's cache as contiguous per-layer views: returns
    (k [Hkv, S, D], v, k_scale [Hkv, S] | None, v_scale | None) for
    ``layer``, gathered through the slot's table row ([MaxP] int32).
    Chunked prefill's per-slot attention uses this — a full read of one
    slot's layer cache, which the attention itself would do anyway."""
    from arks_tpu.ops.paged_attention import paged_gather_kv, unpack_int4

    int4 = cache.kv_bits == 4

    def per(pool, unpack=False):
        # One pool-gather implementation (paged_attention.paged_gather_kv);
        # a [1, MaxP] table row is a batch of one.  int4 pools unpack AFTER
        # the gather (only the slot's rows, never the whole pool).
        g = paged_gather_kv(pool, tables_row[None], layer)[0]
        return unpack_int4(g, axis=1) if unpack else g

    k = per(cache.k, int4)
    v = per(cache.v, int4)
    if cache.quantized:
        return k, v, per(cache.k_scale), per(cache.v_scale)
    return k, v, None, None


def prefill_chunk_paged(
    params: Params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    tables_row: jnp.ndarray,  # [MaxP] int32 — the slot's block table
    tokens: jnp.ndarray,      # [C] int32 — chunk tokens (C == cache.page)
    start: jnp.ndarray,       # () int32 — global position of tokens[0]
    valid: jnp.ndarray,       # () int32 — true token count (<= C)
    mesh: Mesh | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Chunked prefill against the paged pool: chunk == page, so each chunk
    fills exactly the page ``tables_row[start / page]`` (one dynamic-slice
    write, no scatter), and attention reads the slot's pages — including
    PREFIX pages other slots share, which is how a prefix hit skips its
    recompute without any KV copy."""
    c = tokens.shape[0]
    page = cache.page
    if c != page:
        raise ValueError(f"paged chunk size {c} must equal the page size {page}")
    positions = (start + jnp.arange(c, dtype=jnp.int32))[None]  # [1, C]
    h = embed_lookup(params["embed"], tokens[None],
                     params["layers"]["attn_norm"].dtype)       # [1, C, E]
    quantized = cache.quantized
    pg = jax.lax.dynamic_index_in_dim(
        tables_row, start.astype(jnp.int32) // page, 0, keepdims=False)

    def body(carry, xs):
        h, kc, vc, ksc, vsc = carry
        lp, layer = xs
        q, k, v = _block_qkv(h, lp, cfg, positions)

        kt = pad_heads(jnp.swapaxes(k[0], 0, 1), kc.shape[-1])
        vt = pad_heads(jnp.swapaxes(v[0], 0, 1), kc.shape[-1])
        at = (layer, pg.astype(jnp.int32), 0, 0, 0)
        if quantized:
            from arks_tpu.ops.pallas_attention import quantize_kv
            int4 = kc.shape[3] != ksc.shape[3]
            qm = 7 if int4 else 127
            kq, ks = quantize_kv(kt, qmax=qm)
            vq, vs = quantize_kv(vt, qmax=qm)
            if int4:
                from arks_tpu.ops.paged_attention import pack_int4
                kq = pack_int4(kq, axis=1)
                vq = pack_int4(vq, axis=1)
            kc = jax.lax.dynamic_update_slice(kc, kq[None, None], at)
            vc = jax.lax.dynamic_update_slice(vc, vq[None, None], at)
            ksc = jax.lax.dynamic_update_slice(ksc, ks[None, None], at[:-1])
            vsc = jax.lax.dynamic_update_slice(vsc, vs[None, None], at[:-1])
        else:
            kc = jax.lax.dynamic_update_slice(kc, kt[None, None].astype(kc.dtype), at)
            vc = jax.lax.dynamic_update_slice(vc, vt[None, None].astype(vc.dtype), at)

        kc_s, vc_s, ks_s, vs_s = gather_pages(
            PagedKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc),
            tables_row, layer)
        g = cfg.num_heads // cfg.num_kv_heads
        qg = jnp.transpose(
            q[0].reshape(c, cfg.num_kv_heads, g, cfg.head_dim), (1, 2, 0, 3))
        d_store = kc.shape[-1]
        if d_store != cfg.head_dim:
            # Lane-padded cache: pad q (prescaled so the op's 1/sqrt(stored
            # d) nets to 1/sqrt(head_dim)); the padded V columns slice off.
            qg = pad_heads(qg, d_store) * ((d_store / cfg.head_dim) ** 0.5)
        from arks_tpu.ops.attention import chunk_attention_xla
        attn = chunk_attention_xla(qg, kc_s, vc_s, start, ks_s, vs_s)
        attn = jnp.transpose(attn[..., : cfg.head_dim],
                             (2, 0, 1, 3)).reshape(1, c, cfg.q_dim)
        attn = _constrain(attn, mesh, None, None, AXIS_MODEL)
        h = _block_tail(h, attn, lp, cfg, mesh, None)
        return (h, kc, vc, ksc, vsc), None

    (h, kc, vc, ksc, vsc), _ = jax.lax.scan(
        body, (h, cache.k, cache.v, cache.k_scale, cache.v_scale),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h_last = jax.lax.dynamic_index_in_dim(h[0], valid - 1, 0, keepdims=True)
    logits = _unembed(h_last, params, cfg, mesh, None)
    return logits, PagedKVCache(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


def extract(cache: KVCache, slot: jnp.ndarray,
            dtype: jnp.dtype | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Read one slot's KV back out time-major ``[L, 1, S, Hkv, D]`` — the
    inverse of ``insert`` (dequantized for int8 caches; re-inserting
    round-trips exactly because quantize(dequantize(x)) reproduces the same
    int8 values and scales).  Serves the prefix cache's harvest of
    chunk-prefilled prompts, whose KV exists only inside the slotted cache.
    """
    k = jax.lax.dynamic_index_in_dim(cache.k, slot, 1, keepdims=True)
    v = jax.lax.dynamic_index_in_dim(cache.v, slot, 1, keepdims=True)
    if cache.quantized:
        ks = jax.lax.dynamic_index_in_dim(cache.k_scale, slot, 1, keepdims=True)
        vs = jax.lax.dynamic_index_in_dim(cache.v_scale, slot, 1, keepdims=True)
        out = dtype or jnp.bfloat16
        k = (k.astype(jnp.float32) * ks[..., None]).astype(out)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(out)
    elif dtype is not None:
        k = k.astype(dtype)
        v = v.astype(dtype)
    return jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: KVCache | PagedKVCache,
    tokens: jnp.ndarray,   # [B] int32 — current token per slot
    lengths: jnp.ndarray,  # [B] int32 — tokens already in cache per slot
    mesh: Mesh | None = None,
    batch_axis: str | None = None,
    tables: jnp.ndarray | None = None,  # [B, MaxP] int32 — PagedKVCache only
) -> tuple[jnp.ndarray, KVCache | PagedKVCache]:
    """Advance every slot one token. The current token's KV is written at
    position ``lengths`` (so the new valid length is lengths+1). Returns
    (logits [B, V] float32, updated cache).

    PRECONDITION (slot cache): lengths[b] < cache.max_len for every active
    slot.  At lengths == max_len the KV scatter is silently dropped (JAX
    out-of-bounds scatter semantics) and logits would be computed against
    stale cache — the engine must retire or evict a slot before it fills.
    Paged caches take ``tables`` and use lengths >= coverage as the
    inactive-slot sentinel (write dropped, nothing attended)."""
    b = tokens.shape[0]
    h = embed_lookup(params["embed"], tokens,
                     params["layers"]["attn_norm"].dtype)  # [B, E]
    h = _constrain(h, mesh, batch_axis, None)
    write_idx = lengths.astype(jnp.int32)
    kv_sharded = mesh is not None and shard_kv_heads(cfg, mesh.shape.get(AXIS_MODEL, 1))
    paged = isinstance(cache, PagedKVCache)
    if paged and tables is None:
        raise ValueError("decode_step with a PagedKVCache requires tables")
    if paged:
        # RoPE positions must be real for active slots; the sentinel value
        # (>= coverage) only matters to the cache ops, which drop it.
        cover = tables.shape[1] * cache.page
        rope_idx = jnp.minimum(write_idx, cover - 1)
    else:
        rope_idx = write_idx

    # The FULL cache rides the scan carry and each layer updates its own
    # rows in place (decode_update_and_attend).  Scanning over the cache as
    # xs/ys instead would make XLA slice + re-stack the whole cache every
    # step — ~2x the model's entire HBM traffic.
    def body(carry, xs):
        h, kc, vc, ksc, vsc = carry
        lp, layer = xs
        with _scope("arks.attn_qkv"):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(x, lp, cfg)
            q = apply_rope(q, rope_idx, cfg.rope_theta)
            k = apply_rope(k, rope_idx, cfg.rope_theta)
        if paged:
            from arks_tpu.ops.attention import paged_decode_update_and_attend
            attn, kc, vc, ksc, vsc = paged_decode_update_and_attend(
                q, k, v, kc, vc, tables, write_idx, layer, mesh, kv_sharded,
                model_axis=AXIS_MODEL, k_scale=ksc, v_scale=vsc)
        else:
            attn, kc, vc, ksc, vsc = decode_update_and_attend(
                q, k, v, kc, vc, write_idx, layer, mesh, batch_axis,
                kv_sharded, model_axis=AXIS_MODEL, k_scale=ksc, v_scale=vsc)
        attn = attn.reshape(b, cfg.q_dim)
        attn = _constrain(attn, mesh, batch_axis, AXIS_MODEL)
        with _scope("arks.attn_out"):
            h = h + qeinsum("bq,qe->be", attn, lp["wo"])
        h = h + _mlp(h, lp, cfg, mesh, batch_axis)
        return (h, kc, vc, ksc, vsc), None

    (h, ks, vs, kss, vss), _ = jax.lax.scan(
        body, (h, cache.k, cache.v, cache.k_scale, cache.v_scale),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    logits = _unembed(h, params, cfg, mesh, batch_axis)
    cls = PagedKVCache if paged else KVCache
    return logits, cls(k=ks, v=vs, k_scale=kss, v_scale=vss)


def decode_state_step(
    params: Params,
    cfg: ModelConfig,
    cache: KVCache | PagedKVCache,
    tokens: jnp.ndarray,    # [B] i32
    lengths: jnp.ndarray,   # [B] i32 — true lengths for alive slots
    alive: jnp.ndarray,     # [B] bool
    sentinel: int,          # engine's write-drop length (park value)
    mesh: Mesh | None = None,
    batch_axis: str | None = None,
    tables: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache | PagedKVCache]:
    """Liveness-masked ``decode_step`` for device-state decoding: dead
    slots read/write at the engine's park sentinel, so their KV scatters
    drop and nothing is attended — identical math to a host that had
    already parked the slot's length, which is what keeps the pipelined
    token stream byte-identical to the sequential path for live slots."""
    eff = jnp.where(alive, lengths, jnp.int32(sentinel))
    return decode_step(params, cfg, cache, tokens, eff, mesh, batch_axis,
                       tables=tables)


def verify_step(
    params: Params,
    cfg: ModelConfig,
    cache: KVCache | PagedKVCache,
    tokens: jnp.ndarray,   # [B, K] int32 — K tokens per slot (t0 + drafts)
    lengths: jnp.ndarray,  # [B] int32 — tokens already in cache per slot
    mesh: Mesh | None = None,
    batch_axis: str | None = None,
    tables: jnp.ndarray | None = None,  # [B, MaxP] int32 — PagedKVCache only
) -> tuple[jnp.ndarray, KVCache | PagedKVCache]:
    """Multi-token decode: advance every slot K tokens in ONE pass.

    A general batched multi-token scorer and the REFERENCE oracle for
    speculative verify (the serving path now expresses verify blocks as
    ragged q_len=K rows of ``mixed_step`` — one dispatch per iteration
    carries decode feeds, prefill chunks, AND spec verify; the parity
    between the two is closed in tests/test_paged_attention.py).  Token k
    of slot b sits at position lengths[b]+k, its KV is written there, and
    it attends the cache prefix plus the earlier tokens of its own block
    (causal).  Returns (logits [B, K, V] f32, cache).
    Rows written for later-rejected draft tokens become garbage beyond the
    accepted length — every read path masks by position, and the next
    dispatch overwrites them (the same invariant as decode_step's padding
    writes).

    Paged caches take ``tables``; ``lengths >= coverage`` is the inactive-
    slot sentinel (block writes dropped, nothing attended), exactly as in
    ``decode_step``.  A verify block may cross a page boundary — the paged
    update routes each row through the table independently."""
    b, kk = tokens.shape
    h = embed_lookup(params["embed"], tokens,
                     params["layers"]["attn_norm"].dtype)      # [B, K, E]
    h = _constrain(h, mesh, batch_axis, None, None)
    positions = lengths[:, None] + jnp.arange(kk, dtype=jnp.int32)  # [B, K]
    kv_sharded = mesh is not None and shard_kv_heads(cfg, mesh.shape.get(AXIS_MODEL, 1))
    paged = isinstance(cache, PagedKVCache)
    if paged and tables is None:
        raise ValueError("verify_step with a PagedKVCache requires tables")
    if paged:
        # RoPE positions must be real for active slots; the sentinel value
        # (>= coverage) only matters to the cache ops, which drop it.
        cover = tables.shape[1] * cache.page
        rope_pos = jnp.minimum(positions, cover - 1)
    else:
        rope_pos = positions
    from arks_tpu.ops.attention import (
        paged_verify_update_and_attend, verify_update_and_attend)

    def body(carry, xs):
        h, kc, vc, ksc, vsc = carry
        lp, layer = xs
        q, k, v = _block_qkv(h, lp, cfg, rope_pos)   # [B, K, H(.kv), D]
        if paged:
            attn, kc, vc, ksc, vsc = paged_verify_update_and_attend(
                q, k, v, kc, vc, tables, positions, layer, mesh, kv_sharded,
                model_axis=AXIS_MODEL, k_scale=ksc, v_scale=vsc)
        else:
            attn, kc, vc, ksc, vsc = verify_update_and_attend(
                q, k, v, kc, vc, positions, lengths, layer, mesh, batch_axis,
                kv_sharded, model_axis=AXIS_MODEL, k_scale=ksc, v_scale=vsc)
        attn = attn.reshape(b, kk, cfg.q_dim)
        attn = _constrain(attn, mesh, batch_axis, None, AXIS_MODEL)
        h = _block_tail(h, attn, lp, cfg, mesh, batch_axis)
        return (h, kc, vc, ksc, vsc), None

    (h, kc, vc, ksc, vsc), _ = jax.lax.scan(
        body, (h, cache.k, cache.v, cache.k_scale, cache.v_scale),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    # unembed_logits is 2D-shaped; fold K into the batch for the vocab dot.
    logits = _unembed(h.reshape(b * kk, -1), params, cfg, mesh,
                      batch_axis).reshape(b, kk, -1)
    cls = PagedKVCache if paged else KVCache
    return logits, cls(k=kc, v=vc, k_scale=ksc, v_scale=vsc)


# ---------------------------------------------------------------------------
# Jit wrappers
# ---------------------------------------------------------------------------


def make_decode_fn(cfg: ModelConfig, mesh: Mesh | None = None,
                   batch_axis: str | None = None):
    fn = functools.partial(decode_step, cfg=cfg, mesh=mesh, batch_axis=batch_axis)
    return jax.jit(
        lambda params, cache, tokens, lengths: fn(params, cache=cache, tokens=tokens, lengths=lengths),
        donate_argnums=(1,),
    )
