"""Model architecture configs for the arks-tpu serving engine.

The reference framework (scitix/arks) never touches model architecture — it
passes a HuggingFace model directory to vLLM/SGLang containers
(/root/reference/internal/controller/arksapplication_controller.go:941-1014).
Here the engine is ours, so architecture configs are first-class.  Presets
cover the model families named in BASELINE.json (Qwen2.5 at 0.5B/1.5B/7B/72B,
Llama-3-8B) plus a ``tiny`` config for CPU-mesh tests.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    qkv_bias: bool = False  # Qwen2-family uses bias on q/k/v projections.
    max_position_embeddings: int = 32768
    dtype: str = "bfloat16"
    eos_token_ids: tuple[int, ...] = ()
    # Mixture-of-Experts (0 experts = dense FFN).  norm_topk_prob=True is
    # Mixtral semantics (softmax over the selected experts); False is
    # Qwen2-MoE (global softmax, selected probs used as-is).
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # Per-model KV-cache dtype preference ("auto"|"bf16"|"int8"|"int4"):
    # consulted when EngineConfig.kv_cache_dtype is left at "auto" — a
    # checkpoint known to tolerate int4 KV can ship that fact with its
    # config instead of every deployment flagging it.  "auto" = no
    # preference (the engine's backend default applies).
    kv_cache_dtype: str = "auto"
    # Latent attention (DeepSeek-V3 / ``kimi_k2`` block; kv_lora_rank 0 =
    # the GQA block).  Queries go down to ``q_lora_rank`` and up to
    # ``num_heads x (qk_nope_head_dim + qk_rope_head_dim)``; keys and
    # values of ALL heads come out of one cached row a token: the normed
    # ``kv_lora_rank`` latent and ``qk_rope_head_dim`` rotary lanes.
    # ``head_dim`` is qk_nope + qk_rope for such a model.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (``rope_scaling`` of type yarn; factor 0 = plain RoPE):
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim).
    rope_yarn: tuple[float, ...] = ()
    # The first ``first_k_dense`` layers keep a dense SwiGLU FFN of
    # ``intermediate_size``; the rest are routed (two stacked trees).
    first_k_dense: int = 0
    # "softmax" (above) | "sigmoid": DeepSeek-V3 ``noaux_tc`` routing:
    # sigmoid scores, top-k of score + a learnt selection bias, weights
    # from the unbiased scores, normalised when ``norm_topk_prob``, times
    # ``routed_scaling_factor``.
    scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    # Ungated shared experts (``n_shared_experts x moe_intermediate_size``
    # wide); ``shared_expert_intermediate_size`` is the sigmoid-gated kind.
    n_shared_experts: int = 0
    # This chip's share of each routed layer (expert parallelism seen
    # from one chip): ``num_experts`` are HELD here, the router scores
    # ``num_experts x expert_parallel_size`` and share ``rank`` holds the
    # experts [rank x held, (rank + 1) x held).  Size 1 = all of them.
    expert_parallel_size: int = 1
    expert_parallel_rank: int = 0
    # Window and full attention layers in one model (``laguna``;
    # sliding_window 0 = every layer attends its whole context).  After the
    # ``first_k_dense`` leading layers (full attention, dense FFN) the
    # layers come in periods of ``window_period`` window layers and one
    # full layer; what is left over behind the last whole period is a tail
    # of window layers (Laguna-S-2.1: 1 + 11 x (3 + 1) + 3 = 48).  A window
    # layer's query attends the keys at most
    # ``sliding_window - 1`` positions behind it, has ``window_num_heads``
    # query heads (``num_heads``: a full layer's) over ``window_kv_heads``
    # KV heads (0: the full layers' ``num_kv_heads``), and rotates the first
    # ``window_partial_rotary_factor`` of a head (1: the whole head) under
    # plain RoPE at ``window_rope_theta``.  A full layer rotates the first
    # ``partial_rotary_factor`` of a head at ``rope_theta``, under
    # ``rope_hf_yarn`` where set: (factor, original context, beta_fast,
    # beta_slow, attention_factor), HF's YaRN, whose attention factor
    # multiplies cos and sin.  ``attn_gate``: one sigmoid scalar a head,
    # from the sublayer's normed input, on the head's output.
    # ``mimo_v2`` beside ``laguna``: a first period cut short by the dense
    # prefix (``short_period`` window layers and its full layer; MiMo-V2.5:
    # 1 + (4 + 1) + 7 x (5 + 1) = 48); values ``v_head_dim`` wide under keys
    # and queries of ``head_dim`` (0: as wide), times ``attn_value_scale``
    # before they are cached; ``attn_sink``: the kinds of layer ("full",
    # "window") whose softmax has one learnt logit a query head in its
    # denominator, which takes mass and has no value.
    sliding_window: int = 0
    window_period: int = 0
    window_num_heads: int = 0
    window_rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    rope_hf_yarn: tuple[float, ...] = ()
    attn_gate: bool = False
    window_kv_heads: int = 0
    window_partial_rotary_factor: float = 1.0
    attn_value_scale: float = 1.0
    attn_sink: tuple[str, ...] = ()
    # Linear-attention and softmax GQA layers in one model (``solar_open2``;
    # linear_period 0 = none).  Layer 0 is a GQA layer; behind it the layers
    # come in periods of ``linear_period`` linear layers and one GQA layer,
    # and what is left over is a tail of linear layers (Solar-Open2-250B:
    # 1 + 11 x (3 + 1) + 3 = 48).  A linear layer is Kimi Delta Attention
    # (arXiv:2510.26692): ``linear_num_heads`` heads of ``linear_head_dim``
    # for queries, keys AND values, each behind a causal depthwise
    # convolution over the last ``linear_conv`` positions; a decay a channel
    # and a step size a head (``linear_neg_eigval``: in (0, 2)) drive the
    # gated delta rule on a float32 state ``[d, d]`` a head a sequence, which
    # is all the layer keeps of the sequence; the decay and the output gate
    # come through low-rank pairs of rank ``linear_head_dim``.  The GQA
    # layers keep pages as ever; ``use_rope`` False: no rotation (NoPE);
    # ``attn_out_gate``: ``sigmoid(x Wg)`` elementwise over the H x D
    # attention outputs before the output projection.
    linear_period: int = 0
    linear_num_heads: int = 0
    linear_head_dim: int = 0
    linear_conv: int = 4
    linear_neg_eigval: bool = False
    use_rope: bool = True
    attn_out_gate: bool = False
    # Gated-delta-rule linear layers beside LATENT attention layers in one
    # model (``gigachat3_5``: ``linear_period`` > 0 AND ``kv_lora_rank`` >
    # 0).  The ``first_k_dense`` leading layers are LINEAR layers with a
    # dense FFN; behind them the layers come in periods of ``linear_period``
    # linear layers and one latent layer, ahead of which a first period cut
    # short by the dense prefix has ``short_period`` linear layers and its
    # latent layer (-1: none; GigaChat3.5-432B: 3 dense + (0 + 1) + 9 x (3 +
    # 1) = 40).  ``linear_head_decay``: the Gated DeltaNet form of the
    # linear layer (arXiv:2412.06464): ``linear_key_heads`` key (and query)
    # heads, each shared by ``linear_num_heads / linear_key_heads`` value
    # heads (0: as many as value heads), ONE log decay a head a token
    # (``-exp(A_log) softplus(x W_a + dt_bias)``), the output gate
    # ``linear_gate_scale sigmoid(x W_z)`` from a full projection, the
    # per-head output norm at ``linear_norm_eps`` (0: ``rms_norm_eps``).
    # ``attn_out_gate`` on such a model gates the latent block's H x v
    # outputs.  ``norm_gate`` g > 0: every norm of the model but that
    # per-head one is ``x / rms(x) * g sigmoid(w)`` (1 at w = 0).
    # ``norm_post``: sandwich norms, ``h + N(Mixer(N(h)))`` and ``h +
    # N(FFN(N(h)))``, four norms a layer.  ``swiglu_limit`` c > 0: every
    # SwiGLU is ``SiLU(min(gate, c)) * clip(up, -c, c)``.
    short_period: int = -1
    linear_head_decay: bool = False
    linear_key_heads: int = 0
    linear_gate_scale: float = 1.0
    linear_norm_eps: float = 0.0
    norm_gate: float = 0.0
    norm_post: bool = False
    swiglu_limit: float = 0.0
    # The shortcut block (``longcat_flash``: ``attn_sublayers`` 2).  A layer
    # holds TWO latent-attention sublayers, each followed by a dense SwiGLU
    # of ``intermediate_size``, and ONE routed layer that reads the first
    # sublayer's normed output and whose result joins the residual stream
    # only at the layer's end, over the second attention and both dense
    # FFNs (`transformer.mixed_step`'s ``shortcut_layer``).  The latent pool
    # keeps a row a token an attention SUBLAYER (``num_attn_sublayers``).
    # ``zero_experts``: identity experts the router scores behind the real
    # ones (ids at or above ``num_experts x expert_parallel_size``): a pair
    # that lands on one adds ``g x`` and reads no weight.
    # ``router_select_bias``: softmax scores, the top-k of score + a learnt
    # selection bias chosen, the UNBIASED scores the weights (no
    # renormalisation: ``norm_topk_prob`` False).  ``mla_q_scale`` /
    # ``mla_kv_scale``: what multiplies the normed query latent and the
    # normed key/value latent (the rotary key lanes not) ahead of their up
    # projections (``mla_scale_q_lora`` / ``mla_scale_kv_lora``:
    # (hidden / rank)^0.5).
    attn_sublayers: int = 1
    zero_experts: int = 0
    router_select_bias: bool = False
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    # The one-sublayer block (``nemotron_h``; ``layer_pattern`` "" = every
    # layer a mixer AND an FFN).  A layer is ONE sublayer under one norm and
    # one residual, ``h + Mixer(N(h))``, chosen by its character of
    # ``layer_pattern`` (the published ``hybrid_override_pattern``, whole):
    # ``M`` a Mamba-2 state-space mixer (arXiv:2405.21060): ``ssm_num_heads``
    # heads of ``ssm_head_dim`` behind one input projection (z | x B C | dt)
    # and a causal depthwise convolution with bias over the last ``ssm_conv``
    # positions of x | B | C, ONE decay a head a token, a float32 state
    # ``[ssm_head_dim, ssm_state_size]`` a head a sequence, B and C of
    # ``ssm_groups`` groups (head h reads group h // (heads / groups)), the
    # gate ahead of a grouped RMS norm; ``*`` a GQA layer (``use_rope``
    # False: no position signal); ``E`` the routed FFN alone.  The periods
    # (a run of layers closed by a ``*`` and the ``E`` behind it) need not
    # be equal (:meth:`pattern_walk`).  ``expert_act`` "relu2": an expert is
    # TWO matrices, ``relu(x W_up)^2 W_down`` (no gate matrix), the shared
    # expert too, ``moe_shared_expert_intermediate_size`` wide and ungated.
    layer_pattern: str = ""
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 0
    ssm_conv: int = 4
    expert_act: str = "swiglu"
    moe_shared_expert_intermediate_size: int = 0

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def windowed(self) -> bool:
        return self.sliding_window > 0

    @property
    def linear(self) -> bool:
        return self.linear_period > 0

    @property
    def ssm(self) -> bool:
        """Layers of ONE sublayer, Mamba-2 mixers among them."""
        return bool(self.layer_pattern)

    @property
    def recurrent(self) -> bool:
        """A slot keeps a fixed state beside its pages, whatever the
        context: the delta rule's (``linear``) or the selective scan's
        (``ssm``)."""
        return self.linear or self.ssm

    @property
    def recurrent_kind(self) -> str:
        """What the layers that keep a state a slot are called, in a
        refusal's words."""
        return "state-space" if self.ssm else "linear-attention"

    @property
    def ssm_dim(self) -> int:
        """Width of a Mamba-2 mixer's x (and of its gate z) over its heads."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of a Mamba-2 mixer's short convolution: x | B | C."""
        return self.ssm_dim + 2 * self.ssm_groups * self.ssm_state_size

    def pattern_walk(self) -> tuple:
        """``layer_pattern`` as the step walks it, runs of equal things
        folded: ``((period, times), ..)`` in model order, a period a run of
        layers closed by a ``*`` and the ``E`` layers behind it (the tail
        has no ``*``), itself ``((run, times), ..)`` with ``run`` an ``ME``
        pair or one layer.  Nemotron-3-Nano's 52 layers: five periods
        ``(ME x 2) M * E``, one ``(ME x 3) M * E``, the tail ``ME x 4``."""
        import re

        def runs(items) -> tuple:
            out: list = []
            for item in items:
                if out and out[-1][0] == item:
                    out[-1][1] += 1
                else:
                    out.append([item, 1])
            return tuple((item, n) for item, n in out)

        return runs(runs(re.findall(r"ME|.", period)) for period in
                    re.findall(r"[^*]*\*E*|[^*]+$", self.layer_pattern))

    @property
    def linear_dim(self) -> int:
        """Width of a linear layer's values (and, where the key heads are
        not fewer, its queries and keys) over its heads."""
        return self.linear_num_heads * self.linear_head_dim

    @property
    def linear_key_dim(self) -> int:
        """Width of a linear layer's keys (queries) over their heads."""
        return (self.linear_key_heads or self.linear_num_heads) \
            * self.linear_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels of a linear layer's short convolution: q | k | v."""
        return 2 * self.linear_key_dim + self.linear_dim

    @property
    def linear_head(self) -> bool:
        """The layers ahead of the first period are LINEAR layers (the
        dense prefix of a model with linear and latent layers)."""
        return self.linear and self.latent

    @property
    def head_layers(self) -> int:
        """Layers ahead of the first period: the dense prefix
        (full-attention layers; linear ones where ``linear_head``), or
        layer 0 of a model with linear and GQA layers."""
        return 1 if self.linear and not self.latent else self.first_k_dense

    @property
    def lead_layers(self) -> int:
        """Layers of a first period cut short by the dense prefix."""
        return self.short_period + 1 if self.short_period >= 0 else 0

    @property
    def inner_period(self) -> int:
        """Window (linear) layers a period."""
        return self.linear_period or (self.window_period if self.windowed
                                      else 0)

    @property
    def num_periods(self) -> int:
        """Periods of window (linear) layers and one full layer behind the
        head; without such layers every layer behind the head is a period
        of its own."""
        return (self.num_layers - self.head_layers - self.lead_layers) // (
            self.inner_period + 1)

    @property
    def inner_tail(self) -> int:
        """Window (linear) layers behind the last whole period."""
        return (self.num_layers - self.head_layers - self.lead_layers) % (
            self.inner_period + 1)

    @property
    def window_tail(self) -> int:
        return self.inner_tail if self.windowed else 0

    @property
    def num_window_layers(self) -> int:
        if not self.windowed:
            return 0
        return self.num_periods * self.window_period + self.window_tail \
            + max(self.short_period, 0)

    @property
    def num_linear_layers(self) -> int:
        """Layers that keep a fixed state a sequence and no page."""
        if self.ssm:
            return self.layer_pattern.count("M")
        if not self.linear:
            return 0
        return self.num_periods * self.linear_period + self.inner_tail \
            + (self.head_layers if self.linear_head else 0) \
            + max(self.short_period, 0)

    @property
    def num_full_layers(self) -> int:
        """Layers that keep every page of a sequence."""
        if self.ssm:
            return self.layer_pattern.count("*")
        return self.num_layers - self.num_window_layers \
            - self.num_linear_layers

    def heads_of(self, window: bool) -> int:
        return self.window_num_heads if window else self.num_heads

    def kv_heads_of(self, window: bool) -> int:
        return (self.window_kv_heads if window else 0) or self.num_kv_heads

    def sink_of(self, window: bool) -> bool:
        """A layer of the kind has a sink logit a head in its softmax."""
        return ("window" if window else "full") in self.attn_sink

    @property
    def value_dim(self) -> int:
        """Width of a head's values (and of its attention output)."""
        return self.v_head_dim or self.head_dim

    def layer_kinds(self) -> tuple[str, ...]:
        """``"full"`` / ``"window"`` / ``"linear"`` of every layer, in model
        order; of a one-sublayer block ``"ssm"`` / ``"moe"`` / ``"full"``,
        its pattern's characters."""
        if self.ssm:
            return tuple({"M": "ssm", "E": "moe", "*": "full"}[c]
                         for c in self.layer_pattern)
        inner = "linear" if self.linear else "window"
        head = inner if self.linear_head else "full"
        lead = ((inner,) * self.short_period + ("full",)
                if self.short_period >= 0 else ())
        return (head,) * self.head_layers + lead + (
            (inner,) * self.inner_period + ("full",)) * self.num_periods \
            + (inner,) * self.inner_tail

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Width of the one cached row a token of a latent model."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_out_dim(self) -> int:
        """Width of the concatenated heads the output projection takes."""
        return self.num_heads * self.value_dim

    @property
    def shortcut(self) -> bool:
        """The shortcut block: two attention sublayers a layer."""
        return self.attn_sublayers > 1

    @property
    def num_attn_sublayers(self) -> int:
        """Leading dimension of the full pool: the attention sublayers that
        keep every page of a sequence (a layer's one; the shortcut block's
        two, sublayer ``j`` of layer ``i`` at ``2 i + j``)."""
        return self.num_full_layers * self.attn_sublayers

    @property
    def num_real_experts(self) -> int:
        """Experts with weights over all the shares; the identity experts'
        ids start here."""
        return self.num_experts * self.expert_parallel_size

    @property
    def router_width(self) -> int:
        """Experts the router scores: the held ones times the shares, and
        the identity experts (which no share holds: they have no weights)
        behind them."""
        return self.num_real_experts + self.zero_experts

    @property
    def select_bias(self) -> bool:
        """The router has a learnt selection bias (``router_bias``): added
        to the scores for the top-k, never to the weights."""
        return self.scoring_func == "sigmoid" or self.router_select_bias

    @property
    def num_routed_layers(self) -> int:
        if self.ssm:
            return self.layer_pattern.count("E")
        return self.num_layers - self.first_k_dense if self.num_experts else 0

    @property
    def softmax_scale(self) -> float:
        """What the attention scores are multiplied by: head_dim^-0.5,
        and under YaRN the square of ``0.1 mscale_all_dim ln(factor) + 1``
        (DeepSeek-V3's modelling file)."""
        scale = self.head_dim ** -0.5
        if self.rope_yarn and self.rope_yarn[0] > 1 and self.rope_yarn[5]:
            m = 0.1 * self.rope_yarn[5] * math.log(self.rope_yarn[0]) + 1.0
            scale *= m * m
        return scale

    def with_expert_share(self, size: int, rank: int) -> "ModelConfig":
        if size < 1 or not 0 <= rank < size:
            raise ValueError(f"expert share {rank}/{size}: the rank must "
                             "lie in [0, size)")
        if size > 1 and not self.num_experts:
            raise ValueError(f"expert share {rank}/{size}: model "
                             f"{self.name!r} has no routed experts")
        return dataclasses.replace(self, expert_parallel_size=size,
                                   expert_parallel_rank=rank)

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def num_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        e, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        if self.ssm:
            # A layer is one sublayer under one norm: the mixer's input and
            # output projections, its convolution with bias, three vectors a
            # head and the grouped norm; q, k, v, o; the router with its
            # bias and two matrices an expert held and the shared one.
            d_in, c = self.ssm_dim, self.ssm_conv_dim
            fs = self.moe_shared_expert_intermediate_size
            kinds = {
                "M": e * (d_in + c + self.ssm_num_heads) + d_in * e
                + (self.ssm_conv + 1) * c + 3 * self.ssm_num_heads + d_in,
                "*": 2 * e * (self.q_dim + self.kv_dim),
                "E": e * self.router_width + self.router_width
                + 2 * e * (self.num_experts * self.moe_intermediate_size
                           + fs)}
            return (0 if self.tie_word_embeddings else e * v) + v * e + e \
                + sum(kinds[c] + e for c in self.layer_pattern)
        if self.latent:
            attn = (e * self.q_lora_rank + self.q_lora_rank * self.q_dim
                    + e * self.latent_row
                    + self.kv_lora_rank * self.num_heads
                    * (self.qk_nope_head_dim + self.v_head_dim)
                    + self.attn_out_dim * e
                    + self.q_lora_rank + self.kv_lora_rank)
        else:
            def gqa(heads: int, kv: int) -> int:
                return (e * (heads + kv) * self.head_dim
                        + (e * kv + heads * e) * self.value_dim)

            attn = gqa(self.num_heads, self.num_kv_heads)
        if self.qkv_bias:
            attn += self.q_dim + 2 * self.kv_dim
        dense = 3 * e * f
        mlp = dense
        if self.num_experts:
            # What is HELD here; the router keeps its whole width.
            mlp = self.num_experts * 3 * e * self.moe_intermediate_size \
                + e * self.router_width
            if self.select_bias:
                mlp += self.router_width
            if self.shared_expert_intermediate_size:
                mlp += 3 * e * self.shared_expert_intermediate_size + e
            mlp += 3 * e * self.n_shared_experts * self.moe_intermediate_size
        norms = (4 if self.norm_post else 2) * e
        routed = self.num_routed_layers if self.num_experts \
            else self.num_layers
        blocks = self.num_layers * (attn + norms) + routed * mlp \
            + (self.num_layers - routed) * dense
        if self.shortcut:
            # Two attention sublayers and two dense FFNs a layer, four
            # norms, beside the routed layer counted above.
            blocks += self.num_layers * (attn + 2 * e + 2 * dense)
        if self.windowed:
            # A window layer's projections have its own head counts.
            blocks += self.num_window_layers * (
                gqa(self.window_num_heads, self.kv_heads_of(True)) - attn)
        for kind in self.attn_sink:
            blocks += (self.num_window_layers * self.window_num_heads
                       if kind == "window"
                       else self.num_full_layers * self.num_heads)
        if self.attn_gate:
            blocks += e * (self.num_full_layers * self.num_heads
                           + self.num_window_layers * self.window_num_heads)
        if self.attn_out_gate:
            blocks += self.num_full_layers * e * (
                self.attn_out_dim if self.latent else self.q_dim)
        if self.linear and self.linear_head_decay:
            # The Gated DeltaNet form: q, k, v and their convolution, the
            # full gate and the output projection, the decay's and the step
            # size's projections a head, the decay's two vectors, the norm.
            ld, lh = self.linear_dim, self.linear_num_heads
            lin = ((e + self.linear_conv) * self.linear_conv_dim
                   + 2 * e * ld + 2 * e * lh + 2 * lh
                   + self.linear_head_dim)
            blocks += self.num_linear_layers * (lin - attn)
        elif self.linear:
            # A linear layer in place of a GQA layer's attention: q, k, v, o
            # at its own width, the two low-rank pairs, the step size, the
            # convolutions, the decay's two vectors and the output norm.
            ld, r = self.linear_dim, self.linear_head_dim
            lin = (4 * e * ld + 2 * (e * r + r * ld)
                   + e * self.linear_num_heads
                   + 3 * self.linear_conv * ld + self.linear_num_heads + ld
                   + r)
            blocks += self.num_linear_layers * (lin - attn)
        head = 0 if self.tie_word_embeddings else e * v
        return v * e + blocks + e + head

    @staticmethod
    def from_hf_config(path_or_dict: str | dict[str, Any], name: str = "") -> "ModelConfig":
        """Build a config from a HuggingFace ``config.json`` (Qwen2/Llama style)."""
        if isinstance(path_or_dict, str):
            p = path_or_dict
            if os.path.isdir(p):
                p = os.path.join(p, "config.json")
            with open(p) as f:
                d = json.load(f)
        else:
            d = dict(path_or_dict)
        arch = (d.get("architectures") or [""])[0].lower()
        model_type = d.get("model_type", "")
        qkv_bias = "qwen2" in arch or model_type in ("qwen2", "qwen2_moe")
        heads = d["num_attention_heads"]
        eos = d.get("eos_token_id")
        if eos is None:
            eos = ()
        elif isinstance(eos, int):
            eos = (eos,)
        # MoE: HF calls the expert count num_local_experts (Mixtral) or
        # num_experts (Qwen2-MoE).
        num_experts = int(d.get("num_local_experts", d.get("num_experts", 0)) or 0)
        is_mixtral = "mixtral" in arch or model_type == "mixtral"
        if model_type == "nemotron_h":
            return _from_nemotron_h(d, name or model_type, tuple(eos))
        # What only the ``nemotron_h`` reader understands: on any other
        # path each would be dropped, and the model served as another.
        for k in sorted(d):
            if d[k] not in (None, False) and (
                    k == "hybrid_override_pattern"
                    or k.startswith(("mamba_", "ssm_"))):
                raise ValueError(
                    f"{k}={d[k]!r} in a config of model_type "
                    f"{model_type!r}: only model_type 'nemotron_h' is read "
                    "with state-space (Mamba-2) layers and layers of one "
                    "sublayer; serving this model without them would be "
                    "another model")
        if model_type == "longcat_flash":
            return _from_longcat_flash(d, name or model_type, tuple(eos))
        # What only the ``longcat_flash`` reader understands: on any other
        # path each would be dropped, and the model served as another.
        for k in _LONGCAT_ONLY:
            if d.get(k):
                raise ValueError(
                    f"{k}={d[k]!r} in a config of model_type "
                    f"{model_type!r}: only model_type 'longcat_flash' is "
                    "read with identity (zero-compute) experts and scaled "
                    "query / key-value latents; serving this model without "
                    "them would be another model")
        if model_type == "mimo_v2":
            return _from_mimo_v2(d, name or model_type, tuple(eos))
        # What only the ``mimo_v2`` reader understands: on any other path
        # each would be dropped, and the model served as another.
        for k in sorted(d):
            if d[k] not in (None, False, *_MIMO_ONLY.get(k, ())) and (
                    k in _MIMO_ONLY or k.startswith("swa_")):
                raise ValueError(
                    f"{k}={d[k]!r} in a config of model_type "
                    f"{model_type!r}: only model_type 'mimo_v2' is read "
                    "with window layers of their own KV head count, a sink "
                    "logit a head, values narrower than keys and a value "
                    "scale; serving this model without it would be another "
                    "model")
        if model_type == "solar_open2":
            return _from_solar_open2(d, name or model_type, tuple(eos))
        if model_type == "gigachat3_5":
            return _from_gigachat3_5(d, name or model_type, tuple(eos))
        # What only the ``gigachat3_5`` reader understands: on any other
        # path each would be dropped, and the model served as another.
        for k in sorted(d):
            if (k in _GIGACHAT_ONLY or k.startswith("linear_")) \
                    and k != "linear_attn_config" \
                    and d[k] not in (None, False, *_GIGACHAT_ONLY.get(k, ())):
                raise ValueError(
                    f"{k}={d[k]!r} in a config of model_type "
                    f"{model_type!r}: only model_type 'gigachat3_5' is read "
                    "with gated-delta-rule linear layers beside gated latent "
                    "attention, sandwich norms, a gated norm and a clamped "
                    "SwiGLU; serving this model without it would be another "
                    "model")
        # What only the ``solar_open2`` reader understands: on any other
        # path each would be dropped, and the model served as another.
        for k in sorted(d):
            if d[k] not in (None, False) and (
                    k in ("linear_attn_config", "gqa_layers", "gqa_interval",
                          "use_gqa_gate") or k.startswith("kda_")):
                raise ValueError(
                    f"{k} in a config of model_type {model_type!r}: only "
                    "model_type 'solar_open2' is read with linear-attention "
                    "layers beside gated GQA layers; serving this model "
                    "with softmax attention in every layer would be "
                    "another model")
        if d.get("use_rope") is False:
            raise ValueError(
                f"use_rope=false in a config of model_type {model_type!r}: "
                "only model_type 'solar_open2' is read without rotary "
                "positions; serving this model with RoPE would be another "
                "model")
        if d.get("kv_lora_rank") or d.get("n_routed_experts"):
            return _from_deepseek_v3(d, name or model_type or "hf-model",
                                     tuple(eos))
        if model_type == "laguna":
            return _from_laguna(d, name or model_type, tuple(eos))
        # What only the ``laguna`` reader understands: on this path each
        # would be dropped, and the model served as another model.
        for k in ("layer_types", "rope_parameters",
                  "num_attention_heads_per_layer"):
            if d.get(k):
                raise ValueError(
                    f"{k} in a config of model_type {model_type!r}: only "
                    "model_type 'laguna' is read with these keys for layers "
                    "of more than one kind ('mimo_v2' with "
                    "hybrid_layer_pattern); serving this model with one "
                    "kind of layer would be another model")
        if d.get("sliding_window") and d.get("use_sliding_window", True):
            raise ValueError(
                f"sliding_window={d['sliding_window']} in a config of "
                f"model_type {model_type!r}: only model_types 'laguna' and "
                "'mimo_v2' are read with window layers; serving this model "
                "with full attention in every layer would be another model")
        if d.get("rope_scaling"):
            raise ValueError(
                f"rope_scaling={d['rope_scaling']!r}: only the latent-"
                "attention block reads a rope_scaling (type yarn); serving "
                "this model with plain RoPE would be another model")
        return ModelConfig(
            name=name or model_type or "hf-model",
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=d.get("num_key_value_heads", heads),
            head_dim=d.get("head_dim", d["hidden_size"] // heads),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            qkv_bias=qkv_bias,
            max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
            eos_token_ids=tuple(eos),
            num_experts=num_experts,
            num_experts_per_tok=int(d.get("num_experts_per_tok", 0) or 0),
            moe_intermediate_size=int(
                d.get("moe_intermediate_size",
                      d["intermediate_size"] if num_experts else 0) or 0),
            shared_expert_intermediate_size=int(
                d.get("shared_expert_intermediate_size", 0) or 0),
            norm_topk_prob=bool(d.get("norm_topk_prob", is_mixtral)),
            kv_cache_dtype=str(d.get("kv_cache_dtype", "auto")),
        )


def _deepseek_yarn(rs: dict | None, refuse) -> tuple[float, ...]:
    """``ModelConfig.rope_yarn`` from a DeepSeek-V3-style ``rope_scaling``
    (none: plain RoPE); any other type goes to ``refuse``."""
    if not rs:
        return ()
    if rs.get("type", rs.get("rope_type")) != "yarn":
        refuse(f"rope_scaling type {rs.get('type', rs.get('rope_type'))!r}"
               " (only yarn)")
    return (float(rs["factor"]),
            float(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
            float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))


def _refuser(name: str):
    """What every reader says of a file it cannot express: ``refuse(what)``
    raises, by the configuration's name."""
    def refuse(what: str) -> None:
        raise ValueError(f"config {name!r}: {what} is not supported (the "
                         "model would be served as another model)")
    return refuse


def _one_group(d: dict[str, Any], refuse) -> None:
    """No group limit on the routing: one group, or none stated."""
    if int(d.get("n_group", 1) or 1) > 1 or int(d.get("topk_group", 1)
                                                 or 1) > 1:
        refuse(f"group-limited routing (n_group={d.get('n_group')}, "
               f"topk_group={d.get('topk_group')})")


def _sigmoid_routing(d: dict[str, Any], refuse) -> None:
    """What ``moe.router_topk``'s sigmoid rule is: sigmoid scores, the
    top-k of score + a selection bias (``noaux_tc``), one group.  Anything
    else goes to ``refuse``."""
    if d.get("scoring_func", "softmax") != "sigmoid":
        refuse(f"scoring_func={d.get('scoring_func', 'softmax')!r} with "
               "n_routed_experts (only sigmoid)")
    if d.get("topk_method", "noaux_tc") != "noaux_tc":
        refuse(f"topk_method={d['topk_method']!r} (only noaux_tc)")
    _one_group(d, refuse)


def _from_deepseek_v3(d: dict[str, Any], name: str,
                      eos: tuple[int, ...]) -> ModelConfig:
    """The DeepSeek-V3 block (``deepseek_v3``, ``kimi_k2``): latent
    attention, a dense prefix, sigmoid-routed experts with a selection
    bias, ungated shared experts, YaRN.  Key for key from the published
    file; what this block cannot express is refused, not approximated."""
    refuse = _refuser(name)
    for k in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "n_routed_experts"):
        if not d.get(k):
            refuse(f"a DeepSeek-V3-style block without {k}")
    _sigmoid_routing(d, refuse)
    if int(d.get("moe_layer_freq", 1) or 1) != 1:
        refuse(f"moe_layer_freq={d['moe_layer_freq']}")
    if d.get("attention_bias"):
        refuse("attention_bias")
    if int(d.get("num_nextn_predict_layers", 0) or 0):
        refuse("multi-token prediction layers")
    yarn = _deepseek_yarn(d.get("rope_scaling"), refuse)
    heads = d["num_attention_heads"]
    layers = d["num_hidden_layers"]
    first = int(d.get("first_k_dense_replace", 0) or 0)
    if not 0 <= first < layers:
        refuse(f"first_k_dense_replace={first} of {layers} layers")
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=1,
        head_dim=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["n_routed_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        q_lora_rank=int(d["q_lora_rank"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        rope_yarn=yarn,
        first_k_dense=first,
        scoring_func="sigmoid",
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        n_shared_experts=int(d.get("n_shared_experts", 0) or 0),
    )


def _from_laguna(d: dict[str, Any], name: str,
                 eos: tuple[int, ...]) -> ModelConfig:
    """The ``laguna`` block (poolside): GQA layers of two kinds, window and
    full, each with a head count and a RoPE of its own, a per-head output
    gate, softmax-routed experts with a scaling factor behind a dense
    prefix, a sigmoid-gated shared expert.  Key for key from the published
    file; what the block cannot express is refused, not approximated."""
    refuse = _refuser(name)
    layers = int(d["num_hidden_layers"])
    kinds = list(d.get("layer_types") or [])
    heads_per = list(d.get("num_attention_heads_per_layer") or [])
    mlp = list(d.get("mlp_layer_types")
               or ["dense" if l in (d.get("mlp_only_layers") or ())
                   else "sparse" for l in range(layers)])
    for key, got in (("layer_types", kinds), ("mlp_layer_types", mlp),
                     ("num_attention_heads_per_layer", heads_per)):
        if len(got) != layers:
            refuse(f"{key} with {len(got)} entries for {layers} layers")
    if float(d.get("moe_router_logit_softcapping", 0) or 0) != 0:
        refuse("moe_router_logit_softcapping="
               f"{d['moe_router_logit_softcapping']}")
    if d.get("moe_apply_router_weight_on_input"):
        refuse("moe_apply_router_weight_on_input")
    gating = d.get("gating", False)
    if gating not in (False, None, True, "per-head", "per_head"):
        refuse(f"gating={gating!r} (only per-head)")
    for g in d.get("gating_types") or ():
        if g != "per_head":
            refuse(f"a gating_types entry {g!r} (only per_head)")
    if d.get("attention_bias"):
        refuse("attention_bias")
    if int(d.get("decoder_sparse_step", 1) or 1) != 1:
        refuse(f"decoder_sparse_step={d['decoder_sparse_step']}")
    window = int(d.get("sliding_window") or 0)
    if window < 1:
        refuse("a laguna config without sliding_window")
    # The dense layers are a prefix of full-attention layers; behind it,
    # periods of window layers and one full layer, and a tail of window
    # layers short of a period.
    first = 0
    while first < layers and mlp[first] == "dense":
        first += 1
    if "dense" in mlp[first:] or first >= layers:
        refuse(f"mlp_layer_types {mlp} (dense layers must be a prefix)")
    if any(k != "full_attention" for k in kinds[:first]):
        refuse("a window layer inside the dense prefix")
    rest = kinds[first:]
    period = rest.index("full_attention") if "full_attention" in rest else 0
    want = (["sliding_attention"] * period + ["full_attention"]) * (
        len(rest) // (period + 1)) \
        + ["sliding_attention"] * (len(rest) % (period + 1))
    if not period or rest != want:
        refuse(f"layer_types {kinds} (behind the dense prefix: periods of "
               "window layers and one full layer, then window layers)")
    full_heads = {h for h, k in zip(heads_per, kinds)
                  if k == "full_attention"}
    win_heads = {h for h, k in zip(heads_per, kinds)
                 if k == "sliding_attention"}
    if len(full_heads) != 1 or len(win_heads) != 1:
        refuse(f"num_attention_heads_per_layer {heads_per} (one head count "
               "a kind of layer)")
    heads, wheads = full_heads.pop(), win_heads.pop()
    if heads != d["num_attention_heads"]:
        refuse(f"num_attention_heads {d['num_attention_heads']} beside "
               f"{heads} heads in the full layers")
    rp = d.get("rope_parameters") or {}
    full, win = rp.get("full_attention"), rp.get("sliding_attention")
    if not full or not win:
        refuse("rope_parameters without full_attention and "
               "sliding_attention")
    legacy = d.get("rope_scaling")
    if legacy and legacy != full:
        refuse(f"rope_scaling {legacy!r} beside another "
               "rope_parameters.full_attention")
    if win.get("rope_type", "default") != "default" \
            or float(win.get("partial_rotary_factor", 1)) != 1:
        refuse(f"sliding_attention rope {win!r} (only plain RoPE over the "
               "whole head)")
    yarn: tuple[float, ...] = ()
    if full.get("rope_type", "default") == "yarn":
        if full.get("mscale") or full.get("mscale_all_dim") \
                or full.get("truncate") is False:
            refuse(f"yarn with mscale / truncate keys {full!r}")
        factor = float(full["factor"])
        yarn = (factor, float(full["original_max_position_embeddings"]),
                float(full.get("beta_fast", 32)),
                float(full.get("beta_slow", 1)),
                float(full.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0))
    elif full.get("rope_type", "default") != "default":
        refuse(f"full_attention rope_type {full.get('rope_type')!r}")
    kv = d.get("num_key_value_heads", heads)
    if heads % kv or wheads % kv:
        refuse(f"{heads} / {wheads} query heads over {kv} KV heads")
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=d.get("head_dim", d["hidden_size"] // heads),
        rope_theta=float(full.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["num_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(
            d.get("shared_expert_intermediate_size", 0) or 0),
        norm_topk_prob=bool(d.get("norm_topk_prob", False)),
        routed_scaling_factor=float(d.get("moe_routed_scaling_factor", 1.0)),
        first_k_dense=first,
        sliding_window=window,
        window_period=period,
        window_num_heads=wheads,
        window_rope_theta=float(win.get("rope_theta", 10000.0)),
        partial_rotary_factor=float(full.get("partial_rotary_factor", 1)),
        rope_hf_yarn=yarn,
        attn_gate=bool(gating),
        kv_cache_dtype=str(d.get("kv_cache_dtype", "auto")),
    )


# Keys only the ``mimo_v2`` reader understands (beside every ``swa_*`` key),
# each with the values that say "the usual thing".
_MIMO_ONLY: dict[str, tuple] = {
    "hybrid_layer_pattern": (), "hybrid_block_size": (),
    "add_full_attention_sink_bias": (), "add_swa_attention_sink_bias": (),
    "attention_value_scale": (1, 1.0),
}


def _from_mimo_v2(d: dict[str, Any], name: str,
                  eos: tuple[int, ...]) -> ModelConfig:
    """The ``mimo_v2`` block (XiaomiMiMo; the language model of
    MiMo-V2-Flash / MiMo-V2.5): GQA layers of two kinds by
    ``hybrid_layer_pattern`` (0 full, 1 window), each with a KV head count
    and a RoPE base of its own, keys and queries ``head_dim`` wide and values
    ``v_head_dim`` wide times ``attention_value_scale``, the first
    ``partial_rotary_factor`` of a head rotated in both kinds, a learnt sink
    logit a head in the softmax of the kinds ``add_*_attention_sink_bias``
    name; sigmoid-routed experts with a selection bias and no shared expert
    behind the dense layers ``moe_layer_freq`` marks 0.  Key for key from
    the published file; what the block cannot express is refused, not
    approximated.  ``attention_projection_layout`` is how a checkpoint lays
    q | k | v out, not mathematics; the family's draft (MTP) layers and its
    vision and audio towers are not in this file and are not built."""
    refuse = _refuser(name)
    layers = int(d["num_hidden_layers"])
    for k in ("hybrid_layer_pattern", "sliding_window", "n_routed_experts",
              "num_experts_per_tok", "moe_intermediate_size"):
        if not d.get(k):
            refuse(f"a mimo_v2 config without {k}")
    pattern = [int(k) for k in d["hybrid_layer_pattern"]]
    freq = d.get("moe_layer_freq", 1)
    freq = [int(f) for f in freq] if isinstance(freq, (list, tuple)) \
        else [int(freq or 1)] * layers
    for key, got in (("hybrid_layer_pattern", pattern),
                     ("moe_layer_freq", freq)):
        if len(got) != layers or set(got) - {0, 1}:
            refuse(f"{key} {got} (one 0 or 1 a layer, {layers} layers)")
    if d.get("hybrid_block_size") is not None:
        refuse(f"hybrid_block_size={d['hybrid_block_size']}")
    _sigmoid_routing(d, refuse)
    if d.get("hidden_act", "silu") != "silu":
        refuse(f"hidden_act={d['hidden_act']!r} (only silu)")
    if d.get("attention_bias"):
        refuse("attention_bias")
    if d.get("tie_word_embeddings"):
        refuse("tie_word_embeddings")
    window = int(d["sliding_window"])
    for k in ("sliding_window_size", "attention_chunk_size"):
        # The window under its other names: a chunk of the window's own
        # span adds no mask to a window layer.
        if d.get(k) not in (None, window):
            refuse(f"{k}={d[k]} beside sliding_window={window}")
    rs = d.get("rope_scaling") or {}
    if rs.get("rope_type", rs.get("type", "default")) != "default":
        refuse(f"rope_scaling {rs!r} (only the default type: plain RoPE)")
    heads, dk = int(d["num_attention_heads"]), int(d["head_dim"])
    dv = int(d.get("v_head_dim") or dk)
    for k, want in (("swa_head_dim", dk), ("swa_v_head_dim", dv)):
        if d.get(k, want) != want:
            refuse(f"{k}={d[k]} beside {want} in the full layers (one "
                   "width of keys and one of values)")
    wheads = int(d.get("swa_num_attention_heads") or heads)
    kv = int(d.get("num_key_value_heads") or heads)
    wkv = int(d.get("swa_num_key_value_heads") or kv)
    if heads % kv or wheads % wkv:
        refuse(f"{heads} / {wheads} query heads over {kv} / {wkv} KV heads")
    # The dense layers are a prefix of full-attention layers; behind it a
    # first period that the prefix may have cut short, whole periods of
    # window layers and one full layer, then window layers.
    first = 0
    while first < layers and not freq[first]:
        first += 1
    if 0 in freq[first:] or first >= layers:
        refuse(f"moe_layer_freq {freq} (dense layers must be a prefix)")
    if any(pattern[:first]):
        refuse("a window layer inside the dense prefix")
    rest = pattern[first:]
    full = [i for i, k in enumerate(rest) if not k]
    if not full:
        refuse(f"hybrid_layer_pattern {pattern} (no full layer behind the "
               "dense prefix)")
    per = full[1] - full[0] - 1 if len(full) > 1 else full[0]
    lead = full[0]
    if per < 1 or lead > per \
            or full != list(range(lead, len(rest), per + 1)):
        refuse(f"hybrid_layer_pattern {pattern} (behind the dense prefix: "
               "a full layer every so many window layers, then window "
               "layers)")
    sink = tuple(kind for kind, k in (
        ("full", "add_full_attention_sink_bias"),
        ("window", "add_swa_attention_sink_bias")) if d.get(k))
    rotary = float(d.get("partial_rotary_factor", 1) or 1)
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=dk,
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("layernorm_epsilon",
                                 d.get("rms_norm_eps", 1e-6))),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["n_routed_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        v_head_dim=0 if dv == dk else dv,
        first_k_dense=first,
        scoring_func="sigmoid",
        routed_scaling_factor=float(d.get("routed_scaling_factor") or 1.0),
        n_shared_experts=int(d.get("n_shared_experts") or 0),
        sliding_window=window,
        window_period=per,
        window_num_heads=wheads,
        window_rope_theta=float(d.get("swa_rope_theta", 10000.0)),
        partial_rotary_factor=rotary,
        short_period=lead if lead < per else -1,
        window_kv_heads=0 if wkv == kv else wkv,
        window_partial_rotary_factor=rotary,
        attn_value_scale=float(d.get("attention_value_scale") or 1.0),
        attn_sink=sink,
        kv_cache_dtype=str(d.get("kv_cache_dtype", "auto")),
    )


def _from_solar_open2(d: dict[str, Any], name: str,
                      eos: tuple[int, ...]) -> ModelConfig:
    """The ``solar_open2`` block (upstage): gated delta-rule linear-attention
    layers (``linear_attn_config``, the ``kda_*`` keys: Kimi Delta
    Attention) beside softmax GQA layers without RoPE and with an output
    gate at ``gqa_layers``, every layer's FFN sigmoid-routed experts beside
    one ungated shared expert.  Key for key from the published file; what
    the block cannot express is refused, not approximated."""
    refuse = _refuser(name)
    layers = int(d["num_hidden_layers"])
    lin = d.get("linear_attn_config") or {}
    for k in ("num_heads", "head_dim", "short_conv_kernel_size"):
        if not lin.get(k):
            refuse(f"a solar_open2 config without linear_attn_config.{k}")
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        refuse(f"linear_attn_config.num_kv_heads={lin['num_kv_heads']} "
               "(keys and values of fewer heads than queries)")
    if d.get("kda_use_full_proj"):
        refuse("kda_use_full_proj (full-rank decay and gate projections)")
    if d.get("use_rope", False):
        refuse("use_rope (rotary positions in the GQA layers)")
    if float(d.get("partial_rotary_factor", 1) or 1) != 1:
        refuse(f"partial_rotary_factor={d['partial_rotary_factor']}")
    if int(d.get("first_k_dense_replace", 0) or 0):
        refuse(f"first_k_dense_replace={d['first_k_dense_replace']} (a "
               "dense prefix)")
    if not d.get("n_routed_experts"):
        refuse("a solar_open2 config without n_routed_experts")
    if d.get("scoring_func", "sigmoid") != "sigmoid":
        refuse(f"scoring_func={d['scoring_func']!r} (only sigmoid)")
    _one_group(d, refuse)
    if d.get("attention_bias"):
        refuse("attention_bias")
    if d.get("rope_scaling"):
        refuse(f"rope_scaling={d['rope_scaling']!r} without RoPE")
    # GQA at layer 0, then every (gqa_interval + 1)-th; the rest linear.
    per = int(d.get("gqa_interval") or 0)
    gqa = [int(l) for l in d.get("gqa_layers") or ()]
    if per < 1 or gqa != list(range(0, layers, per + 1)):
        refuse(f"gqa_layers {gqa} with gqa_interval {per} over {layers} "
               "layers (a GQA layer, then periods of gqa_interval linear "
               "layers and one GQA layer, then linear layers)")
    heads = d["num_attention_heads"]
    kv = d.get("num_key_value_heads", heads)
    if heads % kv:
        refuse(f"{heads} query heads over {kv} KV heads")
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=d.get("head_dim", d["hidden_size"] // heads),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["n_routed_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        scoring_func="sigmoid",
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        n_shared_experts=int(d.get("n_shared_experts", 0) or 0),
        linear_period=per,
        linear_num_heads=int(lin["num_heads"]),
        linear_head_dim=int(lin["head_dim"]),
        linear_conv=int(lin["short_conv_kernel_size"]),
        linear_neg_eigval=bool(d.get("kda_allow_neg_eigval", False)),
        use_rope=False,
        attn_out_gate=bool(d.get("use_gqa_gate", False)),
        kv_cache_dtype=str(d.get("kv_cache_dtype", "auto")),
    )


# Keys only the ``gigachat3_5`` reader understands (beside every
# ``linear_*`` key), each with the values that say "the usual thing".
_GIGACHAT_ONLY: dict[str, tuple] = {
    "full_attention_layers": (), "gated_attention": (),
    "norm_type": ("RMSNorm",), "layernorm_type": ("pre",),
    "swiglu_limit": (0,), "use_shared_expert_sigmoid": (),
}


def _from_gigachat3_5(d: dict[str, Any], name: str,
                      eos: tuple[int, ...]) -> ModelConfig:
    """The ``gigachat3_5`` block (ai-sage): Gated DeltaNet linear layers
    (the ``linear_*`` keys: key heads shared by value heads, a decay a head,
    an output gate from a full projection) beside gated latent-attention
    layers at ``full_attention_layers`` (the DeepSeek-V3 block with an
    elementwise output gate), a gated norm in sandwich placement, a clamped
    SwiGLU, sigmoid-routed experts beside one ungated shared expert behind
    a dense prefix of LINEAR layers.  Key for key from the published file;
    what the block cannot express is refused, not approximated.  The
    multi-token-prediction modules (``num_nextn_predict_layers``,
    ``nextn_is_sparse``) are read and DROPPED: they do not enter the
    next-token logits, and nothing here drafts with them."""
    refuse = _refuser(name)
    for k in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_key_head_dim", "linear_conv_kernel_dim",
              "full_attention_layers"):
        if not d.get(k):
            refuse(f"a gigachat3_5 config without {k}")
    for k, want in (("linear_attention_type", "GigaChat35GatedDeltaNet"),
                    ("linear_gating_type",
                     "gated_rmsnorm_sigmoid_zero_centered"),
                    ("hidden_act", "silu"), ("scoring_func", "sigmoid"),
                    ("topk_method", "noaux_tc")):
        if d.get(k, want) != want:
            refuse(f"{k}={d[k]!r} (only {want!r})")
    if d.get("linear_value_head_dim", d["linear_key_head_dim"]) \
            != d["linear_key_head_dim"]:
        refuse(f"linear_value_head_dim={d['linear_value_head_dim']} beside "
               f"linear_key_head_dim={d['linear_key_head_dim']} (a state "
               "that is not square)")
    hk, hv = int(d["linear_num_key_heads"]), int(d["linear_num_value_heads"])
    if hv % hk:
        refuse(f"linear_num_value_heads={hv} over linear_num_key_heads={hk}")
    _one_group(d, refuse)
    if d.get("attention_bias"):
        refuse("attention_bias")
    if d.get("use_shared_expert_sigmoid"):
        refuse("use_shared_expert_sigmoid (a gated shared expert)")
    if not d.get("use_mla_scaling_factor", True):
        refuse("use_mla_scaling_factor=false (YaRN without its factor in "
               "the softmax scale)")
    heads = d["num_attention_heads"]
    if d.get("num_key_value_heads", heads) != heads:
        refuse(f"num_key_value_heads={d['num_key_value_heads']} beside "
               f"{heads} latent-attention heads")
    if d.get("qk_head_dim", d["qk_nope_head_dim"] + d["qk_rope_head_dim"]) \
            != d["qk_nope_head_dim"] + d["qk_rope_head_dim"]:
        refuse(f"qk_head_dim={d['qk_head_dim']} (not nope + rope)")
    norm = d.get("norm_type", "RMSNorm")
    if norm not in ("RMSNorm", "ZeroCenteredGatedNorm"):
        refuse(f"norm_type={norm!r}")
    norm_gate = float(d.get("layernorm_gating_weight", 2)) \
        if norm == "ZeroCenteredGatedNorm" else 0.0
    if norm == "ZeroCenteredGatedNorm" and norm_gate <= 0:
        refuse(f"layernorm_gating_weight={d['layernorm_gating_weight']}")
    placement = d.get("layernorm_type", "pre")
    if placement not in ("pre", "pre_post"):
        refuse(f"layernorm_type={placement!r}")
    yarn = _deepseek_yarn(d.get("rope_scaling"), refuse)
    layers = int(d["num_hidden_layers"])
    first = int(d.get("first_k_dense_replace", 0) or 0)
    full = [int(l) for l in d["full_attention_layers"]]
    # The dense prefix is linear layers; behind it a first period that the
    # prefix may have cut short, whole periods, then linear layers.
    per = full[1] - full[0] - 1 if len(full) > 1 else full[0] - first
    lead = full[0] - first
    want = list(range(full[0], layers, per + 1)) if per >= 1 else []
    if not 0 <= first < layers or not 0 <= lead <= per or full != want:
        refuse(f"full_attention_layers {full} with first_k_dense_replace "
               f"{first} over {layers} layers (a dense prefix of linear "
               "layers, then a latent layer every so many linear layers, "
               "then linear layers)")
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=1,
        head_dim=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["n_routed_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        q_lora_rank=int(d["q_lora_rank"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        rope_yarn=yarn,
        first_k_dense=first,
        scoring_func="sigmoid",
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        n_shared_experts=int(d.get("n_shared_experts", 0) or 0),
        linear_period=per,
        linear_num_heads=hv,
        linear_head_dim=int(d["linear_key_head_dim"]),
        linear_conv=int(d["linear_conv_kernel_dim"]),
        attn_out_gate=bool(d.get("gated_attention", False)),
        short_period=lead if lead < per else -1,
        linear_head_decay=True,
        linear_key_heads=hk,
        linear_gate_scale=float(d.get("linear_sigmoid_gate_scale", 1)),
        linear_norm_eps=float(d.get("linear_attn_o_norm_eps", 0) or 0),
        norm_gate=norm_gate,
        norm_post=placement == "pre_post",
        swiglu_limit=float(d.get("swiglu_limit", 0) or 0),
    )


# Keys only the ``longcat_flash`` reader understands.
_LONGCAT_ONLY = ("zero_expert_num", "mla_scale_q_lora", "mla_scale_kv_lora")


def _from_longcat_flash(d: dict[str, Any], name: str,
                        eos: tuple[int, ...]) -> ModelConfig:
    """The ``longcat_flash`` block (meituan-longcat): ``num_layers`` layers
    of TWO latent-attention sublayers (the DeepSeek-V3 attention under plain
    RoPE, the query latent and the key/value latent scaled by (hidden /
    rank)^0.5 where ``mla_scale_q_lora`` / ``mla_scale_kv_lora``), each
    followed by a dense SwiGLU of ``ffn_hidden_size``, and ONE routed layer
    on a shortcut from the first sublayer's normed output to the layer's
    end: ``n_routed_experts`` experts of ``expert_ffn_hidden_size`` and
    ``zero_expert_num`` identity experts scored by one softmax, the top
    ``moe_topk`` of score + a selection bias chosen, the unbiased scores
    times ``routed_scaling_factor`` the weights, no shared expert.  Key for
    key from the published file; what the block cannot express is refused,
    not approximated."""
    refuse = _refuser(name)
    for k in ("num_layers", "ffn_hidden_size", "expert_ffn_hidden_size",
              "moe_topk", "n_routed_experts", "kv_lora_rank", "q_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"):
        if not d.get(k):
            refuse(f"a longcat_flash config without {k}")
    for k, want in (("zero_expert_type", "identity"), ("hidden_act", "silu"),
                    ("attention_method", "MLA")):
        if d.get(k, want) != want:
            refuse(f"{k}={d[k]!r} (only {want!r})")
    if int(d.get("num_nextn_predict_layers", 0) or 0):
        refuse("multi-token prediction layers")
    if d.get("router_bias"):
        refuse("router_bias (a bias term in the router's projection)")
    if d.get("rope_scaling"):
        refuse(f"rope_scaling={d['rope_scaling']!r} (this block rotates "
               "under plain RoPE)")
    if d.get("attention_bias"):
        refuse("attention_bias")
    if d.get("norm_topk_prob"):
        refuse("norm_topk_prob (the chosen experts' scores renormalised)")
    if int(d.get("n_shared_experts", 0) or 0):
        refuse(f"n_shared_experts={d['n_shared_experts']}")
    if int(d.get("first_k_dense_replace", 0) or 0):
        refuse(f"first_k_dense_replace={d['first_k_dense_replace']}")
    _one_group(d, refuse)
    if d.get("sliding_window"):
        refuse(f"sliding_window={d['sliding_window']}")
    hidden = int(d["hidden_size"])
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=hidden,
        intermediate_size=int(d["ffn_hidden_size"]),
        num_layers=int(d["num_layers"]),
        num_heads=d["num_attention_heads"],
        num_kv_heads=1,
        head_dim=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["n_routed_experts"]),
        num_experts_per_tok=int(d["moe_topk"]),
        moe_intermediate_size=int(d["expert_ffn_hidden_size"]),
        norm_topk_prob=False,
        q_lora_rank=int(d["q_lora_rank"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        attn_sublayers=2,
        zero_experts=int(d.get("zero_expert_num", 0) or 0),
        router_select_bias=True,
        mla_q_scale=(hidden / int(d["q_lora_rank"])) ** 0.5
        if d.get("mla_scale_q_lora") else 1.0,
        mla_kv_scale=(hidden / int(d["kv_lora_rank"])) ** 0.5
        if d.get("mla_scale_kv_lora") else 1.0,
    )


def _from_nemotron_h(d: dict[str, Any], name: str,
                     eos: tuple[int, ...]) -> ModelConfig:
    """The ``nemotron_h`` block (nvidia; Nemotron-3-Nano): layers of ONE
    sublayer each by ``hybrid_override_pattern``, a character a layer: ``M``
    a Mamba-2 mixer (the ``mamba_*`` keys, ``ssm_state_size``, ``n_groups``,
    ``conv_kernel``), ``*`` a GQA layer that applies no rotation, ``E`` a
    routed FFN of two-matrix relu^2 experts (``mlp_hidden_act``) under
    sigmoid routing with a selection bias beside one ungated shared expert
    ``moe_shared_expert_intermediate_size`` wide.  The pattern is read
    WHOLE; a ``-`` (a dense FFN layer, the family's dense siblings) is
    refused by name.  Key for key from the published file; what the block
    cannot express is refused, not approximated.  Read and unused:
    ``chunk_size`` (the published kernels' block; the recurrence is exact
    at any), ``time_step_*`` (the initialiser's), ``expand`` (the mixer's
    width is heads x head width), ``rope_theta`` / ``partial_rotary_factor``
    (the family's attention module rotates nothing)."""
    refuse = _refuser(name)
    for k in ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
              "ssm_state_size", "n_groups", "conv_kernel",
              "n_routed_experts", "num_experts_per_tok",
              "moe_intermediate_size"):
        if not d.get(k):
            refuse(f"a nemotron_h config without {k}")
    pattern = str(d["hybrid_override_pattern"])
    layers = int(d["num_hidden_layers"])
    if "-" in pattern:
        refuse(f"hybrid_override_pattern {pattern!r} (a '-' layer: a dense "
               "FFN alone)")
    if set(pattern) - set("ME*") or len(pattern) != layers:
        refuse(f"hybrid_override_pattern {pattern!r} (one of M, E, * a "
               f"layer, {layers} layers)")
    if "M" not in pattern or "*" not in pattern:
        refuse(f"hybrid_override_pattern {pattern!r} (a state a slot AND "
               "pages: at least one M and one *)")
    for k, want in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
                    ("use_conv_bias", True)):
        if d.get(k, want) != want:
            refuse(f"{k}={d[k]!r} (only {want!r})")
    for k in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias"):
        if d.get(k):
            refuse(k)
    _one_group(d, refuse)
    if int(d.get("n_shared_experts", 1) or 0) > 1:
        refuse(f"n_shared_experts={d['n_shared_experts']} (one shared "
               "expert of moe_shared_expert_intermediate_size)")
    if d.get("sliding_window"):
        refuse(f"sliding_window={d['sliding_window']}")
    heads = int(d["num_attention_heads"])
    kv = int(d.get("num_key_value_heads") or heads)
    mh, groups = int(d["mamba_num_heads"]), int(d["n_groups"])
    if heads % kv:
        refuse(f"{heads} query heads over {kv} KV heads")
    if mh % groups:
        refuse(f"mamba_num_heads={mh} over n_groups={groups}")
    shared = int(d.get("moe_shared_expert_intermediate_size") or 0) \
        if int(d.get("n_shared_experts", 1) or 0) else 0
    return ModelConfig(
        name=name,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=int(d.get("intermediate_size") or 0),
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=int(d.get("head_dim") or d["hidden_size"] // heads),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=float(d.get("layer_norm_epsilon",
                                 d.get("norm_eps", 1e-5))),
        tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
        max_position_embeddings=int(d.get("max_position_embeddings", 32768)),
        eos_token_ids=eos,
        num_experts=int(d["n_routed_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        norm_topk_prob=bool(d.get("norm_topk_prob", True)),
        scoring_func="sigmoid",
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        use_rope=False,
        layer_pattern=pattern,
        ssm_num_heads=mh,
        ssm_head_dim=int(d["mamba_head_dim"]),
        ssm_state_size=int(d["ssm_state_size"]),
        ssm_groups=groups,
        ssm_conv=int(d["conv_kernel"]),
        expert_act="relu2",
        moe_shared_expert_intermediate_size=shared,
        kv_cache_dtype=str(d.get("kv_cache_dtype", "auto")),
    )


_REGISTRY: dict[str, ModelConfig] = {}


def register_config(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name.lower()] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    key = name.lower()
    if key in _REGISTRY:
        return _REGISTRY[key]
    raise KeyError(f"unknown model config {name!r}; known: {sorted(_REGISTRY)}")


# Tiny config for CPU-mesh tests: dims divisible by 8 so every mesh shape works.
register_config(ModelConfig(
    name="tiny", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=8, num_kv_heads=8, head_dim=8,
    qkv_bias=True, eos_token_ids=(0,),
))
register_config(ModelConfig(
    name="tiny-gqa", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=8, num_kv_heads=4, head_dim=8,
    qkv_bias=True, eos_token_ids=(0,),
))

# Qwen2.5 family (HF: Qwen/Qwen2.5-*-Instruct).
register_config(ModelConfig(
    name="qwen2.5-0.5b", vocab_size=151936, hidden_size=896,
    intermediate_size=4864, num_layers=24, num_heads=14, num_kv_heads=2,
    head_dim=64, rope_theta=1000000.0, tie_word_embeddings=True,
    qkv_bias=True, eos_token_ids=(151645, 151643),
))
register_config(ModelConfig(
    name="qwen2.5-1.5b", vocab_size=151936, hidden_size=1536,
    intermediate_size=8960, num_layers=28, num_heads=12, num_kv_heads=2,
    head_dim=128, rope_theta=1000000.0, tie_word_embeddings=True,
    qkv_bias=True, eos_token_ids=(151645, 151643),
))
register_config(ModelConfig(
    name="qwen2.5-7b", vocab_size=152064, hidden_size=3584,
    intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
    head_dim=128, rope_theta=1000000.0, qkv_bias=True,
    eos_token_ids=(151645, 151643),
))
register_config(ModelConfig(
    name="qwen2.5-72b", vocab_size=152064, hidden_size=8192,
    intermediate_size=29568, num_layers=80, num_heads=64, num_kv_heads=8,
    head_dim=128, rope_theta=1000000.0, qkv_bias=True,
    eos_token_ids=(151645, 151643),
))

# MoE tiny configs for CPU-mesh tests (dims divisible by 8).
register_config(ModelConfig(
    name="tiny-moe", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=8, num_kv_heads=4, head_dim=8, qkv_bias=True,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=96,
    shared_expert_intermediate_size=64, norm_topk_prob=False,
    eos_token_ids=(0,),
))
register_config(ModelConfig(
    name="tiny-mixtral", vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=8, num_kv_heads=4, head_dim=8,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=96,
    norm_topk_prob=True, eos_token_ids=(0,),
))

# Latent attention + sigmoid-routed experts behind one dense layer (the
# DeepSeek-V3 / kimi_k2 block) at CPU-test size: 4 heads of 16 | 8 over a
# 32 + 8 latent row, 1 dense + 2 routed layers, 16 experts top-4.
register_config(ModelConfig(
    name="tiny-mla-moe", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=3, num_heads=4, num_kv_heads=1,
    head_dim=24, rms_norm_eps=1e-5, eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_yarn=(4.0, 64.0, 32.0, 1.0, 1.0, 1.0), first_k_dense=1,
    scoring_func="sigmoid", routed_scaling_factor=2.5, n_shared_experts=1,
))

# Window and full attention layers in one model (the ``laguna`` block) at
# CPU-test size: 1 dense full layer, then 2 periods of 2 window layers (6
# heads, window 16, plain RoPE) and 1 full layer (4 heads, half of each head
# rotated under YaRN), 2 KV heads, a per-head gate, 16 softmax-routed
# experts top-4 times 2.5 and a gated shared expert.
register_config(ModelConfig(
    name="tiny-swa-moe", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=7, num_heads=4, num_kv_heads=2,
    head_dim=16, rope_theta=500000.0, eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    routed_scaling_factor=2.5, first_k_dense=1, sliding_window=16,
    window_period=2, window_num_heads=6, window_rope_theta=10000.0,
    partial_rotary_factor=0.5,
    rope_hf_yarn=(4.0, 32.0, 32.0, 1.0, 1.1386294361119891),
    attn_gate=True,
))

# The ``mimo_v2`` block at CPU-test size: 1 dense full layer, a first period
# cut to 1 window layer and its full layer, 2 whole periods of 2 window
# layers (8 heads over 4 KV heads, window 16, a sink logit a head) and 1
# full layer (8 heads over 2 KV heads, no sink); keys 24 and values 16 wide,
# the first 8 lanes of a head rotated in both kinds, values times 0.707; 16
# sigmoid-routed experts top-4 with a selection bias, no shared expert.
register_config(ModelConfig(
    name="tiny-swa-sink-moe", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=9, num_heads=8, num_kv_heads=2,
    head_dim=24, rope_theta=10000000.0, rms_norm_eps=1e-5,
    eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, v_head_dim=16, first_k_dense=1,
    scoring_func="sigmoid", sliding_window=16, window_period=2,
    window_num_heads=8, window_rope_theta=10000.0,
    partial_rotary_factor=0.334, short_period=1, window_kv_heads=4,
    window_partial_rotary_factor=0.334, attn_value_scale=0.707,
    attn_sink=("window",),
))

# Linear-attention and gated NoPE GQA layers in one model (the
# ``solar_open2`` block) at CPU-test size: a GQA layer (4 heads of 16 over 2
# KV heads, an elementwise output gate), then 2 periods of 2 delta-rule
# linear layers (4 heads of 16, convolution of 4, negative eigenvalues) and
# a GQA layer, then a tail of 2 linear layers; every layer 16 sigmoid-routed
# experts top-4 beside one shared expert.
register_config(ModelConfig(
    name="tiny-linear-moe", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=9, num_heads=4, num_kv_heads=2,
    head_dim=16, rms_norm_eps=1e-5, eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, scoring_func="sigmoid", n_shared_experts=1,
    linear_period=2, linear_num_heads=4, linear_head_dim=16, linear_conv=4,
    linear_neg_eigval=True, use_rope=False, attn_out_gate=True,
))

# Gated-delta-rule linear layers beside gated latent-attention layers (the
# ``gigachat3_5`` block) at CPU-test size: 2 dense linear layers, a first
# period cut short (1 linear layer and its latent layer), a whole period (2
# linear layers and a latent layer) and a tail of 1 linear layer; a linear
# layer 2 key heads under 4 value heads of 16, a decay a head; a latent
# layer 4 heads of 16 | 8 over a 32 + 8 row, gated; the gated norm in
# sandwich placement, SwiGLU clamped at 0.2 (so that the clamp bites at this
# size), 16 sigmoid-routed experts top-4 times 2.5 beside a shared one.
register_config(ModelConfig(
    name="tiny-latent-linear-moe", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=8, num_heads=4, num_kv_heads=1,
    head_dim=24, rope_theta=100000.0, eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_yarn=(4.0, 64.0, 32.0, 1.0, 1.0, 1.0), first_k_dense=2,
    scoring_func="sigmoid", routed_scaling_factor=2.5, n_shared_experts=1,
    linear_period=2, linear_num_heads=4, linear_head_dim=16, linear_conv=4,
    attn_out_gate=True, short_period=1, linear_head_decay=True,
    linear_key_heads=2, linear_gate_scale=2.0, linear_norm_eps=1e-6,
    norm_gate=2.0, norm_post=True, swiglu_limit=0.2,
))

# The ``longcat_flash`` block at CPU-test size: 2 layers of two latent
# sublayers (4 heads of 16 | 8 over a 32 + 8 row, plain RoPE, the query
# latent times (64 / 48)^0.5 and the key/value latent times (64 / 32)^0.5),
# each behind it a dense SwiGLU of 128, and on the shortcut 16 experts of 32
# and 8 identity experts under one softmax, top-4 of score + a selection
# bias, times 2.5, no shared expert.
register_config(ModelConfig(
    name="tiny-shortcut-mla-moe", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=1,
    head_dim=24, rope_theta=10000000.0, rms_norm_eps=1e-5,
    eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=False, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    routed_scaling_factor=2.5, attn_sublayers=2, zero_experts=8,
    router_select_bias=True, mla_q_scale=(64 / 48) ** 0.5,
    mla_kv_scale=(64 / 32) ** 0.5,
))

# The ``nemotron_h`` block at CPU-test size, 15 layers of ONE sublayer: two
# equal periods ``ME M * E``, a shorter one ``M * E`` (an attention layer
# directly behind a Mamba layer) and a tail ``ME`` with no attention layer.
# A Mamba-2 mixer: 8 heads of 8 over 2 groups (4 heads share a group's B and
# C), state 16, convolution of 4 with bias; a GQA layer 4 heads of 16 over 2
# KV heads, no rotation; 16 sigmoid-routed two-matrix relu^2 experts top-2
# times 2.5 beside one ungated shared expert of 48.
register_config(ModelConfig(
    name="tiny-ssm-moe", vocab_size=512, hidden_size=64,
    intermediate_size=32, num_layers=15, num_heads=4, num_kv_heads=2,
    head_dim=16, rms_norm_eps=1e-5, eos_token_ids=(0,),
    num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, scoring_func="sigmoid", routed_scaling_factor=2.5,
    use_rope=False, layer_pattern="MEM*EMEM*EM*EME", ssm_num_heads=8,
    ssm_head_dim=8, ssm_state_size=16, ssm_groups=2, ssm_conv=4,
    expert_act="relu2", moe_shared_expert_intermediate_size=48,
))

# MoE families (HF: mistralai/Mixtral-8x7B-Instruct-v0.1, Qwen/Qwen2-57B-A14B).
register_config(ModelConfig(
    name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=1000000.0, rms_norm_eps=1e-5,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=14336,
    norm_topk_prob=True, eos_token_ids=(2,),
))
register_config(ModelConfig(
    name="qwen2-57b-a14b", vocab_size=151936, hidden_size=3584,
    intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
    head_dim=128, rope_theta=1000000.0, qkv_bias=True,
    num_experts=64, num_experts_per_tok=8, moe_intermediate_size=2560,
    shared_expert_intermediate_size=20480, norm_topk_prob=False,
    eos_token_ids=(151645, 151643),
))

# Llama-3 family.
register_config(ModelConfig(
    name="llama3-8b", vocab_size=128256, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=500000.0, rms_norm_eps=1e-5,
    eos_token_ids=(128001, 128009),
))
register_config(ModelConfig(
    name="llama3-70b", vocab_size=128256, hidden_size=8192,
    intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
    head_dim=128, rope_theta=500000.0, rms_norm_eps=1e-5,
    eos_token_ids=(128001, 128009),
))
