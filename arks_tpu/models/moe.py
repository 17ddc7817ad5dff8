"""Mixture-of-Experts FFN (Mixtral / Qwen2-MoE families).

The reference orchestrates MoE models only by passing their names to
vLLM/SGLang containers (no MoE code of its own); here the block is native.

TPU-first formulation:
- **Dense dispatch**: every expert's FFN runs as one batched einsum over the
  expert dim, with unselected experts zeroed by the router-weight tensor.
  Decode is HBM-bound — all expert weights are read once per step no matter
  how many tokens route to them — so compute-all costs nothing extra at
  serving batch sizes while keeping shapes static for XLA.
- **Batched dispatch** (:func:`_batched_dispatch`; large token batches on an
  unsharded expert dim): every expert takes a fixed batch of rows in one
  expert-major SwiGLU, what it draws beyond them follows in overflow tiles,
  and one contraction with a selection matrix puts the experts' rows back
  on their tokens (:func:`_combine`).
  Quantised leaves are read as they are stored in both: the int8 -> bf16
  convert sits in the contraction's operand read and the per-channel scale
  lands on its output, so no full-width copy of an expert stack is written
  to HBM in any step program.  Timed on a v5e at Mixtral-8x7B's widths
  (8 experts top-2, int8, 4 layers; PERF.md, PR 33): 64 rows 7.6 ms dense
  (the HBM rate) against 46 ms for dequantise + ``ragged_dot``; 320 rows
  14.4 ms batched, 19.8 dense, 58 dequantise + ``ragged_dot``, 37 the
  block-sparse Pallas kernel (deleted with its knob).  ``jax.lax.ragged_dot``
  stays for plain (unquantised) leaves: training and float tests.
- **Expert parallelism = model-axis sharding**: expert dims shard over the
  ``model`` mesh axis (each device holds E/tp experts); activations stay
  replicated across that axis between blocks, so XLA turns the final
  expert-contraction into one psum over ICI — the same Megatron pattern the
  dense MLP already uses, no all-to-all needed.
- Router math in float32 (softmax over expert logits is tiny but
  precision-sensitive).

Weight layout per layer (leading [L] from the stacked-layer convention):
  router      [L, E, X]
  w_gate/up   [L, X, E, Fm]     w_down [L, X, Fm, E]
  shared gate/up [L, E, Fs], shared down [L, Fs, E], shared_gate [L, E]
  (a two-matrix expert: no w_gate, no shared gate projection, and w_up
  stored transposed as w_upt [L, X, Fm, E])
where X = num_experts, Fm = moe_intermediate_size.

**An expert of two matrices** (``cfg.expert_act`` "relu2", the
``nemotron_h`` block): ``relu(x W_up)^2 W_down``, no gate matrix, the shared
expert alike; the tree holds neither ``w_gate`` nor ``shared_gate_proj``
and every dispatch below reads one input projection where a SwiGLU reads
two (:func:`_hidden`, :func:`expert_act`): a static property of the block,
no path of its own.

DeepSeek-V3 / kimi_k2 routing (``cfg.scoring_func == "sigmoid"``): the
router also carries ``router_bias [L, W]``, the shared experts are
ungated (``n_shared_experts x Fm`` wide, no ``shared_gate``), and L is
the ROUTED stack's depth (the dense prefix has a tree of its own).

**A share of a layer** (``cfg.expert_parallel_size`` > 1: expert
parallelism as one chip sees it): the router keeps its whole width
``W = X x size``, X experts are held here, the experts
``[rank x X, (rank + 1) x X)``, and the layer returns the part of the
result its own experts give (plus the shared expert, which every chip
computes alike).  What the absent experts would add is left out; on one
chip the layer runs without its exchange.

**A softmax router with a selection bias** (``cfg.router_select_bias``,
the ``longcat_flash`` block): one softmax over the router's whole width,
the top-k of ``p + router_bias`` chosen, the unbiased ``p`` times
``routed_scaling_factor`` the weights, nothing renormalised.

**Identity (zero-compute) experts** (``cfg.zero_experts`` Z > 0): the
router's width is ``W = X x size + Z`` and the ids at or above ``X x size``
name experts with no weights, each the identity: a pair that lands on one
adds ``g x``.  No dispatch gathers such a pair (it enters no batch, no
overflow tile and no one-hot column); a token's identity weights are summed
and multiplied onto ``x`` once (:func:`_zero_experts`, scope
``arks.moe_zero``).  Under a share every chip computes that part alike, as
it does a shared expert: it is counted once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Params = dict


def shared_width(cfg) -> int:
    """Width of the shared expert's FFN (0: none): the sigmoid-gated kind
    states it, the ungated kind is ``n_shared_experts`` routed widths or,
    where the file states one, ``moe_shared_expert_intermediate_size``."""
    return (cfg.shared_expert_intermediate_size
            or cfg.moe_shared_expert_intermediate_size
            or cfg.n_shared_experts * cfg.moe_intermediate_size)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray, limit: float = 0.0
           ) -> jnp.ndarray:
    """``SiLU(gate) * up``, the SiLU in float32; with a ``limit`` c > 0
    (``cfg.swiglu_limit``) ``SiLU(min(gate, c)) * clip(up, -c, c)``."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def two_matrix(cfg) -> bool:
    """An expert is ``W_up`` and ``W_down`` alone (``cfg.expert_act``
    "relu2"): the tree holds no ``w_gate`` and no ``shared_gate_proj``, and
    the routed experts' ``W_up`` as ``w_upt`` (:func:`init_moe_params`)."""
    return cfg.expert_act == "relu2"


def expert_act(gate: jnp.ndarray | None, up: jnp.ndarray, cfg
               ) -> jnp.ndarray:
    """An expert's hidden activation: :func:`swiglu` of its two input
    projections, or of a two-matrix expert (``gate`` None) ``relu(up)^2``,
    squared in float32."""
    if gate is not None:
        return swiglu(gate, up, cfg.swiglu_limit)
    return jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(up.dtype)


def _hidden(dot, w: Params, cfg) -> jnp.ndarray:
    """The routed experts' hidden activations: ``dot(leaf, transposed)`` is
    a dispatch's contraction of its rows with an input projection, ``[.., E,
    F]`` or (a two-matrix expert's one, ``transposed``) ``[.., F, E]``."""
    if two_matrix(cfg):
        return expert_act(None, dot(w["w_upt"], True), cfg)
    return expert_act(dot(w["w_gate"], False), dot(w["w_up"], False), cfg)


# A softmax router's probabilities are of the order of 1 / W, where the
# seeded draw of every leaf (normal x 0.02) would decide the top-k alone and
# for every token alike.  The seeded selection bias of such a router is the
# draw times ``SELECT_BIAS_SCALE / W``: a deviation of half a uniform
# probability, which moves the choice where scores are close and leaves it
# to the token elsewhere (a learnt bias balances loads on that scale).
SELECT_BIAS_SCALE = 25.0


def seeded_select_bias(w: jnp.ndarray) -> jnp.ndarray:
    """The seeded draw ``w`` [.., W] of a softmax router's selection bias as
    it is stored (``cfg.router_select_bias``; the sigmoid routers' bias is
    the draw itself: their scores are of the order of 1)."""
    return (w.astype(jnp.float32) * (SELECT_BIAS_SCALE / w.shape[-1])
            ).astype(w.dtype)


def init_moe_params(cfg, key, dtype, layers: int | None = None) -> Params:
    """The routed FFN's leaves, stacked ``layers`` deep (every routed layer
    of the model where not given)."""
    l = cfg.num_routed_layers if layers is None else layers
    e = cfg.hidden_size
    x, fm = cfg.num_experts, cfg.moe_intermediate_size
    keys = iter(jax.random.split(key, 9))

    def w(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    gated = not two_matrix(cfg)
    p: Params = {"router": w(next(keys), (l, e, cfg.router_width))}
    if gated:
        p["w_gate"] = w(next(keys), (l, x, e, fm))
        p["w_up"] = w(next(keys), (l, x, e, fm))
    else:
        # Stored TRANSPOSED, [L, X, Fm, E], the numbers of the [.., E, Fm]
        # draw: where Fm is no whole number of 128-lane tiles and E is
        # (1856 under 2688) the chip lays a [.., E, Fm] stack out E-minor,
        # the batched dispatch's dots want it Fm-minor, and a step that
        # carries a chunk copied the whole stack first, 1.8 GB (compiled
        # for a described v5e, PERF.md section 6, PR 56).  As it is read
        # is as it is stored, as `transformer.init_params`'s q / k / v.
        p["w_upt"] = w(next(keys), (l, x, e, fm)).swapaxes(-1, -2)
    p["w_down"] = w(next(keys), (l, x, fm, e))
    fs = shared_width(cfg)
    if fs:
        if gated:
            p["shared_gate_proj"] = w(next(keys), (l, e, fs))
        p["shared_up"] = w(next(keys), (l, e, fs))
        p["shared_down"] = w(next(keys), (l, fs, e))
    if cfg.shared_expert_intermediate_size:
        p["shared_gate"] = w(next(keys), (l, e))
    if cfg.select_bias:
        # Non-zero, so that what selects and what weighs differ.
        p["router_bias"] = w(next(keys), (l, cfg.router_width))
        if cfg.router_select_bias:
            p["router_bias"] = seeded_select_bias(p["router_bias"])
    return p


def moe_pspecs(cfg, axis_model: str, shard_experts: bool) -> Params:
    """PartitionSpecs matching init_moe_params.  Experts shard over the model
    axis when divisible (expert parallelism); else expert weights replicate
    and only the shared expert uses tensor parallelism."""
    from jax.sharding import PartitionSpec as P
    ex = axis_model if shard_experts else None
    p: Params = {
        "router": P(None, None, None),
        "w_gate": P(None, ex, None, None),
        "w_up": P(None, ex, None, None),
        "w_down": P(None, ex, None, None),
    }
    if shared_width(cfg):
        p["shared_gate_proj"] = P(None, None, axis_model)
        p["shared_up"] = P(None, None, axis_model)
        p["shared_down"] = P(None, axis_model, None)
    if cfg.shared_expert_intermediate_size:
        p["shared_gate"] = P(None, None)
    if cfg.select_bias:
        p["router_bias"] = P(None, None)
    return p


def shard_experts(cfg, tp: int) -> bool:
    return tp > 1 and cfg.num_experts % tp == 0


def router_topk(logits: jnp.ndarray, cfg, bias: jnp.ndarray | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[.., W] router logits → ([.., k] combine weights, [.., k] expert ids)
    over the router's whole width.  Float32 throughout.  Shared by the
    dense and grouped dispatch paths so routing semantics can never diverge
    between them.  By ``cfg.scoring_func``:

    - ``softmax``: softmax over all experts, top-k selected; renormalized
      when ``norm_topk_prob`` (Mixtral semantics — equal to softmax over
      the top-k logits), then times ``routed_scaling_factor`` (``laguna``;
      1.0 elsewhere);
    - ``sigmoid`` (DeepSeek-V3 ``noaux_tc``, no group limit): scores are
      sigmoids; the top-k of ``score + bias`` are CHOSEN, their weights are
      the UNBIASED scores, normalised over the chosen when
      ``norm_topk_prob``, times ``routed_scaling_factor``;
    - ``softmax`` with a ``bias`` (``cfg.router_select_bias``, the
      ``longcat_flash`` block): softmax over all experts, the identity ones
      among them; the top-k of ``p + bias`` are CHOSEN, their weights are
      the UNBIASED ``p`` times ``routed_scaling_factor``.  An id at or
      above ``cfg.num_real_experts`` is an identity expert's."""
    k = cfg.num_experts_per_tok
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk_prob:
            vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
        return vals * cfg.routed_scaling_factor, idx
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if bias is not None:
        _, idx = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        vals = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        vals, idx = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        vals = vals * cfg.routed_scaling_factor
    return vals, idx


def router_weights(logits: jnp.ndarray, cfg,
                   bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """[.., W] router logits → [.., X] combine weights of the experts HELD
    here (unselected experts zero) — the dense-dispatch form of
    router_topk; under a share, the held columns of the whole width."""
    return _held_weights(*router_topk(logits, cfg, bias), cfg)


def _held_weights(vals: jnp.ndarray, idx: jnp.ndarray, cfg) -> jnp.ndarray:
    """:func:`router_topk`'s pairs as [.., X] weights of the held experts;
    absent and identity experts fall off the one-hot."""
    if cfg.expert_parallel_size > 1:
        idx = idx - held_first(cfg)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=vals.dtype)  # [.., k, X]
    return jnp.einsum("...k,...kx->...x", vals, onehot)


def _zero_experts(x: jnp.ndarray, vals: jnp.ndarray, idx: jnp.ndarray, cfg,
                  row_valid: jnp.ndarray | None
                  ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """The identity experts' part of a routed layer on ``x`` [.., E] with
    :func:`router_topk`'s pairs [.., k]: ``(sum of the weights that landed
    on an identity expert) x``, and, with ``row_valid`` [..], how many
    pairs of the valid rows did."""
    with jax.named_scope("arks.moe_zero"):
        zero = idx >= cfg.num_real_experts
        g = jnp.sum(jnp.where(zero, vals, 0), axis=-1, keepdims=True)
        pairs = None if row_valid is None else jnp.sum(
            zero & row_valid[..., None], dtype=jnp.int32)
        return x * g.astype(x.dtype), pairs


def held_first(cfg) -> int:
    """Id, in the router's numbering, of the first expert held here."""
    return cfg.expert_parallel_rank * cfg.num_experts


def _held_capacity(n_tokens: int, cfg) -> int:
    """Rows each held expert takes in the batched dispatch, in tiles of
    128, at most every token.  A share: the fewest tiles that hold THREE
    times what a uniform router sends an expert, so that the batch follows
    what lands (its experts draw few rows of many).  At the benchmark's
    whole-budget steps, 34-42 fair rows of ~1,060, that is ONE tile; four
    times was rounded up to two, and the seeded routers put 13 % (laguna)
    and 11 % (gigachat) of those rows to use where they now fill 27 and
    22 % (PERF.md section 6, PR 49: laguna's step 90.6 -> 72.6 ms, of it
    the batch's combine, a scatter-add until PR 53, 13.9 -> 7.3 and its
    three dots 13.8 -> 7.6);
    the price is overflow tiles, 2.0-2.9 a layer in laguna's windows where
    0.2-0.5 were needed, each a 32nd of the batch.  A layer held whole: one
    and a half times (every pair lands here, the batch is what the step
    computes, and at 8 experts top-2 four times would be every token: the
    dense dispatch).  What an expert draws beyond them goes through
    :func:`_batched_dispatch`'s overflow tiles: nothing is ever dropped."""
    fair = -(-n_tokens * cfg.num_experts_per_tok // cfg.router_width)
    if cfg.expert_parallel_size > 1:
        return min(n_tokens, -(-3 * fair // 128) * 128)
    return min(n_tokens, -(-3 * fair // 256) * 128)


# Overflow tiles a share's dispatch runs whether it needs them or not, so
# that a step's time does not follow a seed's router: a 45 s closed-loop
# reading moved by 1-2 % with it (PERF.md §6, PR 27).  What a layer needs
# is its router's skew, which no shape says: at one 128-row tile an expert
# the windows of PR 49 read 2.0-2.9 tiles a layer-step by seed (laguna; 5
# at the 95th percentile, 10 the most), 0.64 (mimo; 3, 6), 0.02 (gigachat)
# and 0.009 (kimi, PR 44) under near-equal shapes.  Four leave the loop
# 0.1-0.7 trips a layer-step in laguna's windows (~45 us each: under 0.5 ms
# of a 73 ms step, and over seven seeds the step's cycle did not follow
# them), 0.02 in mimo's and none elsewhere; four more would cost ~3 ms of
# EVERY laguna step and ~2 ms of kimi's, more than they steady.  A layer
# that needs more runs more.  (A layer held whole runs a count fixed by its
# shape: :func:`_batched_dispatch`.)
_SPARE_TILES = 4


def share_rows(n_tokens: int, cfg) -> tuple[int, int]:
    """What a SHARE's routed layer puts through each of its experts'
    contractions in a step program of ``n_tokens`` rows: ``(rows whatever
    the router does, rows a trip of the overflow loop adds)``.  The dense
    dispatch computes every row for every held expert; the batched one
    an expert's batch and the spare tiles (``moe_batch_rows_total``,
    docs/monitoring.md: held pairs over these rows is how full the
    experts' batches are).  :func:`moe_ffn`'s own rule, for the flat
    ``[1, n, E]`` batch of a step (a share is never meshed)."""
    if not _batch_pays(n_tokens, None, cfg):
        return n_tokens * cfg.num_experts, 0
    cap = _held_capacity(n_tokens, cfg)
    return (cfg.num_experts + _SPARE_TILES) * cap, cap


def _expert_dot(eq: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """``[X, C, a] x [X, a, b] -> [X, C, b]`` where ``w`` may be a
    quantised leaf: int8 converts in the dot's operand read and scales the
    output per channel (``quant.qeinsum``'s rule with the expert dim
    leading); int4's group scales vary along the contraction, so its
    dequant is the operand's elementwise producer (``qeinsum``'s rule
    again)."""
    from arks_tpu.models.quant import is_quantized, qeinsum
    if not is_quantized(w) or "gs" in w:
        return qeinsum(eq, x, w)
    scale = w["s"]
    if eq.split("->")[0][-1] not in eq.split("->")[1]:
        scale = scale.swapaxes(-1, -2)      # a leaf stored [X, b, a]
    return jnp.einsum(eq, x, w["q"].astype(x.dtype)) * scale.astype(x.dtype)


# Rows of a flat batch up to which :func:`_combine` contracts.  The
# contraction runs at the MXU's rate, 2 x n x S x E operations at 186-192
# TFLOP/s bf16 on a v5e (165 at n = 320); the scatter-add it replaces goes
# slot by slot at 55 GB/s at its best, so both are linear in S x E and n
# alone decides.  The combine alone, ms a layer, scatter-add / contraction
# (my chip runs, PR 53, PERF.md section 6; S = the batch and the unrolled
# tiles at n, by :func:`_held_capacity`):
#        32 held of 256, top-10,   12 held of 384, top-8,  8 of 8, top-2,
#        E 3072 (laguna's share)   E 7168 (kimi's share)   E 4096 (mixtral)
#   n   320  S  4608  0.52 / 0.06  S 2048  0.60 / 0.06  S  1536  0.23 / 0.03
#     1,056     4608  0.56 / 0.16    2048  1.12 / 0.17
#     2,048     9216  1.12 / 0.61    4096  2.23 / 0.65     9984  1.51 / 0.88
#     3,072    13824  1.69 / 1.36                         14976  2.53 / 1.97
#     3,584    18432  2.27 / 2.11
#     4,096    18432  2.32 / 2.42    6144  4.14 / 1.90    19968  3.38 / 3.49
# 3,584 is the most rows read at which the contraction won in every family;
# at 4,096 it loses 3-4 % in two and wins 2.2 x in the third.  Every step
# program of the benchmark has n <= 1,088.
_CONTRACT_MAX_TOKENS = 3584


def _contract_pays(n_tokens: int) -> bool:
    """Whether :func:`_combine` puts a batch's slots back on ``n_tokens``
    tokens by a contraction (else by a scatter-add): the shape's own rule,
    as :func:`_batch_pays` is the dispatch's."""
    return n_tokens <= _CONTRACT_MAX_TOKENS


def _combine(n: int, down: jnp.ndarray, token_of: jnp.ndarray,
             w: jnp.ndarray, out: jnp.ndarray | None = None) -> jnp.ndarray:
    """The batched dispatch's combine: ``[n, E]`` rows, token ``t``'s the
    sum over the slots ``s`` with ``token_of[s] == t`` of ``w[s] x
    down[s]`` (``down [S, E]``; a dead slot has weight 0 and any token), on
    top of ``out`` where given.  ONE contraction on the MXU of the selection
    matrix ``[n, S]``, ``w[s]`` at ``(token_of[s], s)`` (an iota compare
    that the compiler builds inside the dot's fusion, never in HBM), with
    the rows: a token's products are exact in float32, summed in float32 and
    rounded once.  The scatter-add this was until PR 53 rounds each product
    and every add and goes slot by slot, dead slots too (0.83 of kimi's 2.65
    ms a routed layer, 0.44 of laguna's 1.47); it stays for a flat batch past
    :func:`_contract_pays`, where the contraction's ``n x S`` loses to it.

    A contraction multiplies every row by every token's zeros, so a value
    that is not finite would reach EVERY token of the step (0 x NaN), where
    the scatter-add kept it on its own: it counts as 0 here, and the token
    that brought it keeps it in the residual stream.  Such rows are real
    (gigachat's cell, the backlog at its ramp's start, two seeds of three:
    with them in, every stream of the step decoded garbage until the
    backlog cleared; PERF.md section 6, PR 53)."""
    dtype = down.dtype
    if not _contract_pays(n):
        if out is None:
            out = jnp.zeros((n, down.shape[1]), dtype)
        return out.at[token_of].add(down * w[:, None].astype(dtype))
    down = jnp.where(jnp.isfinite(down), down, 0)
    sel = jnp.where(token_of[None, :] == jnp.arange(n)[:, None],
                    w[None, :], 0).astype(dtype)
    got = jnp.einsum("ns,se->ne", sel, down,
                     preferred_element_type=jnp.float32)
    if out is not None:
        got = got + out
    return got.astype(dtype)


def _batched_dispatch(x2: jnp.ndarray, vals: jnp.ndarray, idx: jnp.ndarray,
                      mp: Params, cfg, row_valid: jnp.ndarray | None = None,
                      stack: tuple | None = None
                      ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The routed experts HELD HERE (a share's, or all of a layer held
    whole) on ``[n, E]`` rows: ``(out [n, E], held [n, k] bool, tiles)``.
    Pairs are sorted by held expert (absent experts and the rows that
    carry no token last, never gathered: a step's padding rows are all
    alike and would all land on the same experts).  Every held expert then
    takes its first :func:`_held_capacity` rows in ONE ``[X, C, E]``
    batched SwiGLU, the int8 convert fused into the contraction: work that
    does not depend on how the router spread the tokens, so a step's time
    is the same from seed to seed.  What an expert draws beyond C rows
    follows in tiles of C rows, one expert a tile, each a read of that
    expert's weights.  A count of tiles fixed by the shape runs unrolled
    (dead where not needed), so that an expert's slice fuses into the
    dots' operand reads.  A layer held whole knows how many pairs it
    holds, all ``n x k``, so ``(n x k - 1) // C`` tiles always suffice,
    and an expert that draws every row is as exact and costs the step the
    same as a uniform router.  A share runs ``_SPARE_TILES``, and what its
    experts need beyond those in a loop, so that only a layer that needs
    more takes longer and nothing is ever dropped; ``tiles`` (a share's;
    None of a layer held whole) is int32 ``[2]``: the overflow tiles the
    layer needed, and the trips that loop made.  The batch's rows and the
    unrolled tiles' go back on their tokens in ONE :func:`_combine` a layer
    (the tiles' ``down`` dots write into the concatenated ``[S, E]`` in
    place), a trip of the loop combines its own tile onto the result.

    A tile takes its expert's weights out of ``stack`` = ``(tree, layer)``,
    the STACKED tree that the program was handed and the index at which
    ``mp`` lies in it (None: ``mp`` is a lone layer, a stack with no
    leading index), by one ``dynamic_slice`` at ``(layer, expert)``: an
    operand of a ``while`` has to be a buffer, the stack is one already (a
    parameter of the program), and a layer's slice of it would be made one
    first, every expert leaf copied whole a layer a step (PERF.md §6,
    PR 44)."""
    n, e = x2.shape
    k, nx = cfg.num_experts_per_tok, cfg.num_experts
    share = cfg.expert_parallel_size > 1
    cap = _held_capacity(n, cfg)
    tree, lead = (mp, ()) if stack is None else (stack[0], (stack[1],))
    with jax.named_scope("arks.moe_route"):
        local = idx - held_first(cfg)
        held = (local >= 0) & (local < nx)
        if row_valid is not None:
            held = held & row_valid.reshape(-1, 1)
        flat_expert = jnp.where(held, local, nx).reshape(-1)        # [n*k]
        # (A compare and a sum, not ``bincount``: that is a scatter-add
        # of n x k updates, one after the other on the chip.)
        sizes = jnp.sum(flat_expert[:, None] == jnp.arange(nx)[None, :],
                        axis=0, dtype=jnp.int32)
        starts = jnp.cumsum(sizes) - sizes
        order = jnp.argsort(flat_expert)
        flat_w = vals.reshape(-1)
        # Overflow tiles, expert after expert: tile t belongs to the first
        # expert whose running count of tiles passes t.
        tiles = (jnp.maximum(sizes - cap, 0) + cap - 1) // cap      # [X]
        tile_ends = jnp.cumsum(tiles)

    def slots(experts, first, weights):
        """What ``experts`` [x] give their rows ``first + [0, C)``, slot by
        slot: ``(down [x C, E], token [x C], weight [x C])``, the weight of
        a slot that holds no pair 0."""
        with jax.named_scope("arks.moe_route"):
            c = first[:, None] + jnp.arange(cap)[None, :]           # [x, C]
            live = c < jnp.take(sizes, experts)[:, None]
            pair = jnp.take(order, jnp.where(
                live, jnp.take(starts, experts)[:, None] + c, 0))   # [x, C]
            token_of = pair // k
            xs = jnp.take(x2, token_of, axis=0)                     # [x, C, E]
        with jax.named_scope("arks.moe_dot"):
            act = _hidden(lambda leaf, t: _expert_dot(
                "xce,xfe->xcf" if t else "xce,xef->xcf", xs, leaf),
                weights, cfg)
            down = _expert_dot("xcf,xfe->xce", act, weights["w_down"])
        with jax.named_scope("arks.moe_route"):
            w = jnp.where(live, jnp.take(flat_w, pair), 0)
        return down.reshape(-1, e), token_of.reshape(-1), w.reshape(-1)

    def overflow_tile(t):
        with jax.named_scope("arks.moe_route"):
            # A spare tile (t past the last one needed) runs on the last
            # expert from past the end of its rows: every slot dead.
            ex = jnp.minimum(jnp.searchsorted(tile_ends, t, side="right"),
                             nx - 1)
            nth = t - (jnp.take(tile_ends, ex) - jnp.take(tiles, ex))

            def expert(a):
                """``a [*lead, X, ..]`` -> ``[1, ..]``, the expert's."""
                at = len(lead) + 1
                start = [np.zeros([], ex.dtype)] * a.ndim
                start[:at] = [*lead, ex]
                return jax.lax.dynamic_slice(
                    a, start, (1,) * at + a.shape[at:],
                    allow_negative_indices=[i < at for i in range(a.ndim)]
                ).reshape((1,) + a.shape[at:])

            one = {name: jax.tree.map(expert, tree[name])
                   for name in ("w_gate", "w_up", "w_upt", "w_down")
                   if name in tree}
        return slots(ex[None], (cap * (1 + nth))[None], one)

    fixed = 0
    if cap < n:
        fixed = _SPARE_TILES if share else (n * k - 1) // cap
    parts = [slots(jnp.arange(nx), jnp.zeros((nx,), jnp.int32), mp)]
    parts += [overflow_tile(jnp.int32(t)) for t in range(fixed)]
    with jax.named_scope("arks.moe_route"):
        out = _combine(n, *(jnp.concatenate(a) for a in zip(*parts)))
    if not share:
        return out, held, None

    def trip(t, out):
        part = overflow_tile(t)
        with jax.named_scope("arks.moe_route"):
            return _combine(n, *part, out=out)

    needed = tile_ends[-1]
    out = jax.lax.fori_loop(fixed, jnp.maximum(needed, fixed), trip, out)
    return out, held, jnp.stack([needed, jnp.maximum(needed - fixed, 0)])


def _shared_expert(x2: jnp.ndarray, mp: Params, cfg,
                   constrain=None) -> jnp.ndarray:
    """The shared expert's FFN (a SwiGLU, or the two-matrix form the
    routed experts have) on [.., E] rows: every chip computes it alike;
    sigmoid-gated by ``shared_gate`` (Qwen2-MoE) or as it is
    (DeepSeek-V3)."""
    from arks_tpu.models.quant import qeinsum
    with jax.named_scope("arks.moe_shared"):
        sg = None if two_matrix(cfg) else qeinsum(
            "...e,ef->...f", x2, mp["shared_gate_proj"])
        su = qeinsum("...e,ef->...f", x2, mp["shared_up"])
        sact = expert_act(sg, su, cfg)
        if constrain is not None:
            sact = constrain(sact, sact.ndim - 1)
        shared = qeinsum("...f,fe->...e", sact, mp["shared_down"])
        if "shared_gate" not in mp:
            return shared
        gatev = jax.nn.sigmoid(
            jnp.einsum("...e,e->...", x2, mp["shared_gate"]).astype(
                jnp.float32))
        return shared * gatev[..., None].astype(shared.dtype)


_GROUPED_MIN_TOKENS = 64


def _ragged_dispatch(x2: jnp.ndarray, vals: jnp.ndarray, idx: jnp.ndarray,
                     mp: Params, cfg) -> jnp.ndarray:
    """The routed experts of a layer held whole, PLAIN leaves (training,
    float tests), on ``[n, E]`` rows: every (token, expert) pair, sorted by
    expert, through three ``ragged_dot`` contractions (differentiable;
    ``ragged_dot`` takes no quantised leaf, those go the batched way)."""
    n, e = x2.shape
    k, nx = cfg.num_experts_per_tok, cfg.num_experts
    if cfg.zero_experts:
        # An identity pair sorts behind every group and gets no row of any.
        vals = jnp.where(idx < nx, vals, 0)
    with jax.named_scope("arks.moe_route"):
        flat_expert = idx.reshape(-1)                       # [T*k]
        order = jnp.argsort(flat_expert)
        token_of = order // k                               # source token
        xs = jnp.take(x2, token_of, axis=0)                 # [T*k, E] sorted
        group_sizes = jnp.bincount(flat_expert, length=nx)
    with jax.named_scope("arks.moe_dot"):
        act = _hidden(lambda leaf, t: jax.lax.ragged_dot(
            xs, leaf.swapaxes(-1, -2) if t else leaf, group_sizes), mp, cfg)
        down = jax.lax.ragged_dot(act, mp["w_down"], group_sizes)  # [T*k, E]
    with jax.named_scope("arks.moe_route"):
        w = jnp.take(vals.reshape(-1), order).astype(down.dtype)   # [T*k]
        if cfg.zero_experts:
            down = jnp.where(w[:, None] != 0, down, 0)
        return jnp.zeros((n, e), down.dtype).at[token_of].add(
            down * w[:, None])


def _batch_pays(n_tokens: int, mp: Params, cfg) -> bool:
    """Whether the grouped path does less than the dense dispatch on
    ``n_tokens`` rows (under ``_GROUPED_MIN_TOKENS`` the dense one wins on
    dispatch cost).  Quantised experts, and any share of a layer (``mp``
    is not read for one), go through the batched dispatch, which is the
    dense one with gathers around it once an expert's batch is every token
    (64 rows of 8 experts top-2 held whole, or of 40 held of 320 top-8: the
    dense dispatch runs at the HBM rate there, and the batched form of such
    a step copied every expert leaf out of its stack first, PERF.md §6,
    PR 36)."""
    from arks_tpu.models.quant import is_quantized
    if n_tokens < _GROUPED_MIN_TOKENS:
        return False
    if cfg.expert_parallel_size == 1 and not is_quantized(mp["w_down"]):
        return True
    return _held_capacity(n_tokens, cfg) < n_tokens


def _counts(held_pairs: jnp.ndarray) -> jnp.ndarray:
    """:func:`moe_ffn`'s counts of a layer that ran no overflow loop."""
    return jnp.stack([held_pairs.astype(jnp.int32), jnp.int32(0),
                      jnp.int32(0)])


def moe_ffn_grouped(x: jnp.ndarray, mp: Params, cfg,
                    row_valid: jnp.ndarray | None = None,
                    stack: tuple | None = None):
    """Dropless grouped dispatch: top-k cost instead of all-expert cost.

    Flattens tokens, sorts the (token, slot) pairs by routed expert, runs
    the expert matmuls over the sorted rows and puts the weighted expert
    outputs back on their tokens (:func:`_combine`'s contraction in the
    batched dispatch, a scatter-add in the ragged one).  Numerically
    equivalent to the dense
    dispatch — no capacity factor, no dropped tokens.  Used for large-T
    prefill and training on an unsharded expert dim; the dense path stays
    for decode (HBM-bound: every expert's weights are read once
    regardless) and for expert-parallel meshes, where the einsum + psum
    formulation lets XLA shard the expert dim (groups can't span devices).

    Quantised leaves, and any share of a layer (``cfg.expert_parallel_size``
    > 1: the pairs whose expert lives on another chip are never gathered),
    run as :func:`_batched_dispatch`'s rounds of fixed size; the plain
    leaves of a layer held whole as :func:`_ragged_dispatch`.  ``row_valid``
    [T] (rows that carry a token) makes the call return ``(out, counts)``,
    :func:`moe_ffn`'s; ``stack`` is :func:`_batched_dispatch`'s."""
    from arks_tpu.models.quant import is_quantized
    lead = x.shape[:-1]
    e = x.shape[-1]
    k = cfg.num_experts_per_tok
    share = cfg.expert_parallel_size > 1
    x2 = x.reshape(-1, e)

    # Profile scopes (docs/monitoring.md): routing and the sort into expert
    # order; the expert matmuls.
    with jax.named_scope("arks.moe_route"):
        logits = jnp.einsum("te,ex->tx", x2, mp["router"])
        vals, idx = router_topk(logits, cfg, mp.get("router_bias"))  # [T, k]

    if share or is_quantized(mp["w_down"]):
        out, held, tiles = _batched_dispatch(x2, vals, idx, mp, cfg,
                                             row_valid, stack)
    else:
        out = _ragged_dispatch(x2, vals, idx, mp, cfg)

    if "shared_up" in mp:
        out = out + _shared_expert(x2, mp, cfg)
    zero_pairs = None
    if cfg.zero_experts:
        part, zero_pairs = _zero_experts(
            x2, vals, idx, cfg,
            None if row_valid is None else row_valid.reshape(-1))
        out = out + part
    out = out.reshape(*lead, e)
    if row_valid is None:
        return out
    if share:
        counts = jnp.concatenate([jnp.sum(held)[None], tiles]
                                 ).astype(jnp.int32)
    else:
        # Held whole, every pair lands here but the identity experts'.
        counts = _counts(jnp.sum(row_valid) * k - (
            0 if zero_pairs is None else zero_pairs))
    if zero_pairs is not None:
        counts = jnp.concatenate([counts, zero_pairs[None]])
    return out, counts


def moe_ffn(x: jnp.ndarray, mp: Params, cfg, constrain=None,
            grouped: bool | None = None,
            row_valid: jnp.ndarray | None = None,
            stack: tuple | None = None):
    """MoE feed-forward on [..., E] activations (works for [B, T, E] prefill
    and [B, E] decode).  ``constrain(t, expert_dim_index)`` optionally pins
    the expert dim of intermediates to the model axis.  ``grouped`` forces
    (True) or forbids (False) the dropless grouped path; None = auto (large
    unsharded token batches).  With ``row_valid`` (the leading dims of
    ``x``, bool: rows that carry a token) the return is ``(out, counts)``,
    int32 ``[3]``: the valid rows' (token, expert) pairs that landed on an
    expert held here (all of them where the layer is held whole), the
    overflow tiles a share's batched dispatch needed, and those of them
    beyond the spare ones, which its loop ran (0 and 0 from the dense
    dispatch and from a layer held whole); a layer with identity experts
    (``cfg.zero_experts``) returns ``[4]``, the pairs that landed on one
    last: ``routed = held + zero + absent``.  ``stack`` = ``(tree, layer)``
    says where ``mp`` lies in the stacked tree the program was handed
    (:func:`_batched_dispatch`; None: a lone layer)."""
    if grouped is None:
        import math
        n_tokens = math.prod(x.shape[:-1])
        # x.ndim >= 3 discriminates prefill/training ([B, T, E]) from decode
        # ([B, E]): decode stays dense regardless of slot count — it is
        # HBM-bound and the sort/gather dispatch only adds overhead there.
        grouped = (constrain is None and x.ndim >= 3
                   and _batch_pays(n_tokens, mp, cfg))
    if grouped:
        return moe_ffn_grouped(x, mp, cfg, row_valid, stack)
    from arks_tpu.models.quant import qeinsum

    with jax.named_scope("arks.moe_route"):
        logits = jnp.einsum("...e,ex->...x", x, mp["router"])
        vals, idx = router_topk(logits, cfg, mp.get("router_bias"))
        weights = _held_weights(vals, idx, cfg)
        counts = None
        if row_valid is not None:
            # A chosen expert's weight is never zero (a softmax or a
            # sigmoid), so the non-zero held columns are the held pairs.
            counts = _counts(jnp.sum((weights != 0) & row_valid[..., None]))
        weights = weights.astype(x.dtype)                      # [.., X]

    # The dequant is fused into the contraction.
    with jax.named_scope("arks.moe_dot"):
        act = _hidden(lambda leaf, t: qeinsum(
            "...e,xfe->...xf" if t else "...e,xef->...xf", x, leaf), mp, cfg)
        if constrain is not None:
            act = constrain(act, act.ndim - 2)
        down = qeinsum("...xf,xfe->...xe", act, mp["w_down"])  # per expert
        out = jnp.einsum("...xe,...x->...e", down, weights)    # psum over EP

    if "shared_up" in mp:
        out = out + _shared_expert(x, mp, cfg, constrain)
    if cfg.zero_experts:
        part, zero_pairs = _zero_experts(x, vals, idx, cfg, row_valid)
        out = out + part
        if row_valid is not None:
            counts = jnp.concatenate([counts, zero_pairs[None]])
    return out if row_valid is None else (out, counts)
