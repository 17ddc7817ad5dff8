"""Mixture-of-Experts FFN (Mixtral / Qwen2-MoE families).

The reference orchestrates MoE models only by passing their names to
vLLM/SGLang containers (no MoE code of its own); here the block is native.

TPU-first formulation:
- **Dense dispatch**: every expert's FFN runs as one batched einsum over the
  expert dim, with unselected experts zeroed by the router-weight tensor.
  Decode is HBM-bound — all expert weights are read once per step no matter
  how many tokens route to them — so compute-all costs nothing extra at
  serving batch sizes while keeping shapes static for XLA.  (A block-sparse
  Pallas dispatch for large-T prefill is a later optimization.)
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
where X = num_experts, Fm = moe_intermediate_size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Params = dict


def init_moe_params(cfg, key, dtype) -> Params:
    l, e = cfg.num_layers, cfg.hidden_size
    x, fm = cfg.num_experts, cfg.moe_intermediate_size
    keys = iter(jax.random.split(key, 8))

    def w(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p: Params = {
        "router": w(next(keys), (l, e, x)),
        "w_gate": w(next(keys), (l, x, e, fm)),
        "w_up": w(next(keys), (l, x, e, fm)),
        "w_down": w(next(keys), (l, x, fm, e)),
    }
    if cfg.shared_expert_intermediate_size:
        fs = cfg.shared_expert_intermediate_size
        p["shared_gate_proj"] = w(next(keys), (l, e, fs))
        p["shared_up"] = w(next(keys), (l, e, fs))
        p["shared_down"] = w(next(keys), (l, fs, e))
        p["shared_gate"] = w(next(keys), (l, e))
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
    if cfg.shared_expert_intermediate_size:
        p["shared_gate_proj"] = P(None, None, axis_model)
        p["shared_up"] = P(None, None, axis_model)
        p["shared_down"] = P(None, axis_model, None)
        p["shared_gate"] = P(None, None)
    return p


def shard_experts(cfg, tp: int) -> bool:
    return tp > 1 and cfg.num_experts % tp == 0


def router_topk(logits: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[.., X] router logits → ([.., k] combine weights, [.., k] expert ids):
    softmax over all experts, top-k selected; renormalized when
    ``norm_topk_prob`` (Mixtral semantics — equal to softmax over the top-k
    logits).  Float32 throughout.  Shared by the dense and grouped dispatch
    paths so routing semantics can never diverge between them."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-9)
    return vals, idx


def router_weights(logits: jnp.ndarray, cfg) -> jnp.ndarray:
    """[.., X] router logits → [.., X] combine weights (unselected experts
    zero) — the dense-dispatch form of router_topk."""
    vals, idx = router_topk(logits, cfg)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=vals.dtype)  # [.., k, X]
    return jnp.einsum("...k,...kx->...x", vals, onehot)


_GROUPED_MIN_TOKENS = 64  # below this, dense dispatch wins on dispatch cost


def moe_ffn_grouped(x: jnp.ndarray, mp: Params, cfg) -> jnp.ndarray:
    """Dropless grouped dispatch: top-k cost instead of all-expert cost.

    Flattens tokens, sorts the (token, slot) pairs by routed expert, runs the
    three expert matmuls as ``jax.lax.ragged_dot`` grouped contractions (one
    MXU pass over exactly T*k rows), and scatter-adds the weighted expert
    outputs back per token.  Numerically equivalent to the dense dispatch —
    no capacity factor, no dropped tokens — at k/X of its FLOPs (8x cheaper
    for a 64-expert top-8 model).  Used for large-T prefill and training on
    an unsharded expert dim; the dense path stays for decode (HBM-bound:
    every expert's weights are read once regardless) and for expert-parallel
    meshes, where the einsum + psum formulation lets XLA shard the expert
    dim (ragged groups can't span devices).
    """
    lead = x.shape[:-1]
    e = x.shape[-1]
    k, nx = cfg.num_experts_per_tok, cfg.num_experts
    x2 = x.reshape(-1, e)
    n = x2.shape[0]

    from arks_tpu.models.quant import dequantize

    # Profile scopes (docs/monitoring.md): routing and the sort into expert
    # order; the dequantised expert weights; the grouped matmuls.
    with jax.named_scope("arks.moe_route"):
        logits = jnp.einsum("te,ex->tx", x2, mp["router"])
        vals, idx = router_topk(logits, cfg)                # [T, k]

        flat_expert = idx.reshape(-1)                       # [T*k]
        order = jnp.argsort(flat_expert)
        token_of = order // k                               # source token
        xs = jnp.take(x2, token_of, axis=0)                 # [T*k, E] sorted
        group_sizes = jnp.bincount(flat_expert, length=nx)

    from arks_tpu.ops.moe_kernel import grouped_ffn, moe_impl
    if moe_impl() == "pallas":
        # Block-sparse Pallas grouped matmul with the dequant FUSED:
        # int8 per-channel scales fold into the accumulator; int4 group
        # scales dequant the weight tile in-register — either way the
        # full-width expert weights never materialize in HBM (ragged_dot
        # below forces exactly that materialization).
        with jax.named_scope("arks.moe_dot"):
            down = grouped_ffn(xs, jnp.take(flat_expert, order),
                               group_sizes, mp["w_gate"], mp["w_up"],
                               mp["w_down"], x.dtype)
    else:
        # ragged_dot needs plain arrays; dequantized expert weights
        # materialize here (prefill-only path — dense/decode keeps the
        # fused dequant).
        def grouped(rows, w):
            # Traced in the order it always was (dequantise, contract,
            # three times over): the scopes add names, not a schedule.
            with jax.named_scope("arks.moe_dequant"):
                w = dequantize(w, x.dtype)
            with jax.named_scope("arks.moe_dot"):
                return jax.lax.ragged_dot(rows, w, group_sizes)

        gate = grouped(xs, mp["w_gate"])
        up = grouped(xs, mp["w_up"])
        with jax.named_scope("arks.moe_dot"):
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(
                gate.dtype) * up
        down = grouped(act, mp["w_down"])                   # [T*k, E]

    with jax.named_scope("arks.moe_route"):
        w = jnp.take(vals.reshape(-1), order).astype(down.dtype)   # [T*k]
        out = jnp.zeros((n, e), down.dtype).at[token_of].add(
            down * w[:, None])

    if cfg.shared_expert_intermediate_size:
        from arks_tpu.models.quant import qeinsum
        sg = qeinsum("te,ef->tf", x2, mp["shared_gate_proj"])
        su = qeinsum("te,ef->tf", x2, mp["shared_up"])
        sact = jax.nn.silu(sg.astype(jnp.float32)).astype(sg.dtype) * su
        shared = qeinsum("tf,fe->te", sact, mp["shared_down"])
        gatev = jax.nn.sigmoid(
            jnp.einsum("te,e->t", x2, mp["shared_gate"]).astype(jnp.float32))
        out = out + shared * gatev[:, None].astype(shared.dtype)
    return out.reshape(*lead, e)


def moe_ffn(x: jnp.ndarray, mp: Params, cfg, constrain=None,
            grouped: bool | None = None) -> jnp.ndarray:
    """MoE feed-forward on [..., E] activations (works for [B, T, E] prefill
    and [B, E] decode).  ``constrain(t, expert_dim_index)`` optionally pins
    the expert dim of intermediates to the model axis.  ``grouped`` forces
    (True) or forbids (False) the dropless grouped path; None = auto (large
    unsharded token batches)."""
    if grouped is None:
        import math
        n_tokens = math.prod(x.shape[:-1])
        # x.ndim >= 3 discriminates prefill/training ([B, T, E]) from decode
        # ([B, E]): decode stays dense regardless of slot count — it is
        # HBM-bound and the sort/gather dispatch only adds overhead there.
        grouped = (constrain is None and x.ndim >= 3
                   and n_tokens >= _GROUPED_MIN_TOKENS)
    if grouped:
        return moe_ffn_grouped(x, mp, cfg)
    from arks_tpu.models.quant import qeinsum

    with jax.named_scope("arks.moe_route"):
        logits = jnp.einsum("...e,ex->...x", x, mp["router"])
        weights = router_weights(logits, cfg).astype(x.dtype)  # [.., X]

    # The dense dispatch keeps the dequant fused into the contraction, so
    # it has no arks.moe_dequant of its own.
    with jax.named_scope("arks.moe_dot"):
        gate = qeinsum("...e,xef->...xf", x, mp["w_gate"])
        up = qeinsum("...e,xef->...xf", x, mp["w_up"])
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up
        if constrain is not None:
            act = constrain(act, act.ndim - 2)
        down = qeinsum("...xf,xfe->...xe", act, mp["w_down"])  # per expert
        out = jnp.einsum("...xe,...x->...e", down, weights)    # psum over EP

    if cfg.shared_expert_intermediate_size:
        sg = qeinsum("...e,ef->...f", x, mp["shared_gate_proj"])
        su = qeinsum("...e,ef->...f", x, mp["shared_up"])
        sact = jax.nn.silu(sg.astype(jnp.float32)).astype(sg.dtype) * su
        if constrain is not None:
            sact = constrain(sact, sact.ndim - 1)
        shared = qeinsum("...f,fe->...e", sact, mp["shared_down"])
        gatev = jax.nn.sigmoid(
            jnp.einsum("...e,e->...", x, mp["shared_gate"]).astype(jnp.float32))
        out = out + shared * gatev[..., None].astype(shared.dtype)
    return out
