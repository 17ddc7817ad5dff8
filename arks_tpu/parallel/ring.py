"""Ring attention: sequence/context-parallel self-attention over a mesh axis.

The reference has NO long-context code of its own — sequence scaling is
whatever vLLM/SGLang do inside their containers, reachable only through the
``runtimeCommonArgs`` passthrough (SURVEY.md §5, /root/reference/api/v1/
arksapplication_types.go:292).  The TPU build makes it first-class: prompts
longer than one chip's prefill budget are sharded across a ``seq`` mesh axis
and attention runs as a ring — each device keeps its Q chunk resident while
KV chunks rotate around the ring over ICI (``ppermute``), accumulating with
an online (flash) softmax.  Peak memory per device is O(T/P) activations +
one in-flight KV chunk, and the KV transfer overlaps with the score/PV
matmuls of the previous chunk under XLA's async collective scheduling.

Chunks are contiguous in ring order: device i holds tokens
[i*Tl, (i+1)*Tl).  Causality falls out of comparing *global* positions, so
fully-masked chunk pairs cost one masked matmul (no separate skip path) —
acceptable because prefill is MXU-bound, not latency-bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def ring_self_attention(
    q: jnp.ndarray,  # [B, Tl, H, D] — local sequence chunk
    k: jnp.ndarray,  # [B, Tl, Hkv, D]
    v: jnp.ndarray,  # [B, Tl, Hkv, D]
    *,
    axis_name: str,
    causal: bool = True,
) -> jnp.ndarray:
    """Runs INSIDE shard_map over ``axis_name``. Returns [B, Tl, H, D]."""
    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, tl, hkv, g, d)
    scale = 1.0 / (d ** 0.5)
    perm = [(j, (j + 1) % p) for j in range(p)]

    m = jnp.full((b, hkv, g, tl, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, hkv, g, tl, 1), jnp.float32)
    acc = jnp.zeros((b, hkv, g, tl, d), jnp.float32)

    # p is static, so the ring is a Python loop: the last rotation (whose
    # result nobody reads) is simply not issued, and XLA can overlap each
    # ppermute with the previous chunk's matmuls.
    k_cur, v_cur = k, v
    for i in range(p):
        src = (my - i) % p  # which chunk we currently hold
        # [B, Hkv, G, Tq, Ts] f32 on the MXU.
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cur,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            pos_q = my * tl + jnp.arange(tl)
            pos_k = src * tl + jnp.arange(tl)
            mask = pos_q[:, None] >= pos_k[None, :]  # [Tq, Ts], global order
            scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
        m_curr = jnp.max(scores, axis=-1, keepdims=True)
        m_next = jnp.maximum(m, m_curr)
        correction = jnp.exp(m - m_next)
        probs = jnp.exp(scores - m_next)
        l = l * correction + jnp.sum(probs, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", probs.astype(v_cur.dtype), v_cur,
                        preferred_element_type=jnp.float32)
        acc = acc * correction + pv
        m = m_next
        if i < p - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    out = acc / (l + 1e-9)  # fully-masked rows can't occur under causal=True
    # [B, Hkv, G, Tl, D] → [B, Tl, H, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tl, h, d).astype(q.dtype)


def ring_prefill_attention(
    q: jnp.ndarray,  # [B, T, H, D], T sharded over ``seq_axis``
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    seq_axis: str = "seq",
    batch_axis: str | None = None,
    causal: bool = True,
    heads_sharded: bool = False,
    model_axis: str = "model",
) -> jnp.ndarray:
    """shard_map wrapper: causal self-attention with T context-parallel.

    With ``heads_sharded`` (q AND kv heads divide the model axis), the head
    dim stays model-sharded inside the ring — TP devices each ring their own
    heads instead of all-gathering q/k/v and redoing every head's FLOPs.
    """
    from jax.sharding import PartitionSpec as P

    model = model_axis if heads_sharded else None
    spec = P(batch_axis, seq_axis, model, None)
    fn = jax.shard_map(
        functools.partial(ring_self_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
