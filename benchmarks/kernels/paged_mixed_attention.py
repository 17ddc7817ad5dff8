"""Operations and bytes the mixed paged-attention call needs, from shapes.

The kernel (``ops/paged_attention.py``, ``name=paged_mixed_attention_*``)
attends, per layer and per sequence, ``q`` query tokens that end at context
length ``ctx`` over that sequence's cached keys and values.  What the
ALGORITHM needs, whatever the kernel's grid does:

- bytes: every cached key and value of the sequence read once per call
  (``ctx x kv_heads x head_dim`` elements each, at the pool's width, plus
  one float32 scale per token and head where the pool is quantised), the
  queries read and the output written once in bfloat16;
- operations: ``q.k`` and ``p.v``, two multiply-adds per (query, key,
  head, lane); a causal chunk of ``q`` tokens ending at ``ctx`` sees
  ``q x ctx - q(q-1)/2`` (query, key) pairs.

Page granularity, padding and recomputation are the kernel's own costs
and do not count.
"""

from __future__ import annotations


def work(*, heads: int, kv_heads: int, head_dim: int, layers: int,
         kv_bytes: float, kv_scale_bytes: float,
         calls: list[tuple[int, int]]) -> dict:
    """``calls``: one ``(q, ctx)`` per sequence per dispatch.  Returns the
    total ``flops`` and ``bytes`` over all layers."""
    flops = bytes_ = 0.0
    for q, ctx in calls:
        pairs = q * ctx - q * (q - 1) / 2
        flops += 4.0 * pairs * heads * head_dim
        bytes_ += 2.0 * ctx * kv_heads * (head_dim * kv_bytes
                                          + kv_scale_bytes)
        bytes_ += 2.0 * q * heads * head_dim * 2
    return {"flops": flops * layers, "bytes": bytes_ * layers}


def least_seconds(w: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    by_flops = w["flops"] / peak["bf16_flops"]
    by_bytes = w["bytes"] / peak["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
