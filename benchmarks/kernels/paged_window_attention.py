"""Operations and bytes a WINDOW layer's paged-attention call needs, from
shapes.

The launch (``ops/paged_attention.py``, ``name=paged_window_attention_*``:
the ragged mixed kernel told a window) attends, per window layer and per
sequence, ``q`` query tokens that end at context length ``ctx``; the query
at position p attends the keys at positions ``(p - window, p]``.  What the
ALGORITHM needs, whatever implements it:

- operations: ``q.k`` and ``p.v``, two multiply-adds per UNMASKED (query,
  key, head, lane): the query at position p sees ``min(p + 1, window)``
  keys;
- bytes: the cached keys and values INSIDE the window of some query of
  the call, read once (``min(ctx, q + window - 1)`` tokens x ``kv_heads``
  x ``head_dim`` elements each at the pool's width, plus one float32
  scale per token and head where the pool is quantised), the queries
  read and the output written once in bfloat16.

Keys behind the window cost nothing here: a launch that streamed them
would read the same work at a lower share.  Page granularity, padding and
recomputation are the kernel's own costs and do not count.
"""

from __future__ import annotations

from benchmarks.kernels.paged_mixed_attention import least_seconds  # noqa: F401


def pairs(q: int, ctx: int, window: int) -> float:
    """Unmasked (query, key) pairs of ``q`` queries ending at ``ctx``."""
    first = ctx - q                       # position of the first query
    # Queries at positions under window - 1 see position + 1 keys.
    short = max(min(window - 1, ctx) - first, 0)
    return short * (first + 1 + first + short) / 2.0 + (q - short) * window


def work(*, heads: int, kv_heads: int, head_dim: int, layers: int,
         window: int, kv_bytes: float, kv_scale_bytes: float,
         calls: list[tuple[int, int]]) -> dict:
    """``calls``: one ``(q, ctx)`` per sequence per dispatch.  Returns the
    total ``flops`` and ``bytes`` over all window layers."""
    flops = bytes_ = 0.0
    for q, ctx in calls:
        flops += 4.0 * pairs(q, ctx, window) * heads * head_dim
        keys = min(ctx, q + window - 1)
        bytes_ += 2.0 * keys * kv_heads * (head_dim * kv_bytes
                                           + kv_scale_bytes)
        bytes_ += 2.0 * q * heads * head_dim * 2
    return {"flops": flops * layers, "bytes": bytes_ * layers}
