"""Operations and bytes the latent paged-attention call needs, from shapes.

The kernel (``ops/paged_attention.py``, ``name=paged_latent_attention_*``)
attends, per layer and per sequence, ``q`` query tokens that end at context
length ``ctx`` over that sequence's cached LATENT rows: one row a token,
``row`` lanes wide (the normed latent and the rotary key lanes), which is
the key of every head, and whose first ``value`` lanes are the value of
every head.  What the ALGORITHM needs, whatever the kernel's grid does:

- bytes: every cached row of the sequence read ONCE per call (it serves as
  key and as value, for all heads) at the width the pool stores it,
  ``row_bytes``; the absorbed queries read (``heads x row`` lanes a token)
  and the output written (``heads x value`` lanes a token) once in
  bfloat16;
- operations: ``q.row`` over ``row`` lanes and ``p.row`` over ``value``
  lanes, a multiply-add each, per (query, key, head): ``2 (row + value)``;
  a causal chunk of ``q`` tokens ending at ``ctx`` sees
  ``q x ctx - q(q-1)/2`` (query, key) pairs.

Page granularity, padding, the rows a decode lane leaves empty in its
block, and recomputation are the kernel's own costs and do not count.
"""

from __future__ import annotations

from benchmarks.kernels.paged_mixed_attention import least_seconds  # noqa: F401


def work(*, heads: int, row: int, value: int, layers: int, row_bytes: float,
         calls: list[tuple[int, int]]) -> dict:
    """``calls``: one ``(q, ctx)`` per sequence per dispatch.  Returns the
    total ``flops`` and ``bytes`` over all layers."""
    flops = bytes_ = 0.0
    for q, ctx in calls:
        pairs = q * ctx - q * (q - 1) / 2
        flops += 2.0 * (row + value) * pairs * heads
        bytes_ += ctx * row_bytes
        bytes_ += 2.0 * q * heads * (row + value)
    return {"flops": flops * layers, "bytes": bytes_ * layers}
