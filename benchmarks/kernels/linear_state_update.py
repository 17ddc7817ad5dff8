"""Operations and bytes the delta rule's state update needs, from shapes.

A linear-attention layer (``models/transformer.py::_linear_state``, the
scope ``arks.linear_state``) keeps one state ``[head_dim, head_dim]`` a head
a sequence and, per dispatch and per sequence, runs ``q`` tokens through
``S' = diag(a_t) S``, ``S <- S' + b_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T
q_t``.  What the ALGORITHM needs, whatever implements it (the one-step
recurrence, a chunked scan, XLA or a Pallas kernel):

- bytes: the sequence's state read ONCE and written ONCE a dispatch a
  layer at its stored width (``state_bytes`` an element: 4, float32; a
  token-step of a decode lane is one such read and write); per token the
  rows that drive it, read once: q, k, v in bfloat16, the decay a channel
  in float32 and the step size a head in float32; and the output a token a
  head written once in float32;
- operations: per token and head the decay of the state (d x d
  multiplies), ``S'^T k`` and ``S^T q`` (two multiply-adds a state element
  each) and the rank-one update (a multiply-add a state element): 7 d x d.

A state kept in fewer bytes, or read twice a step, reads a different share
of the same work.  The chunk form's triangular solve and its products
between rows are the implementation's own and do not count.
"""

from __future__ import annotations

from benchmarks.kernels.paged_mixed_attention import least_seconds  # noqa: F401


def work(*, heads: int, head_dim: int, layers: int, state_bytes: float,
         calls: list[tuple[int, int]]) -> dict:
    """``calls``: one ``(q, ctx)`` per sequence per dispatch (``ctx`` is not
    used: the state does not grow).  Returns the total ``flops`` and
    ``bytes`` over all linear layers."""
    state = heads * head_dim * head_dim
    flops = bytes_ = 0.0
    for q, _ in calls:
        flops += 7.0 * q * state
        bytes_ += 2.0 * state * state_bytes
        bytes_ += q * heads * (3 * head_dim * 2 + head_dim * 4 + 4
                               + head_dim * 4)
    return {"flops": flops * layers, "bytes": bytes_ * layers}
