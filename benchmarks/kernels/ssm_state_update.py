"""Operations and bytes the selective scan's state update needs, from shapes.

A Mamba-2 layer (``models/transformer.py::_ssm_state``, the scope
``arks.ssm_state``) keeps one state ``[head_dim, state]`` a head a sequence
and, per dispatch and per sequence, runs ``q`` tokens through ``S <- a_t S +
(dt_t x_t) (x) B_t``, ``y_t = S C_t``.  What the ALGORITHM needs, whatever
implements it (the one-step recurrence, a chunked scan, XLA or a Pallas
kernel):

- bytes: the sequence's state read ONCE and written ONCE a dispatch a layer
  at its stored width (``state_bytes`` an element: 4, float32; a token-step
  of a decode lane is one such read and write); per token the rows that
  drive it, read once in float32: the head's input times its step size
  ``[heads, head_dim]``, ``B`` and ``C`` ``[groups, state]`` and the log
  decay a head; and the output a token a head written once in float32;
- operations: per token and head the decay of the state (one multiply a
  state element), the rank-one update and the read-out ``S C`` (a
  multiply-add a state element each): 5 a state element.

A state kept in fewer bytes, or read twice a step, reads a different share
of the same work.  The chunk form's products between rows are the
implementation's own and do not count.
"""

from __future__ import annotations

from benchmarks.kernels.paged_mixed_attention import least_seconds  # noqa: F401


def work(*, heads: int, head_dim: int, state: int, groups: int, layers: int,
         state_bytes: float, calls: list[tuple[int, int]]) -> dict:
    """``calls``: one ``(q, ctx)`` per sequence per dispatch (``ctx`` is not
    used: the state does not grow).  Returns the total ``flops`` and
    ``bytes`` over all Mamba-2 layers."""
    elements = heads * head_dim * state
    flops = bytes_ = 0.0
    for q, _ in calls:
        flops += 5.0 * q * elements
        bytes_ += 2.0 * elements * state_bytes
        bytes_ += q * 4 * (2 * heads * head_dim + 2 * groups * state + heads)
    return {"flops": flops * layers, "bytes": bytes_ * layers}
