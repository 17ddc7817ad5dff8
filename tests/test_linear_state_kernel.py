"""The linear layers' one-step kernel (``arks_tpu/ops/linear_state.py``)
alone, in interpret mode, against the recurrence written out in float32.

What the kernel promises beyond the arithmetic: only the listed slots of
the one layer are read or written (an idle slot's state, a chunk lane's
and every other layer's come back bit for bit), a slot that starts a
sequence reads zeros whatever it holds, and the list's padding can do no
harm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arks_tpu.models import transformer as tf
from arks_tpu.ops.linear_state import linear_state_step

L, LAYER, B, H, D = 3, 1, 8, 4, 16


def _step(q, k, v, a, b, s, fresh):
    """One recurrence step a slot in float32: ``q, k, v [B, H, d]``, ``a
    [B, H, d | 1]``, ``b [B, H]``, ``s [B, H, d, d]``."""
    s = a[..., None] * jnp.where(fresh[:, None, None, None], 0.0, s)
    u = b[..., None] * (v - jnp.sum(s * k[..., None], axis=2))
    s = s + k[..., None] * u[:, :, None, :]
    return jnp.sum(s * q[..., None], axis=2), s


# q_len a slot (a lane's rows lie in slot order in the flat batch), the
# slots that start a sequence, and for the kernel called alone its list.
_CASES = {
    "every lane one row": dict(q_len=[1] * B),
    "no lane one row": dict(q_len=[0, 5, 0, 0, 70, 0, 0, 0],
                            slots=[4, 1, 4, 4, 1, 1, 4, 4]),
    "one-row lanes among idle slots and chunks": dict(
        q_len=[0, 1, 5, 0, 1, 1, 0, 66]),
    "a fresh lane over a stale state": dict(
        q_len=[1, 0, 1, 1, 0, 0, 1, 0], fresh=[2, 6]),
    "a padded list that repeats live slots": dict(
        q_len=[0, 0, 1, 0, 0, 1, 0, 0], slots=[5, 2, 5, 2, 2, 5, 5, 2]),
}


@pytest.mark.parametrize("decay", ["a channel", "a head"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_step_kernel_is_the_recurrence_over_its_list_alone(case, decay):
    spec = _CASES[case]
    q_len = np.asarray(spec["q_len"], np.int32)
    q_start = (np.cumsum(q_len) - q_len).astype(np.int32)
    t = int(q_len.sum()) + 3                        # padding rows behind
    fresh = np.zeros(B, bool)
    fresh[spec.get("fresh", [])] = True
    rng = np.random.default_rng(len(case))
    q, k, v = (rng.standard_normal((t, H, D)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.001, 0.9, (t, H, D if decay == "a channel" else 1)
                     ).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (t, H)).astype(np.float32)
    s_all = rng.standard_normal((L, B, H, D, D)).astype(np.float32)
    one = q_len == 1
    at = np.clip(q_start, 0, t - 1)
    if "slots" in spec:
        # The kernel alone, handed a list whose padding names slots in use.
        o, new = linear_state_step(
            *(jnp.asarray(x) for x in (
                q, k, v, g, beta, s_all, LAYER,
                np.asarray(spec["slots"], np.int32), int(one.sum()), fresh,
                at)), interpret=True)
        assert not np.asarray(o)[np.setdiff1d(np.arange(t), at[one])].any()
    else:
        o, new = jax.jit(tf._linear_state)(
            *(jnp.asarray(x) for x in (q, k, v, g, beta, s_all, LAYER,
                                       q_start, q_len, fresh)))
    o, new = np.asarray(o), np.asarray(new)
    want_o, want_s = (np.asarray(x) for x in _step(
        *(jnp.asarray(x) for x in (q[at], k[at], v[at], np.exp(g[at]),
                                   beta[at], s_all[LAYER], fresh))))
    others = [x for x in range(L) if x != LAYER]
    assert np.array_equal(new[others], s_all[others])
    assert np.array_equal(new[LAYER][q_len == 0], s_all[LAYER][q_len == 0])
    if not one.any():
        assert np.array_equal(new, s_all)          # a step of chunks only
    np.testing.assert_allclose(new[LAYER][one], want_s[one], rtol=1e-6,
                               atol=1e-6)
    # (An output is a sum of 16 terms that cancel: against the largest.)
    np.testing.assert_allclose(o[at[one]], want_o[one], rtol=1e-6,
                               atol=1e-6 * np.abs(want_o).max())
    for b in np.flatnonzero(fresh & one):
        # Zeros under the decay: the state is the rank-one update alone.
        np.testing.assert_allclose(
            new[LAYER][b], k[at[b]][..., None] * (
                beta[at[b]][:, None] * v[at[b]])[:, None, :],
            rtol=1e-6, atol=1e-6)
